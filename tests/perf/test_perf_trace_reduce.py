"""The reduction from a trace to numbers, on a hand-made trace whose answers
are known and on a small trace recorded on the chip."""

import glob
import os

import pytest

from perf import registry, trace_reduce as tr

TESTDATA = os.path.join(registry.ROOT, "testdata")
WINDOW = (0, 1000)


@pytest.fixture(scope="module")
def handmade():
    return tr.load(os.path.join(TESTDATA, "handmade_trace.json"))


def test_interval_arithmetic():
    assert tr.union([(0, 5), (3, 8), (10, 12), (12, 12)]) == [(0, 8), (10, 12)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)]) == [
        (0, 2), (4, 8), (22, 25), (26, 30)]
    assert tr.clip([(0, 10), (20, 30)], (5, 25)) == [(5, 10), (20, 25)]
    assert tr.total([(0, 2), (4, 8)]) == 6


def test_busy_union_and_idle_share(handmade):
    # chip 0: [100,700) and [800,900) busy (the while spans its body and is
    # not counted twice); chip 1 busy throughout.
    assert tr.busy_seconds(handmade, WINDOW) == pytest.approx(850e-9)
    assert tr.idle_share(handmade, WINDOW) == pytest.approx(0.15)
    assert tr.window_of(handmade, "traced_window") == WINDOW
    assert tr.window_of(handmade) == (0, 1000)
    assert tr.busy_seconds(handmade, (0, 500)) == pytest.approx(450e-9)


def test_containers_are_not_leaves(handmade):
    names = [e.name for e in tr.leaves(handmade.devices[0])]
    assert "while.1" not in names and len(names) == 6


def test_kernel_time_by_name(handmade):
    assert tr.kernel_seconds(handmade, WINDOW, "^attention",
                             "tpu_custom_call") == \
        pytest.approx(100e-9)        # 200 ns on chip 0, none on chip 1
    assert tr.kernel_seconds(handmade, WINDOW, "^attention", "kLoop") == 0.0
    assert tr.kernel_seconds(handmade, WINDOW, "no_such_kernel") == 0.0


def test_flash_roofline_reader(handmade):
    """The reader finds the kernels by the name they have on one chip and
    divides the causal FLOPs of each chip's share by their device time; the
    name they have under a mesh says nothing of attention and is not read."""
    from perf import harness, work
    from perf.readers import flash_roofline

    cfg = registry.config("smollm2-360m")
    cell = {"config_file": cfg, "peaks": registry.peaks("TPU v5 lite")}
    obs = harness.Observations(
        cell=cell, spans=harness.Spans(), window=(0.0, 1.0),
        counters={"steps": 1, "sequences_per_step": 2, "seq_len": 2048},
        trace=handmade, trace_window=WINDOW)
    want = 100.0 * (work.flash_train_flops(cfg, 2048, 1) / 197e12) / 100e-9
    assert flash_roofline.read(obs) == pytest.approx(want)
    for ev in handmade.devices[0]:
        if ev.name == "attention.2":
            ev.name = "shard_map.7"          # its name under a mesh
    assert flash_roofline.read(obs) is None
    for ev in handmade.devices[0]:
        if ev.name == "shard_map.7":
            ev.name = "attention.2"
    obs.trace = None
    assert flash_roofline.read(obs) is None


def test_hlo_text_is_parsed():
    text = ('%attention.515 = (bf16[8,2048,1024]{2,1,0:T(8,128)(2,1)S(1)}, '
            'f32[8,16,1,2048]{3,2,1,0}) custom-call(u32[1,1]{1,0} %gte.4352, '
            'bf16[8,2048,1024]{2,1,0} %custom-call.275), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    assert tr.parse_hlo(text) == ("attention.515", "tpu_custom_call")
    assert tr.parse_hlo("%fusion.40 = f32[16,9]{1,0} fusion(f32[] %x), "
                        "kind=kOutput, calls=%fc.1") == ("fusion.40",
                                                         "kOutput")
    assert tr.parse_hlo("%while.3 = (s32[]) while(%t), body=%b") == (
        "while.3", "")


def test_exposed_collective_share(handmade):
    # chip 0: start [500,510), done [600,700), all-reduce [800,900) = 210;
    # chip 1: all-gather [400,600) = 200; no compute runs beside them.
    assert tr.exposed_collective_share(handmade, WINDOW) == \
        pytest.approx((0.21 + 0.20) / 2)
    assert tr.is_collective(tr.Event("%all-gather-start.12", 0, 1))
    assert tr.is_collective(tr.Event("reduce-scatter", 0, 1))
    assert not tr.is_collective(tr.Event("fusion.3", 0, 1))


def test_breakdown(handmade):
    ops = dict(tr.top_device_ops(handmade, WINDOW))
    assert ops["fusion <kOutput>"] == pytest.approx(290e-9)
    assert ops["attention <tpu_custom_call>"] == pytest.approx(200e-9)
    assert len(tr.top_device_ops(handmade, WINDOW, n=2)) == 2
    gaps = dict(tr.idle_gaps_by_span(handmade, WINDOW))
    assert gaps == {"train_step": pytest.approx(100e-9),
                    "next_batch": pytest.approx(100e-9),
                    "(no span)": pytest.approx(100e-9)}


def test_json_round_trip(handmade):
    again = tr.Trace.from_json(handmade.to_json())
    assert again == handmade
    small = tr.sample(handmade, max_events=2)
    assert all(len(v) == 2 for v in small.devices.values())


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(TESTDATA, "recorded_*.json"))))
def test_recorded_trace(path):
    """A piece of a trace taken on the chip (PR 24): the reductions run on
    what the profiler really writes and stay inside what must hold."""
    trace = tr.load(path)
    window = tr.window_of(trace)
    busy = tr.busy_seconds(trace, window)
    length = (window[1] - window[0]) / 1e9
    assert 0.0 < busy <= length
    assert 0.0 <= tr.idle_share(trace, window) < 1.0
    assert tr.top_device_ops(trace, window)
    everything = tr.kernel_seconds(trace, window, ".")
    assert 0.0 < everything <= busy  # containers add their own overhead
    assert 0.0 <= tr.exposed_collective_share(trace, window) <= 1.0
