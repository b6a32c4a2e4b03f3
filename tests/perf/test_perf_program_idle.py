"""Device idle credited to the program's own spans, and the collector's
host time (PR 38: ``perf/readers/program_idle.py``,
``perf/readers/program_span_total.py``), on a hand-made trace whose answers
are known.

``perf/testdata/handmade_program_idle.json``, window (0, 1000) ns, one step:
chip 0 idles in three gaps, chip 1 runs one op throughout. The gap
[100, 300) has its middle (200) in a ``host:gc`` span nested in a
``trainer:train_step`` span: it goes to ``host:gc``. The gap [500, 600)
goes to ``trainer:place_batch``. The gap [700, 900) lies under no program
span: it goes to no reading. The last ``trainer:train_step`` span ends after
the window and counts nowhere. A first ``host:gc`` span, 20 ns, falls while
the chips were busy: host time, no idle.
"""

import os

import pytest

from perf import harness, program_trace as pt, registry, trace_reduce
from perf.readers import program_idle

TESTDATA = os.path.join(registry.ROOT, "testdata")
WINDOW = (0, 1000)
CELLS = ["train-360m-1chip", "train-1.7b-fsdp4", "train-lfm2-24b-ep8-1chip",
         "train-joyai-flash-ep32-1chip", "train-nemotron-twotower-ep16-1chip"]
NEW = ["gc_ms.train", "idle_gc_ms.train", "idle_dispatch_ms.train",
       "idle_place_batch_ms.train"]


def observations(trace, steps=1):
    obs = harness.Observations(
        cell={"name": "handmade"}, spans=harness.Spans(), window=(10.0, 20.0),
        counters={"steps": steps}, trace=object(), trace_window=WINDOW)
    obs.program_trace = trace
    return obs


def read(obs, metric):
    spec = registry.metric(metric)
    reader = registry.code("readers", spec["reader"])
    return reader.read(obs, **spec.get("args", {}))


@pytest.fixture()
def handmade():
    return pt.load(os.path.join(TESTDATA, "handmade_program_idle.json"))


@pytest.mark.parametrize("metric,ns", [
    # ns a step a chip: 200 of chip 0's idle over two chips; the dispatch
    # span's gap went to the collection nested in it.
    ("idle_gc_ms.train", 200 / 2), ("idle_dispatch_ms.train", 0),
    ("idle_place_batch_ms.train", 100 / 2),
    # Host time, not averaged over chips: both collections in the window.
    ("gc_ms.train", 20 + 80)])
def test_each_reading_of_the_handmade_trace(handmade, metric, ns):
    assert read(observations(handmade), metric) == pytest.approx(ns / 1e6)
    assert read(observations(handmade, steps=2), metric) == pytest.approx(
        ns / 2e6)


def test_a_gap_under_no_span_goes_to_no_reading(handmade):
    by = program_idle.idle_ns_by_span(handmade, WINDOW)
    assert by == {"host:gc": 100, "trainer:place_batch": 50}
    # The window's idle, averaged over the chips, holds one more gap.
    idle = trace_reduce.idle_share(
        trace_reduce.Trace(handmade.devices, []), WINDOW) * (WINDOW[1]
                                                             - WINDOW[0])
    assert idle == pytest.approx(250)
    assert sum(by.values()) == pytest.approx(idle - 200 / 2)


def test_zero_where_spans_are_there_and_none_where_they_are_not(handmade):
    obs = observations(handmade)
    assert program_idle.read(obs, span="serve:dispatch") == 0.0
    # A commit whose program opens no span: nothing to read.
    bare = observations(pt.ProgramTrace(handmade.devices, {}, []))
    for metric in NEW:
        assert read(bare, metric) is None, metric
    # No trace was taken.
    untraced = observations(None)
    untraced.trace = None
    for metric in NEW:
        assert read(untraced, metric) is None, metric
    # Spans there, no collection in the window.
    no_gc = observations(pt.ProgramTrace(
        handmade.devices, {},
        [s for s in handmade.spans if s.name != "host:gc"]))
    assert read(no_gc, "gc_ms.train") == 0.0
    assert read(no_gc, "idle_gc_ms.train") == 0.0
    # The dispatch span now holds the gap the collection took.
    assert read(no_gc, "idle_dispatch_ms.train") == pytest.approx(100 / 1e6)


@pytest.mark.parametrize("cell", CELLS)
def test_the_four_metrics_resolve_in_every_cell(cell):
    resolved = registry.workload(cell)
    assert set(NEW) <= set(resolved["per_layer"])
    for metric in NEW:
        spec = resolved["per_layer_specs"][metric]
        assert spec["moves"] in resolved["end_to_end"]
        assert spec["layer"] == ("trainer" if metric == "gc_ms.train"
                                 else "device")
