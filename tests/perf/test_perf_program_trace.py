"""The region x phase table and the readers of the program's own spans and
counters (PR 26), on a hand-made trace whose answers are known and on
pieces recorded on the chip in both cells."""

import glob
import os

import pytest

from perf import harness, program_trace as pt, registry
from perf.readers import program_counter, program_span, region_ms

TESTDATA = os.path.join(registry.ROOT, "testdata")
WINDOW = (0, 1000)
# Device ns in the window, both chips together, by (region, phase): chip 0
# runs the eighteen ops of the file (the `while` spans its body, the backward
# kernel spans a copy-start of 2 ns, the last op is cut by the window; a conv
# operator's projection, the mixer's grouped norm, the scan's backward kernel
# and a tap the backward recomputes stand for the two sequence operators),
# chip 1 one matmul fusion of the MLP throughout.
KNOWN_NS = {
    ("mlp", "fwd"): 100 + 1000, ("flash", "fwd"): 100, ("flash", "bwd"): 148,
    ("conv", "fwd"): 30, ("ssm", "fwd"): 40, ("ssm", "bwd"): 20 + 10,
    ("attn_proj", "bwd"): 50, ("collective", "fwd"): 50,
    ("head_loss", "fwd"): 50, ("norm", "fwd"): 50, ("embed", "fwd"): 50,
    ("grad_accum", "opt"): 50, ("grad_finalize", "opt"): 60,
    ("optimizer", "opt"): 100, ("other", "other"): 42,
}
RECORDED = sorted(glob.glob(os.path.join(TESTDATA, "program_recorded_*.json")))
NEW_METRICS = [
    "fwd_ms.train", "bwd_ms.train", "opt_ms.train", "flash_fwd_ms.train",
    "flash_bwd_ms.train", "attn_proj_ms.train", "mlp_ms.train",
    "head_loss_ms.train", "unattributed_frac.train", "dispatch_ms.train",
    "recompiles.train", "trace_lower_s", "executable_s"]
# Which of them a cell's list leaves out. `mlp_ms.train` names the dense
# cells alone: in a cell with expert layers `moe_ms.train` reads the same
# scope and a second name would double it. The JoyAI cell's step reports more
# nested traces than the program's compile log keeps entries, so its first
# calls are gone from the log when the metrics are read (`log_covers`).
DENSE_CELLS = ["train-1.7b-fsdp4", "train-360m-1chip"]
NOT_LISTED = {"train-joyai-flash-ep32-1chip": {"trace_lower_s",
                                               "executable_s"}}


def observations(trace, steps=1, window=WINDOW):
    obs = harness.Observations(
        cell={"name": "handmade"}, spans=harness.Spans(), window=(10.0, 20.0),
        counters={"steps": steps}, trace=object(), trace_window=window)
    obs.program_trace = trace
    return obs


@pytest.fixture()
def handmade(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    return observations(
        pt.load(os.path.join(TESTDATA, "handmade_program_trace.json")))


def read(obs, metric):
    spec = registry.metric(metric)
    reader = registry.code("readers", spec["reader"])
    return reader.read(obs, **spec.get("args", {}))


def test_every_region_and_phase_of_the_handmade_trace(handmade):
    table = pt.table_of(handmade)
    per_ns = 1.0 / (1e6 * 1 * 2)          # ms a step a chip: 1 step, 2 chips
    for region in pt.REGIONS:
        for phase in pt.PHASES:
            want = KNOWN_NS.get((region, phase), 0) * per_ns
            assert table["regions"][region][phase] == pytest.approx(want), \
                (region, phase)
    assert table["longest_other"] == [
        {"op": "copy", "op_name": "", "ms": pytest.approx(40 * per_ns)},
        {"op": "copy-start", "op_name": "", "ms": pytest.approx(2 * per_ns)}]
    # Rows named as `breakdown.device_ops` names them, split by region.
    assert [f["op"] for f in table["op_families"][:3]] == [
        "fusion <kOutput>", "fusion <kLoop>", "attention <tpu_custom_call>"]
    assert table["op_families"][0]["ms"] == pytest.approx(1200 * per_ns)
    assert table["op_families"][0]["of"] == {
        "mlp/fwd": pytest.approx(1100 * per_ns),
        "attn_proj/bwd": pytest.approx(50 * per_ns),
        "head_loss/fwd": pytest.approx(50 * per_ns)}
    assert table["collectives"] == [
        {"kind": "all-gather", "origin": "mlp", "phase": "fwd",
         "ms": pytest.approx(50 * per_ns),
         "alone_ms": pytest.approx(50 * per_ns)}]
    assert os.path.isfile(os.path.join(harness.OUT_DIR,
                                       "handmade.regions.json"))


def test_regions_sum_to_the_busy_time(handmade):
    table = pt.table_of(handmade)
    from perf import trace_reduce

    busy_s = trace_reduce.busy_seconds(
        trace_reduce.Trace(handmade.program_trace.devices, []), WINDOW)
    assert busy_s == pytest.approx(975e-9)          # (950 + 1000) / 2
    assert table["busy_ms"] == pytest.approx(busy_s * 1e3)
    assert sum(table["phases"].values()) == pytest.approx(busy_s * 1e3)
    assert read(handmade, "unattributed_frac.train") == pytest.approx(
        100.0 * 42 / 1950)


@pytest.mark.parametrize("metric,ns", [
    ("fwd_ms.train", 1470), ("bwd_ms.train", 228), ("opt_ms.train", 210),
    ("flash_fwd_ms.train", 100), ("flash_bwd_ms.train", 148),
    ("attn_proj_ms.train", 50), ("mlp_ms.train", 1100),
    ("head_loss_ms.train", 50),
    # The scope metrics of the two sequence operators read what the regions
    # `conv` and `ssm` hold; the scan's kernel is a part of the mixer.
    ("conv_ms.train", 30), ("ssm_ms.train", 70), ("ssd_ms.train", 20)])
def test_region_metrics_read_the_table(handmade, metric, ns):
    assert read(handmade, metric) == pytest.approx(ns / 2e6)
    two_steps = observations(handmade.program_trace, steps=2)
    assert read(two_steps, metric) == pytest.approx(ns / 4e6)


def test_span_median_counts_spans_inside_the_window_only(handmade):
    # 10, 50 and 30 ns inside; the fourth ends after the window.
    assert read(handmade, "dispatch_ms.train") == pytest.approx(30e-6)
    assert program_span.read(handmade, span="trainer:eval_step") is None
    assert pt.span_summary(handmade.program_trace, WINDOW)[
        "trainer:place_batch"] == {"count": 1, "median_ms": 4e-6}


class Entry:
    """A row of the program's compile log, as ``<cell>.regions.json`` keeps
    it (``dataclasses.asdict`` of ``profiling.CompileEntry``)."""

    def __init__(self, kind, seconds, end, **_):
        self.kind, self.seconds, self.end = kind, seconds, end
        self.start = end - seconds


def test_counters_inside_and_outside_the_window(handmade, monkeypatch):
    log = [Entry("trace", 2.0, 3.0), Entry("lower", 1.0, 4.0),
           Entry("cache_read", 0.5, 4.9), Entry("compile", 1.0, 5.0),
           Entry("trace", 0.1, 2.5),           # nested in the first trace
           Entry("compile", 0.25, 12.0),       # inside the window
           Entry("compile", 0.5, 25.0)]        # after it
    monkeypatch.setattr(pt, "compile_entries", lambda: log)
    handmade.spans.all.append(harness.Span("first_call", 0.5, 6.0))
    assert read(handmade, "recompiles.train") == 1.0
    assert read(handmade, "trace_lower_s") == pytest.approx(3.0)
    assert read(handmade, "executable_s") == pytest.approx(1.0)
    handmade.window = (13.0, 20.0)
    assert read(handmade, "recompiles.train") == 0.0


def test_the_setup_note_says_what_the_first_calls_were_made_of(monkeypatch):
    """``first_call_compiles`` on the compile log a cold traced run of the
    Nemotron cell recorded on the chip (PR 36): the sums the ``setup`` note
    of every run carries, by the function ``trace_lower_s`` and
    ``executable_s`` read through."""
    import json

    with open(os.path.join(
            registry.ROOT, "records",
            "pr36.train-nemotron-twotower-ep16-1chip.regions.json")) as f:
        log = [Entry(**row) for row in json.load(f)["compile_log"]]
    monkeypatch.setattr(pt, "compile_entries", lambda: log)
    spans = harness.Spans()
    assert pt.first_call_compiles(spans) == {
        **{kind: {"seconds": 0, "count": 0} for kind in pt.COMPILE_KINDS},
        "complete": True}
    # Two first calls: the state's (with the eager ops before it, one of
    # them read from the cache) and the step's; the reference's compiles
    # between them and the `compare` after them lie in neither.
    spans.all += [harness.Span("first_call", 54.0, 97.0),
                  harness.Span("first_call", 240.0, 298.5),
                  harness.Span("next_batch", 0.0, 400.0)]
    sums = pt.first_call_compiles(spans)
    assert sums.pop("complete")
    assert {k: v["count"] for k, v in sums.items()} == {
        "trace": 3, "lower": 4, "compile": 4, "cache_read": 1}
    assert sums["trace"]["seconds"] == pytest.approx(2.2126 + 1.0065 + 13.5189,
                                                     abs=1e-3)
    assert sums["compile"]["seconds"] == pytest.approx(
        0.0078 + 0.4884 + 35.2480 + 35.7827, abs=1e-3)
    # The read lies inside its compile: the three others are disjoint and
    # fit inside the spans that hold them.
    assert sums["cache_read"]["seconds"] < 0.0078
    assert sum(sums[k]["seconds"] for k in ("trace", "lower", "compile")) \
        <= (97.0 - 54.0) + (298.5 - 240.0)
    # What the two listed metrics read of the same spans is the same sums.
    obs = observations(None)
    obs.spans = spans
    assert read(obs, "trace_lower_s") == pytest.approx(
        sums["trace"]["seconds"] + sums["lower"]["seconds"])
    assert read(obs, "executable_s") == pytest.approx(
        sums["compile"]["seconds"])
    # A log that has lost a first call's entries (the program keeps its
    # newest 4,096, and one large step's trace reports more: the JoyAI
    # cell, my chip run, PR 37) says so, and the metrics read nothing
    # rather than the part that is left.
    kept = [e for e in log if e.start > 240.0]
    monkeypatch.setattr(pt, "compile_entries", lambda: kept)
    partial = pt.first_call_compiles(spans)
    assert not partial["complete"] and partial["compile"]["count"] == 1
    assert read(obs, "trace_lower_s") is None
    assert read(obs, "executable_s") is None
    # A program that keeps no log: nothing to say.
    monkeypatch.setattr(pt, "compile_entries", lambda: None)
    assert pt.first_call_compiles(spans) is None


def test_a_reader_whose_source_is_missing_returns_none(handmade, monkeypatch):
    # No trace was taken.
    untraced = observations(None)
    untraced.trace = None
    # A program that names no op's origin, opens no span, keeps no log.
    bare = observations(pt.ProgramTrace(
        handmade.program_trace.devices, {}, []))
    monkeypatch.setattr(pt, "compile_entries", lambda: None)
    for metric in NEW_METRICS:
        assert read(untraced, metric) is None, metric
        assert read(bare, metric) is None, metric
    # A region with no op in the window reads nothing, not zero.
    assert region_ms.read(handmade, regions=["flash"],
                          phases=["opt"]) is None
    assert program_counter.read(
        handmade, kinds=["compile"], within="first_call",
        value="seconds") is None             # no first_call span


def test_region_rules():
    assert pt.region_of("all-gather-start.3", "", "a/mlp/b") == "collective"
    assert pt.scope_region("a/mlp/b") == "mlp"
    assert pt.scope_region("a/moe_mlp/b") == "mlp"
    assert pt.scope_region("a/attention/shard_map/pallas_call",
                           "tpu_custom_call") == "flash"
    assert pt.scope_region("a/attention/q_proj/dot_general") == "attn_proj"
    assert pt.scope_region("a/head_loss/embed_tokens/dot") == "head_loss"
    assert pt.scope_region("a/post_attention_layernorm/mul") == "norm"
    assert pt.scope_region("a/embed_tokens/gather") == "embed"
    assert pt.scope_region("jit(f)/normalize/x") == "other"
    # The sequence operators that came after PR 26: a module whole, its own
    # norm, kernels and recomputed core included; `taps` is not `conv`.
    assert pt.scope_region("a/jvp(GPT)/layers/conv/in_proj/dot") == "conv"
    assert pt.scope_region("a/jvp(GPT)/layers/mamba/in_proj/dot") == "ssm"
    assert pt.scope_region("a/mamba/norm/mul") == "ssm"
    assert pt.scope_region("a/mamba/ssd/jit(ssd_fwd)/pallas_call",
                           "tpu_custom_call") == "ssm"
    assert pt.scope_region("a/mamba/taps/conv_general_dilated") == "ssm"
    recomputed = ("jit(f)/transpose(jvp(GPT))/layers/checkpoint/mamba/ssd/"
                  "jit(ssd_fwd_keeping)/pallas_call")
    assert pt.scope_region(recomputed, "tpu_custom_call") == "ssm"
    assert pt.phase_of(recomputed) == "bwd"
    # `mtp` is no region: its block's ops keep theirs.
    assert pt.scope_region("a/mtp/block/attention/q_proj/dot") == "attn_proj"
    assert pt.scope_region("a/mtp/block/moe_mlp/route/top_k") == "mlp"
    assert set(pt.REGIONS) >= {"conv", "ssm"} and "mtp" not in pt.REGIONS
    assert pt.phase_of("jit(f)/transpose(jvp(GPT))/mlp/dot") == "bwd"
    assert pt.phase_of("jit(f)/jvp(GPT)/mlp/dot") == "fwd"
    assert pt.phase_of("jit(f)/while/body/grad_accum/add") == "opt"
    assert pt.phase_of("jit(f)/add") == "other"


def test_op_names_from_the_compiled_program_text():
    line = ('%fusion.7 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]'
            '{1,0} %p), kind=kLoop, calls=%fused, metadata={op_type="mul" '
            'op_name="jit(_train_step)/optimizer/mul" source_file="x.py"}')
    text = "ENTRY %main {\n  " + line + "\n  %copy.1 = f32[] copy(f32[] %p)" \
        "\n  ROOT %t.1 = (f32[], s32[]) tuple(f32[] %a, s32[] %b), " \
        'metadata={op_name="jit(_train_step)"}\n}'
    assert pt.instructions(text) == [
        ("fusion.7", "fusion", "jit(_train_step)/optimizer/mul"),
        ("t.1", "tuple", "jit(_train_step)")]
    assert pt.op_names(text)["fusion.7"].endswith("optimizer/mul")


def test_json_round_trip_and_sample(handmade):
    trace = handmade.program_trace
    again = pt.ProgramTrace.from_json(trace.to_json())
    assert again == trace
    # Three ops from the first kernel on, and the `while` round them, cut
    # to where the fourth would start.
    piece = pt.sample(trace, max_events=3)
    assert piece.devices[0] == [
        pt.Event("while.1", 200, 250, ""),
        pt.Event("attention.2", 200, 100, "tpu_custom_call"),
        pt.Event("attention.3", 300, 150, "tpu_custom_call"),
        pt.Event("copy-start.1", 310, 2, "")]
    assert piece.devices[1] == trace.devices[1]
    assert set(piece.op_names) == {"fusion.1", "attention.2", "attention.3"}


@pytest.mark.parametrize(
    "path", RECORDED, ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_pieces_read_through_every_region_reader(
        path, tmp_path, monkeypatch):
    """A piece of each cell's traced run on the chip (PR 26): the events as
    the TPU runtime writes them map to regions, and every reader of the
    table and of the spans finds something to read."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    trace = pt.load(path)
    from perf import trace_reduce

    window = trace_reduce.window_of(trace_reduce.Trace(trace.devices, []))
    obs = observations(trace, window=window)
    table = pt.table_of(obs)
    assert sum(table["phases"].values()) == pytest.approx(
        table["busy_ms"])
    # The first ops of a step are its prologue (the micro-batch's slices and
    # copies): the piece's share is not the step's.
    assert 0.0 <= read(obs, "unattributed_frac.train") < 50.0
    for metric in ("fwd_ms.train", "flash_fwd_ms.train",
                   "attn_proj_ms.train", "mlp_ms.train"):
        assert read(obs, metric) > 0, metric
    kernels = [e for evs in trace.devices.values() for e in evs
               if e.detail == "tpu_custom_call"]
    assert kernels and all(
        pt.region_of(e.name, e.detail, trace.op_names.get(e.name, ""))
        in ("flash", "head_loss") for e in kernels)


@pytest.mark.parametrize("cell", registry.names("workloads"))
def test_run_cell_is_the_traced_run_with_the_new_metrics_listed(
        cell, monkeypatch):
    """The thirteen metrics' files name their cells (PR 37), so the resolved
    cell holds them already: ``python3 -m perf.program_trace --workload`` is
    ``perf.run --trace 1`` and leaves ``registry.workload`` alone."""
    from perf import run

    resolve = registry.workload
    listed = resolve(cell)["per_layer"]
    seen = {}
    monkeypatch.setattr(run, "main", lambda argv: seen.update(
        argv=argv, resolve=registry.workload,
        cell=registry.workload(cell)) or 0)
    assert pt.run_cell(["--workload", cell, "--seed", "7"]) == 0
    assert seen["argv"] == ["--workload", cell, "--seed", "7", "--trace", "1"]
    assert seen["resolve"] is resolve and registry.workload is resolve
    assert seen["cell"]["per_layer"] == listed
    naming = [m for m in registry.names("metrics")
              if cell in registry.metric(m).get("workloads", ())]
    assert set(naming) <= set(listed)
    assert set(NEW_METRICS) - set(listed) == NOT_LISTED.get(cell, set()) | (
        set() if cell in DENSE_CELLS else {"mlp_ms.train"})


def test_recorded_pieces_of_both_cells_are_kept():
    assert [os.path.basename(p) for p in RECORDED] == [
        "program_recorded_train-1.7b-fsdp4.json",
        "program_recorded_train-360m-1chip.json"]


def test_nothing_in_the_benchmark_rewrites_the_registry():
    """A cell's metrics are resolved in one place: no module under perf/
    assigns to ``registry.workload`` (``run_cell`` did, before a metric's
    file could name its cells)."""
    for base, _, files in os.walk(registry.ROOT):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(base, name)) as f:
                    assert "registry.workload =" not in f.read(), name
