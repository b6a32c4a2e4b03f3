"""utils/profiling.py: scopes inside the jitted step, host spans on the
profiler's clock, and the compile log (ISSUE 26).

The compiled step's text is read through ``perf/program_trace.py`` (the
benchmark's reduction), so what these tests pin is what the per-layer
metrics read. The compiles for a described v5e are in
``tests/test_chip_compile.py``.
"""

import collections
import contextlib
import dataclasses
import gc
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from perf import program_trace
from tpu_trainer.models.config import GPTConfig
from tpu_trainer.parallel.mesh import MeshConfig, make_mesh
from tpu_trainer.serving.tracing import ServingLedger
from tpu_trainer.training.config import TrainingConfig
from tpu_trainer.training.trainer import ParallelConfig, Trainer
from tpu_trainer.utils import profiling
from tpu_trainer.utils.telemetry import GoodputLedger


# Opcodes that move or name data and do no work of their own.
STRUCTURAL = frozenset({"parameter", "get-tuple-element", "tuple", "constant",
                        "bitcast", "while", "conditional", "call"})


def tiny_trainer(accum=2, **model_kw):
    model = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=2, intermediate_size=64, max_seq_len=16,
                      dropout=0.0, attention_dropout=0.0,
                      use_flash_attention=False, **model_kw)
    train = TrainingConfig(batch_size=2, gradient_accumulation_steps=accum,
                           max_seq_len=16, mixed_precision="fp32")
    mesh_cfg = MeshConfig(data=1, fsdp=1)
    return Trainer(model, train, ParallelConfig(mesh_cfg, "replicated"),
                   mesh=make_mesh(mesh_cfg, devices=jax.devices()[:1]))


def batches(trainer, n, seed=0, seq=16):
    rng = np.random.default_rng(seed)
    rows = trainer.global_batch_size * \
        trainer.training_config.gradient_accumulation_steps
    return [rng.integers(0, 64, (rows, seq), dtype=np.int32)
            for _ in range(n)]


@contextlib.contextmanager
def open_trace(trace_dir):
    """A profiler trace without the interpreter's own calls."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_events(trace_dir):
    """``[(name, stats)]`` of every event on the host plane of a trace."""
    path = glob.glob(str(trace_dir) + "/plugins/profile/*/*.xplane.pb")[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(ev.name, dict(ev.stats)) for ev in line.events]
    return out


# --- (i) scopes: the compiled step's text maps to regions ---------------------

@pytest.fixture(scope="module")
def small_step_rows():
    """``(name, opcode, op_name)`` of the `small` preset's compiled step
    (4 x 128 tokens, 2 accumulations), from shapes alone."""
    model = dataclasses.replace(
        GPTConfig.preset("small"), max_seq_len=128, fused_loss=True,
        dropout=0.0, attention_dropout=0.0)
    train = TrainingConfig(batch_size=2, gradient_accumulation_steps=2,
                           max_seq_len=128, mixed_precision="bf16")
    mesh_cfg = MeshConfig(data=1, fsdp=1)
    trainer = Trainer(model, train, ParallelConfig(mesh_cfg, "replicated"),
                      mesh=make_mesh(mesh_cfg, devices=jax.devices()[:1]))
    state = jax.eval_shape(trainer.init_state, 0)
    batch = jax.ShapeDtypeStruct((2, 2, 128), np.int32)
    return program_trace.instructions(
        trainer.compiled_step_text(state, batch))


def test_every_named_instruction_maps_to_a_region(small_step_rows):
    assert len(small_step_rows) > 1000
    for name, opcode, op_name in small_step_rows:
        assert program_trace.region_of(name, "", op_name) in \
            program_trace.REGIONS
        assert program_trace.phase_of(op_name) in program_trace.PHASES


@pytest.mark.parametrize(
    "scope", ["grad_accum", "grad_finalize", "optimizer", "head_loss"])
def test_each_new_scope_owns_instructions(small_step_rows, scope):
    owned = [r for r in small_step_rows
             if program_trace.scope_region(r[2]) == scope]
    assert owned, f"no instruction under the scope {scope!r}"
    phases = {program_trace.phase_of(r[2]) for r in owned}
    if scope == "head_loss":
        assert phases >= {"fwd", "bwd"}
    else:
        assert phases == {"opt"}


def test_unattributed_instructions_are_few(small_step_rows):
    """Of the instructions that do work (parameters, tuples and the loop
    containers aside, and the reducers' one-line bodies, whose op_name is
    bare), under 5% fall to no region."""
    work = [r for r in small_step_rows
            if r[1] not in STRUCTURAL and "/" in r[2]]
    by = collections.Counter(program_trace.scope_region(r[2]) for r in work)
    assert by["other"] / len(work) < 0.05, by


def test_forward_and_backward_of_the_mlp_are_told_apart(small_step_rows):
    phases = collections.Counter(
        program_trace.phase_of(r[2]) for r in small_step_rows
        if r[1] == "dot" and program_trace.scope_region(r[2]) == "mlp")
    # 12 layers: each matmul once forward and twice backward (dx, dw).
    assert phases["fwd"] >= 12 * 2 and phases["bwd"] >= 2 * phases["fwd"]
    assert set(phases) == {"fwd", "bwd"}


# --- (iii) host spans ----------------------------------------------------------

def test_span_lands_on_the_host_plane_with_its_step(tmp_path):
    with open_trace(tmp_path):
        with profiling.span("trainer:train_step", step=7, variant="plain"):
            with profiling.span("inner"):
                pass
    found = dict(host_events(tmp_path))
    assert found["tpu_trainer:trainer:train_step"] == {
        "step": 7, "variant": "plain"}
    assert "tpu_trainer:inner" in found


@pytest.mark.parametrize("ledger_cls,prefix,category", [
    (GoodputLedger, "goodput:", "data_wait"),
    (ServingLedger, "serve:", "dispatch"),
])
def test_ledgers_emit_spans_and_keep_their_records(
        tmp_path, ledger_cls, prefix, category):
    def run(traced):
        t = [0.0]
        ledger = ledger_cls(clock=lambda: t[0])
        with (open_trace(tmp_path) if traced
              else profiling.span("untraced")):
            with ledger.track(category):
                t[0] += 2.0
        t[0] += 2.0
        return ledger.record(final=True)

    plain, traced = run(False), run(True)
    assert plain == traced
    assert plain[category + "_seconds"] == 2.0
    assert plain[category + "_frac"] == 0.5
    names = [name for name, _ in host_events(tmp_path)]
    assert names.count("tpu_trainer:" + prefix + category) == 1
    assert "tpu_trainer:untraced" not in names


def test_with_no_trace_open_nothing_is_kept():
    before = len(profiling.compile_log())
    for step in range(100):
        with profiling.span("trainer:train_step", step=step):
            assert profiling._stack()[-1] == ("trainer:train_step", step)
    assert profiling._stack() == []
    assert len(profiling.compile_log()) == before


# --- (iv) the compile log -------------------------------------------------------

def test_compile_log_names_the_span_and_call_that_compiled():
    trainer = tiny_trainer()
    state = trainer.init_state(0)
    start = len(profiling.compile_log())
    for batch in batches(trainer, 3):
        state, _ = trainer.train_step(state, batch)
    first = profiling.compile_log()[start:]
    compiles = [e for e in first if e.kind == "compile"
                and e.fun_name == "jit(_train_step)"]
    assert [(e.span, e.step) for e in compiles] == [
        ("trainer:train_step", 0)]
    assert {e.kind for e in first if e.span == "trainer:train_step"} >= {
        "trace", "lower", "compile"}
    assert all(e.start <= e.end and e.seconds >= 0 for e in first)

    # Ten steady steps compile nothing.
    steady = len(profiling.compile_log())
    for batch in batches(trainer, 10, seed=1):
        state, _ = trainer.train_step(state, batch)
    assert profiling.compile_log()[steady:] == []

    # A new shape recompiles, and the log says under which call.
    state, _ = trainer.train_step(state, batches(trainer, 1, seq=8)[0])
    again = [e for e in profiling.compile_log()[steady:]
             if e.kind == "compile"]
    assert [(e.span, e.step, e.fun_name) for e in again] == [
        ("trainer:train_step", 13, "jit(_train_step)")]


def test_of_nested_entries_the_log_keeps_the_outermost():
    """An unrolled step traces thousands of inner jits, and its lowering
    traces thousands of small functions more (the random bits'); kept, they
    pushed the set-up's own entries out of the log (on the chip, PR 26)."""
    inner = jax.jit(lambda x: x * 2)

    @jax.jit
    def outer(x, key):
        return inner(x) + inner(x + 1) + jax.random.normal(key)

    key = jax.random.PRNGKey(0)
    start = len(profiling.compile_log())
    outer(np.float32(3.0), key)
    new = profiling.compile_log()[start:]
    assert [(e.kind, e.fun_name) for e in new] == [
        ("trace", "outer"), ("lower", "jit(outer)"), ("compile", "jit(outer)")]


def test_outermost_drops_nested_entries_and_cache_reads():
    entry = profiling.CompileEntry
    log = [entry("trace", 0.2, 10.3, "inner", None, None, 0),     # in outer
           entry("trace", 1.0, 11.0, "outer", None, None, 0),
           entry("lower", 0.5, 11.5, "outer", None, None, 0),
           entry("cache_read", 0.3, 11.9, None, None, None, 0),   # in compile
           entry("compile", 0.5, 12.0, "outer", None, None, 0)]
    kept = program_trace.outermost(log)
    assert [(e.kind, e.fun_name) for e in kept] == [
        ("trace", "outer"), ("lower", "outer"), ("compile", "outer")]
    assert program_trace.entries_within(kept, [(11.2, 12.5)]) == kept[1:]


def test_compiled_step_text_needs_no_live_buffers():
    trainer = tiny_trainer()
    with pytest.raises(ValueError, match="has not run yet"):
        trainer.compiled_step_text()
    state = trainer.init_state(0)
    batch = trainer.place_batch(batches(trainer, 1)[0])
    want = trainer.compiled_step_text(state, batch)
    state, _ = trainer.train_step(state, batch)       # donates the state
    assert trainer.compiled_step_text() == want
    assert profiling.program_texts()["train_step"] == want
    names = program_trace.op_names(want)
    assert any("/optimizer/" in path for path in names.values())
    # Read after the run has dropped its trainer (perf/program_trace.py).
    del trainer, state, batch
    gc.collect()
    assert profiling.program_texts()["train_step"] == want


# --- (v) tracing changes nothing that is computed ----------------------------------

def test_losses_are_bit_identical_with_a_trace_open(tmp_path):
    def losses(traced):
        trainer = tiny_trainer()
        state = trainer.init_state(3)
        out = []
        with (open_trace(tmp_path) if traced
              else profiling.span("untraced")):
            for batch in batches(trainer, 3, seed=5):
                state, metrics = trainer.train_step(state, batch)
                out.append(np.asarray(metrics["loss"]).tobytes())
        return out

    assert losses(False) == losses(True)
    steps = [stats for name, stats in host_events(tmp_path)
             if name == "tpu_trainer:trainer:train_step"]
    assert [s["step"] for s in steps] == [0, 1, 2]
    assert {s["variant"] for s in steps} == {"plain"}
