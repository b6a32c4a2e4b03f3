"""perf/families/lfm2_moe.py, perf/runners/train_family.py and the readers
the LFM2 cell brings: counts against hand numbers, the runner end to end on
the CPU at a tiny width, the readers on a hand-made trace. No time measured
here is a result. (The reference against the program: tests/test_lfm2.py.)"""

import time

import jax
import jax.numpy as jnp
import pytest

from perf import controls, harness, lower_precision, program_trace as pt
from perf import registry
from perf.families import lfm2_moe as family
from perf.trace_reduce import Event
from tests.test_lfm2 import TINY

CELL = "train-lfm2-24b-ep8-1chip"
CFG = registry.config("lfm2-24b-a2b-ep8")


def test_the_file_is_the_catalog_row_but_for_the_cut():
    assert CFG["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "num_experts", "vocab_size"]
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "routed_scaling_factor": 1,
        "use_expert_bias": True,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    assert {k: CFG[k] for k in published} == published
    assert (CFG["num_experts_published"], CFG["vocab_size_published"],
            CFG["num_hidden_layers_published"]) == (64, 65536, 40)
    assert family.layer_kinds(CFG) == [
        ("conv", "dense"), ("attention", "moe"), ("conv", "moe"),
        ("conv", "moe"), ("conv", "moe")]


def test_parameter_counts_by_hand_and_by_the_program():
    conv = 3 * 2048 * 2048 + 2048 * 3 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert (conv, attention) == (16_783_360, 10_485_888)
    dense, expert, router = 3 * 2048 * 11776, 3 * 2048 * 1536, 2048 * 64 + 64
    assert (dense, expert) == (72_351_744, 9_437_184)
    norms = 2 * 2048
    want = (8192 * 2048 + 2048 + (conv + dense + norms)
            + (attention + 8 * expert + router + norms)
            + 3 * (conv + 8 * expert + router + norms))
    assert family.param_count(CFG) == want == 469_285_248
    config = family.gpt_config(CFG)
    assert config.num_parameters() == want
    assert config.experts_held == (0, 8) and config.num_experts == 64
    assert config.layer_kinds() == tuple(family.layer_kinds(CFG))
    assert family.param_count(TINY) == family.gpt_config(TINY).num_parameters()


def test_flops_by_hand():
    seq = 4096
    # One attention layer: 2 matmuls * 2 * 32 heads * 64 * S (S + 1) / 2.
    attn = 4 * 32 * 64 * seq * (seq + 1) // 2
    assert family.attention_flops_fwd(CFG, seq) == attn
    assert family.flash_train_flops(CFG, seq, 8) == 3 * attn * 8
    assert family.even_rows_per_token(CFG) == 0.5 and family.moe_layers(CFG) == 4
    matmul = (8192 * 2048 + 4 * (4 * 2048 * 2048)
              + 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 11776
              + 4 * (2048 * 64 + 0.5 * 3 * 2048 * 1536))
    taps = 4 * 2048 * 3
    want = 6 * (matmul + taps) + 3 * attn / seq
    assert family.train_flops_per_token(CFG, seq) == pytest.approx(want)
    assert 1.1e9 < want < 1.2e9
    # The experts count by the rows they are given.
    more = family.train_flops_per_token(CFG, seq, rows_per_token=1.0)
    assert more - want == pytest.approx(6 * 4 * 0.5 * 3 * 2048 * 1536)
    assert family.mfu(CFG, seq, 50_000.0, 1, 197e12) == pytest.approx(
        50_000 * want / 197e12)


def test_gmm_work_by_hand():
    rows, passes = 65536.0, 8       # 8192 rows a pass, 4 layers x 2 micro
    work = family.gmm_work(CFG, rows, passes)
    assert work["flops"] == 9 * 2 * rows * 2048 * 1536
    weights = 8 * 2048 * 1536
    assert work["bytes"] == (rows * 9 * (2048 + 1536) * 2
                             + passes * weights * (6 * 2 + 3 * 4))
    # Compute bound at an even load, by a little: the roofline's two sides.
    assert 1.0 < (work["flops"] / 197e12) / (work["bytes"] / 819e9) < 3.0


def test_a_file_the_program_cannot_run_is_refused():
    with pytest.raises(ValueError, match="norm_topk_prob"):
        family.gpt_config(dict(TINY, norm_topk_prob=False))
    with pytest.raises(ValueError, match="conv_bias"):
        family.gpt_config(dict(TINY, conv_bias=True))
    # Published as 1 and computed as 1: the program has no such factor.
    assert family.gpt_config(dict(TINY, routed_scaling_factor=1))
    with pytest.raises(ValueError, match="routed_scaling_factor"):
        family.gpt_config(dict(TINY, routed_scaling_factor=2.5))


# --- the runner on the CPU at a tiny width ------------------------------------

def _cell(**tolerance):
    cell = registry.workload(CELL)
    tol = dict(cell["config_file"]["reference_tolerance"]["bf16"],
               logit_rel_rms=0.1, logit_max_over_rms=0.9, loss_rel=1e-2,
               routing_flipped_frac=0.2, grad_leaf_rel=0.5)
    tol.update(tolerance)
    cell["config_file"] = dict(TINY, reference_tolerance={"bf16": tol})
    cell["traffic_file"] = dict(cell["traffic_file"], seq_len=64,
                                tokens_per_step=256)
    cell["job"].update(micro_batch=2, grad_accum=2)
    cell["peaks"] = registry.peaks("TPU v5 lite")
    return cell


def test_train_family_runner():
    cell = _cell()
    result = registry.code("runners", "train_family").run(
        cell, devices=jax.devices()[:1], seed=2 ** 31 + 11, seconds=1.0,
        trace=False, process_start=time.perf_counter())
    assert result.correct and result.failed == 0 and result.attempted >= 2
    assert result.end_to_end["train_tokens_per_s"] > 0
    counters = result.observations.counters
    steps = result.attempted
    assert counters["moe_rows_routed"] == steps * 256 * 4 * 8
    assert 0 < counters["moe_rows_held"] < counters["moe_rows_routed"]
    assert 0.25 <= counters["moe_max_load"] <= 1.0
    got = harness.read_per_layer(cell, result.observations)
    # Span and counter metrics are read; trace metrics find nothing.
    assert set(got) == {"compile_s", "step_ms.train", "data_wait_frac.train",
                        "moe_held_rows_frac.train", "mfu.train"}
    assert got["moe_held_rows_frac.train"]["value"] == pytest.approx(
        100 * counters["moe_rows_held"] / counters["moe_rows_routed"])
    assert 10 < got["moe_held_rows_frac.train"]["value"] < 50   # 4 of 16 held


@pytest.mark.parametrize("limit", ["logit_rel_rms", "grad_leaf_rel"])
def test_train_family_runner_sees_a_wrong_tolerance(limit):
    result = registry.code("runners", "train_family").run(
        _cell(**{limit: 1e-9}), devices=jax.devices()[:1], seed=3,
        seconds=0.2, trace=False, process_start=time.perf_counter())
    assert not result.correct


def test_the_comparisons_numbers_by_hand():
    runner = registry.code("runners", "train_family")
    passes = [
        {"sq_err": 1.0, "sq_ref": 100.0, "max_abs": 0.5, "logits": 50,
         "finite": True, "flipped": 3, "rows": 40},
        {"sq_err": 3.0, "sq_ref": 300.0, "max_abs": 1.5, "logits": 50,
         "finite": True, "flipped": 1, "rows": 40}]
    got = runner.errors(passes)
    assert got["logit_rel_rms"] == pytest.approx(0.1)
    assert got["ref_rms"] == pytest.approx(2.0)
    assert got["logit_max_over_rms"] == pytest.approx(0.75)
    assert got["routing_flipped_frac"] == pytest.approx(0.05)
    held = runner.judge(dict(got, loss_rel=2e-4, grad_leaf_rel=0.5),
                        {"logit_rel_rms": 0.2, "loss_rel": 1e-4,
                         "grad_leaf_rel": 0.5, "why": "by hand"})
    assert held == {"logit_rel_rms": [pytest.approx(0.1), 0.2, True],
                    "loss_rel": [2e-4, 1e-4, False],
                    "grad_leaf_rel": [0.5, 0.5, True],
                    "finite": [True, True, True]}


def test_a_state_left_unchanged_reads_one():
    """The gradient check's number is |got - want| / |want| of AdamW's first
    moment, leaf by leaf: 0 for the reference's own gradient (clipped as
    the trainer clips), 1 where the step left the state at its zeros."""
    import types

    runner = registry.code("runners", "train_family")
    trainer = types.SimpleNamespace(training_config=types.SimpleNamespace(
        grad_clip=1.0, beta1=0.9))
    gradient = {"a": jnp.full((4,), 3.0), "b": jnp.zeros((2,))}   # norm 6
    moment = {"a": 0.1 * gradient["a"] / 6.0, "b": jnp.zeros((2,))}
    leaves, norm = runner.gradient_errors_fn(trainer)(moment, gradient)
    assert float(norm) == pytest.approx(6.0)
    assert float(leaves["['a']"]) == pytest.approx(0.0, abs=1e-6)
    assert float(leaves["['b']"]) == 0.0
    zeros = jax.tree_util.tree_map(jnp.zeros_like, gradient)
    leaves, _ = runner.gradient_errors_fn(trainer)(zeros, gradient)
    assert float(leaves["['a']"]) == pytest.approx(1.0)


def test_the_reference_in_bf16_rounds_every_result():
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 1024))
    b = jax.random.normal(jax.random.PRNGKey(1), (1024, 32))

    def fn(tree, b):
        out = jax.nn.softmax(tree["a"] @ b, axis=-1)
        return {"probs": out, "ids": jax.lax.top_k(out, 2)[1]}

    got = jax.jit(lower_precision.in_bf16(fn))({"a": a}, b)
    want = fn({"a": a}, b)
    assert got["ids"].dtype == want["ids"].dtype and set(got) == set(want)
    probs = got["probs"]
    assert probs.dtype == jnp.float32
    assert bool(jnp.all(probs.astype(jnp.bfloat16).astype(jnp.float32)
                        == probs))
    # A matmul accumulates in bfloat16 a slice of 128 at a time: further
    # from float32 than bfloat16 operands under a float32 accumulator are.
    rel = lambda x, y: float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y))  # noqa: E731
    exact = jnp.matmul(a, b, precision="highest")
    operands = jnp.matmul(a.astype(jnp.bfloat16).astype(jnp.float32),
                          b.astype(jnp.bfloat16).astype(jnp.float32),
                          precision="highest")
    lowered = lower_precision.in_bf16(lambda a, b: a @ b)(a, b)
    assert rel(operands, exact) < 0.8 * rel(lowered, exact) < 0.02
    with pytest.raises(NotImplementedError, match="scan"):
        lower_precision.in_bf16(
            lambda a: jax.lax.map(lambda row: row * 2.0, a))(a)


def test_controls_read_false_through_the_cells_own_comparison():
    """At the tiny width and limits read there (program: logits 0.0074 /
    0.042, loss 2e-5, rows flipped 1.6%, gradient 0.08): the reference in
    bfloat16 fails by the rows it flips, each planted fault by the logits or
    the gradient, and the program passes."""
    cell = _cell(logit_rel_rms=0.02, logit_max_over_rms=0.12, loss_rel=1e-4,
                 routing_flipped_frac=0.05, grad_leaf_rel=0.2)
    lines = {r["what"]: r for r in controls.readings(
        cell, jax.devices()[:1], [2 ** 31 + 7],
        controls=("bf16", "taps_reversed", "expert_dropped",
                  "unnormalised_gates", "wgrad_expert_dropped"))}
    assert lines["program"]["correct"], lines["program"]["held"]
    assert set(lines["program"]["held"]) == {
        "logit_rel_rms", "logit_max_over_rms", "loss_rel",
        "routing_flipped_frac", "grad_leaf_rel", "finite"}
    assert len(lines["program"]["numbers"]["grad_leaves"]) == 33
    bf16 = lines["bf16"]["held"]
    assert not bf16["routing_flipped_frac"][2] and bf16["logit_rel_rms"][2]
    for fault in ("taps_reversed", "expert_dropped", "unnormalised_gates"):
        assert not lines[fault]["held"]["logit_rel_rms"][2], fault
    dropped = lines["wgrad_expert_dropped"]
    assert not dropped["held"]["grad_leaf_rel"][2]
    assert dropped["held"]["logit_rel_rms"][2] and dropped["held"]["loss_rel"][2]
    assert all(not lines[c]["correct"] for c in lines if c != "program")


# --- the readers on a hand-made trace -----------------------------------------

ROOT = "jit(_train_step)/while/body/closed_call/"
LAYER = "GPT._mixed_layers/TransformerBlock_2/"
OP_NAMES = {
    "fusion.1": ROOT + "jvp(GPT)/" + LAYER + "conv/in_proj/dot_general",
    "fusion.2": ROOT + "transpose(jvp(GPT))/" + LAYER + "conv/mul",
    "fusion.3": ROOT + "jvp(GPT)/" + LAYER
    + "moe_mlp/moe_mlp._dropless_ffn/route/sort",
    "experts.4": ROOT + "jvp(GPT)/" + LAYER
    + "moe_mlp/moe_mlp._dropless_ffn/experts/pallas_call",
    "experts.5": ROOT + "transpose(jvp(GPT))/" + LAYER
    + "moe_mlp/moe_mlp._dropless_ffn/experts/pallas_call",
    "fusion.6": ROOT + "jvp(GPT)/" + LAYER
    + "moe_mlp/moe_mlp._dropless_ffn/experts/mul",
    "attention.7": ROOT + "jvp(GPT)/" + LAYER + "attention/pallas_call",
    "fusion.8": ROOT + "jvp(GPT)/" + LAYER + "route/elsewhere",
}


def _observations(counters):
    kinds = {"experts.4": pt.PALLAS, "experts.5": pt.PALLAS,
             "attention.7": pt.PALLAS}
    events = [Event(name, 100 * i, 100, kinds.get(name, "kOutput"))
              for i, name in enumerate(OP_NAMES)]
    cell = dict(registry.workload(CELL), peaks=registry.peaks("TPU v5 lite"))
    obs = harness.Observations(
        cell=cell, spans=harness.Spans(), window=(0.0, 1.0),
        counters=counters, trace=object(), trace_window=(0, 850))
    obs.program_trace = pt.ProgramTrace({0: events}, OP_NAMES, [])
    return obs


def _read(obs, metric):
    spec = registry.metric(metric)
    return registry.code("readers", spec["reader"]).read(
        obs, **spec.get("args", {}))


def test_scope_readers_on_a_handmade_trace():
    obs = _observations({"steps": 2})
    # ns over 2 steps and one chip -> ms a step.
    assert _read(obs, "conv_ms.train") == pytest.approx(200 / 2e6)
    assert _read(obs, "moe_ms.train") == pytest.approx(400 / 2e6)
    # `route` counts under `moe_mlp` only.
    assert _read(obs, "moe_route_ms.train") == pytest.approx(100 / 2e6)
    from perf.readers import scope_ms
    assert scope_ms.read(obs, scopes=["conv"], phases=["bwd"]) == \
        pytest.approx(100 / 2e6)
    assert scope_ms.read(obs, scopes=["no_such_scope"]) is None


def test_gmm_roofline_on_a_handmade_trace():
    counters = {"steps": 2, "grad_accum": 2, "moe_rows_held": 131072.0,
                "moe_rows_routed": 1048576.0}
    obs = _observations(counters)
    work = family.gmm_work(CFG, 131072.0, 2 * 2 * 4)
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    # The two kernels under moe_mlp ran 200 ns; attention's is not counted.
    assert _read(obs, "gmm_roofline.train") == pytest.approx(
        100 * least / 200e-9)
    assert _read(obs, "moe_held_rows_frac.train") == 12.5


def test_readers_with_nothing_to_read_return_none():
    """A program that counts no rows or hands out no op names (the parent
    commit), or a run without a trace: nothing is read, nothing raises."""
    obs = _observations({"steps": 2, "grad_accum": 2})
    assert _read(obs, "gmm_roofline.train") is None
    assert _read(obs, "moe_held_rows_frac.train") is None
    obs.program_trace = pt.ProgramTrace(obs.program_trace.devices, {}, [])
    obs.counters["moe_rows_held"] = 8.0
    for metric in ("conv_ms.train", "moe_ms.train", "moe_route_ms.train",
                   "gmm_roofline.train"):
        assert _read(obs, metric) is None, metric
    obs.trace = obs.program_trace = None
    assert _read(obs, "conv_ms.train") is None
