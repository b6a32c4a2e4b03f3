"""perf/families/qwen3_next.py, perf/controls_qwen3_next.py and the reader the
Qwen3-Next cell brings: the file against the catalog row, counts against hand
numbers, the ``train_family`` runner and the controls end to end on the CPU
at a tiny width, the readers on a hand-made trace. No time measured here is
a result. (The reference against the program: tests/test_qwen3_next.py.)"""

import json
import time

import jax
import pytest

from perf import controls_qwen3_next as controls
from perf import harness, program_trace as pt, registry
from perf.families import qwen3_next as family
from perf.runners import train_family
from perf.trace_reduce import Event
from tests.perf.test_perf_registry import check_config_file
from tests.test_qwen3_next import TINY

CELL = "train-qwen3-next-ep32-1chip"
NAME = "qwen3-next-80b-a3b-ep32"
CFG = registry.config(NAME)

# The catalog's row (model-configs guide, architectures.jsonl,
# `Qwen3-Next-80B-A3B-Instruct`): its `config`, every key.
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_the_file_is_the_catalog_row_but_for_the_cut():
    check_config_file(NAME)
    assert CFG["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert CFG["source"] == ("https://huggingface.co/Qwen/Qwen3-Next-80B-"
                             "A3B-Instruct/blob/main/config.json")
    cut = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992}
    assert {k: CFG[k] for k in PUBLISHED} == {**PUBLISHED, **cut}
    assert (CFG["num_hidden_layers_published"], CFG["num_experts_published"],
            CFG["vocab_size_published"], CFG["experts_held_first"],
            CFG["experts_held_pick"]) == (48, 512, 151936, 0,
                                          "nearest_mean_load")
    assert CFG["vocab_size"] * 8 == CFG["vocab_size_published"]
    assert CFG["num_experts"] * 32 == CFG["num_experts_published"]
    assert family.held(CFG) == (0, 16) and family.router_width(CFG) == 512
    # The published period: linear, linear, linear, full.
    assert family.layer_types(CFG) == ["linear_attention"] * 3 + [
        "full_attention"]
    assert "32" in CFG["deployment"] and "mtp" in CFG["departures"]
    for key in family.WIDTH_KEYS:
        assert key in CFG, key


def test_parameter_counts_by_hand_and_by_the_program():
    h = 2048
    mixer = (h * (2 * 16 * 128 + 2 * 32 * 128) + h * 64 + 32 * 128 * h
             + 8192 * 4 + 2 * 32 + 128)
    attention = 3 * h * 16 * 256 + 2 * h * 2 * 256 + 2 * 256
    shared, router, expert = 3 * h * 512 + h, h * 512, 3 * h * 512
    assert (mixer, attention, shared, expert) == (
        33_718_464, 27_263_488, 3_147_776, 3_145_728)
    # A layer's parameters but for its routed experts.
    assert mixer + shared + router + 2 * h == 37_918_912
    assert attention + shared + router + 2 * h == 31_463_936
    want = (3 * 37_918_912 + 31_463_936 + 4 * 16 * expert
            + 2 * 18992 * h + h)
    assert family.param_count(CFG) == want == 424_340_544
    config = family.gpt_config(CFG)
    assert config.num_parameters() == want
    assert config.experts_held == (0, 16) and config.num_experts == 512
    assert family.param_count(TINY) == family.gpt_config(TINY).num_parameters()
    # The uncut model: 48 layers, every expert, the whole vocabulary.
    whole = dict(CFG, num_hidden_layers=48, num_experts=512,
                 vocab_size=151936)
    assert 79.6e9 < family.param_count(whole) < 79.7e9
    # 18 B a parameter: f32 master, AdamW m and v, f32 accumulation, bf16
    # copy: 45% of the chip's 15.75 GiB.
    assert 7.63e9 < 18 * want < 7.64e9
    assert 0.45 < 18 * want / (15.75 * 2 ** 30) < 0.46


def test_flops_by_hand():
    seq = 4096
    attn = 2 * 2 * 16 * 256 * seq * (seq + 1) // 2
    assert family.attention_flops_fwd(CFG, seq) == attn
    assert family.moe_layers(CFG) == 4
    assert family.even_rows_per_token(CFG) == 10 * 16 / 512
    # A value head of one layer: K K^T, Q K^T, W (2 C d_k each), U and the
    # in-chunk output (2 C d_v each), the inverse (2 C^2 / 3), W S, (Q
    # e^gamma) S and the state's update (2 d_k d_v each); 32 value heads.
    head = 2 * 64 * (3 * 128 + 2 * 128) + 2 * 64 ** 2 / 3 + 6 * 128 * 128
    assert head == pytest.approx(182_954.667, abs=1e-3)
    assert family.delta_flops_fwd_per_token(CFG) == pytest.approx(32 * head)
    linear = 2048 * (4096 + 8192) + 2048 * 64 + 4096 * 2048
    gated = 3 * 2048 * 4096 + 2 * 2048 * 512
    sparse = 2048 * 512 + 3 * 2048 * 512 + 2048 + 10 * 16 / 512 * 3 * 2048 * 512
    matmul = 3 * linear + gated + 4 * sparse + 18992 * 2048
    want = 6 * matmul + 3 * 3 * 32 * head + 3 * attn / seq
    assert family.train_flops_per_token(CFG, seq) == pytest.approx(want)
    # About 1.28 GFLOP a token trained, 41.8 TFLOP a step of 32,768 tokens.
    assert 1.27e9 < want < 1.29e9
    assert 41.7e12 < want * 32768 < 42.1e12
    more = family.train_flops_per_token(CFG, seq, rows_per_token=1.0)
    assert more - want == pytest.approx(
        6 * 4 * (1.0 - 10 * 16 / 512) * 3 * 2048 * 512)
    assert family.mfu(CFG, seq, 40_000.0, 1, 197e12) == pytest.approx(
        40_000 * want / 197e12)


def test_gated_delta_work_by_hand():
    small = dict(TINY)
    # hk 2 of d_k 16, hv 4 of d_v 16, chunk 64: a value head's forward.
    head = 2 * 64 * (3 * 16 + 2 * 16) + 2 * 64 ** 2 / 3 + 6 * 16 * 16
    work = family.gated_delta_work(small, 10)
    assert work["flops"] == pytest.approx(3 * 4 * head * 10 * 3)  # 3 layers
    # q, k (2 x 16 lanes each), v, o (4 x 16) in 2 bytes, g and beta (4) in
    # 4 bytes: moved once forward and their gradients once backward.
    once = (2 * 32 + 2 * 64) * 2 + 2 * 4 * 4
    assert work["bytes"] == 2 * once * 10 * 3
    # The cell's step: about 1.73 TFLOP and 4.9 GB, bound by its FLOPs at
    # 8.8 ms.
    cell = family.gated_delta_work(CFG, 32768)
    assert 1.72e12 < cell["flops"] < 1.74e12
    assert 4.8e9 < cell["bytes"] < 5.0e9
    assert 8.7e-3 < cell["flops"] / 197e12 < 8.8e-3
    assert cell["flops"] / 197e12 > cell["bytes"] / 819e9


def test_flash_and_gmm_work_by_hand():
    # The one attention layer: 16 query heads of 256 lanes over 2 K/V heads.
    fwd = 2 * 2 * 16 * 256 * 4096 * 4097 / 2
    work = family.flash_work(CFG, 4096, 8)
    assert work["flops"] == 3 * fwd * 8
    q, kv = 16 * 256, 2 * 256
    values = 4096 * ((q + 2 * kv) + q + 2 * (q + 2 * kv) + 2 * q)
    assert work["bytes"] == 8 * values * 2
    rows, passes = 100.0, 3
    gmm = family.gmm_work(TINY, rows, passes)
    assert gmm["flops"] == 9 * 2 * rows * 64 * 24
    weights = 4 * 64 * 24                               # four held, a matrix
    assert gmm["bytes"] == (rows * 9 * (64 + 24) * 2
                            + passes * weights * (6 * 2 + 3 * 4))


def test_a_file_the_program_cannot_run_is_refused():
    for key, value in (("norm_topk_prob", False), ("hidden_act", "gelu"),
                       ("tie_word_embeddings", True),
                       ("decoder_sparse_step", 2), ("mlp_only_layers", [1]),
                       ("use_sliding_window", True),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            family.gpt_config(dict(TINY, **{key: value}))
    config = family.gpt_config(CFG)
    assert (config.moe_router, config.attention_output_gate,
            config.moe_shared_expert_gate, config.partial_rotary_factor,
            config.tie_word_embeddings, config.moe_aux_weight) == (
                "softmax", True, True, 0.25, False, 0.0)


def test_a_program_without_the_family_fails_at_once(monkeypatch):
    """The parent commit under this PR's benchmark files: its GPTConfig has
    no such fields; the run ends before the chip, with another exit code
    than 0."""
    from tpu_trainer.models import config as program_config

    class Before:
        def __init__(self, vocab_size=0, hidden_size=0):
            pass

    monkeypatch.setattr(program_config, "GPTConfig", Before)
    with pytest.raises(SystemExit, match="cannot state this configuration"):
        family.gpt_config(CFG)


# --- the runner and the controls on the CPU at a tiny width -------------------

def _cell(**tolerance):
    cell = registry.workload(CELL)
    tol = dict(cell["config_file"]["reference_tolerance"]["bf16"],
               logit_rel_rms=0.1, logit_max_over_rms=0.9, loss_rel=1e-2,
               routing_flipped_frac=0.3, grad_leaf_rel=0.6)
    tol.update(tolerance)
    cell["config_file"] = dict(TINY, reference_tolerance={"bf16": tol})
    # Four chunks of the program's 64 a sequence: a state is carried past
    # the second.
    cell["traffic_file"] = dict(cell["traffic_file"], seq_len=256,
                                tokens_per_step=1024)
    cell["job"].update(micro_batch=2, grad_accum=2)
    cell["peaks"] = registry.peaks("TPU v5 lite")
    return cell


def test_train_family_runner(capsys):
    cell = _cell()
    result = registry.code("runners", "train_family").run(
        cell, devices=jax.devices()[:1], seed=2 ** 31 + 11, seconds=1.0,
        trace=False, process_start=time.perf_counter())
    assert result.correct and result.failed == 0 and result.attempted >= 2
    assert result.end_to_end["train_tokens_per_s"] > 0
    notes = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    window, = [n for n in notes if n["note"] == "train_window"]
    # Half of the experts held: the row buffers hold every row and no pass
    # can overflow, so none is counted.
    assert window["moe_overflow_passes"] is None and window["mtp_loss"] is None
    assert set(result.compared) == {*train_family.LIMITS, "finite",
                                    "losses_not_finite"}
    assert all(reading <= limit
               for reading, limit in result.compared.values())
    counters = result.observations.counters
    # Four expert layers, two experts a token.
    assert counters["moe_rows_routed"] == result.attempted * 1024 * 2 * 4
    assert 0 < counters["moe_rows_held"] < counters["moe_rows_routed"]
    got = harness.read_per_layer(cell, result.observations)
    # Span and counter metrics are read; trace metrics find nothing.
    assert {"compile_s", "step_ms.train", "data_wait_frac.train",
            "moe_held_rows_frac.train", "mfu.train"} <= set(got)
    assert not {"linear_attn_ms.train", "gated_delta_ms.train",
                "gated_delta_roofline.train"} & set(got)
    assert got["mfu.train"]["value"] == pytest.approx(
        100.0 * window["mfu"], rel=1e-6)


# Of the controls, those that the logits show at the tiny width; the two
# faults of the delta rule itself barely move this small stack's logits at
# the initialiser's scale (the linear-attention layers' part of them is
# small), and at larger scales the bf16 program itself is chaotic here
# (test_qwen3_next.py, TOL), so they are held to move the rule below, and
# read through the whole comparison on the chip at the cell's shapes.
LOGIT_CONTROLS = ("bf16_reference", "output_gate_dropped",
                  "rope_over_the_whole_head", "gate_before_norm",
                  "shared_expert_gate_dropped")


def test_controls_read_false_through_the_cells_own_comparison():
    """Each control through ``train_family``'s own comparison at the tiny
    width: the program alone is held, every control is refused by at least
    one limit. The limits are tiny-width ones (between the program's
    readings here and the controls'), not the configuration's."""
    cell = _cell(logit_rel_rms=0.03, logit_max_over_rms=0.3, loss_rel=1e-3,
                 routing_flipped_frac=0.2, grad_leaf_rel=0.3)
    record = controls.run_controls(
        cell, devices=jax.devices()[:1], seed=7, names=LOGIT_CONTROLS)
    assert record["program"]["correct"], record["program"]
    assert set(record["controls"]) == set(LOGIT_CONTROLS)
    for name in LOGIT_CONTROLS:
        assert not record["controls"][name]["correct"], (
            name, record["controls"][name])


def test_the_faults_of_the_rule_move_the_rule():
    """The delta correction dropped (the reference's side) and the decay
    lost at one chunk boundary (the program's side), on four chunks of 64
    with decays slow enough that a state outlives its chunk: each moves the
    rule's output by a large share of it, and the boundary's fault only
    from the third chunk on."""
    from tests.test_qwen3_next import _rel, _rule_inputs
    from tpu_trainer.ops.gated_delta import gated_delta

    args = _rule_inputs(256, decay=0.05, seed=5)
    assert set(controls.CONTROLS) == {*LOGIT_CONTROLS, *(
        "delta_correction_dropped", "decay_lost_at_a_chunk_boundary")}
    with jax.default_matmul_precision("highest"):
        want = family.delta_rule(*args)
        healthy = gated_delta(*args)[0]
        with controls.planted(family, "delta_correction_dropped"):
            dropped = family.delta_rule(*args)
        with controls.decay_lost_at_a_chunk_boundary():
            lost = jax.jit(lambda *a: gated_delta(*a)[0])(*args)
    assert _rel(healthy, want) < 1e-5
    assert _rel(dropped, want) > 0.3
    assert _rel(lost[:, :128], want[:, :128]) < 1e-5
    assert _rel(lost[:, 128:], want[:, 128:]) > 0.1


# --- the readers on a hand-made trace -------------------------------------------

ROOT = "jit(_train_step)/while/body/closed_call/"
LAYER = "GPT._mixed_layers/TransformerBlock_0/"
OP_NAMES = {
    "fusion.1": ROOT + "jvp(GPT)/" + LAYER
    + "linear_attn/in_proj_qkvz/dot_general",
    "fusion.2": ROOT + "jvp(GPT)/" + LAYER + "linear_attn/taps/mul",
    "fusion.3": ROOT + "jvp(GPT)/" + LAYER
    + "linear_attn/gated_delta/dot_general",
    "fusion.4": ROOT + "transpose(jvp(GPT))/" + LAYER
    + "linear_attn/gated_delta/while/body/dot_general",
    "fusion.5": ROOT + "jvp(GPT)/" + LAYER + "attention/gated_delta/x",
    "fusion.6": ROOT + "jvp(GPT)/" + LAYER
    + "moe_mlp/shared_expert/up_proj/dot_general",
    "attention.7": ROOT + "transpose(jvp(GPT))/" + LAYER
    + "attention/pallas_call",
}
COUNTERS = {"steps": 2, "grad_accum": 4, "seq_len": 4096,
            "sequences_per_step": 8, "tokens_per_step": 32768}


def _observations(counters):
    kinds = {name: pt.PALLAS for name in OP_NAMES
             if name.split(".")[0] == "attention"}
    events = [Event(name, 100 * i, 100, kinds.get(name, "kOutput"))
              for i, name in enumerate(OP_NAMES)]
    cell = dict(registry.workload(CELL), peaks=registry.peaks("TPU v5 lite"))
    obs = harness.Observations(
        cell=cell, spans=harness.Spans(), window=(0.0, 1.0),
        counters=counters, trace=object(), trace_window=(0, 750))
    obs.program_trace = pt.ProgramTrace({0: events}, OP_NAMES, [])
    return obs


def _read(obs, metric):
    spec = registry.metric(metric)
    return registry.code("readers", spec["reader"]).read(
        obs, **spec.get("args", {}))


def test_scope_readers_on_a_handmade_trace():
    obs = _observations(dict(COUNTERS))
    # ns over 2 steps and one chip -> ms a step: the mixer whole is its
    # four ops, the rule the two under `gated_delta` INSIDE `linear_attn`.
    assert _read(obs, "linear_attn_ms.train") == pytest.approx(400 / 2e6)
    assert _read(obs, "gated_delta_ms.train") == pytest.approx(200 / 2e6)
    assert _read(obs, "shared_expert_ms.train") == pytest.approx(100 / 2e6)
    # The taps' scope is not the gated short convolution's, nor is the rule
    # the state-space scan.
    assert _read(obs, "conv_ms.train") is None
    assert _read(obs, "ssm_ms.train") is None


def test_gated_delta_roofline_on_a_handmade_trace():
    obs = _observations(dict(COUNTERS))
    work = family.gated_delta_work(CFG, 2 * 32768)
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert least == work["flops"] / 197e12
    # Every leaf op under `gated_delta` in `linear_attn`: 200 ns.
    assert _read(obs, "gated_delta_roofline.train") == pytest.approx(
        100 * least / 200e-9)


def test_readers_with_nothing_to_read_return_none():
    """A program without the scopes (the parent), one that hands out no op
    names, a family that counts no such work, or a run without a trace:
    nothing is read, nothing raises."""
    metrics = ("linear_attn_ms.train", "gated_delta_ms.train",
               "gated_delta_roofline.train")
    obs = _observations(dict(COUNTERS))
    names = {k: v.replace("linear_attn", "mamba").replace("gated_delta", "x")
             for k, v in OP_NAMES.items()}
    obs.program_trace = pt.ProgramTrace(obs.program_trace.devices, names, [])
    for metric in metrics:
        assert _read(obs, metric) is None, metric
    obs.program_trace = pt.ProgramTrace(obs.program_trace.devices, {}, [])
    for metric in metrics:
        assert _read(obs, metric) is None, metric
    other = _observations(dict(COUNTERS))
    other.cell = dict(other.cell, config_file=registry.config(
        "nemotron-twotower-30b-a3b-ep16"))
    assert _read(other, "gated_delta_roofline.train") is None
    obs.trace = obs.program_trace = None
    for metric in metrics:
        assert _read(obs, metric) is None, metric
