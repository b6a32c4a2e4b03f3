"""perf/families/nemotron_h.py, perf/controls_nemotron_h.py and the reader the
Nemotron-Labs-TwoTower cell brings: the file against the catalog row, counts
against hand numbers, the ``train_family`` runner and the controls end to end
on the CPU at a tiny width, the readers on a hand-made trace. No time
measured here is a result. (The reference against the program:
tests/test_nemotron_h.py.)"""

import json
import time

import jax
import pytest

from perf import controls_nemotron_h as controls
from perf import harness, program_trace as pt, registry
from perf.families import nemotron_h as family
from perf.runners import train_family
from perf.trace_reduce import Event
from tests.perf.test_perf_registry import check_config_file
from tests.test_nemotron_h import TINY

CELL = "train-nemotron-twotower-ep16-1chip"
NAME = "nemotron-twotower-30b-a3b-ep16"
CFG = registry.config(NAME)

# The catalog's row (model-configs guide, architectures.jsonl,
# `Nemotron-Labs-TwoTower-30B-A3B-Base-BF16`): its `config`, every key.
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_limit": [0, None],
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}


def test_the_file_is_the_catalog_row_but_for_the_cut():
    check_config_file(NAME)
    assert CFG["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    assert CFG["source"] == (
        "https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-"
        "BF16/blob/main/config.json")
    cut = {"num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
           "n_routed_experts": 8, "vocab_size": 16384}
    assert {k: CFG[k] for k in PUBLISHED} == {**PUBLISHED, **cut}
    # The cut is the published pattern's first nine blocks, and the
    # published values stand beside the cut ones.
    assert PUBLISHED["hybrid_override_pattern"].startswith(
        CFG["hybrid_override_pattern"])
    assert (CFG["num_hidden_layers_published"],
            CFG["hybrid_override_pattern_published"],
            CFG["n_routed_experts_published"], CFG["vocab_size_published"],
            CFG["experts_held_first"]) == (
                52, PUBLISHED["hybrid_override_pattern"], 128, 131072, 0)
    assert CFG["vocab_size"] * 8 == CFG["vocab_size_published"]
    assert CFG["n_routed_experts"] * 16 == CFG["n_routed_experts_published"]
    assert family.held(CFG) == (0, 8) and family.router_width(CFG) == 128
    assert [family.blocks_of(CFG, k) for k in "M*E-"] == [4, 1, 4, 0]
    # What is not built is said in the file, under its own key.
    assert {"second_tower", "denoising_objective"} <= set(CFG["departures"])
    for key in family.WIDTH_KEYS:
        assert key in CFG, key


def test_parameter_counts_by_hand_and_by_the_program():
    mamba = (2688 * (4096 + 6144 + 64) + 6144 * 4 + 6144 + 3 * 64 + 4096
             + 4096 * 2688 + 2688)
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    expert, shared = 2 * 2688 * 1856, 2 * 2688 * 3712
    router = 2688 * 128 + 128
    assert (mamba, attention, expert, shared, router) == (
        38_744_896, 23_399_040, 9_977_856, 19_955_712, 344_192)
    sparse = 8 * expert + shared + router + 2688
    want = 2 * 16384 * 2688 + 2688 + 4 * mamba + 4 * sparse + attention
    assert family.param_count(CFG) == want == 666_963_456
    config = family.gpt_config(CFG)
    assert config.num_parameters() == want
    assert config.experts_held == (0, 8) and config.num_experts == 128
    assert (config.head_dim, config.attention_width, config.kv_heads) == (
        128, 4096, 2)
    assert (config.mamba_inner, config.mamba_conv_dim) == (4096, 6144)
    assert config.shared_expert_width == 3712 and config.ffn_matrices == 2
    assert family.param_count(TINY) == family.gpt_config(TINY).num_parameters()
    # The uncut model: 52 blocks, every expert, the whole vocabulary.
    whole = dict(CFG, num_hidden_layers=52, n_routed_experts=128,
                 vocab_size=131072,
                 hybrid_override_pattern=PUBLISHED["hybrid_override_pattern"])
    assert 31.5e9 < family.param_count(whole) < 31.7e9
    # 18 B a parameter: f32 master, AdamW m and v, f32 accumulation, bf16 copy.
    assert 12.0e9 < 18 * want < 12.1e9


def test_flops_by_hand():
    seq = 4096
    attn = 2 * 2 * 32 * 128 * seq * (seq + 1) // 2
    assert family.attention_flops_fwd(CFG, seq) == attn
    assert family.moe_layers(CFG) == 4
    assert family.even_rows_per_token(CFG) == 6 * 8 / 128
    scan = 2 * (128 * 128 * 8 + 128 * 64 * 64 + 2 * 64 * 128 * 64)
    assert family.ssd_flops_fwd_per_token(CFG) == scan == 3_407_872
    sparse = 2688 * 128 + 2 * 2688 * 3712 + 0.375 * 2 * 2688 * 1856
    matmul = (4 * (2688 * 10304 + 4096 * 2688)
              + 2 * 2688 * 4096 + 2 * 2688 * 256
              + 4 * sparse + 16384 * 2688)
    want = 6 * matmul + 3 * 4 * scan + 3 * attn / seq
    assert family.train_flops_per_token(CFG, seq) == pytest.approx(want)
    # The issue's reckoning: 684 M forward, about 2.05 G a token trained.
    assert 2.04e9 < want < 2.06e9
    more = family.train_flops_per_token(CFG, seq, rows_per_token=1.0)
    assert more - want == pytest.approx(6 * 4 * 0.625 * 2 * 2688 * 1856)
    assert family.mfu(CFG, seq, 30_000.0, 1, 197e12) == pytest.approx(
        30_000 * want / 197e12)


def test_ssd_and_gmm_work_by_hand():
    small = dict(TINY, chunk_size=4)
    # A token of one block, forward: scores 2 Q N G, scores x x 2 Q P H,
    # chunk states 2 P N H, the carried part 2 N P H.
    fwd = 2 * 4 * 16 * 2 + 2 * 4 * 8 * 8 + 2 * 8 * 16 * 8 + 2 * 16 * 8 * 8
    assert family.ssd_flops_fwd_per_token(small) == fwd == 4864
    work = family.ssd_work(small, 10)
    assert work["flops"] == 3 * fwd * 10 * 2            # two `M` blocks
    # x 64 + B 32 + C 32 values of 2 bytes and dt 8 of 4, read forward,
    # read backward, their gradients written; y and its cotangent 64 each.
    operands = (64 + 32 + 32) * 2 + 8 * 4
    assert work["bytes"] == (3 * operands + 2 * 64 * 2) * 10 * 2
    # At the cell's sizes the scan is bound by memory.
    cell = family.ssd_work(CFG, 32768)
    assert cell["flops"] == 3 * 3_407_872 * 32768 * 4
    assert (cell["bytes"] / 819e9) > (cell["flops"] / 197e12)
    rows, passes = 100.0, 3
    gmm = family.gmm_work(TINY, rows, passes)
    assert gmm["flops"] == 6 * 2 * rows * 48 * 24       # 2 + 2 gmm, 2 tgmm
    weights = 2 * 48 * 24                               # two held, a matrix
    assert gmm["bytes"] == (rows * 6 * (48 + 24) * 2
                            + passes * weights * (4 * 2 + 2 * 4))


def test_a_file_the_program_cannot_run_is_refused():
    for key, value in (("norm_topk_prob", False), ("n_group", 8),
                       ("use_conv_bias", False), ("mlp_hidden_act", "silu"),
                       ("tie_word_embeddings", True), ("attention_bias", True),
                       ("time_step_limit", [0.001, 0.1])):
        with pytest.raises(ValueError, match=key):
            family.gpt_config(dict(TINY, **{key: value}))
    with pytest.raises(ValueError, match="one norm epsilon"):
        family.gpt_config(dict(TINY, norm_eps=1e-6))
    config = family.gpt_config(TINY)
    assert (config.moe_routed_scale, config.moe_gate_eps, config.ffn_kind,
            config.tie_word_embeddings, config.rotary_embedding,
            config.one_sublayer_blocks) == (2.5, 1e-20, "relu2", False,
                                            False, True)


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
        family.gpt_config(TINY)


# --- the runner and the controls on the CPU at a tiny width -------------------

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


def test_train_family_runner(capsys):
    cell = _cell()
    result = registry.code("runners", "train_family").run(
        cell, devices=jax.devices()[:1], seed=2 ** 31 + 11, seconds=1.0,
        trace=False, process_start=time.perf_counter())
    assert result.correct and result.failed == 0 and result.attempted >= 2
    assert result.end_to_end["train_tokens_per_s"] > 0
    notes = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    window, = [n for n in notes if n["note"] == "train_window"]
    assert window["moe_overflow_passes"] >= 0 and window["mtp_loss"] is None
    assert set(result.compared) == {*train_family.LIMITS, "finite",
                                    "losses_not_finite"}
    assert all(reading <= limit
               for reading, limit in result.compared.values())
    counters = result.observations.counters
    # Two expert blocks, three experts a token.
    assert counters["moe_rows_routed"] == result.attempted * 256 * 3 * 2
    assert 0 < counters["moe_rows_held"] < counters["moe_rows_routed"]
    got = harness.read_per_layer(cell, result.observations)
    # Span and counter metrics are read; trace metrics find nothing.
    assert set(got) == {"compile_s", "step_ms.train", "data_wait_frac.train",
                        "moe_held_rows_frac.train", "mfu.train",
                        "ssd_kernel_frac.train"}
    assert 5 < got["moe_held_rows_frac.train"]["value"] < 50    # 2 of 8 held
    # The scan's counters reach the run: two state-space blocks a token; off
    # the chip no scan takes the kernels, and the share says so.
    assert counters["ssm_tokens"] == window["ssm_tokens"] \
        == result.attempted * 256 * 2
    assert counters["ssd_kernel_tokens"] == 0
    assert got["ssd_kernel_frac.train"]["value"] == 0.0
    assert window["ssd_min_log_decay"] < 0
    assert got["mfu.train"]["value"] == pytest.approx(
        100.0 * window["mfu"], rel=1e-6)


@pytest.mark.parametrize("wanted", [
    ("bf16_reference", "decay_dropped_at_a_chunk_boundary"),
    pytest.param(tuple(c for c in controls.CONTROLS if c not in (
        "bf16_reference", "decay_dropped_at_a_chunk_boundary")),
        marks=pytest.mark.slow)])
def test_controls_read_false_through_the_cells_own_comparison(wanted):
    """Each control through ``train_family``'s own comparison at the tiny
    width: the program alone is held, every control is refused by at least
    one limit. The limits are tiny-width ones (between the program's
    readings here and the controls'), not the configuration's."""
    cell = _cell(logit_rel_rms=0.03, logit_max_over_rms=0.3, loss_rel=1e-4,
                 routing_flipped_frac=0.04, grad_leaf_rel=0.08)
    # Activations of order one at 48 lanes, as the published widths give at
    # the family's 0.02: the scan's part of `y` then stands beside `D x`.
    cell["config_file"]["initializer_range"] = 0.1
    record = controls.run_controls(
        cell, devices=jax.devices()[:1], seed=7, names=wanted)
    assert record["program"]["correct"], record["program"]
    if "decay_dropped_at_a_chunk_boundary" in wanted:
        # The gradient is what catches one boundary's factor, on the leaves
        # whose gradient flows through it.
        scan = record["controls"]["decay_dropped_at_a_chunk_boundary"]
        assert [k for k, (_, _, ok) in scan["held"].items() if not ok] == [
            "grad_leaf_rel"]
        worst = max(scan["numbers"]["grad_leaves"],
                    key=scan["numbers"]["grad_leaves"].get)
        assert "A_log" in worst or "dt_bias" in worst
    for name in wanted:
        assert not record["controls"][name]["correct"], (
            name, record["controls"][name])


# --- the readers on a hand-made trace -------------------------------------------

ROOT = "jit(_train_step)/while/body/closed_call/"
LAYER = "GPT._mixed_layers/TransformerBlock_0/"
OP_NAMES = {
    "fusion.1": ROOT + "jvp(GPT)/" + LAYER + "mamba/in_proj/dot_general",
    "fusion.2": ROOT + "jvp(GPT)/" + LAYER + "mamba/taps/mul",
    "fusion.3": ROOT + "jvp(GPT)/" + LAYER + "mamba/ssd/dot_general",
    "fusion.4": ROOT + "transpose(jvp(GPT))/" + LAYER
    + "mamba/checkpoint/ssd/exp",
    "ssd.5": ROOT + "transpose(jvp(GPT))/" + LAYER + "mamba/ssd/pallas_call",
    "fusion.6": ROOT + "jvp(GPT)/" + LAYER + "attention/ssd/elsewhere",
    "fusion.7": ROOT + "jvp(GPT)/" + LAYER
    + "moe_mlp/shared_expert/up_proj/dot_general",
    "experts.8": ROOT + "jvp(GPT)/" + LAYER
    + "moe_mlp/moe_mlp._dropless_ffn/experts/pallas_call",
    "attention.9": ROOT + "transpose(jvp(GPT))/" + LAYER
    + "attention/pallas_call",
}
COUNTERS = {"steps": 2, "grad_accum": 2, "seq_len": 4096,
            "sequences_per_step": 8, "tokens_per_step": 32768}


def _observations(counters):
    kinds = {name: pt.PALLAS for name in OP_NAMES
             if name.split(".")[0] in ("ssd", "experts", "attention")}
    events = [Event(name, 100 * i, 100, kinds.get(name, "kOutput"))
              for i, name in enumerate(OP_NAMES)]
    cell = dict(registry.workload(CELL), peaks=registry.peaks("TPU v5 lite"))
    obs = harness.Observations(
        cell=cell, spans=harness.Spans(), window=(0.0, 1.0),
        counters=counters, trace=object(), trace_window=(0, 950))
    obs.program_trace = pt.ProgramTrace({0: events}, OP_NAMES, [])
    return obs


def _read(obs, metric):
    spec = registry.metric(metric)
    return registry.code("readers", spec["reader"]).read(
        obs, **spec.get("args", {}))


def test_scope_readers_on_a_handmade_trace():
    obs = _observations(dict(COUNTERS))
    # ns over 2 steps and one chip -> ms a step: the mixer whole is its
    # five ops, the scan the three under `ssd` INSIDE `mamba`.
    assert _read(obs, "ssm_ms.train") == pytest.approx(500 / 2e6)
    assert _read(obs, "ssd_ms.train") == pytest.approx(300 / 2e6)
    assert _read(obs, "shared_expert_ms.train") == pytest.approx(100 / 2e6)
    assert _read(obs, "moe_ms.train") == pytest.approx(200 / 2e6)
    # The taps' scope is not the gated short convolution's.
    assert _read(obs, "conv_ms.train") is None


def test_ssd_roofline_on_a_handmade_trace():
    obs = _observations(dict(COUNTERS))
    work = family.ssd_work(CFG, 2 * 32768)
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert least == work["bytes"] / 819e9
    # Every leaf op under `ssd` in `mamba`, a Pallas kernel or not: 300 ns.
    assert _read(obs, "ssd_roofline.train") == pytest.approx(
        100 * least / 300e-9)


def test_flash_work_by_hand_and_its_roofline_on_a_handmade_trace():
    # One `*` block of 4 query heads x 16 lanes over 2 K/V heads, 8 tokens,
    # 3 sequences: QK^T and PV over the causal half, backward twice that.
    fwd = 2 * 2 * 4 * 16 * 8 * 9 / 2
    work = family.flash_work(TINY, 8, 3)
    assert work["flops"] == 3 * fwd * 3
    # q 64 + k 32 + v 32 lanes read and o 64 written forward; those, o and
    # do read and three gradients written backward; 2 bytes a value.
    assert work["bytes"] == 3 * 8 * ((128 + 64) + (128 + 128 + 128)) * 2
    cell = family.flash_work(CFG, 4096, 2 * 8)
    assert cell["flops"] == 3 * 2 * 2 * 32 * 128 * 4096 * 4097 / 2 * 16
    assert cell["flops"] / 197e12 > cell["bytes"] / 819e9   # compute bound
    obs = _observations(dict(COUNTERS))
    # The Pallas kernels under `attention` alone (100 ns), not the fusion
    # beside them.
    assert _read(obs, "gqa_flash_roofline.train") == pytest.approx(
        100 * (cell["flops"] / 197e12) / 100e-9)
    assert "gqa_flash_roofline.train" in registry.workload(CELL)["per_layer"]
    # No kernel under `attention`, or a family without the count: nothing.
    names = {k: v.replace("attention", "x") for k, v in OP_NAMES.items()}
    obs.program_trace = pt.ProgramTrace(obs.program_trace.devices, names, [])
    assert _read(obs, "gqa_flash_roofline.train") is None
    other = _observations(dict(COUNTERS))
    other.cell = dict(other.cell, config_file=registry.config(
        "lfm2-24b-a2b-ep8"))
    assert _read(other, "gqa_flash_roofline.train") is None


def test_readers_with_nothing_to_read_return_none():
    """A program without the scopes (the parent), one that hands out no op
    names, a family that counts no such work, or a run without a trace:
    nothing is read, nothing raises."""
    metrics = ("ssm_ms.train", "ssd_ms.train", "ssd_roofline.train")
    obs = _observations(dict(COUNTERS))
    names = {k: v.replace("mamba", "conv").replace("ssd", "x")
             for k, v in OP_NAMES.items()}
    obs.program_trace = pt.ProgramTrace(obs.program_trace.devices, names, [])
    for metric in metrics:
        assert _read(obs, metric) is None, metric
    obs.program_trace = pt.ProgramTrace(obs.program_trace.devices, {}, [])
    for metric in metrics:
        assert _read(obs, metric) is None, metric
    other = _observations(dict(COUNTERS))
    other.cell = dict(other.cell, config_file=registry.config(
        "lfm2-24b-a2b-ep8"))
    assert _read(other, "ssd_roofline.train") is None
    obs.trace = obs.program_trace = None
    for metric in metrics:
        assert _read(obs, metric) is None, metric
