"""perf/families/mla_moe.py, perf/controls_mla_moe.py and the readers the
JoyAI-LLM-Flash cell brings: the file against the catalog row, counts against
hand numbers, the ``train_family`` runner and the controls end to end on the
CPU at a tiny width, the readers on a hand-made trace. No time measured here
is a result. (The reference against the program: tests/test_mla_moe.py.)"""

import json
import time

import jax
import pytest

from perf import controls_mla_moe as controls
from perf import harness, program_trace as pt, registry
from perf.families import mla_moe as family
from perf.runners import train_family
from perf.trace_reduce import Event
from tests.test_mla_moe import TINY

CELL = "train-joyai-flash-ep32-1chip"
CFG = registry.config("joyai-llm-flash-ep32")

# The catalog's row (model-configs guide, architectures.jsonl,
# `JoyAI-LLM-Flash`): its `config`, every key.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}


def test_the_file_is_the_catalog_row_but_for_the_cut():
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert CFG["source"] == ("https://huggingface.co/jdopensource/"
                             "JoyAI-LLM-Flash/blob/main/config.json")
    cut = {"num_hidden_layers": 5, "n_routed_experts": 8,
           "vocab_size": 16160}
    assert {k: CFG[k] for k in PUBLISHED} == {**PUBLISHED, **cut}
    # The untied head under its published key, and under no other.
    assert CFG["tie_word_embeddings"] is False and "untied_head" not in CFG
    assert "tie_word_embeddings" not in CFG["assumed"]
    assert (CFG["num_hidden_layers_published"],
            CFG["n_routed_experts_published"],
            CFG["vocab_size_published"], CFG["experts_held_first"]) == (
                40, 256, 129280, 0)
    assert CFG["vocab_size"] * 8 == CFG["vocab_size_published"]
    assert family.layer_kinds(CFG) == ["dense", "moe", "moe", "moe", "moe"]
    assert family.held(CFG) == (0, 8) and family.router_width(CFG) == 256


def test_parameter_counts_by_hand_and_by_the_program():
    attention = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576
                 + 512 * 32 * 256 + 4096 * 2048)
    assert attention == family.attention_params(CFG) == 26_345_472
    latent_norms, norms = 1536 + 512, 2 * 2048
    dense, expert, router = 3 * 2048 * 7168, 3 * 2048 * 768, 2048 * 256 + 256
    assert (dense, expert, router) == (44_040_192, 4_718_592, 524_544)
    sparse = attention + latent_norms + norms + 9 * expert + router
    assert sparse == 69_343_488
    want = (2 * 16160 * 2048 + 2048                     # embedding, head
            + attention + latent_norms + norms + dense  # layer 0
            + 4 * sparse
            + 2 * 2048 * 2048 + sparse + 3 * 2048)      # the MTP module
    assert family.param_count(CFG) == want == 491_697_408
    config = family.gpt_config(CFG)
    assert config.num_parameters() == want
    assert config.experts_held == (0, 8) and config.num_experts == 256
    assert [ffn for _, ffn in config.layer_kinds()] == family.layer_kinds(CFG)
    assert family.param_count(TINY) == family.gpt_config(TINY).num_parameters()
    # 18 B a parameter: f32 master, AdamW m and v, f32 accumulation, bf16 copy.
    assert 8.8e9 < 18 * want < 8.9e9


def test_flops_by_hand():
    seq = 4096
    # One operator: 2 FLOPs x 32 heads x (192 + 128) lanes x S (S + 1) / 2.
    attn = 6 * 2 * 32 * 320 * seq * (seq + 1) // 2
    assert family.attention_flops_fwd(CFG, seq) == attn
    assert family.attention_layers(CFG) == 6 and family.moe_layers(CFG) == 5
    assert family.even_rows_per_token(CFG) == 0.25
    sparse = 2048 * 256 + (1 + 0.25) * 3 * 2048 * 768
    matmul = (6 * 26_345_472 + 3 * 2048 * 7168 + 4 * sparse
              + 16160 * 2048                            # the head
              + 2 * 2048 * 2048 + sparse + 16160 * 2048)    # the module
    want = 6 * matmul + 3 * attn / seq
    assert family.train_flops_per_token(CFG, seq) == pytest.approx(want)
    assert 2.60e9 < want < 2.62e9
    # The routed experts count by the rows they are given.
    more = family.train_flops_per_token(CFG, seq, rows_per_token=1.0)
    assert more - want == pytest.approx(6 * 5 * 0.75 * 3 * 2048 * 768)
    assert family.mfu(CFG, seq, 30_000.0, 1, 197e12) == pytest.approx(
        30_000 * want / 197e12)


def test_flash_and_gmm_work_by_hand():
    seq = 4096
    work = family.flash_work(CFG, seq, 8)
    assert work["flops"] == 3 * family.attention_flops_fwd(CFG, seq) * 8
    assert 24.7e12 < work["flops"] < 24.8e12
    # Values a token and operator: q 32 x 192, k 32 x 128 + the one shared
    # 64, v 32 x 128; the forward reads them and writes the result, the
    # backward reads them, the result and its cotangent and writes the
    # gradients.
    operands = 32 * 192 + 32 * 128 + 64 + 32 * 128
    values = operands + 4096 + 2 * operands + 2 * 4096
    assert work["bytes"] == 6 * 8 * seq * values * 2
    # Compute bound, by far: the roofline's two sides.
    assert (work["flops"] / 197e12) / (work["bytes"] / 819e9) > 4
    rows, passes = 81920.0, 10      # 8192 rows a pass, 5 layers x 2 micro
    work = family.gmm_work(CFG, rows, passes)
    assert work["flops"] == 9 * 2 * rows * 2048 * 768
    weights = 8 * 2048 * 768
    assert work["bytes"] == (rows * 9 * (2048 + 768) * 2
                             + passes * weights * (6 * 2 + 3 * 4))
    # 512 rows an expert at an even load: compute bound still, by a third.
    assert 1.0 < (work["flops"] / 197e12) / (work["bytes"] / 819e9) < 2.0


def test_a_file_the_program_cannot_run_is_refused():
    for key, value in (("norm_topk_prob", False), ("scoring_func", "softmax"),
                       ("n_group", 8), ("rope_scaling", {"type": "yarn"}),
                       ("tie_word_embeddings", True), ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            family.gpt_config(dict(TINY, **{key: value}))
    with pytest.raises(ValueError, match="qk_head_dim"):
        family.gpt_config(dict(TINY, qk_head_dim=32))
    config = family.gpt_config(TINY)
    assert (config.moe_routed_scale, config.moe_gate_eps,
            config.tie_word_embeddings, config.mtp_layers,
            config.rope_interleave) == (2.5, 1e-20, False, 1, True)


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
    # Counters no metric reads, carried to the note: the module's own loss
    # before its weight, and the passes that took the overflow path.
    assert 0 < window["mtp_loss"] < window["loss_last"] / 0.3
    assert window["moe_overflow_passes"] >= 0
    assert set(result.compared) == {*train_family.LIMITS, "finite",
                                    "losses_not_finite"}
    assert all(reading <= limit
               for reading, limit in result.compared.values())
    counters = result.observations.counters
    # Three expert layers: two of the stack and the prediction module's.
    assert counters["moe_rows_routed"] == result.attempted * 256 * 4 * 3
    assert 0 < counters["moe_rows_held"] < counters["moe_rows_routed"]
    got = harness.read_per_layer(cell, result.observations)
    # Span and counter metrics are read; trace metrics find nothing.
    assert set(got) == {"compile_s", "step_ms.train", "data_wait_frac.train",
                        "moe_held_rows_frac.train", "mfu.train"}
    assert 10 < got["moe_held_rows_frac.train"]["value"] < 50   # 4 of 16 held
    assert got["mfu.train"]["value"] == pytest.approx(
        100.0 * window["mfu"], rel=1e-6)


_TINY_CONTROLS = ("kv_norm_dropped", "shared_expert_dropped",
                  "mtp_backward_dropped")


@pytest.mark.parametrize("wanted", [
    _TINY_CONTROLS,
    pytest.param(tuple(c for c in controls.CONTROLS
                       if c not in _TINY_CONTROLS), marks=pytest.mark.slow)],
    ids=["three", "the_rest"])
def test_controls_read_false_through_the_cells_own_comparison(wanted):
    """At the tiny width and limits read there (program: logits 0.0056 /
    0.029, loss 1e-5, rows flipped 1.4%, gradient 0.064, the module's
    router): the reference in bfloat16 fails by the rows it flips, a planted
    fault by the logits, the two faults of the prediction module by the
    gradient alone, and the program passes. The rotation's pairing and
    softmax gates read within rounding of the program at this width and the
    initialiser's 0.02 (0.0064 and 0.0061): tests/test_mla_moe.py shows
    them at larger weights, the chip at the published widths."""
    cell = _cell(logit_rel_rms=0.012, logit_max_over_rms=0.08, loss_rel=1e-4,
                 routing_flipped_frac=0.05, grad_leaf_rel=0.2)
    lines = {r["what"]: r for r in controls.readings(
        cell, jax.devices()[:1], [2 ** 31 + 7], wanted)}
    assert lines["program"]["correct"], lines["program"]["held"]
    assert set(lines["program"]["held"]) == {
        "logit_rel_rms", "logit_max_over_rms", "loss_rel",
        "routing_flipped_frac", "grad_leaf_rel", "finite"}
    assert len(lines["program"]["numbers"]["grad_leaves"]) == 53
    assert set(lines) == {"program", *wanted}
    weak = {"rope_half_split", "softmax_gates"}
    for what, line in lines.items():
        held = line["held"]
        if what == "program" or what in weak:
            continue
        assert not line["correct"], what
        if what == "bf16":
            assert not held["routing_flipped_frac"][2]
        elif what.startswith("mtp_"):
            assert not held["grad_leaf_rel"][2] and held["logit_rel_rms"][2]
            assert held["grad_leaf_rel"][0] >= 0.99
        else:
            assert not held["logit_rel_rms"][2], what


# --- the readers on a hand-made trace -----------------------------------------

ROOT = "jit(_train_step)/while/body/closed_call/"
LAYER = "GPT._mixed_layers/TransformerBlock_2/"
MTP = "GPT/mtp/block/"
OP_NAMES = {
    "fusion.1": ROOT + "jvp(GPT)/" + LAYER + "attention/q_b_proj/dot_general",
    "attention.2": ROOT + "jvp(GPT)/" + LAYER + "attention/pallas_call",
    "attention.3": ROOT + "transpose(jvp(GPT))/" + LAYER
    + "attention/pallas_call",
    "attention.4": ROOT + "transpose(jvp(GPT))/" + MTP
    + "attention/pallas_call",
    "fusion.5": ROOT + "jvp(GPT)/" + LAYER
    + "moe_mlp/shared_expert/up_proj/dot_general",
    "fusion.6": ROOT + "jvp(GPT)/" + MTP
    + "moe_mlp/shared_expert/down_proj/dot_general",
    "experts.7": ROOT + "jvp(GPT)/" + MTP
    + "moe_mlp/moe_mlp._dropless_ffn/experts/pallas_call",
    "fusion.8": ROOT + "jvp(GPT)/GPT/mtp/eh_proj/dot_general",
    "head_loss.9": ROOT + "jvp(GPT)/GPT/mtp/head_loss/pallas_call",
    "fusion.10": ROOT + "jvp(GPT)/GPT/shared_expert/elsewhere",
}


def _observations(counters):
    kinds = {name: pt.PALLAS for name in OP_NAMES
             if name.split(".")[0] in ("attention", "experts", "head_loss")}
    events = [Event(name, 100 * i, 100, kinds.get(name, "kOutput"))
              for i, name in enumerate(OP_NAMES)]
    cell = dict(registry.workload(CELL), peaks=registry.peaks("TPU v5 lite"))
    obs = harness.Observations(
        cell=cell, spans=harness.Spans(), window=(0.0, 1.0),
        counters=counters, trace=object(), trace_window=(0, 1050))
    obs.program_trace = pt.ProgramTrace({0: events}, OP_NAMES, [])
    return obs


def _read(obs, metric):
    spec = registry.metric(metric)
    return registry.code("readers", spec["reader"]).read(
        obs, **spec.get("args", {}))


COUNTERS = {"steps": 2, "grad_accum": 2, "seq_len": 4096,
            "sequences_per_step": 8}


def test_scope_readers_on_a_handmade_trace():
    obs = _observations(dict(COUNTERS))
    # ns over 2 steps and one chip -> ms a step.
    assert _read(obs, "mla_ms.train") == pytest.approx(400 / 2e6)
    # `shared_expert` counts under `moe_mlp` only.
    assert _read(obs, "shared_expert_ms.train") == pytest.approx(200 / 2e6)
    # The module whole: its block's attention and experts, W_eh, its head.
    assert _read(obs, "mtp_ms.train") == pytest.approx(500 / 2e6)
    assert _read(obs, "moe_ms.train") == pytest.approx(300 / 2e6)


def test_mla_flash_roofline_on_a_handmade_trace():
    obs = _observations(dict(COUNTERS))
    work = family.flash_work(CFG, 4096, 2 * 8)
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert least == work["flops"] / 197e12
    # The three kernels under `attention` ran 300 ns; the experts' and the
    # head's are not counted, nor the projection.
    assert _read(obs, "mla_flash_roofline.train") == pytest.approx(
        100 * least / 300e-9)


def test_readers_with_nothing_to_read_return_none():
    """A program that hands out no op names, a trace without the kernels, a
    family that counts no such work, or a run without a trace: nothing is
    read, nothing raises."""
    metrics = ("mla_ms.train", "mla_flash_roofline.train",
               "shared_expert_ms.train", "mtp_ms.train")
    obs = _observations(dict(COUNTERS))
    names = {k: v.replace("attention", "conv").replace("mtp", "x").replace(
        "shared_expert", "y") for k, v in OP_NAMES.items()}
    obs.program_trace = pt.ProgramTrace(obs.program_trace.devices, names, [])
    for metric in metrics:
        assert _read(obs, metric) is None, metric
    obs.program_trace = pt.ProgramTrace(obs.program_trace.devices, {}, [])
    for metric in metrics:
        assert _read(obs, metric) is None, metric
    other = _observations(dict(COUNTERS))
    other.cell = dict(other.cell, config_file=registry.config(
        "lfm2-24b-a2b-ep8"))
    assert _read(other, "mla_flash_roofline.train") is None
    obs.trace = obs.program_trace = None
    for metric in metrics:
        assert _read(obs, metric) is None, metric
