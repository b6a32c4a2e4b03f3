"""perf/families/mellum.py, perf/controls_mellum.py and the reader the Mellum
cell brings: the file against the catalog row, counts against hand numbers,
the controls through the cell's own comparison on the CPU at a tiny width,
the readers on a hand-made trace. No time measured here is a result. (The
reference against the program: tests/test_mellum.py.)"""

import jax
import pytest

from perf import controls_mellum as controls
from perf import harness, program_trace as pt, registry
from perf.families import mellum as family
from perf.runners import train_family
from perf.trace_reduce import Event
from tests.perf.test_perf_registry import check_config_file
from tests.test_mellum import TINY, scaled

CELL = "train-mellum2-ep8-1chip"
NAME = "mellum2-12b-a2.5b-ep8"
CFG = registry.config(NAME)

# The catalog's row (model-configs guide, architectures.jsonl,
# `Mellum2-12B-A2.5B-Instruct`): its `config`, every key.
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}


def test_the_file_is_the_catalog_row_but_for_the_cut():
    check_config_file(NAME)
    assert CFG["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types", "num_experts", "vocab_size"]
    assert CFG["source"] == ("https://huggingface.co/JetBrains/Mellum2-12B-"
                             "A2.5B-Instruct/blob/main/config.json")
    cut = {"num_hidden_layers": 4, "layer_types": PERIOD,
           "mlp_layer_types": ["sparse"] * 4, "num_experts": 8,
           "vocab_size": 12288}
    assert {k: CFG[k] for k in PUBLISHED} == {**PUBLISHED, **cut}
    assert (CFG["num_hidden_layers_published"], CFG["num_experts_published"],
            CFG["vocab_size_published"], CFG["experts_held_first"],
            CFG["experts_held_pick"]) == (28, 64, 98304, 0,
                                          "nearest_mean_load")
    assert family.held(CFG) == (0, 8) and family.router_width(CFG) == 64


def test_counts_by_hand():
    # A layer: attention 2 x 2304 x 4096 + 2 x 2304 x 512, router 2304 x
    # 64, two norms, 8 experts of 3 x 2304 x 896.
    layer = 21_233_664 + 147_456 + 4_608 + 8 * 6_193_152
    assert layer == 70_930_944
    assert family.param_count(CFG) == 4 * layer + 2 * 12288 * 2304 + 2304
    assert family.param_count(CFG) == 340_349_184
    assert family.gpt_config(CFG).num_parameters() == 340_349_184
    # The pairs of one sequence of 8,192: a window layer's band, the full
    # layer's triangle.
    band = 1024 * 1025 // 2 + (8192 - 1024) * 1024
    assert family.pairs(8192, 1024) == band
    assert family.pairs(8192, 8192) == 8192 * 8193 // 2
    per_pair = 2 * 2 * 32 * 128
    attention = per_pair * (3 * band + 8192 * 8193 // 2)
    assert family.attention_flops_fwd(CFG, 8192) == attention
    # 6 x (attention, router, 8 x 8 / 64 experts' rows a token, a layer;
    # the head) + 3 x attention a token.
    matmul = 4 * (21_233_664 + 147_456 + 6_193_152) + 12288 * 2304
    assert family.train_flops_per_token(CFG, 8192) == pytest.approx(
        6 * matmul + 3 * attention / 8192)
    work = family.window_flash_work(CFG, 8192, 4)
    assert work["flops"] == 3 * 4 * per_pair * 3 * band
    assert family.flash_work(CFG, 8192, 4)["flops"] == 3 * 4 * attention
    assert work["bytes"] == 3 * 4 * 8192 * (
        3 * (4096 + 1024) + 3 * 4096) * 2


def test_a_file_the_program_cannot_run_is_refused():
    for key, value in (("attention_bias", True), ("hidden_act", "gelu"),
                       ("use_sliding_window", False)):
        with pytest.raises(ValueError, match="has no option"):
            family.gpt_config(dict(CFG, **{key: value}))
    plain = dict(CFG["rope_parameters"], full_attention={
        "rope_type": "default", "rope_theta": 500000})
    with pytest.raises(ValueError, match="YaRN on full ones"):
        family.gpt_config(dict(CFG, rope_parameters=plain))
    with pytest.raises(ValueError, match="every layer"):
        family.gpt_config(dict(CFG, mlp_layer_types=["dense"] * 4))


# --- the controls on the CPU at a tiny width ------------------------------------

def test_controls_read_false_through_the_cells_own_comparison():
    """Each control through ``train_family``'s own logit comparison at the
    tiny width, 64 tokens a sequence (a window of 16; YaRN over an original
    32): the program alone is held, every control is refused by at least one
    limit. The limits are tiny-width ones (between the program's readings
    here and the controls'), not the configuration's."""
    cell = registry.workload(CELL)
    # Here the program reads 0.053 / 0.60 / flips 0.16 and the bf16
    # reference 0.077 / 0.79 / 0.25 with a loss 2.1e-3 off; every fault of
    # the program reads 0.107-0.92 / 0.83-5.1 / 0.30-0.74.
    tol = dict(cell["config_file"]["reference_tolerance"]["bf16"],
               logit_rel_rms=0.065, logit_max_over_rms=0.7, loss_rel=1e-3,
               routing_flipped_frac=0.2)
    cfg = dict(TINY, reference_tolerance={"bf16": tol})
    traffic = dict(cell["traffic_file"], seq_len=64, tokens_per_step=128)
    job = dict(cell["job"], micro_batch=2, grad_accum=1)
    family_ = registry.family(cfg)
    devices = jax.devices()[:1]
    trainer = train_family.build_trainer(family_, cfg, traffic, job, devices)
    spans = harness.Spans()
    batch = next(registry.code("generators", "token_batches").generate(
        traffic, seed=7, vocab_size=cfg["vocab_size"]))
    params = scaled(trainer.init_state(7).params)
    healthy = train_family.check_logits(
        trainer, params, family_, cfg, job, batch, spans,
        train_family.program_side(trainer))[0]
    assert all(ok for _, _, ok in train_family.judge(healthy, tol).values())
    for control in controls.CONTROLS:
        numbers = controls.reading(control, trainer, params, family_, cfg,
                                   job, batch, spans, traffic["seq_len"])
        held = train_family.judge(numbers, tol)
        assert not all(ok for _, _, ok in held.values()), (control, held)


# --- the readers on a hand-made trace -------------------------------------------

ROOT = "jit(_train_step)/while/body/closed_call/"
LAYER = "GPT._mixed_layers/TransformerBlock_0/"
OP_NAMES = {
    "fusion.1": ROOT + "jvp(GPT)/" + LAYER + "attention/q_proj/dot_general",
    "fusion.2": ROOT + "jvp(GPT)/" + LAYER + "attention/sliding_window/mul",
    "band_flash_fwd.3": ROOT + "jvp(GPT)/" + LAYER
    + "attention/sliding_window/jit(band_flash_fwd)/pallas_call",
    "band_flash_bwd.4": ROOT + "transpose(jvp(GPT))/" + LAYER
    + "attention/sliding_window/jit(band_flash_bwd)/pallas_call",
    "band_flash_fwd.5": ROOT + "jvp(GPT)/" + LAYER
    + "attention/jit(band_flash_fwd)/pallas_call",
    "fusion.6": ROOT + "jvp(GPT)/" + LAYER + "moe_mlp/route/x",
}
COUNTERS = {"steps": 2, "grad_accum": 2, "seq_len": 8192,
            "sequences_per_step": 4, "tokens_per_step": 32768}


def _observations():
    kinds = {name: pt.PALLAS for name in OP_NAMES if "band" in name}
    events = [Event(name, 100 * i, 100, kinds.get(name, "kOutput"))
              for i, name in enumerate(OP_NAMES)]
    cell = dict(registry.workload(CELL), peaks=registry.peaks("TPU v5 lite"))
    obs = harness.Observations(
        cell=cell, spans=harness.Spans(), window=(0.0, 1.0),
        counters=dict(COUNTERS), trace=object(), trace_window=(0, 650))
    obs.program_trace = pt.ProgramTrace({0: events}, OP_NAMES, [])
    return obs


def _read(obs, metric):
    spec = registry.metric(metric)
    return registry.code("readers", spec["reader"]).read(
        obs, **spec.get("args", {}))


def test_readers_on_a_handmade_trace():
    obs = _observations()
    # Under `sliding_window` in `attention`: the rotation and the window
    # layer's two kernels, 300 ns over 2 steps and one chip.
    assert _read(obs, "window_flash_ms.train") == pytest.approx(300 / 2e6)
    work = family.window_flash_work(CFG, 8192, 2 * 4)
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert least == work["flops"] / 197e12
    assert _read(obs, "window_flash_roofline.train") == pytest.approx(
        100 * least / 300e-9)
    # Every Pallas kernel under `attention`, both kinds of layer: 300 ns.
    work = family.flash_work(CFG, 8192, 2 * 4)
    assert _read(obs, "gqa_flash_roofline.train") == pytest.approx(
        100 * work["flops"] / 197e12 / 300e-9)


def test_readers_with_nothing_to_read_return_none():
    """A program without the scope (the parent), a family that counts no
    such work, or a run without a trace: nothing is read, nothing raises."""
    metrics = ("window_flash_ms.train", "window_flash_roofline.train")
    obs = _observations()
    names = {k: v.replace("sliding_window/", "") for k, v in OP_NAMES.items()}
    obs.program_trace = pt.ProgramTrace(obs.program_trace.devices, names, [])
    for metric in metrics:
        assert _read(obs, metric) is None, metric
    other = _observations()
    other.cell = dict(other.cell, config_file=registry.config(
        "nemotron-twotower-30b-a3b-ep16"))
    assert _read(other, "window_flash_roofline.train") is None
    obs.trace = obs.program_trace = None
    for metric in metrics:
        assert _read(obs, metric) is None, metric
