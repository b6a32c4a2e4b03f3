"""Window layers beside full ones with YaRN, every layer a softmax-routed MoE
with no shared expert: the program against the plain reference of
``perf/families/mellum.py`` at a small size (the window layers through the
banded kernels under the interpreter), YaRN's table against its formula at
the published numbers, the shares of the experts tied to the uncut layer
and loss, the counters, and what window layers cannot do yet refused in
words."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.families import mellum as family
from tpu_trainer.models import moe
from tpu_trainer.models.gpt import GPT, CausalSelfAttention, RMSNorm
from tpu_trainer.ops.rope import yarn_inv_freq, yarn_tables
from tpu_trainer.utils import telemetry

# A window layer, then a full one, at small widths: 64 experts top-8 (the
# published router) of which 8 are held (ids 8-15), 4 heads of 32 lanes over
# 2 K/V heads, a window of 16 keys, YaRN over an original context of 32.
TINY = {
    "name": "tiny-mellum", "family": "mellum", "hidden_size": 64,
    "num_hidden_layers": 2,
    "layer_types": ["sliding_attention", "full_attention"],
    "mlp_layer_types": ["sparse"] * 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "sliding_window": 16,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 32, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "intermediate_size": 96, "moe_intermediate_size": 16, "num_experts": 8,
    "num_experts_published": 64, "experts_held_first": 8,
    "num_experts_per_tok": 8, "norm_topk_prob": True, "hidden_act": "silu",
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "attention_bias": False, "use_sliding_window": True, "vocab_size": 128,
    "max_position_embeddings": 128, "initializer_range": 0.02,
    "attention_dropout": 0.0,
}
UNCUT = dict(TINY, num_experts=64, experts_held_first=0)


def scaled(params):
    """Larger matrices than the initialiser's, so that logits are of order
    one and scores spread; norm weights off their ones."""
    def scale(path, p):
        if "router" in str(path):
            return p * 16
        return p * 4 if p.ndim > 1 else p + 0.1 * jax.random.normal(
            jax.random.PRNGKey(p.size), p.shape)

    return jax.tree_util.tree_map_with_path(scale, params)


def init(cfg, **options):
    model = GPT(family.gpt_config(cfg, dtype="float32", **options))
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    return model, scaled(params)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)


@pytest.fixture(scope="module")
def tiny():
    return init(TINY)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def test_program_matches_the_reference(tokens, tiny, monkeypatch):
    # The window layers through the banded kernels (one block of 64, a
    # window of 16 inside it), the full layer through the streaming one.
    monkeypatch.setenv("TPU_TRAINER_FLASH_INTERPRET", "1")
    params = tiny[1]
    model = GPT(dataclasses.replace(tiny[0].config, use_flash_attention=True))

    def loss(p):
        logits, loss = model.apply({"params": p}, tokens, labels=tokens,
                                   train=True)
        return loss, logits

    with jax.default_matmul_precision("highest"):
        (got_loss, logits), got_grad = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(params)
        want, (want_loss, want_grad) = jax.jit(lambda p: (
            family.forward(p, tokens, TINY), jax.value_and_grad(
                family.loss)(p, tokens, TINY, 2)))(params)
    assert _rel(logits, want) < 1e-5
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    leaves = jax.tree_util.tree_leaves_with_path(want_grad)
    assert len(leaves) == 23
    for (path, w), g in zip(leaves, jax.tree_util.tree_leaves(got_grad)):
        assert float(jnp.linalg.norm(w)) > 0, path
        assert _rel(g, w) < 1e-3, path
    # Every parameter is counted alike by the program and the family.
    count = sum(p.size for p in jax.tree_util.tree_leaves(params))
    assert count == model.config.num_parameters() == family.param_count(TINY)
    assert set(params) == {"embed_tokens", "lm_head", "norm",
                           "layers_sliding_attention_moe",
                           "layers_attention_moe"}


def test_yarn_at_the_published_numbers():
    """Mellum 2's full layers: d = 128, theta 5e5, factor 16 over 8,192
    positions, betas 32 and 1: the ramp runs from dimension 18 to 35, and cos
    and sin carry the attention factor 0.1 ln 16 + 1."""
    d, theta = 128, 5e5
    c = lambda beta: d * math.log(8192 / (2 * math.pi * beta)) / (  # noqa
        2 * math.log(theta))
    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)
    got = np.asarray(yarn_inv_freq(d, theta, 16, 8192, 32, 1))
    i = np.arange(64)
    plain = theta ** (-2.0 * i / d)
    ramp = np.clip((i - 18) / (35 - 18), 0, 1)
    np.testing.assert_allclose(got, ramp * plain / 16 + (1 - ramp) * plain,
                               rtol=1e-6)
    assert got[18] == pytest.approx(plain[18], rel=1e-6)
    assert got[35] == pytest.approx(plain[35] / 16, rel=1e-6)
    factor = 0.1 * math.log(16) + 1
    assert factor == pytest.approx(1.2772588722239782)
    cos, sin = yarn_tables(4, d, theta, 16, 8192, 32, 1, factor)
    angles = np.outer(np.arange(4), got)
    np.testing.assert_allclose(np.asarray(cos[:, :64]),
                               np.cos(angles) * factor, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sin[:, 64:]),
                               np.sin(angles) * factor, rtol=1e-5, atol=1e-7)
    # The reference's own table, from the row's keys.
    ref, scale = family.inv_freq(
        {"head_dim": d, "rope_parameters": {"full_attention": {
            "rope_type": "yarn", "rope_theta": theta, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": factor}}},
        "full_attention")
    np.testing.assert_allclose(np.asarray(ref), got, rtol=1e-6)
    assert scale == factor


def test_the_eight_shares_add_up_to_the_uncut_layer_and_loss(tokens):
    """Eight chips of 8 of the 64 experts each: every share of an expert
    layer is the reference's share, their sum is the uncut layer, and summed
    at every layer (attention run whole) they give the uncut model's loss.
    A share holding experts ``f .. f + 7`` is run as the program's layer
    holding experts 0-7 with the router's columns turned by ``f``: the
    softmax over all 64 columns, the top 8 and their renormalised gates do
    not depend on the columns' order (the program's layer at an offset of
    its held experts is ``test_program_matches_the_reference``'s), so the
    eight shares are one program mapped over ``f``."""
    uncut = family.gpt_config(UNCUT, dtype="float32")
    _, params = init(UNCUT)
    share_layer = moe.MoEMLP(dataclasses.replace(uncut,
                                                 moe_experts_held=(0, 8)))
    firsts = jnp.arange(0, 64, 8)
    norm = RMSNorm(eps=uncut.norm_eps)

    def shares(layer, h):
        def one(first):
            p = {"router": {"kernel": jnp.roll(layer["router"]["kernel"],
                                               -first, axis=1)},
                 **{k: jax.lax.dynamic_slice_in_dim(layer[k], first, 8)
                    for k in ("experts_gate", "experts_up", "experts_down")}}
            return share_layer.apply({"params": p}, h)[0]

        return jax.lax.map(one, firsts)

    def references(layer, h):
        return [family.routed_experts(
            h, {"router": layer["router"],
                **{k: layer[k][f:f + 8] for k in (
                    "experts_gate", "experts_up", "experts_down")}},
            dict(UNCUT, num_experts=8, experts_held_first=f))[0]
            for f in range(0, 64, 8)]

    @jax.jit
    def run(params):
        x = params["embed_tokens"]["embedding"][tokens]
        seen, parts, wanted = {}, [], []
        for kind in family.layer_types(UNCUT):
            i = seen.get(kind, 0)
            seen[kind] = i + 1
            p = jax.tree_util.tree_map(lambda a: a[i],
                                       params[family.STACKS[kind]])
            h = norm.apply({"params": p["operator_norm"]}, x)
            x = x + family.attention(h, p["attention"], UNCUT, kind)
            h = norm.apply({"params": p["ffn_norm"]}, x)
            parts.append(shares(p["moe_mlp"], h))
            wanted.append((references(p["moe_mlp"], h),
                           family.routed_experts(h, p["moe_mlp"], UNCUT)[0]))
            x = x + jnp.sum(parts[-1], axis=0)
        x = norm.apply({"params": params["norm"]}, x)
        logp = jax.nn.log_softmax((x @ params["lm_head"].T)[:, :-1])
        loss = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))
        return parts, wanted, loss, family.loss(params, tokens, UNCUT, 2)

    with jax.default_matmul_precision("highest"):
        parts, wanted, got_loss, want_loss = run(params)
    for got, (each, whole) in zip(parts, wanted):
        assert float(jnp.std(whole)) > 0.01
        assert all(_rel(a, b) < 1e-5 for a, b in zip(got, each))
        assert _rel(jnp.sum(got, axis=0), whole) < 1e-5
    assert abs(float(got_loss) - float(want_loss)) < 1e-5


@pytest.mark.parametrize("hook,kernel", [("0", False), ("1", True)])
def test_a_window_layer_counts_its_tokens(monkeypatch, tiny, hook, kernel):
    """Counted in every apply: the tokens of a window layer, those whose
    attention took the banded kernels (none in XLA), the block pairs the
    kernels' band visits."""
    monkeypatch.setenv("TPU_TRAINER_FLASH_INTERPRET", hook)
    cfg = dataclasses.replace(tiny[0].config, use_flash_attention=True)
    layer = jax.tree_util.tree_map(
        lambda a: a[0],
        tiny[1]["layers_sliding_attention_moe"]["attention"])
    module = CausalSelfAttention(cfg, window=cfg.sliding_window)

    @jax.jit
    def counted(p, x):
        with telemetry.counters() as counts:
            module.apply({"params": p}, x)
        return telemetry.flat_counts(counts)

    got = counted(layer, jnp.ones((1, 1024, 64)))
    assert int(got["window_attn_tokens"]) == 1024
    assert int(got["window_kernel_tokens"]) == 1024 * kernel
    # Two blocks of 512 rows, a window of 16: the first meets itself, the
    # second itself and the first (the first's last 15 keys): 3 pairs a
    # head, 4 heads.
    assert int(got["window_key_blocks"]) == 4 * 3 * kernel


def test_generation_the_caches_and_segments_are_refused_in_words(tiny):
    from tpu_trainer.models.gpt import (
        generate, generate_bucketed, generate_kv, init_cache)

    model, params = tiny
    cfg = model.config
    prompt = jnp.zeros((1, 4), jnp.int32)
    for fn in (generate, generate_bucketed, generate_kv):
        with pytest.raises(NotImplementedError, match="sliding_attention"):
            fn(params, jax.random.PRNGKey(0), prompt, config=cfg,
               max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="behind a layer's window"):
        init_cache(cfg, 1)
    with pytest.raises(NotImplementedError, match="by position alone"):
        model.apply({"params": params}, jnp.zeros((1, 8), jnp.int32),
                    segment_ids=jnp.ones((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="window of the 'sliding_attention'"):
        dataclasses.replace(cfg, sliding_window=0)
    with pytest.raises(ValueError, match="stack whose layers differ"):
        dataclasses.replace(cfg, layer_types=None, num_layers=2,
                            sliding_window=0)
    with pytest.raises(ValueError, match="has no option"):
        family.gpt_config(dict(TINY, attention_bias=True))
