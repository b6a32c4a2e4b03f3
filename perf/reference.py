"""The plain reference: SmolLM2's (Llama-style) decoder forward pass and
next-token loss in straightforward ``jax.numpy``, float32, every matmul at
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
batching tricks.

Written from the published description of the architecture (the Hugging
Face ``LlamaForCausalLM`` the SmolLM2 checkpoints declare): token embedding;
per layer ``x += Wo . attn(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x))`` then
``x += Wd (silu(Wg n2(x)) * Wu n2(x))`` with RMSNorm ``n(x) = x /
sqrt(mean(x^2) + eps) * w``, causal softmax attention scaled by
``1/sqrt(d)`` with grouped K/V heads, rotary embedding in the half-split
("rotate_half") layout at base ``rope_theta``; final RMSNorm; logits through
the tied embedding. It depends on ``tpu_trainer/models/gpt.py`` only for the
NAMES of the parameter tree it reads (kernels are stored ``[in, out]``, the
layers stacked on a leading axis).

Departure from the published model, shared with the program and listed in
the configuration files: ``rms_norm_eps`` is the program's 1e-6.
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(F32)


def _rope(x, theta):
    """x: [batch, seq, heads, d]; positions 0..seq-1."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)       # [seq, d]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


def _layer(x, p, cfg):
    heads = cfg["num_attention_heads"]
    kvh = cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    b, s, _ = x.shape
    f = lambda name: p["attention"][name]["kernel"].astype(F32)  # noqa: E731
    h = _rms_norm(x, p["input_layernorm"]["weight"], eps)
    q = (h @ f("q_proj")).reshape(b, s, heads, d)
    k = (h @ f("k_proj")).reshape(b, s, kvh, d)
    v = (h @ f("v_proj")).reshape(b, s, kvh, d)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    group = heads // kvh
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(b, s, heads * d) @ f("o_proj")
    m = lambda name: p["mlp"][name]["kernel"].astype(F32)  # noqa: E731
    h = _rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
    return x + (jax.nn.silu(h @ m("gate_proj")) * (h @ m("up_proj"))) \
        @ m("down_proj")


def forward(params, tokens, cfg: Mapping):
    """Logits ``[batch, seq, vocab]`` in float32 for ``tokens [batch, seq]``.
    ``cfg`` is the configuration file (the published key names)."""
    with jax.default_matmul_precision("highest"):
        embedding = params["embed_tokens"]["embedding"]
        x = embedding[tokens].astype(F32)

        def body(x, layer_params):
            return _layer(x, layer_params, cfg), None

        x, _ = jax.lax.scan(body, x, params["layers"])
        x = _rms_norm(x, params["norm"]["weight"], cfg["rms_norm_eps"])
        return x @ embedding.astype(F32).T


def loss(params, tokens, cfg: Mapping, rows_per_pass: int = 1):
    """Mean next-token cross entropy over ``tokens [batch, seq]``: position
    ``i`` predicts token ``i + 1``, every row weighs the same. Rows go
    through ``forward`` ``rows_per_pass`` at a time, so that the
    ``[rows, heads, seq, seq]`` scores and the logits fit the chip."""
    batch, seq = tokens.shape
    passes = tokens.reshape(batch // rows_per_pass, rows_per_pass, seq)

    def one(rows):
        logp = jax.nn.log_softmax(forward(params, rows, cfg)[:, :-1], axis=-1)
        picked = jnp.take_along_axis(logp, rows[:, 1:, None], axis=-1)
        return -jnp.mean(picked)

    return jnp.mean(jax.lax.map(one, passes))
