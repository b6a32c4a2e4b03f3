"""Rotary position embeddings (reference ``gpt.py:70-147`` — SURVEY.md C3).

Lives in ``ops`` (not ``models``) so the attention dispatch can apply it
without an import cycle: the fused flash path rotates q/k *inside* the
Pallas kernel, while the jnp/ring paths rotate here first. Tables are
recomputed under jit (XLA constant-folds them) and never checkpointed — the
reference persists them as buffers in every state_dict (SURVEY.md §2.1 b8).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp


def rope_tables(
    seq_len: int, dim: int, base: float = 10000.0
) -> Tuple[jax.Array, jax.Array]:
    """Precompute RoPE cos/sin tables, shape ``[seq_len, dim]``.

    Matches the reference cache construction (``gpt.py:76-93``): inverse
    frequencies over even indices, angles tiled as ``concat(freqs, freqs)``.
    """
    inv_freq = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def yarn_inv_freq(dim: int, base: float, factor: float,
                  original_max_position: int, beta_fast: float,
                  beta_slow: float) -> jax.Array:
    """YaRN's inverse frequencies (arXiv:2309.00071; transformers'
    ``_compute_yarn_parameters`` with ``truncate``): frequency ``i`` of
    ``dim / 2`` is ``ramp_i theta_i / factor + (1 - ramp_i) theta_i``,
    ``theta_i = base^(-2i / dim)``, the ramp ``clip((i - low) / (high -
    low), 0, 1)`` between ``low = floor(c(beta_fast))`` and ``high =
    ceil(c(beta_slow))``, ``c(beta) = dim ln(original_max_position / (2 pi
    beta)) / (2 ln base)``: the dimension whose wavelength turns ``beta``
    times over the original context."""
    def correction(beta):
        return (dim * math.log(original_max_position / (beta * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    theta = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return ramp * theta / factor + (1.0 - ramp) * theta


def yarn_tables(seq_len: int, dim: int, base: float, factor: float,
                original_max_position: int, beta_fast: float,
                beta_slow: float, attention_factor: float
                ) -> Tuple[jax.Array, jax.Array]:
    """``rope_tables`` at YaRN's frequencies (``yarn_inv_freq``), cos and
    sin both times ``attention_factor``: ``[seq_len, dim]`` each."""
    inv_freq = yarn_inv_freq(dim, base, factor, original_max_position,
                             beta_fast, beta_slow)
    freqs = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb) * attention_factor, jnp.sin(emb) * attention_factor


def rotate_half(x: jax.Array) -> jax.Array:
    """``[a, b, c, d] -> [-c, -d, a, b]`` (reference ``gpt.py:100-117``)."""
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def apply_rotary_pos_emb(
    q: jax.Array, k: jax.Array, cos: jax.Array, sin: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Rotate q/k by position (reference ``gpt.py:120-147``).

    q, k: ``[batch, seq, heads, head_dim]``; cos, sin: ``[seq, head_dim]``,
    or ``[batch, seq, head_dim]`` for per-row positions (ragged decode:
    left-padded rows start their RoPE positions at their own first real
    token). Applied in float32, cast back to the inputs' dtype.
    """
    if cos.ndim == 3:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    else:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    q32, k32 = q.astype(jnp.float32), k.astype(jnp.float32)
    q_rot = q32 * cos + rotate_half(q32) * sin
    k_rot = k32 * cos + rotate_half(k32) * sin
    return q_rot.astype(q.dtype), k_rot.astype(k.dtype)
