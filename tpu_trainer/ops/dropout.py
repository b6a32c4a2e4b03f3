"""Counter-based dropout: a fused-friendly alternative to threefry masks.

The reference applies ``nn.Dropout`` after the attention output projection
and the MLP (``/root/reference/src/models/gpt.py:241,282``). The direct JAX
translation (``jax.random.bernoulli`` per call site) runs threefry2x32 per
element — ~30 32-bit ALU ops each — and measures ~11 ms of the ~120 ms
headline step (24 masks of ~12.6M elements). This module derives the mask
from a murmur3-finalizer hash of the element's linear index instead (~6 ALU
ops), the same counter-based construction the flash kernel uses for its
in-kernel attention dropout (``ops/flash.py:_keep_mask``): cheap enough that
XLA fuses mask generation into the surrounding elementwise chain, and
deterministic given the PRNG key (the key collapses to a 32-bit seed).

Falls back to ``jax.random.bernoulli`` when the tensor has >= 2**32 elements
(index would overflow the uint32 counter).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _murmur_mix(x: jax.Array) -> jax.Array:
    """murmur3 fmix32 — full avalanche on uint32."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def hash_dropout(
    x: jax.Array,
    rate: float,
    rng: jax.Array,
    deterministic: bool = False,
) -> jax.Array:
    """Inverted dropout with a counter-based keep mask.

    Semantics match ``nn.Dropout``: each element is zeroed with probability
    ``rate`` and survivors are scaled by ``1 / (1 - rate)``; the mask is a
    deterministic function of ``rng``. Only the mask's bit stream differs
    (hash of the linear index vs threefry counters) — both are Bernoulli.
    """
    if deterministic or rate <= 0.0:
        return x
    if x.size >= 2**32:
        keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
        return jnp.where(keep, x / (1.0 - rate), jnp.zeros_like(x))
    seed = jax.random.bits(rng, dtype=jnp.uint32)
    flat_iota = jax.lax.broadcasted_iota(
        jnp.uint32, (x.size,), 0
    ).reshape(x.shape)
    h = _murmur_mix(flat_iota ^ seed)
    threshold = jnp.uint32(min(int(rate * 2**32), 2**32 - 1))
    keep = h >= threshold
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros_like(x))


def residual_dropout(module, x: jax.Array, rate: float,
                     deterministic: bool) -> jax.Array:
    """Residual-stream dropout of a flax ``module`` (reference
    ``gpt.py:241,282``): :func:`hash_dropout` keyed by the module's
    ``"dropout"`` RNG stream, which is drawn from only when a mask is."""
    if deterministic or rate <= 0.0:
        return x
    return hash_dropout(x, rate, module.make_rng("dropout"))
