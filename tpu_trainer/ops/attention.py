"""Attention ops: jnp reference path + fused ("flash") path.

Mirrors the two attention paths of the reference
(``/root/reference/src/models/gpt.py:199-234``):

- ``reference_attention`` — the manual path (``gpt.py:230-234``): QK^T/sqrt(d)
  → causal mask → float32 softmax → dropout → @V. Kept as the numerics oracle
  for the fused kernel, exactly as the reference keeps its manual branch.
- ``flash_attention`` — the fused path (``gpt.py:199-206`` calls torch's
  ``scaled_dot_product_attention``). Here this dispatches to the Pallas TPU
  kernel (``tpu_trainer.ops.flash``) when available, falling back to XLA's
  fused attention otherwise.

All functions take ``q, k, v`` of shape ``[batch, seq, num_heads, head_dim]``
(BSHD layout — the natural layout for TPU, avoiding the transpose the reference
does for torch's BHSD convention).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

# Test hook: route the dispatch to the Pallas kernel in interpret mode even
# off-TPU, so the fake-8-device CPU mesh tests exercise the kernel (and its
# shard_map wrapping) end to end. Set TPU_TRAINER_FLASH_INTERPRET=1.
_INTERPRET_ENV = "TPU_TRAINER_FLASH_INTERPRET"


def causal_mask(seq_len: int) -> jax.Array:
    """Boolean [seq, seq] mask, True where attention is allowed (lower tri)."""
    return jnp.tril(jnp.ones((seq_len, seq_len), dtype=jnp.bool_))


def repeat_kv(k: jax.Array, v: jax.Array, num_heads: int):
    """Expand grouped K/V heads to ``num_heads`` by contiguous-group repeat.

    THE query-to-KV-head mapping: query head ``i`` reads K/V head
    ``i // (num_heads // kv_heads)`` — the same contiguous-group order the
    flash kernel's BlockSpec index map uses (``ops/flash.py``). Every
    jnp-level GQA expansion goes through here so the mapping is pinned in
    one place.
    """
    kvh = k.shape[2]
    if kvh == num_heads:
        return k, v
    group = num_heads // kvh
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def _flash_mesh(q: jax.Array):
    """The active mesh context's mesh, when sharding the kernel is useful.

    Returns None (plain kernel call — GSPMD sees one device, nothing to
    partition) unless a mesh with a non-trivial ``data``/``fsdp``/``tensor``
    axis is published by the trainer (``parallel/context.py``). Attention is
    independent across batch and heads, so those axes shard the kernel
    losslessly; the ``sequence`` axis is the ring path's job and never
    reaches this dispatch (the model routes SP through ``ops/ring.py``).
    """
    from tpu_trainer.parallel.context import current_mesh

    mesh = current_mesh()
    if mesh is None:
        return None
    from tpu_trainer.parallel.mesh import DATA_AXIS, FSDP_AXIS, TENSOR_AXIS

    sizes = [
        mesh.shape.get(DATA_AXIS, 1),
        mesh.shape.get(FSDP_AXIS, 1),
        mesh.shape.get(TENSOR_AXIS, 1),
    ]
    if all(s == 1 for s in sizes):
        return None
    return mesh


def _sharded_kernel(q, k, v, mesh, kernel_kwargs):
    """Run the Pallas kernel under ``shard_map`` over batch/head mesh axes.

    A ``pallas_call`` is opaque to the SPMD partitioner: left inside a GSPMD
    region on a multi-device mesh it forces replication (all-gather of
    q/k/v). Wrapping it in ``shard_map`` over the axes attention is
    independent along — batch over ``data`` x ``fsdp``, heads over
    ``tensor`` — runs the unchanged kernel on each shard with zero
    communication. Axes that don't divide the dim (tiny test batches) stay
    replicated, mirroring ``ring_attention``'s spec fallback.

    In-kernel dropout stays decorrelated across shards by folding each
    shard's mesh coordinates into the PRNG key (the kernel's counter-based
    mask hashes *local* positions, which coincide across shards).
    """
    from tpu_trainer.utils.jax_compat import shard_map
    from jax.sharding import PartitionSpec as P

    from tpu_trainer.parallel.context import kernel_manual_axes
    from tpu_trainer.parallel.mesh import (
        attention_shard_coord, attention_shard_spec,
    )
    from tpu_trainer.ops import flash

    b, _, h, _ = q.shape
    b_spec, h_spec = attention_shard_spec(mesh, b, h, k.shape[2])
    if b_spec is None and h_spec is None:
        return flash.flash_attention(q, k, v, **kernel_kwargs)
    spec = P(b_spec, None, h_spec, None)

    # Traced values (rng key, rope tables, segment ids) enter shard_map as
    # explicit arguments, not closure captures. Segment ids shard with the
    # batch axis like every other per-row operand.
    static_kwargs = dict(kernel_kwargs)
    rng = static_kwargs.pop("dropout_rng")
    rope_tabs = static_kwargs.pop("rope")
    seg = static_kwargs.pop("segment_ids", None)
    has_rng = rng is not None
    has_rope = rope_tabs is not None
    has_seg = seg is not None
    extras = (() if not has_rng else (rng,)) + (
        tuple(rope_tabs) if has_rope else ()
    ) + ((seg,) if has_seg else ())
    extra_specs = (() if not has_rng else (P(),)) + (
        (P(None, None), P(None, None)) if has_rope else ()
    ) + ((P(b_spec, None),) if has_seg else ())

    def local(q, k, v, *extra):
        i = 0
        rng_local = None
        if has_rng:
            # Decorrelate the in-kernel dropout mask across (sharded-axis)
            # shards — see attention_shard_coord.
            coord = attention_shard_coord(mesh, b_spec, h_spec)
            rng_local = jax.random.fold_in(extra[0], coord)
            i = 1
        rope_local = (extra[i], extra[i + 1]) if has_rope else None
        if has_rope:
            i += 2
        seg_local = extra[i] if has_seg else None
        return flash.flash_attention(
            q, k, v, dropout_rng=rng_local, rope=rope_local,
            segment_ids=seg_local, **static_kwargs
        )

    # Manual over the axes this wrapper actually shards, plus the size-1
    # axes Mosaic needs manual too (kernel_manual_axes): other sharded axes
    # (e.g. a pipeline `stage` axis whose manual region we may be nested
    # inside) stay untouched, letting the kernel keep its batch/head
    # sharding inside the GPipe stage body. When tracing inside another
    # manual region, shard_map requires the *context* abstract mesh (same
    # axes, with the outer region's axes typed Manual) rather than the
    # concrete mesh.
    used_axes = set()
    if b_spec is not None:
        used_axes.update(b_spec)
    if h_spec is not None:
        used_axes.add(h_spec)
    from jax.sharding import get_abstract_mesh

    sm_mesh = mesh
    ctx_mesh = get_abstract_mesh()
    if ctx_mesh.shape_tuple and ctx_mesh.shape == mesh.shape:
        sm_mesh = ctx_mesh
    fn = shard_map(
        local,
        mesh=sm_mesh,
        in_specs=(spec, spec, spec) + extra_specs,
        out_specs=spec,
        axis_names=kernel_manual_axes(mesh, used_axes),
        check_vma=False,
    )
    return fn(q, k, v, *extras)


def segment_mask(segment_ids: jax.Array) -> jax.Array:
    """Boolean [batch, 1, seq, seq] mask, True where q and k positions share
    a segment id — the dense form of the kernels' packed-document
    isolation. Broadcastable against [batch, heads, q, k] score tensors."""
    return (segment_ids[:, None, :, None] == segment_ids[:, None, None, :])


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    dropout_rate: float = 0.0,
    deterministic: bool = True,
    dropout_rng: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Manual causal attention (reference ``gpt.py:230-234``).

    float32 softmax for stability (the reference passes ``dtype=torch.float32``
    to softmax), dropout applied to the attention weights. Accepts grouped
    K/V (``num_kv_heads < num_heads``) by head repetition — the GQA oracle.
    ``segment_ids`` ([batch, seq] int) additionally restricts attention to
    same-segment pairs — the dense oracle for the packed flash kernels.
    ``window`` keeps the keys ``i - window < j <= i`` of position ``i``.
    """
    _, s, h, d = q.shape
    k, v = repeat_kv(k, v, h)
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = causal_mask(s)
    if window is not None:
        mask = mask & ~jnp.tril(mask, -window)
    mask = mask[None, None, :, :]
    if segment_ids is not None:
        mask = mask & segment_mask(segment_ids)
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and not deterministic:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, weights.shape)
        weights = jnp.where(keep, weights / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    dropout_rate: float = 0.0,
    deterministic: bool = True,
    dropout_rng: Optional[jax.Array] = None,
    rope: Optional[tuple] = None,
    segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Fused causal attention (reference flash path, ``gpt.py:199-206``).

    Dispatches to the Pallas TPU kernel when running on TPU — including
    training with attention-weight dropout (counter-based in-kernel mask)
    and RoPE fused into the kernel when ``rope=(cos, sin)`` is given.
    Off-TPU, applies rope externally and uses XLA's fused attention, with
    the manual path covering the dropout case (same semantics as the
    reference's manual branch). ``segment_ids`` ([batch, seq] int)
    isolates attention within packed documents on every path. ``window``
    keeps the keys ``i - window < j <= i`` of position ``i`` (the banded
    kernels of ``ops/flash.py`` on TPU; the masked reference otherwise).
    """
    active_dropout = dropout_rate > 0.0 and not deterministic
    interpret = os.environ.get(_INTERPRET_ENV, "0") == "1"
    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    if on_tpu or interpret:
        from tpu_trainer.ops import flash

        kernel_kwargs = dict(
            causal=True,
            dropout_rate=dropout_rate if active_dropout else 0.0,
            dropout_rng=dropout_rng,
            rope=rope,
            interpret=interpret,
            segment_ids=segment_ids,
        )
        if window is not None:
            kernel_kwargs["window"] = window
        mesh = _flash_mesh(q)
        if mesh is not None:
            return _sharded_kernel(q, k, v, mesh, kernel_kwargs)
        return flash.flash_attention(q, k, v, **kernel_kwargs)
    if rope is not None:
        from tpu_trainer.ops.rope import apply_rotary_pos_emb

        q, k = apply_rotary_pos_emb(q, k, rope[0], rope[1])
    if active_dropout or segment_ids is not None or window is not None:
        return reference_attention(
            q, k, v,
            dropout_rate=dropout_rate if active_dropout else 0.0,
            deterministic=deterministic and not active_dropout,
            dropout_rng=dropout_rng,
            segment_ids=segment_ids,
            window=window,
        )
    # jax.nn.dot_product_attention handles grouped K/V natively (K heads
    # dividing N) — pass the compact tensors straight through.
    return jax.nn.dot_product_attention(q, k, v, is_causal=True)


def window_kernel_block(q_shape) -> Optional[int]:
    """The block at which a window layer's attention of ``q_shape`` ([b, s,
    heads, head_dim]) runs the banded kernels of ``ops/flash.py`` (on TPU,
    or under the interpret hook), or None where it runs in XLA."""
    interpret = os.environ.get(_INTERPRET_ENV, "0") == "1"
    if not (interpret or any(d.platform == "tpu" for d in jax.devices())):
        return None
    from tpu_trainer.ops.flash import band_block

    return band_block(q_shape[1], q_shape[3], interpret)


def _mla_reference(q_nope, q_rope, k_nope, k_rope, v, scale):
    """Latent attention in plain jnp (the off-TPU path): float32 scores and
    softmax, the result in the inputs' dtype."""
    s = q_nope.shape[1]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    scores = jnp.where(causal_mask(s)[None, None], scores,
                       jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def mla_attention(q_nope, q_rope, k_nope, k_rope, v, *, scale: float):
    """Causal latent attention (MLA): ``softmax((q_nope . k_nope + q_rope .
    k_rope) * scale) v`` with ``k_rope [b, s, d_rope]`` one head shared by
    all, q_rope / k_rope already rotated. On TPU (or under
    ``TPU_TRAINER_FLASH_INTERPRET=1``) the Pallas kernels of
    ``ops/flash_mla.py``, under ``shard_map`` over the batch axes of a
    published mesh; plain jnp otherwise, and for a sequence the kernels'
    blocks do not divide (the parameter initialiser's eight tokens)."""
    from tpu_trainer.ops.flash_mla import fits, mla_flash_attention

    interpret = os.environ.get(_INTERPRET_ENV, "0") == "1"
    if not (interpret or any(d.platform == "tpu" for d in jax.devices())
            ) or not fits(q_nope.shape[1]):
        return _mla_reference(q_nope, q_rope, k_nope, k_rope, v, scale)

    kernel = functools.partial(
        mla_flash_attention, scale=scale, interpret=interpret)
    mesh = _flash_mesh(q_nope)
    if mesh is None:
        return kernel(q_nope, q_rope, k_nope, k_rope, v)
    from jax.sharding import PartitionSpec as P

    from tpu_trainer.parallel.context import kernel_manual_axes
    from tpu_trainer.parallel.mesh import attention_shard_spec
    from tpu_trainer.utils.jax_compat import shard_map

    # Batch only: the shared key has no head axis to split.
    b_spec, _ = attention_shard_spec(mesh, q_nope.shape[0], 1, 1)
    if b_spec is None:
        return kernel(q_nope, q_rope, k_nope, k_rope, v)
    heads, shared = P(b_spec, None, None, None), P(b_spec, None, None)
    return shard_map(
        kernel, mesh=mesh, in_specs=(heads, heads, heads, shared, heads),
        out_specs=heads, axis_names=kernel_manual_axes(mesh, set(b_spec)),
        check_vma=False,
    )(q_nope, q_rope, k_nope, k_rope, v)
