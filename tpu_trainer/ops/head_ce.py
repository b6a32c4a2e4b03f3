"""Pallas fused LM-head + cross-entropy forward (flash-style softmax).

The XLA blockwise loss (``ops/loss.py``) is two HBM passes at headline
geometry: the head matmul writes a transient ``[tokens, vocab]`` f32 block
(~1.54 GB hidden behind the 190 TFLOP/s dot), then a logsumexp pass re-reads
all of it (~2.2 ms of pure HBM traffic on one v5e — the single removable
slice left in the round-4 step profile).

This kernel removes that pass the way flash attention removes the score
buffer: the logits tile is produced in VMEM by the MXU and the softmax
statistics (running row max ``m``, running scaled exp-sum ``s``, and the
exact-f32 label logit ``ll``) are folded in online before the tile leaves
the core. The logits are stored once, in COMPUTE dtype (bf16 — half the
f32 block the XLA path writes), solely as the backward's input; the loss
itself is ``(m + log s - ll)`` — exact f32 end to end (the label logit is
accumulated from the f32 MXU output, never the rounded store).

Backward is deliberately NOT Pallas: ``d logits = (softmax - onehot) * w``
feeds two roofline matmuls (``dx``, ``dE``) that XLA already fuses the
exp/onehot arithmetic into; its only change of regime is reading bf16
saved logits instead of CSE-reusing the f32 block, which rounds the
recomputed probabilities by 2^-9 — the same order as the flash kernel's
backward, which recomputes probabilities from bf16 q/k.

Grid: ``(vocab tiles, token tiles)``, token minor — the embedding tile is
loaded once per vocab tile (one full 77 MB sweep of E per step total) while
x re-reads scale with the vocab tile count. Running stats live in VMEM
scratch sized ``[1, padded tokens]`` and persist across the whole grid;
edge tiles rely on Pallas' masked stores plus an explicit column-validity
mask (cols >= vocab -> -1e30) so no operand is ever padded in HBM.

No reference counterpart (the reference materializes full logits into
``F.cross_entropy``, ``/root/reference/src/models/gpt.py:447-453``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = np.float32(-1e30)  # -inf stand-in (no inf-inf => NaN hazard)

# Tile shapes (one v5e core, 16 MB VMEM scope): x [256, H] + E [bv, H] +
# bf16 logits tile + f32 dot accumulator + double buffering. bv adapts to
# the hidden size — 2048 fits H=768 in ~11 MB, but H=1280 (gpt2-large)
# needs 1024 to stay under the scope (measured: 17.8 MB at bv=2048).
_BLOCK_T = 256


def _block_v(h: int, dtype_bytes: int) -> int:
    bt = _BLOCK_T
    for bv in (2048, 1536, 1024, 512):
        est = (2 * bv * h * dtype_bytes      # E tile, double-buffered
               + 2 * bt * h * dtype_bytes    # x tile, double-buffered
               + 2 * bv * bt * dtype_bytes   # logits out, double-buffered
               + bv * bt * 4)                # f32 dot accumulator
        if est <= 12 * 1024 * 1024:
            return bv
    return 256


def _head_ce_fwd_kernel(x_ref, e_ref, lab_ref, out_ref, m_ref, s_ref,
                        ll_ref, m_scr, s_scr, ll_scr, *, vocab: int,
                        block_t: int, block_v: int):
    v = pl.program_id(0)
    t = pl.program_id(1)

    # TRANSPOSED logits tile [bv, bt] — vocab-major comes free by swapping
    # the dot operands, and a [V, T] saved-logits layout (tokens minor) is
    # exactly what the backward's dx/dE matmuls consume without a relayout
    # (the row-major [T, V] variant measured a 5 ms copy + 4 ms convert in
    # the backward before the matmuls even started).
    lg = jax.lax.dot_general(
        e_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bv, bt] f32

    # Global vocab row ids of this tile; mask the vocab overhang (the last
    # E tile reads out of bounds — Pallas gives undefined values there, and
    # -1e30 neutralizes them for max/exp/store alike).
    rows = v * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_v, block_t), 0
    )
    lg = jnp.where(rows < vocab, lg, _NEG)
    out_ref[...] = lg.astype(out_ref.dtype)

    # Label logit: the label's row lands in exactly one vocab tile.
    lab = lab_ref[...]  # [1, bt] int32
    hit = rows == lab.reshape(1, block_t)
    ll_c = jnp.sum(jnp.where(hit, lg, 0.0), axis=0)  # [bt] f32

    tile_max = jnp.max(lg, axis=0)  # [bt]
    sl = pl.ds(t * block_t, block_t)
    first = v == 0
    prev_m = jnp.where(first, jnp.full((block_t,), _NEG), m_scr[0, sl])
    prev_s = jnp.where(first, 0.0, s_scr[0, sl])
    prev_ll = jnp.where(first, 0.0, ll_scr[0, sl])

    m_new = jnp.maximum(prev_m, tile_max)
    # prev_m = -1e30 on the first tile: exp(-1e30 - m) == 0, so the stale
    # scratch value is multiplied away without an inf-inf.
    s_new = prev_s * jnp.exp(prev_m - m_new) + jnp.sum(
        jnp.exp(lg - m_new[None, :]), axis=0
    )
    ll_new = prev_ll + ll_c

    m_scr[0, sl] = m_new
    s_scr[0, sl] = s_new
    ll_scr[0, sl] = ll_new
    # Outputs are re-written on every vocab step (tiny [1, bt] blocks); the
    # final vocab tile's flush is the value the caller sees.
    m_ref[...] = m_new.reshape(1, block_t)
    s_ref[...] = s_new.reshape(1, block_t)
    ll_ref[...] = ll_new.reshape(1, block_t)


def head_ce_forward(x2: jax.Array, emb: jax.Array, labels: jax.Array,
                    *, interpret: bool = False):
    """Fused head+CE forward on flattened tokens.

    Args:
      x2: ``[T, H]`` hidden states (compute dtype).
      emb: ``[V, H]`` LM head weight, same dtype as ``x2``.
      labels: ``[T]`` int32 target ids.

    Returns ``(logitsT [V, T] compute-dtype, lse [T] f32, ll [T] f32)`` —
    the saved logits come back TRANSPOSED (vocab-major; see the kernel
    comment), ``lse`` is the exact f32 per-token logsumexp, ``ll`` the f32
    label logit; ``loss_t = lse - ll``.
    """
    T, H = x2.shape
    V = emb.shape[0]
    bt, bv = _BLOCK_T, _block_v(H, x2.dtype.itemsize)
    nt, nv = pl.cdiv(T, bt), pl.cdiv(V, bv)

    kernel = functools.partial(
        _head_ce_fwd_kernel, vocab=V, block_t=bt, block_v=bv
    )
    logits_t, m, s, ll = pl.pallas_call(
        kernel,
        grid=(nv, nt),
        in_specs=[
            pl.BlockSpec((bt, H), lambda v, t: (t, 0)),
            pl.BlockSpec((bv, H), lambda v, t: (v, 0)),
            pl.BlockSpec((1, bt), lambda v, t: (0, t)),
        ],
        out_specs=[
            pl.BlockSpec((bv, bt), lambda v, t: (v, t)),
            pl.BlockSpec((1, bt), lambda v, t: (0, t)),
            pl.BlockSpec((1, bt), lambda v, t: (0, t)),
            pl.BlockSpec((1, bt), lambda v, t: (0, t)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((V, T), x2.dtype),
            jax.ShapeDtypeStruct((1, T), jnp.float32),
            jax.ShapeDtypeStruct((1, T), jnp.float32),
            jax.ShapeDtypeStruct((1, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, nt * bt), jnp.float32),
            pltpu.VMEM((1, nt * bt), jnp.float32),
            pltpu.VMEM((1, nt * bt), jnp.float32),
        ],
        interpret=interpret,
    )(x2, emb, labels.reshape(1, T))
    lse = m[0] + jnp.log(s[0])
    return logits_t, lse, ll[0]


# --- custom_vjp wrapper over [b, s] batches --------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def pallas_head_ce(emb, x, labels, mask, mesh=None, interpret=False):
    """Mean masked cross entropy via the fused kernel (labels pre-shifted).

    Same contract as ``ops/loss._chunked_ce``: ``x [b, s, h]``, shifted
    ``labels [b, s]``, ``mask [b, s]`` f32 weights; scalar f32 mean loss.
    ``mesh``/``interpret`` are trace-time constants (nondiff).
    """
    return _pallas_ce_fwd(emb, x, labels, mask, mesh, interpret)[0]


def _shard_axes(mesh, b: int, s: int):
    """Mesh axes the kernel shard_maps over: ``(batch_axes, seq_axes)``.

    Batch shards over data x fsdp when ``b`` divides; the sequence dim
    shards over the ``sequence`` axis when ``s`` divides (the shift and
    the position mask are computed GLOBALLY by the caller before dispatch,
    so shard-local labels/mask slices are already correct — no boundary
    exchange is needed at the kernel level). ``(None, None)`` = run the
    kernel unsharded (replicated manual region).
    """
    if mesh is None:
        return None, None
    from tpu_trainer.parallel.mesh import (
        DATA_AXIS, FSDP_AXIS, SEQUENCE_AXIS)

    axes = tuple(
        a for a in (DATA_AXIS, FSDP_AXIS) if mesh.shape.get(a, 1) > 1
    )
    size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if axes and b % size != 0:
        axes = ()
    seq_axes = ()
    if (mesh.shape.get(SEQUENCE_AXIS, 1) > 1
            and s % mesh.shape[SEQUENCE_AXIS] == 0):
        seq_axes = (SEQUENCE_AXIS,)
    return (axes or None), (seq_axes or None)


def _fwd_parts(emb, x, labels, mask, mesh, interpret):
    b, s, h = x.shape
    e_c = emb.astype(x.dtype)

    def local(x_l, e_l, lab_l):
        bl, sl = x_l.shape[:2]
        logits_t, lse, ll = head_ce_forward(
            x_l.reshape(bl * sl, h), e_l, lab_l.reshape(bl * sl),
            interpret=interpret,
        )
        # Saved logits as [V, b, s]: with the token dim factored, each
        # shard's output declares its true (batch block, seq block)
        # position — a flat [V, T] out-spec would permute the global
        # token order when BOTH batch and sequence axes shard. A free
        # bitcast when unsharded.
        return (logits_t.reshape(-1, bl, sl), lse.reshape(bl, sl),
                ll.reshape(bl, sl))

    b_axes, s_axes = _shard_axes(mesh, b, s)
    if b_axes is None and s_axes is None:
        logits_t, lse, ll = local(x, e_c, labels)
    else:
        from tpu_trainer.parallel.context import kernel_manual_axes
        from tpu_trainer.utils.jax_compat import shard_map
        from jax.sharding import PartitionSpec as P

        # Partial-manual over the batch (and, round 5, sequence) axes only
        # (the attention dispatch's pattern, ops/attention.py): other mesh
        # axes stay under GSPMD. Under SP the caller's global shift/mask
        # make the shard-local label slices correct as-is (see
        # _shard_axes).
        t_axes = tuple(b_axes or ()) + tuple(s_axes or ())
        logits_t, lse, ll = shard_map(
            local, mesh=mesh,
            in_specs=(P(b_axes, s_axes), P(), P(b_axes, s_axes)),
            out_specs=(P(None, b_axes, s_axes), P(b_axes, s_axes),
                       P(b_axes, s_axes)),
            axis_names=kernel_manual_axes(mesh, t_axes),
            check_vma=False,
        )(x, e_c, labels)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum((lse - ll) * mask) / denom
    return loss, logits_t, lse, denom


def _pallas_ce_fwd(emb, x, labels, mask, mesh, interpret):
    loss, logits_t, lse, denom = _fwd_parts(emb, x, labels, mask, mesh,
                                            interpret)
    return loss, (emb, x, labels, mask, logits_t, lse, denom)


def _pallas_ce_bwd(mesh, interpret, res, g):
    emb, x, labels, mask, logits_t, lse, denom = res
    b, s, h = x.shape
    vocab = emb.shape[0]
    e_c = emb.astype(x.dtype)
    scale = g / denom

    # (softmax - onehot) * weight, in the kernel's vocab-major layout —
    # XLA fuses the exp/onehot chain into the two matmuls' operand reads
    # (this is why the kernel emits vocab-major: the row-major variant
    # forced a measured 5 ms relayout + 4 ms convert before the matmuls),
    # so no vocab-major f32 cotangent is ever materialized.
    #
    # Shape regime, decided at trace time from the mesh: the flat [V, T]
    # form lowers to two plain GEMMs (the fast path — the factored 3-D
    # dot_general measured 18% off the headline, 115.7k -> 94.9k tok/s).
    # But whenever the SEQUENCE axis shards dim 2 of the residual, the
    # merged T cannot carry that sharding (batch-only sharding merges
    # fine — T blocks stay contiguous) and the reshape would reshard the
    # largest buffer of the step — there the backward stays in the
    # residual's [V, b, s] form.
    b_axes, s_axes = _shard_axes(mesh, b, s)
    if s_axes is not None:
        p_t = jnp.exp(logits_t.astype(jnp.float32) - lse[None, :, :])
        rows = jax.lax.broadcasted_iota(jnp.int32, (vocab, b, s), 0)
        onehot_t = (rows == labels[None, :, :]).astype(jnp.float32)
        dlg_t = ((p_t - onehot_t)
                 * (mask * scale)[None, :, :]).astype(x.dtype)  # [V, b, s]
        dx = jax.lax.dot_general(
            dlg_t, e_c, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(x.dtype)  # [b, s, h]
        de = jax.lax.dot_general(
            dlg_t, x, (((1, 2), (0, 1)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(emb.dtype)  # [V, h]
        return de, dx, None, None

    T = b * s
    p_t = jnp.exp(logits_t.reshape(vocab, T).astype(jnp.float32)
                  - lse.reshape(T)[None, :])
    rows = jax.lax.broadcasted_iota(jnp.int32, (vocab, T), 0)
    onehot_t = (rows == labels.reshape(T)[None, :]).astype(jnp.float32)
    w = (mask.reshape(T) * scale)
    dlg_t = ((p_t - onehot_t) * w[None, :]).astype(x.dtype)
    dx = jax.lax.dot_general(
        dlg_t, e_c, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype).reshape(b, s, h)
    de = jax.lax.dot_general(
        dlg_t, x.reshape(T, h), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(emb.dtype)
    return de, dx, None, None


pallas_head_ce.defvjp(_pallas_ce_fwd, _pallas_ce_bwd)
