"""Pallas TPU causal flash attention for multi-head LATENT attention (MLA).

What ``ops/flash.py`` cannot express (its kernels take one ``head_dim`` for
q, k and v and rotate the whole head): a head's score contracts
``qk_nope_head_dim`` un-rotated lanes of its own key plus
``qk_rope_head_dim`` rotated lanes of a key that ALL heads share, and its
values are ``v_head_dim`` wide (128 + 64 and 128 for the DeepSeek-V3
family). The kernels here keep the structure of ``ops/flash.py``'s streaming
forward and split backward (one head a program, 512 x 512 blocks, causal
block skipping by ``pl.when``, lane-replicated softmax state, bf16 operands
with f32 accumulation, scores never in HBM), written over a tuple of score
PARTS so that how the 192 lanes are laid out is data, not code:

- a per-head part: q and k folded ``[b, s, h*w]``, ``w`` a multiple of 128,
  sliced a head by the BlockSpecs;
- a shared-key part: q head-major ``[b, h, s, w]`` (a 64-lane block must be
  its array's whole last dimension), k ``[b, s, w]``, the same block for
  every head; its ``dk`` leaves the kernel as per-head f32 partials and is
  summed over heads outside.

A score is the sum of its parts' dots. The MXU's time for a dot is its
left-hand rows times the 128 x 128 tiles of its right-hand side, so a
128-deep ``nope`` dot plus a 64-deep ``rope`` dot cost what one dot padded to
256 lanes costs (two passes); what the split layout saves is HBM copies: no
``[b, s, h, 64]`` expansion of the shared key, no padded q / k, no
reduction of a padded ``dk`` (PERF.md section 6, PR 32, has both layouts
measured on a v5e through these same bodies).

The split backward's grids walk every (q block, k block) pair; a pair above
the diagonal is skipped by ``pl.when``, and its operands' index maps are
clamped to the diagonal's so that the skipped step moves no block either.

``mla_flash_attention`` is the model's entry. RoPE is applied by the caller
(64 of a head's 192 lanes and one head of k: an elementwise pass outside).
Sequence lengths are a multiple of the 512 block, or one block of a multiple
of 128; no dropout, no packed segments.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tpu_trainer.ops.flash import _LANES, _NEG_INF, _lane_tile

BLOCK = 512
_F32 = dict(preferred_element_type=jnp.float32)
_CONTRACT_LANES = (((1,), (1,)), ((), ()))
_CONTRACT_ROWS = (((0,), (0,)), ((), ()))


def fits(seq: int) -> bool:
    """Whether the kernels take this sequence length."""
    return seq % BLOCK == 0 or (seq < BLOCK and seq % _LANES == 0)


def _block(seq: int) -> int:
    if fits(seq):
        return min(seq, BLOCK)
    raise NotImplementedError(
        f"the latent-attention kernels take sequences of a multiple of "
        f"{BLOCK} tokens, or one block of a multiple of {_LANES}; got {seq}")


def _spec(width: int, rows: int, row_index, layout: str) -> pl.BlockSpec:
    """``rows`` rows, block ``row_index(*grid[2:])``, of one operand of a
    grid ``(batch, head, ...)``: ``folded`` ``[b, s, h*width]``,
    ``head_major`` ``[b, h, s, width]`` or ``shared`` ``[b, s, width]``."""
    if layout == "head_major":
        return pl.BlockSpec((1, 1, rows, width),
                            lambda ib, ih, *g: (ib, ih, row_index(*g), 0))
    if layout == "shared":
        return pl.BlockSpec((1, rows, width),
                            lambda ib, ih, *g: (ib, row_index(*g), 0))
    return pl.BlockSpec((1, rows, width),
                        lambda ib, ih, *g: (ib, row_index(*g), ih))


def _part_specs(parts, shared, heads, rows_q, q_index, rows_k, k_index):
    """BlockSpecs of every part's q, then of every part's k."""
    qs = [_spec(q.shape[-1] if sh else q.shape[-1] // heads, rows_q, q_index,
                "head_major" if sh else "folded")
          for (q, _), sh in zip(parts, shared)]
    ks = [_spec(k.shape[-1] if sh else k.shape[-1] // heads, rows_k, k_index,
                "shared" if sh else "folded")
          for (_, k), sh in zip(parts, shared)]
    return qs, ks


def _rows(ref):
    """A ``[rows, w]`` block out of its ref, whichever layout it has."""
    return ref[0, 0] if len(ref.shape) == 4 else ref[0]


def _store(ref, scr):
    """An accumulator into its output block, whichever layout that has."""
    if len(ref.shape) == 4:
        ref[0, 0] = scr[...].astype(ref.dtype)
    else:
        ref[0] = scr[...].astype(ref.dtype)


def _scaled(q_refs, scale):
    # 1/sqrt(d) folded into q at its load, as ops/flash.py does: the score
    # block then needs no multiply, and dk = ds^T @ q_scaled is the scaled dk.
    return [(_rows(r).astype(jnp.float32) * scale).astype(r.dtype)
            for r in q_refs]


def _scores(qs, ks, masked, q_start, k_start):
    """``[bq, bk]`` f32 scores of one block pair: the parts' dots summed,
    the causal mask where the pair meets the diagonal."""
    s = sum(jax.lax.dot_general(q, k, _CONTRACT_LANES, **_F32)
            for q, k in zip(qs, ks))
    if masked:
        diff = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        s = jnp.where(diff >= k_start - q_start, s, _NEG_INF)
    return s


def _when_needed(body, q_start, k_start, block_q, block_k):
    """``body(masked)`` for a causal block pair: not at all above the
    diagonal, unmasked where every element is valid."""
    needed = q_start + block_q - 1 >= k_start
    full = q_start >= k_start + block_k - 1
    pl.when(full)(functools.partial(body, False))
    pl.when(needed & jnp.logical_not(full))(functools.partial(body, True))


# --------------------------------------------------------------------------
# forward: grid (b, h, q blocks); K / V whole-sequence blocks, walked by a
# static unroll.
# --------------------------------------------------------------------------

def _fwd_kernel(*refs, n, block_k, scale):
    q_refs, k_refs = refs[:n], refs[n:2 * n]
    v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[2 * n:]
    block_q, dv = o_ref.shape[1], o_ref.shape[2]
    q_start = pl.program_id(2) * block_q
    qs = _scaled(q_refs, scale)
    m_scr[...] = jnp.full((block_q, _LANES), _NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros((block_q, _LANES), jnp.float32)
    acc_scr[...] = jnp.zeros((block_q, dv), jnp.float32)

    def body(ik: int, masked: bool):
        rows = pl.ds(ik * block_k, block_k)
        s = _scores(qs, [r[0, rows, :] for r in k_refs], masked, q_start,
                    ik * block_k)
        m, l = m_scr[...], l_scr[...]              # [bq, 128], replicated
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lane_tile(m_new, block_k))
        alpha = jnp.exp(m - m_new)
        l_scr[...] = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        v = v_ref[0, rows, :]
        acc_scr[...] = acc_scr[...] * _lane_tile(alpha, dv) + jnp.dot(
            p.astype(v.dtype), v, **_F32)

    for ik in range(v_ref.shape[1] // block_k):
        _when_needed(functools.partial(body, ik), q_start, ik * block_k,
                     block_q, block_k)
    m, l = m_scr[...], l_scr[...]
    lse_ref[0, 0, 0, :] = m[:, 0] + jnp.log(l[:, 0])
    o_ref[0] = (acc_scr[...] / _lane_tile(l, dv)).astype(o_ref.dtype)


def _forward(parts, v3, *, shared, heads, scale, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, s, hv = v3.shape
    dv, block = hv // heads, _block(s)
    here = lambda i: i                                    # noqa: E731
    whole = lambda i: 0                                   # noqa: E731
    q_specs, k_specs = _part_specs(parts, shared, heads, block, here, s,
                                   whole)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, n=len(parts), block_k=block,
                          scale=scale),
        grid=(b, heads, s // block),
        in_specs=q_specs + k_specs + [_spec(dv, s, whole, "folded")],
        out_specs=[_spec(dv, block, here, "folded"),
                   pl.BlockSpec((1, 1, 1, block),
                                lambda ib, ih, i: (ib, ih, 0, i))],
        out_shape=[jax.ShapeDtypeStruct(v3.shape, v3.dtype),
                   jax.ShapeDtypeStruct((b, heads, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32)] * 2
        + [pltpu.VMEM((block, dv), jnp.float32)],
        interpret=interpret,
    )(*[q for q, _ in parts], *[k for _, k in parts], v3)


# --------------------------------------------------------------------------
# backward: the split pair. dkv: grid (b, h, k blocks, q blocks), dk / dv
# accumulate in VMEM across the q walk; dq: grid (b, h, q blocks, k blocks).
# --------------------------------------------------------------------------

def _probabilities(q_refs, k_refs, v_ref, do_ref, lse_ref, delta_ref, scale,
                   masked, q_start, k_start):
    """One block pair's scaled q's, k's, ``do``, probabilities and score
    gradient (the last two ``[bq, bk]`` in the operands' dtype)."""
    qs, ks = _scaled(q_refs, scale), [_rows(r) for r in k_refs]
    do = do_ref[0]
    s = _scores(qs, ks, masked, q_start, k_start)
    p = jnp.exp(s - lse_ref[0, 0, 0, :][:, None])
    dp = jax.lax.dot_general(do, v_ref[0], _CONTRACT_LANES, **_F32)
    ds = p * (dp - delta_ref[0, 0, 0, :][:, None])
    return qs, ks, do, p.astype(do.dtype), ds.astype(do.dtype)


def _bwd_dkv_kernel(*refs, n, scale):
    q_refs, k_refs = refs[:n], refs[n:2 * n]
    v_ref, do_ref, lse_ref, delta_ref = refs[2 * n:2 * n + 4]
    dk_refs, dv_ref = refs[2 * n + 4:3 * n + 4], refs[3 * n + 4]
    dk_scrs, dv_scr = refs[3 * n + 5:4 * n + 5], refs[4 * n + 5]
    block_k, block_q = v_ref.shape[1], do_ref.shape[1]
    iq = pl.program_id(3)
    k_start, q_start = pl.program_id(2) * block_k, iq * block_q

    @pl.when(iq == 0)
    def _zero():
        for scr in (*dk_scrs, dv_scr):
            scr[...] = jnp.zeros_like(scr)

    def body(masked: bool):
        qs, _, do, p, ds = _probabilities(
            q_refs, k_refs, v_ref, do_ref, lse_ref, delta_ref, scale, masked,
            q_start, k_start)
        dv_scr[...] += jax.lax.dot_general(p, do, _CONTRACT_ROWS, **_F32)
        for scr, q in zip(dk_scrs, qs):
            scr[...] += jax.lax.dot_general(ds, q, _CONTRACT_ROWS, **_F32)

    _when_needed(body, q_start, k_start, block_q, block_k)

    @pl.when(iq == pl.num_programs(3) - 1)
    def _flush():
        for ref, scr in zip((*dk_refs, dv_ref), (*dk_scrs, dv_scr)):
            _store(ref, scr)


def _bwd_dq_kernel(*refs, n, scale):
    q_refs, k_refs = refs[:n], refs[n:2 * n]
    v_ref, do_ref, lse_ref, delta_ref = refs[2 * n:2 * n + 4]
    dq_refs, dq_scrs = refs[2 * n + 4:3 * n + 4], refs[3 * n + 4:]
    block_k, block_q = v_ref.shape[1], do_ref.shape[1]
    ik = pl.program_id(3)
    q_start, k_start = pl.program_id(2) * block_q, ik * block_k

    @pl.when(ik == 0)
    def _zero():
        for scr in dq_scrs:
            scr[...] = jnp.zeros_like(scr)

    def body(masked: bool):
        _, ks, _, _, ds = _probabilities(
            q_refs, k_refs, v_ref, do_ref, lse_ref, delta_ref, scale, masked,
            q_start, k_start)
        for scr, k in zip(dq_scrs, ks):
            scr[...] += jnp.dot(ds, k, **_F32) * scale

    _when_needed(body, q_start, k_start, block_q, block_k)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _flush():
        for ref, scr in zip(dq_refs, dq_scrs):
            _store(ref, scr)


def _backward(parts, v3, o3, lse, do3, *, shared, heads, scale, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, s, hv = v3.shape
    dv, block = hv // heads, _block(s)
    n = len(parts)
    delta = jnp.moveaxis(
        (do3.astype(jnp.float32) * o3.astype(jnp.float32))
        .reshape(b, s, heads, dv).sum(axis=-1), 1, 2)[:, :, None, :]
    operands = (*[q for q, _ in parts], *[k for _, k in parts], v3, do3, lse,
                delta)
    widths = [k.shape[-1] if sh else k.shape[-1] // heads
              for (_, k), sh in zip(parts, shared)]
    row = lambda index: pl.BlockSpec(                     # noqa: E731
        (1, 1, 1, block), lambda ib, ih, *g: (ib, ih, 0, index(*g)))

    # dkv. A q block above the diagonal (iq < ik) is not computed with; its
    # index is clamped to the diagonal's so that it is not fetched either.
    k_at = lambda ik, iq: ik                              # noqa: E731
    q_at = lambda ik, iq: jnp.maximum(iq, ik)             # noqa: E731
    q_specs, k_specs = _part_specs(parts, shared, heads, block, q_at, block,
                                   k_at)
    dk_specs = [_spec(w, block, k_at, "head_major" if sh else "folded")
                for w, sh in zip(widths, shared)]
    *dks, dv3 = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n=n, scale=scale),
        grid=(b, heads, s // block, s // block),
        in_specs=q_specs + k_specs + [
            _spec(dv, block, k_at, "folded"), _spec(dv, block, q_at, "folded"),
            row(q_at), row(q_at)],
        out_specs=dk_specs + [_spec(dv, block, k_at, "folded")],
        out_shape=[jax.ShapeDtypeStruct(
            (b, heads, s, w) if sh else (b, s, heads * w),
            jnp.float32 if sh else k.dtype)
            for (_, k), w, sh in zip(parts, widths, shared)]
        + [jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
        scratch_shapes=[pltpu.VMEM((block, w), jnp.float32) for w in widths]
        + [pltpu.VMEM((block, dv), jnp.float32)],
        interpret=interpret,
    )(*operands)
    # A shared key's gradient is the sum of what each head gave it.
    dks = [dk.sum(axis=1).astype(k.dtype) if sh else dk
           for dk, (_, k), sh in zip(dks, parts, shared)]

    # dq; a k block above the diagonal (ik > iq) clamped likewise.
    q_at = lambda iq, ik: iq                              # noqa: E731
    k_at = lambda iq, ik: jnp.minimum(ik, iq)             # noqa: E731
    q_specs, k_specs = _part_specs(parts, shared, heads, block, q_at, block,
                                   k_at)
    dqs = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n=n, scale=scale),
        grid=(b, heads, s // block, s // block),
        in_specs=q_specs + k_specs + [
            _spec(dv, block, k_at, "folded"), _spec(dv, block, q_at, "folded"),
            row(q_at), row(q_at)],
        out_specs=q_specs,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype) for q, _ in parts],
        scratch_shapes=[pltpu.VMEM((block, w), jnp.float32) for w in widths],
        interpret=interpret,
    )(*operands)
    return tuple(zip(dqs, dks)), dv3


# --------------------------------------------------------------------------
# entries
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make(shared: Tuple[bool, ...], heads: int, scale: float,
          interpret: bool):
    kw = dict(shared=shared, heads=heads, scale=scale, interpret=interpret)

    @jax.custom_vjp
    def attention(parts, v3):
        return _forward(parts, v3, **kw)[0]

    def fwd(parts, v3):
        o3, lse = _forward(parts, v3, **kw)
        return o3, (parts, v3, o3, lse)

    def bwd(res, do3):
        return _backward(*res, do3, **kw)

    attention.defvjp(fwd, bwd)
    return attention


def parts_attention(parts, v3, *, shared, heads: int, scale: float,
                    interpret: bool = False):
    """Causal attention whose scores are the sum of the ``parts``' dots:
    ``parts`` a tuple of ``(q, k)``, per-head (folded ``[b, s, h*w]`` both)
    or, where ``shared`` says so, shared-key (q ``[b, h, s, w]``, k
    ``[b, s, w]``); ``v3`` folded ``[b, s, h*dv]``; the result folded like
    ``v3``. Differentiable in every operand."""
    _block(v3.shape[1])
    return _make(tuple(shared), heads, float(scale), interpret)(
        tuple(tuple(p) for p in parts), v3)


def mla_flash_attention(q_nope, q_rope, k_nope, k_rope, v, *, scale: float,
                        interpret: bool = False):
    """Latent attention's kernel call: ``q_nope`` / ``k_nope``
    ``[b, s, h, d_nope]``, ``q_rope`` ``[b, s, h, d_rope]`` and the shared
    ``k_rope`` ``[b, s, d_rope]`` both already rotated, ``v``
    ``[b, s, h, d_v]``; ``softmax((q_nope . k_nope + q_rope . k_rope) *
    scale) v``, causal, ``[b, s, h, d_v]``."""
    b, s, h, _ = q_nope.shape
    fold = lambda a: a.reshape(b, s, -1)                  # noqa: E731
    out = parts_attention(
        ((fold(q_nope), fold(k_nope)), (jnp.swapaxes(q_rope, 1, 2), k_rope)),
        fold(v), shared=(False, True), heads=h, scale=scale,
        interpret=interpret)
    return out.reshape(b, s, h, -1)
