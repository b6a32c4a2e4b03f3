"""Pallas TPU causal flash attention for multi-head LATENT attention (MLA).

What ``ops/flash.py`` cannot express (its kernels take one ``head_dim`` for
q, k and v and rotate the whole head): a head's score contracts
``qk_nope_head_dim`` un-rotated lanes of its own key plus
``qk_rope_head_dim`` rotated lanes of a key that ALL heads share, and its
values are ``v_head_dim`` wide (128 + 64 and 128 for the DeepSeek-V3
family). The kernels here, a streaming forward and a one-pass backward, keep
the ways of ``ops/flash.py`` (one head a program, 512 x 512 blocks, causal
block skipping, lane-replicated softmax state, bf16 operands with f32
accumulation, scores never in HBM), written over a tuple of score PARTS so
that how the 192 lanes are laid out is data, not code:

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

The backward evaluates a block pair's scores, ``p``, ``dp`` and ``ds`` ONCE
and feeds ``dv``, every part's ``dk`` and every part's ``dq`` from them:
eight dots a pair at 128 + 64 lanes, where a dk/dv kernel beside a dq
kernel pushed eleven (PERF.md section 6, PR 33). Its grid's last axis is the
list of a head's block pairs on and under the diagonal, K/V block by K/V
block and each one's q blocks upwards from the diagonal, named by two
scalar-prefetch tables: no step is there to be skipped. ``dk`` / ``dv``
accumulate in f32 over a K/V block's walk; a head's whole ``dq`` stays in
VMEM as f32 rows through all of its walks, each row block summing its K/V
blocks in ascending order, and is rounded once at the head's last step. That
residency grows with the sequence, so the kernel asks for its own VMEM limit;
it compiles for a v5e at every length the forward does.

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
import numpy as np
from jax.experimental import pallas as pl

from tpu_trainer.ops.flash import _LANES, _NEG_INF, _d_row_form, _lane_tile

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


def _store(ref, rows, at=slice(None)):
    """Accumulated rows into (rows ``at`` of) their output block, whichever
    layout that has."""
    if len(ref.shape) == 4:
        ref[0, 0, at, :] = rows.astype(ref.dtype)
    else:
        ref[0, at, :] = rows.astype(ref.dtype)


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
# backward: one pass. Grid (b, h, causal block pairs): a head's pairs on and
# under the diagonal, K/V block by K/V block, each one's q blocks upwards
# from the diagonal; two scalar-prefetch tables name a step's blocks.
# --------------------------------------------------------------------------

# A head's whole dq stays in VMEM through its walk, every part's output rows
# and f32 accumulator: over 1 KiB a token at 128 + 64 lanes beside a few MiB
# of blocks and temporaries. That passes Mosaic's default scope of 16 MiB at
# s = 4096; under this limit (the chip has 128 MiB) the kernel compiles for a
# v5e through 32,768 tokens (65 MB asked for at 36,864), twice the 13,312 at
# which the forward's whole-sequence K / V blocks stop.
_BWD_VMEM_LIMIT = 48 * 1024 * 1024


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


def _bwd_kernel(ik_of, iq_of, *refs, n, scale, d_rows):
    q_refs, k_refs = refs[:n], refs[n:2 * n]
    v_ref, do_ref, lse_ref, delta_ref = refs[2 * n:2 * n + 4]
    dq_refs, dk_refs = refs[2 * n + 4:3 * n + 4], refs[3 * n + 4:4 * n + 4]
    dv_ref = refs[4 * n + 4]
    dq_scrs, dk_scrs = refs[4 * n + 5:5 * n + 5], refs[5 * n + 5:6 * n + 5]
    dv_scr = refs[6 * n + 5]
    block, q_blocks = v_ref.shape[1], dq_scrs[0].shape[0]
    step = pl.program_id(2)
    ik, iq = ik_of[step], iq_of[step]

    @pl.when(step == 0)
    def _zero_dq():
        for scr in dq_scrs:
            scr[...] = jnp.zeros_like(scr)

    @pl.when(iq == ik)
    def _zero_dkv():
        for scr in (*dk_scrs, dv_scr):
            scr[...] = jnp.zeros_like(scr)

    def body(masked: bool):
        # One evaluation of the pair's scores, p, dp and ds feeds all of
        # dv, every part's dk and every part's dq.
        qs, ks, do, p, ds = _probabilities(
            q_refs, k_refs, v_ref, do_ref, lse_ref, delta_ref, scale, masked,
            iq * block, ik * block)
        dv_scr[...] += jax.lax.dot_general(p, do, _CONTRACT_ROWS, **_F32)
        for dk_scr, dq_scr, q, k, d_row in zip(dk_scrs, dq_scrs, qs, ks,
                                               d_rows):
            if d_row:   # dk^T = q^T @ ds, dq^T = k^T @ ds^T: w rows, not 512
                dk_scr[...] += jnp.dot(q.T, ds, **_F32)
                dq_scr[iq] += jax.lax.dot_general(
                    k.T, ds, _CONTRACT_LANES, **_F32) * scale
            else:
                dk_scr[...] += jax.lax.dot_general(
                    ds, q, _CONTRACT_ROWS, **_F32)
                dq_scr[iq] += jnp.dot(ds, k, **_F32) * scale

    # Square blocks: the pair on the diagonal is the masked one.
    pl.when(iq == ik)(functools.partial(body, True))
    pl.when(iq != ik)(functools.partial(body, False))

    def rows(acc, d_row):
        return acc.T if d_row else acc

    @pl.when(iq == q_blocks - 1)
    def _flush_dkv():
        for ref, scr, d_row in zip((*dk_refs, dv_ref), (*dk_scrs, dv_scr),
                                   (*d_rows, False)):
            _store(ref, rows(scr[...], d_row))

    @pl.when(step == pl.num_programs(2) - 1)
    def _flush_dq():
        for ref, scr, d_row in zip(dq_refs, dq_scrs, d_rows):
            for j in range(q_blocks):
                _store(ref, rows(scr[j], d_row), pl.ds(j * block, block))


def _backward(parts, v3, o3, lse, do3, *, shared, heads, scale, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, s, hv = v3.shape
    dv, block = hv // heads, _block(s)
    n = len(parts)
    delta = jnp.moveaxis(
        (do3.astype(jnp.float32) * o3.astype(jnp.float32))
        .reshape(b, s, heads, dv).sum(axis=-1), 1, 2)[:, :, None, :]
    widths = [k.shape[-1] if sh else k.shape[-1] // heads
              for (_, k), sh in zip(parts, shared)]
    # Under 128 lanes a part's two gradient dots take their d-row form and
    # accumulate transposed (``ops/flash.py::_d_row_form``, PR 31): a 64-lane
    # part then costs the MXU half the passes. The same bits on the chip.
    d_rows = tuple(_d_row_form(w) for w in widths)
    # Row-major upper triangle: K/V block ik with q blocks ik, ik + 1, ...
    # No step is skipped, so none fetches a block it does not use.
    ik_of, iq_of = (jnp.asarray(a, jnp.int32)
                    for a in np.triu_indices(s // block))
    k_at = lambda step, ik_of, iq_of: ik_of[step]         # noqa: E731
    q_at = lambda step, ik_of, iq_of: iq_of[step]         # noqa: E731
    whole = lambda *_: 0                                  # noqa: E731
    q_specs, k_specs = _part_specs(parts, shared, heads, block, q_at, block,
                                   k_at)
    dq_specs, _ = _part_specs(parts, shared, heads, s, whole, block, k_at)
    row = pl.BlockSpec((1, 1, 1, block),
                       lambda ib, ih, *g: (ib, ih, 0, q_at(*g)))
    *grads, dv3 = pl.pallas_call(
        functools.partial(_bwd_kernel, n=n, scale=scale, d_rows=d_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, heads, len(ik_of)),
            in_specs=q_specs + k_specs + [
                _spec(dv, block, k_at, "folded"),
                _spec(dv, block, q_at, "folded"), row, row],
            out_specs=dq_specs + [
                _spec(w, block, k_at, "head_major" if sh else "folded")
                for w, sh in zip(widths, shared)]
            + [_spec(dv, block, k_at, "folded")],
            scratch_shapes=[          # dq a q block, then dk, then dv
                pltpu.VMEM(lead + ((w, block) if d_row else (block, w)),
                           jnp.float32)
                for lead in ((s // block,), ()) for w, d_row in zip(
                    widths, d_rows)]
            + [pltpu.VMEM((block, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype) for q, _ in parts]
        + [jax.ShapeDtypeStruct(
            (b, heads, s, w) if sh else (b, s, heads * w),
            jnp.float32 if sh else k.dtype)
            for (_, k), w, sh in zip(parts, widths, shared)]
        + [jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret,
    )(ik_of, iq_of, *[q for q, _ in parts], *[k for _, k in parts], v3, do3,
      lse, delta)
    # A shared key's gradient is the sum of what each head gave it.
    dks = [dk.sum(axis=1).astype(k.dtype) if sh else dk
           for dk, (_, k), sh in zip(grads[n:], parts, shared)]
    return tuple(zip(grads[:n], dks)), dv3


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
        # The scope names the kernel in every trace (`bwd_fused.<n>`).
        with jax.named_scope("bwd_fused"):
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
