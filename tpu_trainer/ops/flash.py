"""Pallas TPU causal flash attention (forward + backward).

The framework's native compute kernel (SURVEY.md C4): the TPU counterpart of
the reference's call into torch's fused ``scaled_dot_product_attention``
(``/root/reference/src/models/gpt.py:199-206``) — except implemented here as a
blockwise-streaming kernel rather than a library call.

Design (flash-attention-2 structure, written for the TPU memory hierarchy).
What runs where, as measured on a v5e (PERF.md has the runs):

- Grid ``(batch, heads/hp, seq // block_q)`` with ``hp`` heads per program
  (2 for head_dim 64 so the block lane width is 128; 1 for d%128==0).
  Each program owns one query block in VMEM and walks key/value blocks
  through the MXU with an online (running max / running sum) softmax. The
  ``[seq, seq]`` score matrix is never materialized in HBM — this is what
  removes the O(S^2) activation memory of the XLA fallback path.
- Block loops are STATIC Python unrolls with ``pl.when``-predicated bodies
  (softmax state in VMEM scratch), not ``fori_loop``s with data-dependent
  trip counts — Mosaic cannot schedule those, and causality's skipped
  blocks measured as costing full price. At ``seq <= block`` (s <= 1024)
  a single-block fast path drops the online softmax entirely.
- Longer sequences STREAM 512 x 512 blocks (the wrapper caps the default
  1024 there: the 1024-block streaming forward does not fit the 16 MB
  scope). That is what the benchmark's cells run at s=2048: 10 of 16
  block pairs a head. The streaming forward works on whole ``[*, hp*d]``
  slabs, full 128-lane registers for a d=64 head pair: heads are told
  apart by zeroed q lanes in the score contraction and a lane select on
  the accumulator, never by slicing 64 lanes out; the running max and sum
  are kept replicated over 128 lanes (``[block_q, 128]``), so nothing is
  broadcast out of a one-lane column; and each K block is rotated ONCE,
  by the first program that needs it, then read back from the rotated-K
  output block, which stays in VMEM along the (sequential) query-block
  axis. PR 27, forward alone, bf16, causal, fused RoPE: ``[4, 2048, 16,
  64]`` 0.87 ms a call (1.36 us a block pair and head) where per-head
  64-lane halves, ``[block_q, 1]`` state and a rotation at every visit
  took 1.53 ms (2.39 us); ``[4, 2048, 32, 64]`` 1.73 ms against 3.05.
- Operands are the model's FOLDED ``[b, s, h*d]`` layout, sliced per
  head(-pair) by the BlockSpecs: no BSHD transpose ever exists in HBM.
- Backward DISPATCHES on sequence length. At s <= 2048 it is one fused
  kernel (grid over key blocks) with its own block shape (512x512: causal
  skipping wins): one score/probability evaluation per block pair feeds
  dk, dv, and dq — dq accumulates in f32 in VMEM across the sequential
  grid steps — using the saved per-row logsumexp and the precomputed
  ``delta = rowsum(dO * O)``. Its multi-block body (1024 < s <= 2048)
  works on whole ``[*, hp*d]`` slabs like the streaming forward (K / V
  with the other head's lanes zeroed against the whole q / do slab) and
  gives the MXU the cheaper form of each gradient dot. The MXU's time for
  a dot is its left-hand rows times the 128 x 128 tiles of its right-hand
  side: ``dv = p^T @ do`` pushes 512 rows through 4 tiles, half of each
  empty at d=64, where ``dv^T = do^T @ p`` pushes 64 rows through 16. So
  below 128 lanes a head dk, dv and dq are computed and accumulated
  TRANSPOSED (``[hp*d, block_k]`` / ``[hp*d, seq]`` f32 scratch, turned
  back once at the flush) and no ``[512, 512]`` operand is transposed at
  all; from d=128 on both forms push the same rows and the natural ones
  stay. PR 31, backward alone, bf16, causal, fused RoPE, ``[4, 2048, 16,
  64]``: 1.06 ms a call (1.66 us a block pair and head) where per-head
  64-lane halves and natural forms took 1.50 ms (2.35 us); the slab alone
  1.33; ``[4, 2048, 32, 64]`` 2.10 ms against 3.00; gradients
  bit-identical. With any one dot taken out the per-head body read 1.69
  us: it was bound by the MXU's passes, not by work per score element.
  The full-row residency grows with s and overflows Mosaic's 16 MB
  default scope past s=2048, so longer sequences take the SPLIT
  two-kernel backward (the FlashAttention-2 structure): a dkv kernel
  gridded over key blocks (dk/dv accumulate in VMEM scratch while q/do
  blocks stream through an extra grid dimension) and a dq kernel gridded
  over query blocks (dq accumulates while k/v blocks stream). Nothing
  resident scales with s, at the cost of a second score evaluation
  (7 dots per block pair vs 5). ``backward="fused"|"split"`` addresses
  one kernel directly (tests); the default picks from the sequence length.
- Attention-weight dropout runs in-kernel from the core's hardware PRNG
  (compiled) or a counter-based hash (interpret), generated in fixed
  512x512 tiles keyed by absolute position so the backward regenerates
  bit-identical masks under its different block shape.
- RoPE fuses in: q/k rotate in VMEM, and the forward *emits* the rotated
  (+ 1/sqrt(d)-scaled) q/k as outputs that replace the raw projections in
  the autodiff residuals — the backward never re-rotates per block, and
  the streaming forward reads its own rotated K back instead of rotating
  a block again for every query block that meets it.
- All accumulation in float32 regardless of input dtype (bf16 in, bf16 out).

The public API is BSHD ``[batch, seq, heads, head_dim]`` (the model's
layout), folded to ``[b, s, h*d]`` at the custom_vjp boundary so saved
residuals stay unpadded. Sequence lengths must be multiples of the block
size and head_dim must be 64 or a multiple of 128 when compiled; the
wrapper falls back to XLA fused attention otherwise.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# The largest block a program may take. At s <= 1024 the whole head is one
# block (no online-softmax rescaling at all: the kernel's single-block
# fast path). Longer sequences do NOT run 1024-blocks: `flash_attention`
# caps streaming at 512 x 512 (the 1024-block streaming forward needs
# 18.9 MB of the 16 MB scope), so s=2048 — the benchmark's cells —
# streams 4 x 4 blocks of 512.
# The wrapper also clamps to the sequence length.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# The fused backward is bound by its five dots' MXU passes (no online
# rescan; PR 31: taking any one dot out of the 512-block body took its
# whole MXU time off the call): causal block-skipping at 512 measured
# faster than the single-block layout, and 512 x 512 is also what the
# d-row forms of the gradient dots were measured at (16 weight tiles a
# dot, 64 rows through each at d=64).
_BWD_BLOCK = 512
_LANES = 128  # lane width of a vector register
_NEG_INF = float("-inf")
# Mask value for SEGMENTED kernel instances. With segment skipping a
# q-row's first *processed* k-block can be fully masked (every column in
# another segment), and -inf there would meet the -inf running-max init:
# exp(-inf - (-inf)) = NaN. A large-finite mask keeps the online softmax
# NaN-free: the fully-masked block leaves m = -1e30 and garbage (l, acc)
# that the first genuinely-valid block wipes via alpha = exp(-1e30 - m)
# = 0, and once m is finite every masked score contributes
# exp(-1e30 - m) which underflows to exactly 0.0 in f32 — bit-identical
# to the -inf masking the dense reference uses. Every row attends at
# least to itself (same segment, causal diff 0), so the diagonal block
# always lands a finite max.
_SEG_MASK = -1e30
_GOLDEN = 0x9E3779B9  # Weyl increment for the per-(batch,head) salt


def _keep_mask(seed_u32, salt_u32, q_start, k_start, bq: int, bk: int,
               seq: int, rate: float):
    """Deterministic counter-based dropout mask for one score block.

    A multiply-xorshift hash of the *global* (q, k) position plus a
    per-(batch, head) salt — recomputable bit-for-bit in the backward
    kernels (the flash-attention equivalent of storing the mask, at zero
    memory). Pure jnp bitwise ops, so it runs identically compiled on TPU
    and interpreted on CPU (``pltpu.prng_*`` has no interpret lowering).
    Positions must fit uint32: seq < 2**16.

    The hash is deliberately small — 6 VPU ops per element on the
    [block_q, block_k] score block (the kernel's hot elementwise chain):
    two multiply+xorshift rounds. One round is not enough: consecutive
    positions along a row make the pre-mix values a Weyl progression with
    stride 0xC2B2AE35, and a single xorshift only partially breaks that
    lattice (keep decisions stay equidistributed but spatially
    correlated). The second round restores per-element independence to
    statistical quality (verified by the autocorrelation test in
    tests/test_flash.py); the full murmur3 finalizer beyond that buys
    nothing for a Bernoulli threshold.
    """
    # Per-row base on a [bq, 1] column (cheap) broadcast against the column
    # iota: one add per element instead of full 2-D index arithmetic.
    rows = (q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            ).astype(jnp.uint32)
    cols = (k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            ).astype(jnp.uint32)
    x = rows * jnp.uint32(seq) + cols
    x = x ^ (seed_u32 + salt_u32 * jnp.uint32(_GOLDEN))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    threshold = jnp.uint32(min(int(rate * 2**32), 2**32 - 1))
    return x >= threshold  # keep with probability 1 - rate


def _keep(seed, salt, q_start, k_start, bq: int, bk: int, seq: int,
          rate: float, hw: bool):
    """Keep-mask for one [bq, bk] score block of one head. Two backends:

    - ``hw=True`` (compiled TPU): the core's hardware PRNG, reseeded
      deterministically per (seed, batch*head salt, block coordinates) so
      the backward kernels regenerate the identical mask from the same
      seed args (fwd and bwd block shapes are forced equal under
      dropout). Replaces ~8 VPU ops/element of hash arithmetic with a
      hardware bit stream + one compare. Generation is per head — a
      single [hp*bq, bk] generation for a paired program keeps an 8 MB
      uint32 block live across both heads' chains and blows the 16 MB
      scoped-VMEM budget in the in-model backward.
    - ``hw=False`` (interpret mode / CPU tests): the multiply-xorshift
      hash (``_keep_mask``) — ``pltpu.prng_*`` has no interpret lowering.

    The two backends draw different (both valid Bernoulli) masks; each is
    deterministic per seed within its backend, which is what training and
    the fwd/bwd mask-consistency contract require.
    """
    threshold = jnp.uint32(min(int(rate * 2**32), 2**32 - 1))
    if hw:
        from jax.experimental.pallas import tpu as pltpu

        # Generation runs in fixed 512x512 TILES keyed by absolute
        # coordinates, so the mask a block sees is independent of the
        # block shape as long as both passes use 512-divisible (or equal)
        # blocks — this is what lets the forward run its single-block
        # layout while the backward runs causal-skipping 512s. Mosaic's
        # prng_seed takes at most 2 scalars: fold the user seed with the
        # (batch, head) salt, and the tile coordinates into one position
        # unique per tile (mod 2^32 — still collision-free since
        # q*seq + k < seq^2 <= 2^32 for seq < 2**16).
        s0 = seed ^ (salt * jnp.uint32(_GOLDEN))
        tq = 512 if bq % 512 == 0 else bq
        tk = 512 if bk % 512 == 0 else bk
        rows = []
        for a in range(0, bq, tq):
            row = []
            for c in range(0, bk, tk):
                pos = (jnp.uint32(q_start + a) * jnp.uint32(seq)
                       + jnp.uint32(k_start + c))
                pltpu.prng_seed(s0, pos)
                row.append(pltpu.bitcast(pltpu.prng_random_bits((tq, tk)),
                                         jnp.uint32))
            rows.append(row[0] if len(row) == 1
                        else jnp.concatenate(row, axis=1))
        bits = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)
        return bits >= threshold
    return _keep_mask(seed, salt, q_start, k_start, bq, bk, seq, rate)


def _block_salt():
    """Per-(batch, head) hash salt from the grid position."""
    return (pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
            ).astype(jnp.uint32)


def _seed_from_ref(seed_ref):
    """uint32 seed scalar from the (1,1) SMEM input."""
    return seed_ref[0, 0]


def _rotate(x, cos, sin, out_dtype, scale=1.0):
    """RoPE rotation of one block (``x [n, d]``, ``cos/sin [n, d]`` f32):
    ``(x*cos + rotate_half(x)*sin) * scale``, f32 math, cast to ``out_dtype``.

    ``scale`` folds the attention's ``1/sqrt(d)`` into the (cheap) per-block
    q rotation so the [block_q, block_k] score matrix needs no per-element
    multiply. For even powers of two (d=16, 64, 256) the scale is itself a
    power of two, so the fold only adjusts exponents and is exact in bf16;
    for d=32/128 the scale is irrational and the folded q rounds once in
    the bf16 cast — one extra bf16-level rounding per q element relative
    to scaling the f32 score matrix, inside the tolerance the kernel tests
    already allow for bf16 inputs (tests/test_flash.py oracle comparison).
    """
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    rx = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    out = x32 * cos + rx * sin
    if scale != 1.0:
        out = out * scale
    return out.astype(out_dtype)


def _rotate_heads(x, cos, sin, d, out_dtype, scale=1.0):
    """``_rotate`` of every head of a ``[n, hp*d]`` slab at once (``cos/sin``
    tiled to ``[n, hp*d]``): the streaming forward's rotation, on full
    128-lane registers when two d=64 heads share a program. ``rotate_half``
    inside each ``d``-lane head is a lane roll by ``d/2`` either way, picked
    by lane; the arithmetic and its order are ``_rotate``'s, so the result
    is bit-identical to rotating head by head."""
    from jax.experimental.pallas import tpu as pltpu

    w, half = x.shape[-1], d // 2
    x32 = x.astype(jnp.float32)
    up = pltpu.roll(x32, w - half, 1)                    # up[j] = x[j + d/2]
    down = up if w == d else pltpu.roll(x32, half, 1)    # down[j] = x[j - d/2]
    lane = jax.lax.broadcasted_iota(jnp.int32, x32.shape, 1)
    out = x32 * cos + jnp.where(lane % d < half, -up, down) * sin
    if scale != 1.0:
        out = out * scale
    return out.astype(out_dtype)


def _lane_tile(x, n):
    """A lane-replicated ``[rows, 128]`` value as ``[rows, n]``: whole
    registers repeated or a prefix of one, never a one-lane broadcast (the
    last case is only met by block widths no compiled kernel uses)."""
    w = x.shape[1]
    if n % w == 0:
        return x if n == w else jnp.concatenate([x] * (n // w), axis=1)
    if n < w:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _unrotate_grad(g, cos, sin):
    """VJP of ``_rotate`` w.r.t. x applied to cotangent ``g`` (f32):
    ``g*cos + rotate_half^T(g*sin)`` where ``rotate_half^T([a,b]) = [b,-a]``."""
    half = g.shape[-1] // 2
    gs = g * sin
    rt = jnp.concatenate([gs[..., half:], -gs[..., :half]], axis=-1)
    return g * cos + rt


def _unrotate_heads(g, cos, sin, d):
    """``_unrotate_grad`` of every head of a ``[n, hp*d]`` slab at once
    (``cos/sin`` tiled to ``[n, hp*d]``): the fused backward's counterpart of
    ``_rotate_heads``, two lane rolls and a select; the arithmetic and its
    order are ``_unrotate_grad``'s, so the result is bit-identical to
    un-rotating head by head."""
    from jax.experimental.pallas import tpu as pltpu

    w, half = g.shape[-1], d // 2
    gs = g * sin
    up = pltpu.roll(gs, w - half, 1)                    # up[j] = gs[j + d/2]
    down = up if w == d else pltpu.roll(gs, half, 1)    # down[j] = gs[j - d/2]
    lane = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    return g * cos + jnp.where(lane % d < half, up, -down)


def _seg_predicates(qseg, kseg):
    """Block-skip predicates from loaded q/k segment-id slices.

    ``overlap``: some q row *may* share a segment with some k column —
    the interval test on [min, max]. Sound for arbitrary id layouts
    (min <= v <= max holds elementwise, so equal ids force overlapping
    intervals) and exact for the packer's sorted rows; padding-0 tails
    only over-approximate, which the elementwise mask then corrects.
    ``uniform``: both blocks are one identical segment end to end, so the
    block needs no elementwise segment mask at all — the segment
    analogue of the causal ``full`` predicate.
    """
    qf, ql = jnp.min(qseg), jnp.max(qseg)
    kf, kl = jnp.min(kseg), jnp.max(kseg)
    overlap = (qf <= kl) & (kf <= ql)
    uniform = (qf == ql) & (kf == kl) & (qf == kf)
    return overlap, uniform


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, *rest,
                block_k, scale, causal, dropout_rate, fuse_rope, hw_prng,
                hp, segmented=False):
    # Operands are the model's FOLDED layout, sliced per head *group* by
    # the BlockSpec: q_ref [1, block_q, hp*d] and k_ref/v_ref
    # [1, seq, hp*d] are column slices of [b, s, h*d] arrays. ``hp`` is
    # the number of heads per program — 2 for d=64 so the block's lane
    # width is 128 (Mosaic requires the last block dim to be a multiple
    # of 128 or the full array width), 1 for d a multiple of 128. Heads
    # within a program run as a static Python loop: over static column
    # slices in the single-block path, over the whole slab in the
    # streaming path (see there). No BSHD transpose ever happens in HBM —
    # round 2 transposed to [b, h, s, d] around every pallas call, costing
    # a layout copy per operand per layer. lse_ref: [1, hp, 1, seq] (full
    # rows, written blockwise). With fuse_rope, cos/sin ride along
    # ([seq, d]; tiled to [seq, hp*d] for the streaming path) and q/k
    # rotate in VMEM — no rotated copies hit HBM.
    #
    # The k loop is a STATIC Python unroll with `pl.when`-predicated block
    # bodies (the splash-attention structure), not a `fori_loop` with
    # data-dependent trip counts. Measured on v5e: with dynamic trip
    # counts Mosaic cannot unroll/schedule the loop and the causal kernel
    # ran no faster than computing every block — causality's 2x FLOP
    # saving bought zero time. Static unroll + predication makes skipped
    # blocks actually free (a branch), and lets the scheduler software-
    # pipeline across block bodies. Softmax state (m, l per head, one acc
    # for the program's heads) lives in VMEM scratch across the predicated
    # regions.
    # Under fuse_rope the kernel additionally WRITES the rotated
    # (and, for q, pre-scaled) projections as outputs: the backward then
    # consumes them directly instead of re-rotating q/k per block — the
    # rotate_half concatenate is a cross-lane shuffle, measured ~0.3 ms
    # per layer in the in-model backward. Same residual footprint (the
    # rotated tensors replace the raw ones in the autodiff save).
    if fuse_rope:
        cos_ref, sin_ref, *rest = rest
    if segmented:
        # Segment ids ride along as [1, block_q] (q rows) and [1, seq]
        # (full k row) int32 blocks; masking/skipping below treats blocks
        # whose q-range and k-range share no segment exactly like the
        # causal below-diagonal blocks.
        qseg_ref, kseg_ref, *rest = rest
    if fuse_rope:
        o_ref, lse_ref, qr_ref, kr_ref, *scrs = rest
    else:
        o_ref, lse_ref, *scrs = rest
        qr_ref = kr_ref = None
    m_scrs, l_scrs, acc_scr = scrs[:hp], scrs[hp:2 * hp], scrs[2 * hp]
    block_q = q_ref.shape[1]
    d = q_ref.shape[2] // hp
    seq = k_ref.shape[1]
    iq = pl.program_id(2)
    q_start = iq * block_q
    seed = _seed_from_ref(seed_ref)
    mask_val = _SEG_MASK if segmented else _NEG_INF
    if segmented:
        qseg = qseg_ref[0, :][:, None]        # [bq, 1]
        kseg_row = kseg_ref[0, :]             # [seq]
    # Hoisted out of the (pl.when-predicated) block bodies: program_id
    # staged inside a predicated body lowers as a plain cond branch in
    # interpret mode, where the primitive has no rule outside the grid
    # interpreter context.
    salt0 = _block_salt()

    def head_salt(t):
        # Unique per (batch, global head); equals _block_salt at hp == 1,
        # keeping the interpret-mode hash stream bit-stable with round 2.
        return salt0 * jnp.uint32(hp) + jnp.uint32(t)

    # Inputs stay in their storage dtype (bf16 in training): the MXU runs
    # bf16 x bf16 -> f32 at full rate, while f32 x f32 matmuls cost ~8x.
    # All softmax state is f32 via preferred_element_type. The 1/sqrt(d)
    # scale is folded into q once per program ([bq, d]) rather than into
    # every [bq, bk] score block.
    def load_q(t):
        q = q_ref[0, :, pl.ds(t * d, d)]  # [bq, d], static column slice
        if fuse_rope:
            q = _rotate(q, cos_ref[pl.ds(q_start, block_q), :],
                        sin_ref[pl.ds(q_start, block_q), :], q_ref.dtype,
                        scale=scale)
            qr_ref[0, :, pl.ds(t * d, d)] = q
            return q
        return (q.astype(jnp.float32) * scale).astype(q_ref.dtype)

    single = seq == block_k and seq == block_q
    if single:
        # Whole-sequence single block (the s <= 1024 fast path, and the
        # headline-config shape): no online softmax, no rescaling, no
        # scratch round-trips — one straight-line masked softmax per
        # (batch, head). Measured ~33% faster than 512-block streaming on
        # v5e at s=1024 even though the masked upper triangle is computed.
        valid = None
        if causal:
            diff = (jax.lax.broadcasted_iota(jnp.int32, (block_q, seq), 0)
                    - jax.lax.broadcasted_iota(jnp.int32, (block_q, seq), 1))
            valid = diff >= 0
        if segmented:
            same = qseg == kseg_row[None, :]
            valid = same if valid is None else valid & same
        for t in range(hp):
            q = load_q(t)
            k = k_ref[0, :, pl.ds(t * d, d)]
            v = v_ref[0, :, pl.ds(t * d, d)]
            if fuse_rope:
                k = _rotate(k, cos_ref[...], sin_ref[...], k_ref.dtype)
                kr_ref[0, :, pl.ds(t * d, d)] = k
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if valid is not None:
                s = jnp.where(valid, s, mask_val)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            if dropout_rate > 0.0:
                keep = _keep(seed, head_salt(t), 0, 0, block_q, block_k,
                             seq, dropout_rate, hw_prng)
                p = jnp.where(keep, p, 0.0)
            acc = jnp.dot(p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)
            denom = l * (1.0 - dropout_rate) if dropout_rate > 0.0 else l
            o_ref[0, :, pl.ds(t * d, d)] = (acc / denom).astype(o_ref.dtype)
            lse_ref[0, t, 0, :] = m[:, 0] + jnp.log(l[:, 0])
        return

    # ---- streaming (multi-block) path. Everything below works on the
    # program's whole ``[*, hp*d]`` slab — full 128-lane registers when two
    # d=64 heads share it — and never slices a head out of the lanes:
    # - head t's scores contract the slab with a copy of q whose other
    #   heads' lanes are zero (a 128-deep contraction costs the MXU what a
    #   64-deep one does, and the zeros add nothing);
    # - ``p_t @ v`` runs against the whole V slab and head t keeps its own
    #   ``d`` columns of the result, so the accumulator, its rescale and
    #   the final store are full-width;
    # - the running max and sum are kept replicated across 128 lanes
    #   (``[block_q, 128]``, as the upstream Pallas TPU kernel keeps them):
    #   ``s - m``, ``exp(m - m_new)`` and ``acc * alpha`` then need no
    #   broadcast out of a one-lane column and the state no masked store.
    # The module docstring has what each of these measured on a v5e.
    width = hp * d
    if hp > 1:
        head_of_lane = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, width), 1) // d

    if fuse_rope:
        q_all = _rotate_heads(q_ref[0], cos_ref[pl.ds(q_start, block_q), :],
                              sin_ref[pl.ds(q_start, block_q), :], d,
                              q_ref.dtype, scale=scale)
        qr_ref[0] = q_all
        # Each K block is rotated ONCE, by the first program that needs
        # it, into the rotated-K output: that block's index does not
        # depend on ``iq`` and the ``iq`` axis runs in order, so it stays
        # in VMEM for the later programs of this (batch, head group), which
        # read it back (as the fused backward reads ``dq_ref``). Under
        # ``causal`` the first program to need K block ``ik`` is the one
        # whose query rows hold the block's first row — it always computes
        # that block, segments or not, since a row attends to itself — and
        # every earlier block was written by an earlier program; without
        # causality program 0 rotates them all. 4 rotations a head group at
        # s=2048 where rotating at every visit made 10.
        for ik in range(seq // block_k):
            k_start = ik * block_k
            if causal:
                first = ((k_start >= q_start)
                         & (k_start < q_start + block_q))
            else:
                first = iq == 0

            @pl.when(first)
            def _rotate_k(k_start=k_start):
                rows = pl.ds(k_start, block_k)
                kr_ref[0, rows, :] = _rotate_heads(
                    k_ref[0, rows, :], cos_ref[rows, :], sin_ref[rows, :],
                    d, k_ref.dtype)
    else:
        q_all = (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)
    qs = [q_all if hp == 1
          else jnp.where(head_of_lane == t, q_all, jnp.zeros_like(q_all))
          for t in range(hp)]

    for t in range(hp):
        m_scrs[t][...] = jnp.full((block_q, _LANES), _NEG_INF, jnp.float32)
        l_scrs[t][...] = jnp.zeros((block_q, _LANES), jnp.float32)
    acc_scr[...] = jnp.zeros((block_q, width), jnp.float32)

    if causal:
        # Row-minus-column iota difference, hoisted out of the block loop:
        # the diagonal block's mask is `diff >= k_start - q_start`, one
        # compare + one select per element instead of two iotas + compare +
        # select inside every masked block.
        diff = (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                - jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))

    def body(ik: int, masked: bool):
        k_start = ik * block_k  # static
        rows = pl.ds(k_start, block_k)
        k = (kr_ref if fuse_rope else k_ref)[0, rows, :]   # [bk, hp*d]
        v = v_ref[0, rows, :]
        if masked:
            valid = None
            if causal:
                valid = diff >= k_start - q_start
            if segmented:
                # k_start is a static unroll index: plain value slice.
                same = qseg == kseg_row[k_start:k_start + block_k][None, :]
                valid = same if valid is None else valid & same
        for t in range(hp):
            m, l = m_scrs[t][...], l_scrs[t][...]     # [bq, 128] replicated
            s = jax.lax.dot_general(
                qs[t], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [bq, bk] f32 (already scaled via q)
            if masked:
                s = jnp.where(valid, s, mask_val)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _lane_tile(m_new, block_k))
            alpha = jnp.exp(m - m_new)
            # The softmax normalizer sums the *undropped* weights (dropout
            # acts on normalized weights in the reference, gpt.py:230-234
            # semantics).
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if dropout_rate > 0.0:
                # Survivors keep their raw weight here; the 1/(1-rate)
                # inverted-dropout scale folds into the final acc/l division
                # instead of a per-element multiply per block.
                keep = _keep(seed, head_salt(t), q_start, k_start,
                             block_q, block_k, seq, dropout_rate, hw_prng)
                p = jnp.where(keep, p, 0.0)
            m_scrs[t][...] = m_new
            l_scrs[t][...] = l_new
            # [bq, hp*d]: head t's own d columns are p_t @ v_t, the rest
            # (p_t against the other heads' V) is dropped by the select.
            acc = acc_scr[...]
            new = acc * _lane_tile(alpha, width) + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            acc_scr[...] = (new if hp == 1
                            else jnp.where(head_of_lane == t, new, acc))

    for ik in range(seq // block_k):
        if not causal and not segmented:
            body(ik, masked=False)
            continue
        k_start = ik * block_k
        # Causal — needed: any (row, col) with row >= col, i.e. the
        # block's last row reaches its first column. full: every element
        # valid (last column <= first row). Both predicates depend on the
        # dynamic q_start. Segments compose the same way: no-overlap
        # blocks are skipped outright (the generalization of the
        # below-diagonal skip), and only non-uniform boundary blocks pay
        # the elementwise mask.
        if causal:
            needed = q_start + block_q - 1 >= k_start
            full = q_start >= k_start + block_k - 1
        else:
            needed = full = True
        if segmented:
            overlap, uniform = _seg_predicates(
                qseg, kseg_row[k_start:k_start + block_k])
            run_full = full & uniform
            run_masked = needed & overlap & jnp.logical_not(run_full)
        else:
            run_full = full
            run_masked = needed & jnp.logical_not(full)
        pl.when(run_full)(functools.partial(body, ik, False))
        pl.when(run_masked)(functools.partial(body, ik, True))

    for t in range(hp):
        m, l = m_scrs[t][...], l_scrs[t][...]
        lse_ref[0, t, 0, pl.ds(q_start, block_q)] = m[:, 0] + jnp.log(l[:, 0])
        # Each head's sum on its own lanes of the accumulator.
        l = _lane_tile(l, width)
        denom = l if t == 0 else jnp.where(head_of_lane == t, l, denom)
    if dropout_rate > 0.0:
        denom = denom * (1.0 - dropout_rate)
    o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _seed_spec():
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _rope_specs(s, d):
    return [pl.BlockSpec((s, d), lambda ib, ih, i: (0, 0))] * 2


def _heads_per_program(d: int, interpret: bool) -> int:
    """Heads per kernel program. Mosaic needs the block's lane width to be
    a multiple of 128 (or the full array width): d=64 pairs two heads per
    program (width 128); d a multiple of 128 runs one head per program.
    Interpret mode has no lane constraint — keep hp=1 so the CPU-test hash
    salts stay bit-identical to the per-head design."""
    if interpret:
        return 1
    if d == 64:
        return 2
    if d % 128 == 0:
        return 1
    raise NotImplementedError(
        f"compiled flash kernel supports head_dim 64 or multiples of 128; "
        f"got {d} (use the XLA fallback path)"
    )


def _flash_forward(q3, k3, v3, seed_f, seg_f, rope, *, num_heads, head_dim,
                   num_kv_heads, causal, block_q, block_k, interpret,
                   dropout_rate, segmented=False):
    # q3: FOLDED [b, s, h*d]. k3/v3: [b, s, kvh*d] with kvh == h when
    # hp > 1 (the caller expands grouped K/V to per-query-head copies —
    # the repeated-KV-MHA identity — because a paired program's two query
    # heads may straddle a K/V head boundary); under hp == 1 GQA stays an
    # index map (grid head ih -> K/V columns (ih // group) * d). The
    # BlockSpecs slice per-head-group [*, hp*d] columns straight out of
    # the folded layout — no BSHD transpose/copy in HBM. seed_f: (1,1)
    # float32 bit-carrier (floats so custom_vjp has a well-defined
    # cotangent; re-bitcast to uint32 here, outside the kernel — Mosaic
    # can't bitcast scalars in-kernel). rope: None or (cos, sin) [s, d]
    # f32. seg_f: [b, s] float32 bit-carrier of the int32 segment ids
    # (same custom_vjp trick as seed_f) when ``segmented``; ignored
    # otherwise.
    seed_f = jax.lax.bitcast_convert_type(seed_f, jnp.uint32)
    b, s, _ = q3.shape
    h, d = num_heads, head_dim
    hp = _heads_per_program(d, interpret)
    group = h // num_kv_heads
    assert group == 1 or hp == 1, "caller expands K/V before pairing heads"
    scale = 1.0 / math.sqrt(d)
    grid = (b, h // hp, s // block_q)
    q_spec = pl.BlockSpec((1, block_q, hp * d),
                          lambda ib, ip, iq: (ib, iq, ip))
    kv_spec = pl.BlockSpec(
        (1, s, hp * d),
        (lambda ib, ip, iq: (ib, 0, ip)) if hp > 1 or group == 1
        else (lambda ib, ip, iq: (ib, 0, ip // group)),
    )
    row_spec = pl.BlockSpec((1, hp, 1, s), lambda ib, ip, iq: (ib, ip, 0, 0))
    fuse_rope = rope is not None
    rope_args = tuple(rope) if fuse_rope else ()
    if fuse_rope and hp > 1 and not (s == block_q == block_k):
        # The streaming path (not the single block) rotates a program's
        # heads together: one table column per lane of the [*, hp*d] slab.
        rope_args = tuple(jnp.tile(t, (1, hp)) for t in rope_args)
    seg_args = ()
    seg_specs = []
    if segmented:
        # The same [b, s] id array enters twice — once blocked by q rows,
        # once as the full k row — so the kernel's q/k segment views ride
        # the grid like every other operand.
        seg = jax.lax.bitcast_convert_type(seg_f, jnp.int32)
        seg_args = (seg, seg)
        seg_specs = [
            pl.BlockSpec((1, block_q), lambda ib, ip, iq: (ib, iq)),
            pl.BlockSpec((1, s), lambda ib, ip, iq: (ib, 0)),
        ]
    from jax.experimental.pallas import tpu as pltpu

    outs = pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_k=block_k, scale=scale, causal=causal,
            dropout_rate=dropout_rate, fuse_rope=fuse_rope,
            hw_prng=not interpret, hp=hp, segmented=segmented,
        ),
        grid=grid,
        in_specs=[_seed_spec(), q_spec, kv_spec, kv_spec]
        + (_rope_specs(s, rope_args[0].shape[1]) if fuse_rope else [])
        + seg_specs,
        out_specs=[q_spec, row_spec]
        + ([q_spec, kv_spec] if fuse_rope else []),
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * d), q3.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ]
        + ([jax.ShapeDtypeStruct((b, s, h * d), q3.dtype),
            jax.ShapeDtypeStruct(k3.shape, k3.dtype)] if fuse_rope else []),
        scratch_shapes=(
            [pltpu.VMEM((block_q, _LANES), jnp.float32)] * (2 * hp)
            + [pltpu.VMEM((block_q, hp * d), jnp.float32)]
        ),
        # The query-block axis must run in order: the lse row, the rotated
        # K and (under GQA by index map, across the heads of a group too)
        # their output blocks stay in VMEM from one program to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(seed_f, q3, k3, v3, *rope_args, *seg_args)
    if fuse_rope:
        return outs  # (o3, lse, rotated-scaled q3, rotated k3)
    o3, lse = outs
    return o3, lse, None, None


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _d_row_form(d: int) -> bool:
    """Whether the fused backward's multi-block body takes its three gradient
    dots in their d-row form (``dv^T = do^T @ p`` ...; see
    ``_bwd_fused_kernel``): below 128 lanes a head it halves the MXU's passes,
    from 128 on it only loads more weight tiles (7% slower at d=128)."""
    return d < _LANES


def _bwd_fused_kernel(
    seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    block_q, scale, causal, dropout_rate, fuse_rope, hw_prng, hp,
):
    """Single-pass backward: grid ``(b, h, seq // block_k)``.

    Each program owns one K/V block, streams the (causally relevant) query
    blocks once, and from a single score/probability computation produces
    its dk/dv block *and* the partial dq contributions. dq's BlockSpec index
    is constant in the kv grid dimension, so the full-row dq block stays
    resident in VMEM and accumulates across sequential grid steps (zeroed at
    the first kv block). Compared to separate dq and dk/dv kernels this
    halves the backward's score matmuls and q/do reads.

    With ``fuse_rope``, q/k arrive already rotated (the forward's residuals);
    dq/dk are accumulated in *rotated* space and the rotation's transpose is
    applied in VMEM before they are written (``_unrotate_grad`` /
    ``_unrotate_heads``; cos/sin ``[seq, d]`` for the single block, tiled to
    ``[seq, hp*d]`` for the multi-block body, as in the forward).
    """
    if fuse_rope:
        cos_ref, sin_ref, dq_ref, dk_ref, dv_ref, *scrs = rest
    else:
        dq_ref, dk_ref, dv_ref, *scrs = rest
    block_k = k_ref.shape[1]
    width = k_ref.shape[2]
    d = width // hp
    seq = q_ref.shape[1]
    ik = pl.program_id(2)
    k_start = ik * block_k
    seed = _seed_from_ref(seed_ref)
    num_q = seq // block_q
    salt0 = _block_salt()  # hoisted out of the pl.when bodies (see _fwd_kernel)

    def head_salt(t):
        return salt0 * jnp.uint32(hp) + jnp.uint32(t)

    # Under fuse_rope the forward already wrote rotated k and
    # rotated-scaled q as outputs (see _fwd_kernel): they arrive here as
    # the residuals, so no per-block re-rotation happens — only the final
    # unrotate of dq/dk needs cos/sin. Without it q is scaled by 1/sqrt(d)
    # at its load: the score recompute then needs no per-element scale, and
    # dk = sum ds^T @ q_scaled IS the correctly-scaled dk (chain rule puts
    # one factor of `scale` on each of dq and dk).
    def scaled(q):
        if fuse_rope:
            return q
        return (q.astype(jnp.float32) * scale).astype(q_ref.dtype)

    contract_lanes = (((1,), (1,)), ((), ()))
    contract_rows = (((0,), (0,)), ((), ()))
    f32 = dict(preferred_element_type=jnp.float32)

    def score_grads(t, q, k, v, do, q_start, masked):
        """Head t's probabilities (after dropout) and score gradient ``ds``
        for one block pair, both ``[bq, bk]`` in the operands' dtype, from
        one score evaluation. ``q`` / ``do`` hold rows ``q_start`` on."""
        rows = pl.ds(q_start, q.shape[0])
        lse = lse_ref[0, t, 0, rows][:, None]
        delta = delta_ref[0, t, 0, rows][:, None]
        s = jax.lax.dot_general(q, k, contract_lanes, **f32)  # scaled via q
        if masked:
            diff = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                    - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            s = jnp.where(diff >= k_start - q_start, s, _NEG_INF)
        p = jnp.exp(s - lse)                                  # normalized
        dp = jax.lax.dot_general(do, v, contract_lanes, **f32)
        if dropout_rate > 0.0:
            # p_drop stays unscaled; the 1/(1-rate) folds into dv once at
            # the end ([bk, d] multiply instead of per-element per block).
            keep = _keep(seed, head_salt(t), q_start, k_start, q.shape[0],
                         block_k, seq, dropout_rate, hw_prng)
            p_drop = jnp.where(keep, p, 0.0)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        else:
            p_drop = p
        return p_drop.astype(do.dtype), (p * (dp - delta)).astype(q.dtype)

    # Whole-sequence single block (mirrors the forward's fast path): no
    # dq accumulation across programs, no scratch round-trips, the dropout
    # seed position is the same static (0, 0) the forward used, and heads
    # run one by one over static column slices.
    if num_q == 1 and seq == block_k:
        for t in range(hp):
            cols = pl.ds(t * d, d)
            q, k = scaled(q_ref[0, :, cols]), k_ref[0, :, cols]
            do = do_ref[0, :, cols]
            p_drop, ds = score_grads(t, q, k, v_ref[0, :, cols], do, 0,
                                     causal)
            dv = jax.lax.dot_general(p_drop, do, contract_rows, **f32)
            dk = jax.lax.dot_general(ds, q, contract_rows, **f32)
            dq = jnp.dot(ds, k, **f32) * scale
            if fuse_rope:
                dq = _unrotate_grad(dq, cos_ref[...], sin_ref[...])
                dk = _unrotate_grad(dk, cos_ref[...], sin_ref[...])
            if dropout_rate > 0.0:
                dv = dv / (1.0 - dropout_rate)
            dq_ref[0, :, cols] = dq.astype(dq_ref.dtype)
            dk_ref[0, :, cols] = dk.astype(dk_ref.dtype)
            dv_ref[0, :, cols] = dv.astype(dv_ref.dtype)
        return

    # ---- multi-block path (what s = 2048 runs: 10 of 16 block pairs a
    # head). As in the streaming forward, the body works on the program's
    # whole ``[*, hp*d]`` slab and never slices a head out of the lanes; and
    # it gives the MXU the cheaper of two forms of each gradient dot (PR 31;
    # the module docstring has what each part measured on a v5e):
    # - head t's scores and ``dp`` contract the whole q / do slab with a copy
    #   of K / V whose other heads' lanes are zero, made once a program (its
    #   K/V block is fixed): a 128-deep contraction costs the MXU what a
    #   64-deep one does, and the zeros add nothing;
    # - a dot costs the MXU its left-hand side's rows once per 128 x 128 tile
    #   of its right-hand side. ``dv = p^T @ do`` pushes ``block_k`` rows
    #   through ``block_q / 128`` tiles (half of each empty at d = 64), after
    #   transposing ``p``; the same products as ``dv^T = do^T @ p`` push d
    #   rows through ``block_q * block_k / 128^2`` tiles: at d = 64 half the
    #   passes, and no ``[block_q, block_k]`` transpose. So below 128 lanes a
    #   head (``d_rows``) the three gradient dots take their d-row form —
    #   ``dv^T = do^T @ p``, ``dk^T = q^T @ ds``, ``dq^T = k^T @ ds^T`` (the
    #   last contracts both operands' lanes, which the MXU does natively) —
    #   on sublane slices of the transposed q / do / K slabs, and accumulate
    #   TRANSPOSED: ``dk^T`` / ``dv^T`` in one ``[hp*d, block_k]`` f32 scratch
    #   each, ``dq^T`` in a ``[hp*d, seq]`` scratch that lives across the
    #   sequential kv grid steps; each is transposed back once, at its flush.
    #   From 128 lanes a head on the two forms push the same rows and the
    #   d-row form only loads more weight tiles (measured 7% slower at
    #   d = 128), so there the natural forms stay, as they were.
    # Either way every product is summed over the same index in the same
    # order as in a per-head body: the gradients are bit-identical to it.
    d_rows = _d_row_form(d)
    assert hp == 1 or d_rows, "heads share a program only below 128 lanes"
    if d_rows:
        dk_scr, dv_scr, dq_scr = scrs
        k_t = k_ref[0].T                                  # [hp*d, bk]
    else:
        dk_scr, dv_scr = scrs
    if hp > 1:
        k_all, v_all = k_ref[0], v_ref[0]
        head_of_lane = jax.lax.broadcasted_iota(
            jnp.int32, (block_k, width), 1) // d
        ks = [jnp.where(head_of_lane == t, k_all, jnp.zeros_like(k_all))
              for t in range(hp)]
        vs = [jnp.where(head_of_lane == t, v_all, jnp.zeros_like(v_all))
              for t in range(hp)]

    def body(iq: int, masked: bool):
        # ``iq`` is a static Python int: the q-block loop is unrolled at
        # trace time with `pl.when` predication per block (see _fwd_kernel
        # for the measured rationale).
        q_start = iq * block_q
        rows = pl.ds(q_start, block_q)
        q_all = scaled(q_ref[0, rows, :])                 # [bq, hp*d]
        do_all = do_ref[0, rows, :]
        if d_rows:
            q_t, do_t = q_all.T, do_all.T                 # [hp*d, bq]
        for t in range(hp):
            # One head a program reads its K/V block where it uses it; a
            # hoisted value measured 4% slower at d=128.
            k, v = (ks[t], vs[t]) if hp > 1 else (k_ref[0], v_ref[0])
            p_drop, ds = score_grads(t, q_all, k, v, do_all, q_start, masked)
            if d_rows:
                head = slice(t * d, (t + 1) * d)          # sublanes
                dv_scr[head, :] += jnp.dot(do_t[head], p_drop, **f32)
                dk_scr[head, :] += jnp.dot(q_t[head], ds, **f32)
                dq_scr[head, rows] += jax.lax.dot_general(
                    k_t[head], ds, contract_lanes, **f32) * scale
            else:
                dv_scr[...] += jax.lax.dot_general(
                    p_drop, do_all, contract_rows, **f32)
                dk_scr[...] += jax.lax.dot_general(
                    ds, q_all, contract_rows, **f32)
                dq_ref[0, rows, :] += jnp.dot(ds, k, **f32) * scale

    @pl.when(ik == 0)
    def _zero_dq():
        acc = dq_scr if d_rows else dq_ref
        acc[...] = jnp.zeros_like(acc)

    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)
    for iq in range(num_q):
        if not causal:
            body(iq, False)
            continue
        # needed: the block's last row reaches its first column; full:
        # every element valid. k_start is dynamic (program id), so both
        # predicates are runtime branches on otherwise-static bodies.
        q_start = iq * block_q
        needed = q_start + block_q - 1 >= k_start
        full = q_start >= k_start + block_k - 1
        pl.when(full)(functools.partial(body, iq, False))
        pl.when(needed & jnp.logical_not(full))(
            functools.partial(body, iq, True))
    dk, dv = dk_scr[...], dv_scr[...]
    if d_rows:
        dk, dv = dk.T, dv.T                               # [bk, hp*d]
    if fuse_rope:
        # dk leaves the kernel already un-rotated (the rotation's
        # transpose applied in VMEM) — no external f32
        # read-modify-write pass.
        k_rows = pl.ds(k_start, block_k)
        dk = _unrotate_heads(dk, cos_ref[k_rows, :], sin_ref[k_rows, :], d)
    if dropout_rate > 0.0:
        dv = dv / (1.0 - dropout_rate)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    if d_rows or fuse_rope:
        # dq finishes accumulating at the last kv grid step (its block index
        # is constant in this grid dimension, so the full-row block is still
        # VMEM-resident): transposed back and un-rotated, block by block,
        # before it is written back.
        @pl.when(ik == pl.num_programs(2) - 1)
        def _finish_dq():
            for iq in range(num_q):
                rows = pl.ds(iq * block_q, block_q)
                dq = dq_scr[:, rows].T if d_rows else dq_ref[0, rows, :]
                if fuse_rope:
                    dq = _unrotate_heads(dq, cos_ref[rows, :],
                                         sin_ref[rows, :], d)
                dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)


# The fused kernel keeps full-sequence q/do/dq row blocks VMEM-resident,
# so its footprint grows with s: measured on v5e it fits Mosaic's 16 MB
# default scope through s=2048 and overflows at s=4096 (raising the
# compiler's scope limit for the whole process would steal scope from
# every other kernel in the step). Past this threshold the dispatch
# selects the two-kernel split backward, whose residency is per-block
# only (s-independent). Below it the fused kernel wins: one score
# evaluation feeds dk, dv, AND dq (the split path recomputes scores in
# each kernel — 7 dots per block pair vs 5).
_FUSED_BWD_MAX_SEQ = 2048


def _bwd_dkv_kernel(
    seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    scale, causal, dropout_rate, fuse_rope, hw_prng, hp, seq,
    segmented=False,
):
    """dk/dv half of the two-kernel (split) backward.

    Grid ``(b, h/hp, seq // block_k, seq // block_q)``: each program owns
    one K/V block (its index map is constant in the innermost grid
    dimension, so dk/dv accumulate in VMEM scratch across the sequential
    q-block walk) and sees one q/do block per grid step. Nothing resident
    scales with the sequence length — q/do arrive blocked through the
    grid, lse/delta arrive as per-q-block rows, and under ``fuse_rope``
    cos/sin arrive as the K-rows block (only the final dk un-rotation
    needs them; the residual q/k are pre-rotated). Causal below-diagonal
    blocks (q entirely before k) are skipped by ``pl.when`` predication,
    exactly as in the fused kernel.

    The per-(q,k) block math is the fused kernel's ``body`` verbatim minus
    the dq contribution, and the dropout mask comes from the same
    absolute-coordinate counter hash / PRNG tiles (``_keep``), so masks
    regenerate bit-for-bit across the forward and both split kernels.
    """
    if fuse_rope:
        cos_ref, sin_ref, *rest = rest
    if segmented:
        qseg_ref, kseg_ref, *rest = rest
    dk_ref, dv_ref, *scrs = rest
    dk_scrs, dv_scrs = scrs[:hp], scrs[hp:]
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    d = k_ref.shape[2] // hp
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    k_start = ik * block_k
    q_start = iq * block_q
    seed = _seed_from_ref(seed_ref)
    mask_val = _SEG_MASK if segmented else _NEG_INF
    if segmented:
        qseg = qseg_ref[0, :][:, None]        # [bq, 1]
        kseg = kseg_ref[0, :][None, :]        # [1, bk]
    salt0 = _block_salt()  # hoisted out of the pl.when bodies (see _fwd_kernel)

    def head_salt(t):
        return salt0 * jnp.uint32(hp) + jnp.uint32(t)

    @pl.when(iq == 0)
    def _zero():
        for t in range(hp):
            dk_scrs[t][...] = jnp.zeros((block_k, d), jnp.float32)
            dv_scrs[t][...] = jnp.zeros((block_k, d), jnp.float32)

    def body(masked: bool):
        for t in range(hp):
            k = k_ref[0, :, pl.ds(t * d, d)]
            v = v_ref[0, :, pl.ds(t * d, d)]
            q = q_ref[0, :, pl.ds(t * d, d)]
            do = do_ref[0, :, pl.ds(t * d, d)]
            if not fuse_rope:
                # fuse_rope residuals arrive pre-scaled (see _fwd_kernel).
                q = (q.astype(jnp.float32) * scale).astype(q_ref.dtype)
            lse = lse_ref[0, t, 0, :][:, None]
            delta = delta_ref[0, t, 0, :][:, None]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [bq, bk] (scaled via q)
            if masked:
                valid = None
                if causal:
                    diff = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                            - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
                    valid = diff >= k_start - q_start
                if segmented:
                    same = qseg == kseg
                    valid = same if valid is None else valid & same
                s = jnp.where(valid, s, mask_val)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if dropout_rate > 0.0:
                keep = _keep(seed, head_salt(t), q_start, k_start,
                             block_q, block_k, seq, dropout_rate, hw_prng)
                p_drop = jnp.where(keep, p, 0.0)
                dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
            else:
                p_drop = p
            dv_scrs[t][...] += jax.lax.dot_general(
                p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta)
            dk_scrs[t][...] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    if not causal and not segmented:
        body(False)
    else:
        if causal:
            needed = q_start + block_q - 1 >= k_start
            full = q_start >= k_start + block_k - 1
        else:
            needed = full = True
        if segmented:
            overlap, uniform = _seg_predicates(qseg, kseg)
            run_full = full & uniform
            run_masked = needed & overlap & jnp.logical_not(run_full)
        else:
            run_full = full
            run_masked = needed & jnp.logical_not(full)
        pl.when(run_full)(functools.partial(body, False))
        pl.when(run_masked)(functools.partial(body, True))

    @pl.when(iq == pl.num_programs(3) - 1)
    def _flush():
        for t in range(hp):
            dk = dk_scrs[t][...]
            dv = dv_scrs[t][...]
            if fuse_rope:
                dk = _unrotate_grad(dk, cos_ref[...], sin_ref[...])
            if dropout_rate > 0.0:
                dv = dv / (1.0 - dropout_rate)
            dk_ref[0, :, pl.ds(t * d, d)] = dk.astype(dk_ref.dtype)
            dv_ref[0, :, pl.ds(t * d, d)] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(
    seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    scale, causal, dropout_rate, fuse_rope, hw_prng, hp, seq,
    segmented=False,
):
    """dq half of the two-kernel (split) backward.

    Grid ``(b, h/hp, seq // block_q, seq // block_k)``: each program owns
    one q/do/dq block (dq accumulates in VMEM scratch across the
    sequential k-block walk; its output index map is constant in the
    innermost grid dimension) and sees one K/V block per grid step.
    Residency is per-block only — see ``_bwd_dkv_kernel``. Under
    ``fuse_rope`` cos/sin arrive as the Q-rows block for the final dq
    un-rotation. ``ds`` recomputes from the same ``p``/``dp``/dropout
    chain as the dkv kernel so both halves see identical score gradients.
    """
    if fuse_rope:
        cos_ref, sin_ref, *rest = rest
    if segmented:
        qseg_ref, kseg_ref, *rest = rest
    dq_ref, *dq_scrs = rest
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    d = q_ref.shape[2] // hp
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    q_start = iq * block_q
    k_start = ik * block_k
    seed = _seed_from_ref(seed_ref)
    mask_val = _SEG_MASK if segmented else _NEG_INF
    if segmented:
        qseg = qseg_ref[0, :][:, None]        # [bq, 1]
        kseg = kseg_ref[0, :][None, :]        # [1, bk]
    salt0 = _block_salt()  # hoisted out of the pl.when bodies (see _fwd_kernel)

    def head_salt(t):
        return salt0 * jnp.uint32(hp) + jnp.uint32(t)

    @pl.when(ik == 0)
    def _zero():
        for t in range(hp):
            dq_scrs[t][...] = jnp.zeros((block_q, d), jnp.float32)

    def body(masked: bool):
        for t in range(hp):
            q = q_ref[0, :, pl.ds(t * d, d)]
            do = do_ref[0, :, pl.ds(t * d, d)]
            k = k_ref[0, :, pl.ds(t * d, d)]
            v = v_ref[0, :, pl.ds(t * d, d)]
            if not fuse_rope:
                q = (q.astype(jnp.float32) * scale).astype(q_ref.dtype)
            lse = lse_ref[0, t, 0, :][:, None]
            delta = delta_ref[0, t, 0, :][:, None]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if masked:
                valid = None
                if causal:
                    diff = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                            - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
                    valid = diff >= k_start - q_start
                if segmented:
                    same = qseg == kseg
                    valid = same if valid is None else valid & same
                s = jnp.where(valid, s, mask_val)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if dropout_rate > 0.0:
                keep = _keep(seed, head_salt(t), q_start, k_start,
                             block_q, block_k, seq, dropout_rate, hw_prng)
                dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
            ds = p * (dp - delta)
            dq_scrs[t][...] += jnp.dot(
                ds.astype(k.dtype), k, preferred_element_type=jnp.float32
            ) * scale

    if not causal and not segmented:
        body(False)
    else:
        if causal:
            needed = q_start + block_q - 1 >= k_start
            full = q_start >= k_start + block_k - 1
        else:
            needed = full = True
        if segmented:
            overlap, uniform = _seg_predicates(qseg, kseg)
            run_full = full & uniform
            run_masked = needed & overlap & jnp.logical_not(run_full)
        else:
            run_full = full
            run_masked = needed & jnp.logical_not(full)
        pl.when(run_full)(functools.partial(body, False))
        pl.when(run_masked)(functools.partial(body, True))

    @pl.when(ik == pl.num_programs(3) - 1)
    def _flush():
        for t in range(hp):
            dq = dq_scrs[t][...]
            if fuse_rope:
                dq = _unrotate_grad(dq, cos_ref[...], sin_ref[...])
            dq_ref[0, :, pl.ds(t * d, d)] = dq.astype(dq_ref.dtype)


def _flash_backward(q3, k3, v3, o3, lse, do3, seed_f, seg_f, rope, *,
                    num_heads, head_dim, num_kv_heads, causal, block_q,
                    block_k, interpret, dropout_rate, dlse=None,
                    f32_kv_grads=False, backward=None, segmented=False):
    # Folded operands throughout (see _flash_forward). The backward runs
    # its own block sizes: measured on v5e the backward is MXU/FLOP-bound
    # (5 dots per block, no online-softmax rescan), so causal block
    # skipping beats the forward's single-block fast path — 512x512 blocks
    # compute 3/4 of the score square instead of all of it.
    # ``num_kv_heads`` here is the KERNEL-level kv-head count: the caller
    # (_make_flash) expands grouped K/V to per-query-head copies before
    # pairing heads, and performs the dk/dv group-sum afterwards.
    b, s, _ = q3.shape
    h, d = num_heads, head_dim
    kvh = num_kv_heads
    group = h // kvh
    scale = 1.0 / math.sqrt(d)
    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian correction term.
    delta = jnp.moveaxis(
        (do3.astype(jnp.float32) * o3.astype(jnp.float32))
        .reshape(b, s, h, d).sum(axis=-1), 1, 2
    )[:, :, None, :]
    if dlse is not None:
        # lse is an exposed output (return_lse path): its cotangent enters
        # the score gradient as ds += p * dlse, i.e. exactly a -dlse shift
        # of the delta row — no kernel change needed.
        delta = delta - dlse.astype(jnp.float32)[:, :, None, :]

    from jax.experimental.pallas import tpu as pltpu

    hp = _heads_per_program(d, interpret)
    assert group == 1 or hp == 1, "caller expands K/V before pairing heads"
    seed_f = jax.lax.bitcast_convert_type(seed_f, jnp.uint32)
    blk = lambda n: pl.BlockSpec((1, n, hp * d),
                                 lambda ib, ip, i: (ib, i, ip))
    kv_blk = lambda n: pl.BlockSpec(
        (1, n, hp * d),
        (lambda ib, ip, i: (ib, i, ip)) if hp > 1 or group == 1
        else (lambda ib, ip, i: (ib, i, ip // group)),
    )
    full = pl.BlockSpec((1, s, hp * d), lambda ib, ip, i: (ib, 0, ip))
    row = pl.BlockSpec((1, hp, 1, s), lambda ib, ip, i: (ib, ip, 0, 0))
    fuse_rope = rope is not None
    rope_args = tuple(rope) if fuse_rope else ()

    # Under GQA (hp == 1 path) each query head writes per-head dk/dv
    # partials ([b, s, h*d], the same size MHA's dk/dv would be). The
    # partials leave the kernel in f32 so the caller's group-sum
    # accumulates at full precision and rounds to the storage dtype
    # exactly once, after the reduction — not once per partial (the
    # [b, s, h*d] f32 footprint is the same one the MHA dq already pays).
    kv_grad_dtype = (jnp.float32 if group > 1 or f32_kv_grads
                     else k3.dtype)

    # Backward dispatch: the fused single pass wins while its full-row
    # q/do/dq residency is cheap (s <= _FUSED_BWD_MAX_SEQ — one score
    # evaluation feeds dk, dv, and dq); past that it would overflow the
    # 16 MB default scope, so the split two-kernel path (s-independent
    # VMEM) takes over. ``backward`` in {"fused", "split"} overrides for
    # the parity tests, which address each kernel at small s.
    # Segmented instances always take the split path — segments were only
    # taught to the split pair (the fused kernel's one-pass dq residency
    # buys nothing once segment skipping fragments the block walk).
    if segmented:
        if backward == "fused":
            raise NotImplementedError(
                "segment_ids require the split backward (the fused kernel "
                "has no segment masking)"
            )
        impl = "split"
    else:
        impl = backward or ("fused" if s <= _FUSED_BWD_MAX_SEQ else "split")
    if impl == "fused":
        # The fused pass takes its preferred 512 blocks (FLOP-bound, 5
        # dots per block pair; causal block-skipping computes 3/4 of the
        # score square, and the paired program's f32 [bq, bk] working set
        # stays inside the 16 MB scope — single 1024x1024 blocks blow
        # it). The split kernels keep the caller's blocks: their
        # residency is s-independent, so larger blocks just mean fewer
        # grid steps.
        if block_q % _BWD_BLOCK == 0:
            block_q = _BWD_BLOCK
        if block_k % _BWD_BLOCK == 0:
            block_k = _BWD_BLOCK
    if impl == "split":
        kernel_kw = dict(scale=scale, causal=causal,
                         dropout_rate=dropout_rate, fuse_rope=fuse_rope,
                         hw_prng=not interpret, hp=hp, seq=s,
                         segmented=segmented)
        gqa_map = not (hp > 1 or group == 1)
        seg_args = ()
        if segmented:
            seg = jax.lax.bitcast_convert_type(seg_f, jnp.int32)
            seg_args = (seg, seg)
        # dkv pass: grid (b, h/hp, k blocks, q blocks) — dk/dv block
        # indices are constant in the innermost (q) dimension, so they
        # stay VMEM-resident accumulating across the q walk.
        q_blk = pl.BlockSpec((1, block_q, hp * d),
                             lambda ib, ip, ik, iq: (ib, iq, ip))
        kv_in = pl.BlockSpec(
            (1, block_k, hp * d),
            (lambda ib, ip, ik, iq: (ib, ik, ip // group)) if gqa_map
            else (lambda ib, ip, ik, iq: (ib, ik, ip)),
        )
        kv_out = pl.BlockSpec((1, block_k, hp * d),
                              lambda ib, ip, ik, iq: (ib, ik, ip))
        row_q = pl.BlockSpec((1, hp, 1, block_q),
                             lambda ib, ip, ik, iq: (ib, ip, 0, iq))
        rope_k = [pl.BlockSpec((block_k, d),
                               lambda ib, ip, ik, iq: (ik, 0))] * 2
        seg_dkv = [
            pl.BlockSpec((1, block_q), lambda ib, ip, ik, iq: (ib, iq)),
            pl.BlockSpec((1, block_k), lambda ib, ip, ik, iq: (ib, ik)),
        ] if segmented else []
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, **kernel_kw),
            grid=(b, h // hp, s // block_k, s // block_q),
            in_specs=[_seed_spec(), q_blk, kv_in, kv_in, q_blk, row_q,
                      row_q] + (rope_k if fuse_rope else []) + seg_dkv,
            out_specs=[kv_out, kv_out],
            out_shape=[
                jax.ShapeDtypeStruct((b, s, h * d), kv_grad_dtype),
                jax.ShapeDtypeStruct((b, s, h * d), kv_grad_dtype),
            ],
            scratch_shapes=(
                [pltpu.VMEM((block_k, d), jnp.float32)] * (2 * hp)
            ),
            interpret=interpret,
        )(seed_f, q3, k3, v3, do3, lse, delta, *rope_args, *seg_args)
        # dq pass: grid (b, h/hp, q blocks, k blocks) — the q/do/dq blocks
        # are constant in the innermost (k) dimension.
        q_blk2 = pl.BlockSpec((1, block_q, hp * d),
                              lambda ib, ip, iq, ik: (ib, iq, ip))
        kv_in2 = pl.BlockSpec(
            (1, block_k, hp * d),
            (lambda ib, ip, iq, ik: (ib, ik, ip // group)) if gqa_map
            else (lambda ib, ip, iq, ik: (ib, ik, ip)),
        )
        row_q2 = pl.BlockSpec((1, hp, 1, block_q),
                              lambda ib, ip, iq, ik: (ib, ip, 0, iq))
        rope_q = [pl.BlockSpec((block_q, d),
                               lambda ib, ip, iq, ik: (iq, 0))] * 2
        seg_dq = [
            pl.BlockSpec((1, block_q), lambda ib, ip, iq, ik: (ib, iq)),
            pl.BlockSpec((1, block_k), lambda ib, ip, iq, ik: (ib, ik)),
        ] if segmented else []
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, **kernel_kw),
            grid=(b, h // hp, s // block_q, s // block_k),
            in_specs=[_seed_spec(), q_blk2, kv_in2, kv_in2, q_blk2, row_q2,
                      row_q2] + (rope_q if fuse_rope else []) + seg_dq,
            out_specs=q_blk2,
            out_shape=jax.ShapeDtypeStruct((b, s, h * d), jnp.float32),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)] * hp,
            interpret=interpret,
        )(seed_f, q3, k3, v3, do3, lse, delta, *rope_args, *seg_args)
        if group > 1:
            dk = dk.reshape(b, s, kvh, group, d).sum(axis=3).reshape(
                b, s, kvh * d).astype(k3.dtype)
            dv = dv.reshape(b, s, kvh, group, d).sum(axis=3).reshape(
                b, s, kvh * d).astype(v3.dtype)
        return dq.astype(q3.dtype), dk, dv

    # Fused single pass; dq accumulates in f32 across kv-block grid steps
    # (its block index is constant in that dimension, so it stays in VMEM).
    # Under fused rope, dq and dk are un-rotated *inside* the kernel (VMEM)
    # before they are written — no external pass over the gradients.
    scratch = []
    if not (s == block_q == block_k):
        # The multi-block body (not the single block) works on the whole
        # [*, hp*d] slab: one table column per lane, as in the forward, and
        # one f32 accumulator each for dk and dv — transposed, with a
        # [hp*d, s] one for dq beside them, where the gradient dots take
        # their d-row form (see _bwd_fused_kernel).
        if fuse_rope and hp > 1:
            rope_args = tuple(jnp.tile(t, (1, hp)) for t in rope_args)
        if _d_row_form(d):
            scratch = ([pltpu.VMEM((hp * d, block_k), jnp.float32)] * 2
                       + [pltpu.VMEM((hp * d, s), jnp.float32)])
        else:
            scratch = [pltpu.VMEM((block_k, hp * d), jnp.float32)] * 2
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, block_q=block_q, scale=scale,
                          causal=causal, dropout_rate=dropout_rate,
                          fuse_rope=fuse_rope, hw_prng=not interpret, hp=hp),
        grid=(b, h // hp, s // block_k),
        in_specs=[_seed_spec(), full, kv_blk(block_k), kv_blk(block_k), full,
                  row, row]
        + (_rope_specs(s, rope_args[0].shape[1]) if fuse_rope else []),
        out_specs=[full, blk(block_k), blk(block_k)],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * d), jnp.float32),
            jax.ShapeDtypeStruct((b, s, h * d), kv_grad_dtype),
            jax.ShapeDtypeStruct((b, s, h * d), kv_grad_dtype),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(seed_f, q3, k3, v3, do3, lse, delta, *rope_args)
    if group > 1:
        # hp == 1 GQA-by-index-map: reduce per-query-head partials here.
        dk = dk.reshape(b, s, kvh, group, d).sum(axis=3).reshape(
            b, s, kvh * d).astype(k3.dtype)
        dv = dv.reshape(b, s, kvh, group, d).sum(axis=3).reshape(
            b, s, kvh * d).astype(v3.dtype)
    return dq.astype(q3.dtype), dk, dv


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _make_flash(causal: bool, block_q: int, block_k: int, interpret: bool,
                dropout_rate: float, num_heads: int, head_dim: int,
                fuse_rope: bool, return_lse: bool = False,
                num_kv_heads: Optional[int] = None,
                backward: Optional[str] = None,
                segmented: bool = False):
    """custom_vjp'd kernel entry over *folded* ``[b, s, h*d]`` operands.

    The fold matters twice. Memory: with head_dim 64, BSHD/BHSD tensors
    pad their minor dim to the 128-lane tile (2x expansion on every saved
    activation — q/k/v/o per layer); saving residuals as ``[b, s, h*d]``
    keeps the minor dim at hidden size, so the autodiff-saved buffers are
    unpadded. Copies: the kernels' BlockSpecs slice per-head ``[*, d]``
    column blocks straight out of the folded layout, so no BSHD transpose
    ever materializes in HBM (round 2 paid a layout copy per operand per
    layer around every pallas call). With ``fuse_rope``, residuals are
    additionally *pre-rotation* — the rotated q/k never exist outside
    VMEM.

    The backward uses its own block sizes (``_BWD_BLOCK``): it is
    FLOP-bound (5 dots per block pair, no online-softmax rescan), so
    causal block-skipping at 512 beats the forward's single-block layout.
    """
    h, d = num_heads, head_dim
    kvh = num_kv_heads if num_kv_heads is not None else h
    group = h // kvh
    hp = _heads_per_program(d, interpret)
    # A paired program's two query heads may straddle a K/V head boundary,
    # so under hp > 1 grouped K/V expands to per-query-head copies (the
    # repeated-KV-MHA identity) before the kernels, and dk/dv group-sum
    # back afterwards (in f32 — one rounding after the reduction).
    expand_kv = group > 1 and hp > 1
    kernel_kvh = h if expand_kv else kvh
    kw = dict(causal=causal, block_q=block_q, block_k=block_k,
              interpret=interpret, dropout_rate=dropout_rate,
              num_heads=h, head_dim=d, num_kv_heads=kernel_kvh,
              segmented=segmented)
    bwd_kw = dict(kw, f32_kv_grads=expand_kv, backward=backward)
    # Backward block shapes are chosen per-path inside _flash_backward
    # (the fused pass prefers 512 blocks, the split kernels keep the
    # caller's). Safe under dropout either way: hardware-PRNG masks
    # generate in fixed 512x512 tiles keyed by absolute coordinates (see
    # _keep), so any pair of 512-divisible (or equal) fwd/bwd block
    # shapes sees identical masks.

    def _expand(x3):
        if not expand_kv:
            return x3
        b, s, _ = x3.shape
        return jnp.broadcast_to(
            x3.reshape(b, s, kvh, 1, d), (b, s, kvh, group, d)
        ).reshape(b, s, h * d)

    def _group_sum(g3, like):
        if not expand_kv:
            return g3
        b, s, _ = g3.shape
        return g3.reshape(b, s, kvh, group, d).sum(axis=3).reshape(
            b, s, kvh * d).astype(like.dtype)

    def _fwd(q3, k3, v3, seed_f, seg_f, cos, sin):
        # Returns (o3, lse, qr3, kr3): under fuse_rope the kernel emits the
        # rotated-scaled q and rotated k, which replace the raw q3/k3 in
        # the autodiff residuals so the backward never re-rotates per
        # block; without rope qr3/kr3 are None. seg_f is the [b, s] f32
        # bit-carrier of the int32 segment ids (a (1, 1) placeholder when
        # not segmented — the same dance as seed_f).
        rope = (cos, sin) if fuse_rope else None
        return _flash_forward(q3, _expand(k3), _expand(v3), seed_f, seg_f,
                              rope, **kw)

    def _save(q3, k3, v3, o3, lse, qr3, kr3, seed_f, seg_f, cos, sin):
        if fuse_rope:
            return (qr3, kr3, v3, o3, lse, seed_f, seg_f, cos, sin)
        return (q3, k3, v3, o3, lse, seed_f, seg_f, cos, sin)

    def _bwd_impl(res, do3, dlse=None):
        qs3, ks3, v3, o3, lse, seed_f, seg_f, cos, sin = res
        rope = (cos, sin) if fuse_rope else None
        # Under fuse_rope, ks3 is the kernel-width rotated k the forward
        # wrote (already expanded for GQA); otherwise expand the raw k3.
        kx3 = ks3 if fuse_rope else _expand(ks3)
        dq, dk, dv = _flash_backward(
            qs3, kx3, _expand(v3), o3, lse, do3, seed_f, seg_f, rope,
            dlse=dlse, **bwd_kw
        )
        return (dq, _group_sum(dk, v3), _group_sum(dv, v3),
                jnp.zeros_like(seed_f), jnp.zeros_like(seg_f),
                jnp.zeros_like(cos), jnp.zeros_like(sin))

    if return_lse:
        # (o, lse [b, h, s]) variant for blockwise composition (ring
        # attention combines per-chunk outputs by their logsumexps, so the
        # lse is a *differentiated* output — its cotangent folds into the
        # backward's delta row, see _flash_backward).
        @jax.custom_vjp
        def flash(q3, k3, v3, seed_f, seg_f, cos, sin):
            o3, lse = _fwd(q3, k3, v3, seed_f, seg_f, cos, sin)[:2]
            return o3, lse[:, :, 0, :]

        def fwd(q3, k3, v3, seed_f, seg_f, cos, sin):
            o3, lse, qr3, kr3 = _fwd(q3, k3, v3, seed_f, seg_f, cos, sin)
            return ((o3, lse[:, :, 0, :]),
                    _save(q3, k3, v3, o3, lse, qr3, kr3, seed_f, seg_f,
                          cos, sin))

        def bwd(res, cot):
            do3, dlse = cot
            return _bwd_impl(res, do3, dlse=dlse)

        flash.defvjp(fwd, bwd)
        return flash

    @jax.custom_vjp
    def flash(q3, k3, v3, seed_f, seg_f, cos, sin):
        return _fwd(q3, k3, v3, seed_f, seg_f, cos, sin)[0]

    def fwd(q3, k3, v3, seed_f, seg_f, cos, sin):
        o3, lse, qr3, kr3 = _fwd(q3, k3, v3, seed_f, seg_f, cos, sin)
        return o3, _save(q3, k3, v3, o3, lse, qr3, kr3, seed_f, seg_f,
                         cos, sin)

    def bwd(res, do3):
        return _bwd_impl(res, do3)

    flash.defvjp(fwd, bwd)
    return flash


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    rope: Optional[tuple] = None,
    return_lse: bool = False,
    backward: Optional[str] = None,
    segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Blockwise causal flash attention; BSHD in, BSHD out.

    ``window`` (positions) limits position ``i`` to the keys ``i - window <
    j <= i``. A window, or a sequence longer than ``STREAMING_MAX_SEQ``,
    takes the banded kernels (``band_attention``) where they fit: causal, no
    dropout, no segments, no exposed lse, the default backward. Past that a
    window runs in XLA through the masked reference.

    ``segment_ids`` ([batch, seq] int) isolates attention within packed
    documents: position i attends position j only when
    ``segment_ids[b, i] == segment_ids[b, j]`` (on top of causality).
    Blocks whose q-rows and k-columns share no segment are skipped at
    block granularity — the generalization of the causal below-diagonal
    skip — and only boundary blocks pay an elementwise mask. The packing
    convention is 0 = padding (pad attends pad; mask those targets in the
    loss) and documents 1..K. Segmented backward always runs the split
    two-kernel path.

    ``dropout_rate > 0`` (with a PRNG key) applies attention-weight dropout
    *inside* the kernel via a counter-based mask — no [seq, seq] mask array
    ever exists, and training with the reference's default attention dropout
    keeps the flash memory profile. ``rope=(cos, sin)`` ([seq, head_dim]
    f32 tables) fuses the rotary embedding into the kernel: q/k rotate in
    VMEM, never materializing rotated copies in HBM. Falls back to XLA's
    fused attention when the sequence length doesn't tile (the kernel
    requires ``seq % block == 0``) — e.g. odd-length generate windows —
    applying rope externally there.

    ``backward`` selects the backward kernel: ``"fused"`` (single pass,
    full-row dq residency), ``"split"`` (two-kernel dkv + dq passes,
    s-independent VMEM), or ``None``/``"auto"`` — fused for
    s <= ``_FUSED_BWD_MAX_SEQ``, split beyond.
    """
    b, s, h, d = q.shape
    if dropout_rate > 0.0 and dropout_rng is None:
        raise ValueError("dropout_rate > 0 requires dropout_rng")
    if backward == "auto":
        backward = None
    if backward not in (None, "fused", "split"):
        raise ValueError(
            f"backward must be 'fused', 'split' or 'auto'; got {backward!r}"
        )
    segmented = segment_ids is not None
    if segmented:
        if segment_ids.shape != (b, s):
            raise ValueError(
                f"segment_ids must be [batch, seq] = {(b, s)}; "
                f"got {segment_ids.shape}"
            )
        if backward == "fused":
            raise NotImplementedError(
                "segment_ids require the split backward (the fused kernel "
                "has no segment masking)"
            )
    if h % k.shape[2] != 0:
        raise ValueError(
            f"num_heads {h} not divisible by num_kv_heads {k.shape[2]}"
        )
    if window is not None or s > STREAMING_MAX_SEQ:
        plain = (causal and dropout_rate == 0.0 and not segmented
                 and not return_lse and backward is None)
        block = block_q or band_block(s, d, interpret)
        if plain and block is not None and s % block == 0:
            return band_attention(q, k, v, window=window or s, rope=rope,
                                  block=block, interpret=interpret)
        if window is not None:
            if not plain:
                raise NotImplementedError(
                    "a sliding window is causal attention with no dropout, "
                    "no segment ids, no exposed lse and the default backward")
            from tpu_trainer.ops.attention import reference_attention

            if rope is not None:
                from tpu_trainer.ops.rope import apply_rotary_pos_emb

                q, k = apply_rotary_pos_emb(q, k, rope[0], rope[1])
            return reference_attention(q, k, v, window=window)
    if return_lse and (s % 128 != 0 or s < 128
                       or not (interpret or d == 64 or d % 128 == 0)):
        # The lse variant exists for blockwise composition (ring attention);
        # its callers check tiling first, so this is a programming error.
        raise NotImplementedError(
            f"return_lse requires a kernel-tileable sequence/head_dim "
            f"(s={s}, d={d})"
        )
    # Largest block <= the requested size that divides the sequence, so e.g.
    # seq=768 runs the kernel with 256-blocks rather than falling back to
    # the O(seq^2) path. (Dropout masks generate in fixed 512x512 tiles
    # keyed by absolute coordinates — see _keep — so the backward's
    # different block shape still sees the identical mask.)
    explicit_q, explicit_k = block_q is not None, block_k is not None
    block_q = block_q if explicit_q else DEFAULT_BLOCK_Q
    block_k = block_k if explicit_k else DEFAULT_BLOCK_K
    block_q = next((blk for blk in (block_q, 512, 256, 128)
                    if blk <= s and s % blk == 0), block_q)
    block_k = next((blk for blk in (block_k, 512, 256, 128)
                    if blk <= s and s % blk == 0), block_k)
    # Multi-block STREAMING (s > block): the [block_q, block_k] f32 score
    # block plus its exp/rotation/dropout temporaries must fit Mosaic's
    # 16 MB scoped VMEM per software-pipelined iteration; 1024x1024 fits
    # only as the single-block layout (s == block — no pipelining across
    # k blocks). Measured on v5e at s=2048: the 1024-block streaming
    # forward needs 18.9 MB and OOMs the scope, so default streaming caps
    # at the 512 shape (the backward already runs 512s). Explicitly-passed
    # block sizes are always honored.
    if not explicit_q and s > block_q > 512 and s % 512 == 0:
        block_q = 512
    if not explicit_k and s > block_k > 512 and s % 512 == 0:
        block_k = 512
    # Compiled Mosaic lowering supports d=64 (two heads per program, lane
    # width 128) and d multiples of 128; other head dims take the XLA
    # fallback below (interpret mode has no lane constraint).
    kernel_ok = interpret or d == 64 or d % 128 == 0
    if s % block_q != 0 or s % block_k != 0 or s < 8 or not kernel_ok:
        if rope is not None:
            from tpu_trainer.ops.rope import apply_rotary_pos_emb

            q, k = apply_rotary_pos_emb(q, k, rope[0], rope[1])
        if segmented:
            # Dense segment-aware fallback (reference_attention builds the
            # combined causal x segment mask); it is unconditionally
            # causal, like the dropout fallback below.
            if not causal:
                raise NotImplementedError(
                    "non-causal segmented attention has no fallback path"
                )
            from tpu_trainer.ops.attention import reference_attention

            return reference_attention(
                q, k, v, dropout_rate=dropout_rate,
                deterministic=dropout_rate <= 0.0, dropout_rng=dropout_rng,
                segment_ids=segment_ids,
            )
        if dropout_rate > 0.0:
            # The XLA fused path has no attention dropout; keep the
            # configured semantics via the jnp reference path. That path is
            # unconditionally causal — fail loudly rather than silently
            # masking a non-causal caller.
            if not causal:
                raise NotImplementedError(
                    "non-causal attention with dropout on a non-tiling "
                    "sequence length has no kernel or fallback path"
                )
            from tpu_trainer.ops.attention import reference_attention

            return reference_attention(
                q, k, v, dropout_rate=dropout_rate, deterministic=False,
                dropout_rng=dropout_rng,
            )
        # jax.nn.dot_product_attention handles grouped K/V natively.
        return jax.nn.dot_product_attention(q, k, v, is_causal=causal)
    if dropout_rate > 0.0:
        if s >= 2**16:
            raise NotImplementedError(
                "kernel dropout counters are uint32: seq must be < 65536"
            )
        seed_bits = jax.random.bits(dropout_rng, dtype=jnp.uint32)
    else:
        seed_bits = jnp.uint32(0)
    seed_f = jax.lax.bitcast_convert_type(seed_bits, jnp.float32).reshape(1, 1)
    if segmented:
        seg_f = jax.lax.bitcast_convert_type(
            segment_ids.astype(jnp.int32), jnp.float32)
    else:
        seg_f = jnp.zeros((1, 1), jnp.float32)  # unused placeholder
    fuse_rope = rope is not None
    if fuse_rope:
        cos, sin = rope[0].astype(jnp.float32), rope[1].astype(jnp.float32)
    else:
        cos = sin = jnp.zeros((1, 1), jnp.float32)  # unused placeholder
    kvh = k.shape[2]
    h_k = h
    if not interpret and d == 64 and h % 2 == 1:
        # Head pairing needs an even head count (e.g. gpt2-xl's 25 heads):
        # expand grouped K/V to per-query-head copies, then append one
        # all-zero head. Zero q/k give uniform scores (finite lse, finite
        # backward); zero dO upstream keeps its gradients zero. The pad and
        # the expansion sit outside the custom_vjp, so their VJPs
        # (slice/group-sum) are ordinary autodiff.
        if kvh != h:
            k = jnp.broadcast_to(k[:, :, :, None, :],
                                 (b, s, kvh, h // kvh, d)).reshape(b, s, h, d)
            v = jnp.broadcast_to(v[:, :, :, None, :],
                                 (b, s, kvh, h // kvh, d)).reshape(b, s, h, d)
        zpad = jnp.zeros((b, s, 1, d), q.dtype)
        q = jnp.concatenate([q, zpad], axis=2)
        k = jnp.concatenate([k, zpad.astype(k.dtype)], axis=2)
        v = jnp.concatenate([v, zpad.astype(v.dtype)], axis=2)
        h_k = h + 1
        kvh = h_k
    fn = _make_flash(
        causal, block_q, block_k, interpret, float(dropout_rate), h_k, d,
        fuse_rope, return_lse, kvh, backward, segmented,
    )
    # Folded [b, s, h*d] at the custom_vjp boundary (unpadded residuals).
    out = fn(
        q.reshape(b, s, h_k * d), k.reshape(b, s, kvh * d),
        v.reshape(b, s, kvh * d), seed_f, seg_f, cos, sin,
    )
    if return_lse:
        o3, lse = out
        return o3.reshape(b, s, h_k, d)[:, :, :h], lse[:, :h]
    return out.reshape(b, s, h_k, d)[:, :, :h]


# --------------------------------------------------------------------------
# banded causal attention: a sliding window, or the whole prefix past 4,096
# --------------------------------------------------------------------------
#
# Position ``i`` attends ``j`` for ``i - window < j <= i``; a window of the
# sequence's length is plain causal attention. Every operand reaches the
# kernels as ``[block, d]`` tiles through the grid, so nothing resident
# grows with the sequence: the forward and the ``dq`` kernel walk, for each
# query block, the ``band`` key blocks that can meet it (the innermost grid
# axis); the ``dk``/``dv`` kernel walks, for each key block, the query
# blocks of its band for every query head of its K/V head, so that a K/V
# head's gradient is summed over its group in VMEM and written once. A step
# whose block lies outside the band (before the sequence's first block, or
# past its last) computes nothing, and its index map is clamped to the
# block the neighbouring step reads, so that it moves no data either. Only
# block pairs that cross an edge of the band pay the element mask. GQA is
# an index map. RoPE and the 1/sqrt(d) scale are applied in XLA before the
# kernels (``band_attention``): no table sits in VMEM.

BAND_BLOCK = 512
# The longest sequence the kernels above take without a window: past it
# their forward's whole-sequence K/V blocks and ``[s, d]`` RoPE tables
# overflow the 16 MB scope, and the banded kernels carry causal attention.
STREAMING_MAX_SEQ = 4096
_BAND_COMPILED_BLOCKS = (512, 256, 128)


def band_blocks(window: int, block: int, blocks: int) -> int:
    """Key blocks one query block of ``block`` rows can meet (and query
    blocks one key block can), of the sequence's ``blocks``."""
    return min(blocks, -(-(window - 1) // block) + 1)


def band_block(s: int, d: int, interpret: bool) -> Optional[int]:
    """The block the banded kernels run a sequence of ``s`` at, or None
    where they cannot (compiled: whole 128-lane heads and a block of 128,
    256 or 512 rows that divides the sequence)."""
    if interpret:
        return next((b for b in (BAND_BLOCK, 256, 128, 64, 32, 16, 8)
                     if b <= s and s % b == 0), None)
    if d % _LANES:
        return None
    return next((b for b in _BAND_COMPILED_BLOCKS if b <= s and s % b == 0),
                None)


def band_pairs(s: int, window: int, block: int) -> int:
    """(query block, key block) pairs the kernels compute for one head of
    one sequence: those with some (row, key) pair inside the band."""
    blocks = s // block
    return sum(1 for iq in range(blocks) for ik in range(iq + 1)
               if (ik + 1) * block - 1 > iq * block - window)


def _band_predicates(q_start, k_start, block, window):
    """``(needed, full)`` of one block pair: some (row, key) pair lies in
    the band; every pair does."""
    needed = (k_start <= q_start + block - 1) & (
        k_start + block - 1 > q_start - window)
    full = (k_start + block - 1 <= q_start) & (
        k_start > q_start + block - 1 - window)
    return needed, full


def _band_mask(s, q_start, k_start, window):
    """Scores outside the band set to a large finite negative: a row whose
    first computed block holds none of its keys then leaves finite garbage
    that its first valid block wipes (``_SEG_MASK``'s reasoning)."""
    diff = (q_start - k_start
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    return jnp.where((diff >= 0) & (diff < window), s, _SEG_MASK)


def _band_when(needed, full, body):
    pl.when(full)(functools.partial(body, False))
    pl.when(needed & jnp.logical_not(full))(functools.partial(body, True))


_LANES_DOT = (((1,), (1,)), ((), ()))
_ROWS_DOT = (((0,), (0,)), ((), ()))


def _band_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                     acc_scr, *, window, band):
    """Grid ``(b, h, s // block, band)``: one query block of one head, its
    band's key blocks along the last axis, an online softmax in VMEM
    scratch (``m`` and ``l`` replicated over 128 lanes, as the streaming
    forward keeps them). ``q`` arrives rotated and scaled."""
    block, width = q_ref.shape[1], q_ref.shape[2]
    iq, j = pl.program_id(2), pl.program_id(3)
    q_start = iq * block
    k_start = (iq - band + 1 + j) * block

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def body(masked):
        v = v_ref[0]
        s = jax.lax.dot_general(q_ref[0], k_ref[0], _LANES_DOT,
                                preferred_element_type=jnp.float32)
        if masked:
            s = _band_mask(s, q_start, k_start, window)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lane_tile(m_new, block))
        alpha = jnp.exp(m - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * _lane_tile(alpha, width) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    needed, full = _band_predicates(q_start, k_start, block, window)
    inside = k_start >= 0
    _band_when(needed & inside, full & inside, body)

    @pl.when(j == band - 1)
    def _flush():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / _lane_tile(l, width)).astype(o_ref.dtype)
        lse_ref[0, 0, 0, :] = m_scr[...][:, 0] + jnp.log(l[:, 0])


def _band_scores(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_start,
                 k_start, window, masked):
    """One block pair's probabilities ``p`` and score gradient ``ds``, f32
    ``[bq, bk]``, from the saved log-sum-exp and ``delta = rowsum(dO O)``."""
    s = jax.lax.dot_general(q_ref[0], k_ref[0], _LANES_DOT,
                            preferred_element_type=jnp.float32)
    if masked:
        s = _band_mask(s, q_start, k_start, window)
    p = jnp.exp(s - lse_ref[0, 0, 0, :][:, None])
    dp = jax.lax.dot_general(do_ref[0], v_ref[0], _LANES_DOT,
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta_ref[0, 0, 0, :][:, None])


def _band_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                    dq_scr, *, window, band):
    """``dq`` of one query block of one head: the forward's grid and walk."""
    block = q_ref.shape[1]
    iq, j = pl.program_id(2), pl.program_id(3)
    q_start = iq * block
    k_start = (iq - band + 1 + j) * block

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def body(masked):
        _, ds = _band_scores(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                             q_start, k_start, window, masked)
        dq_scr[...] += jnp.dot(ds.astype(k_ref.dtype), k_ref[0],
                               preferred_element_type=jnp.float32)

    needed, full = _band_predicates(q_start, k_start, block, window)
    inside = k_start >= 0
    _band_when(needed & inside, full & inside, body)

    @pl.when(j == band - 1)
    def _flush():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _band_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                     dv_ref, dk_scr, dv_scr, *, window, band, blocks, group):
    """``dk``, ``dv`` of one key block of one K/V head. Grid ``(b, kv heads,
    s // block, group * band)``: the last axis walks the band's query
    blocks of each query head of the group in turn, so both gradients sum
    over the group in f32 scratch and leave once."""
    block = k_ref.shape[1]
    ik, t = pl.program_id(2), pl.program_id(3)
    iq = ik + t % band
    q_start, k_start = iq * block, ik * block

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def body(masked):
        p, ds = _band_scores(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                             q_start, k_start, window, masked)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], _ROWS_DOT,
            preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], _ROWS_DOT,
            preferred_element_type=jnp.float32)

    needed, full = _band_predicates(q_start, k_start, block, window)
    inside = iq < blocks
    _band_when(needed & inside, full & inside, body)

    @pl.when(t == group * band - 1)
    def _flush():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _band_forward(q3, k3, v3, *, heads, kv_heads, window, block, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = q3.shape
    d = q3.shape[2] // heads
    group, blocks = heads // kv_heads, s // block
    band = band_blocks(window, block, blocks)
    tile = lambda index: pl.BlockSpec((1, block, d), index)  # noqa: E731
    q_spec = tile(lambda ib, ih, iq, j: (ib, iq, ih))
    kv_spec = tile(lambda ib, ih, iq, j: (
        ib, jnp.maximum(iq - band + 1 + j, 0), ih // group))
    lse_spec = pl.BlockSpec((1, 1, 1, block),
                            lambda ib, ih, iq, j: (ib, ih, 0, iq))
    return pl.pallas_call(
        functools.partial(_band_fwd_kernel, window=window, band=band),
        grid=(b, heads, blocks, band),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                   jax.ShapeDtypeStruct((b, heads, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32)] * 2
        + [pltpu.VMEM((block, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q3, k3, v3)


def _band_backward(q3, k3, v3, o3, lse, do3, *, heads, kv_heads, window,
                   block, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = q3.shape
    d = q3.shape[2] // heads
    group, blocks = heads // kv_heads, s // block
    band = band_blocks(window, block, blocks)
    delta = jnp.moveaxis(
        (do3.astype(jnp.float32) * o3.astype(jnp.float32))
        .reshape(b, s, heads, d).sum(axis=-1), 1, 2)[:, :, None, :]
    tile = lambda index: pl.BlockSpec((1, block, d), index)  # noqa: E731
    row = lambda index: pl.BlockSpec((1, 1, 1, block), index)  # noqa: E731
    params = pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))

    # dq: the forward's grid.
    q_spec = tile(lambda ib, ih, iq, j: (ib, iq, ih))
    kv_spec = tile(lambda ib, ih, iq, j: (
        ib, jnp.maximum(iq - band + 1 + j, 0), ih // group))
    row_spec = row(lambda ib, ih, iq, j: (ib, ih, 0, iq))
    dq = pl.pallas_call(
        functools.partial(_band_dq_kernel, window=window, band=band),
        grid=(b, heads, blocks, band),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        compiler_params=params, interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)

    # dk, dv: a K/V head's key block, the band's query blocks of each of its
    # query heads.
    def head(ikv, t):
        return ikv * group + t // band

    def q_block(ik, t):
        return jnp.minimum(ik + t % band, blocks - 1)

    q_spec = tile(lambda ib, ikv, ik, t: (ib, q_block(ik, t), head(ikv, t)))
    kv_spec = tile(lambda ib, ikv, ik, t: (ib, ik, ikv))
    row_spec = row(lambda ib, ikv, ik, t: (ib, head(ikv, t), 0,
                                           q_block(ik, t)))
    dk, dv = pl.pallas_call(
        functools.partial(_band_dkv_kernel, window=window, band=band,
                          blocks=blocks, group=group),
        grid=(b, kv_heads, blocks, group * band),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)] * 2,
        compiler_params=params, interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _band_kernels(window: int, block: int, heads: int, kv_heads: int,
                  interpret: bool):
    """custom_vjp'd banded attention over folded ``[b, s, heads * d]``
    operands (q rotated and scaled). The bodies are jitted under names of
    their own (``band_flash_fwd``, ``band_flash_bwd``), so that a trace's
    op names tell them from the streaming kernels."""
    kw = dict(heads=heads, kv_heads=kv_heads, window=window, block=block,
              interpret=interpret)

    @jax.jit
    def band_flash_fwd(q3, k3, v3):
        return _band_forward(q3, k3, v3, **kw)

    @jax.jit
    def band_flash_bwd(q3, k3, v3, o3, lse, do3):
        return _band_backward(q3, k3, v3, o3, lse, do3, **kw)

    @jax.custom_vjp
    def band(q3, k3, v3):
        return band_flash_fwd(q3, k3, v3)[0]

    def fwd(q3, k3, v3):
        o3, lse = band_flash_fwd(q3, k3, v3)
        return o3, (q3, k3, v3, o3, lse)

    def bwd(res, do3):
        return band_flash_bwd(*res, do3)

    band.defvjp(fwd, bwd)
    return band


def band_attention(q, k, v, *, window: int, rope=None,
                   block: Optional[int] = None, interpret: bool = False):
    """Causal attention within a band of ``window`` positions through the
    banded kernels; BSHD in, BSHD out. ``rope=(cos, sin)`` (``[s, d]``
    tables, any scaling already in them) rotates q and k here, in f32, and
    q takes the ``1/sqrt(d)`` scale before its one rounding to the compute
    type. ``block`` defaults to ``band_block``'s choice."""
    from tpu_trainer.ops.rope import rotate_half

    b, s, h, d = q.shape
    kvh = k.shape[2]
    block = block or band_block(s, d, interpret)
    if block is None or s % block:
        raise ValueError(f"no banded block divides s={s} at head_dim {d}")
    q32 = q.astype(jnp.float32)
    if rope is not None:
        cos = rope[0].astype(jnp.float32)[None, :, None, :]
        sin = rope[1].astype(jnp.float32)[None, :, None, :]
        q32 = q32 * cos + rotate_half(q32) * sin
        k32 = k.astype(jnp.float32)
        k = (k32 * cos + rotate_half(k32) * sin).astype(k.dtype)
    q = (q32 * (1.0 / math.sqrt(d))).astype(q.dtype)
    fn = _band_kernels(min(window, s), block, h, kvh, interpret)
    return fn(q.reshape(b, s, h * d), k.reshape(b, s, kvh * d),
              v.reshape(b, s, kvh * d)).reshape(b, s, h, d)


# --- flash-decode: single-query attention over a PAGED KV cache -------------
#
# The serving engine's decode step (tpu_trainer/serving/): each request's
# KV history lives in fixed-size blocks scattered through a preallocated
# pool, addressed by a per-request block table. The kernel is the
# split-KV sibling of the split dkv/dq backward above — grid
# ``(batch, n_splits, blocks_per_split)`` where each (batch, split)
# program walks its share of the request's cache blocks with an online
# softmax over ALL heads in VMEM scratch and flushes a partial
# (m, l, acc) triple; the per-split partials merge in plain jnp (the
# standard flash-decoding recombination: ``o = sum_s exp(m_s - m*) acc_s /
# sum_s exp(m_s - m*) l_s``). The block gather rides the BlockSpec index
# maps via scalar prefetch: the block table and lengths are
# ``num_scalar_prefetch`` operands, so ``tables[b, split*bps + j]``
# *indexes the k/v pool block to DMA* — the gather costs nothing beyond
# the reads the attention needed anyway.
#
# Tiling. Mosaic wants the last two dims of every block to be (8, 128)-
# tileable or to span the whole array, and it does not slice lanes at a
# 64-offset, so a program never slices a head out of anything: it DMAs
# the page's whole ``[block_size, kv_heads * d]`` row slab and multiplies
# it with a BLOCK-DIAGONAL query ``[heads, kv_heads * d]`` (head ``ih``'s
# vector sits in kv head ``ih // group``'s lanes, zeros elsewhere — built
# outside the kernel). ``q_bd @ k^T`` is then exactly the per-head score
# tile ``[heads, block_size]`` and ``p @ v`` carries each head's output on
# its own lanes; the flush masks the foreign lanes and folds the slab into
# 128-lane chunks. The MXU does ``kv_heads`` times the useful work, which
# is free next to the page reads decode is bound by. ``m``/``l`` leave
# lane-broadcast as ``[.., heads, 128]``.
#
# An int8 cache mode dequantizes gathered blocks in VMEM: the pools carry
# ``int8 [nblk, bs, kvh, d]`` plus blockwise absmax scales
# ``f32 [nblk, bs, kvh, d // quant_block_len(d)]`` (utils/quant.py — the
# same scheme as the quantized optimizer state).
#
# ``paged_attention_reference`` is the pure-jnp path: identical math via
# a full-table gather, used as the CPU serving path and the parity oracle
# tier-1 pins the kernel against (interpret=True).


def _decode_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, *rest,
                   block_size, bps, int8, d, group, fold):
    """One (batch row, split) program over all heads; grid dim 2 walks the
    split's cache blocks sequentially with (m, l, acc) online-softmax
    state in VMEM scratch."""
    if int8:
        ks_ref, vs_ref, m_ref, l_ref, acc_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_ref, l_ref, acc_ref, m_scr, l_scr, acc_scr = rest
    ib = pl.program_id(0)
    isp = pl.program_id(1)
    jb = pl.program_id(2)
    h, width = acc_scr.shape                            # width = kvh * d

    @pl.when(jb == 0)
    def _zero():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    length = lengths_ref[ib]
    start = (isp * bps + jb) * block_size

    def _load(p_ref, s_ref):
        x = p_ref[0].astype(jnp.float32)                # [block_size, width]
        if int8:
            # Spread each scale over its quant block's lanes with a 0/1
            # matmul (one non-zero term per output: exact at HIGHEST).
            sc = s_ref[0]                               # [block_size, kvh*nbq]
            blkq = width // sc.shape[1]
            spread = (
                jax.lax.broadcasted_iota(
                    jnp.int32, (sc.shape[1], width), 1) // blkq
                == jax.lax.broadcasted_iota(
                    jnp.int32, (sc.shape[1], width), 0)
            ).astype(jnp.float32)
            x = x * jax.lax.dot_general(
                sc, spread, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        return x

    # Static body, predicated off for blocks wholly past this row's length
    # (same structure as the causal block skip in the training kernels).
    @pl.when(start < length)
    def _body():
        q = q_ref[0]                          # [h, width] block-diag, scaled
        k = _load(k_ref, ks_ref if int8 else None)
        v = _load(v_ref, vs_ref if int8 else None)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (h, block_size), 1)
        s = jnp.where(pos < length, s, _NEG_INF)
        m_old = jnp.max(m_scr[...], axis=-1, keepdims=True)        # [h, 1]
        l_old = jnp.max(l_scr[...], axis=-1, keepdims=True)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_old - m_new)
        l_new = l_old * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(jb == pl.num_programs(2) - 1)
    def _flush():
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]
        # Keep each head's own kv-head lanes, then fold the slab into
        # ``fold``-lane chunks (exact: every other term is a masked 0).
        own = (jax.lax.broadcasted_iota(jnp.int32, (h, width), 1) // d
               == jax.lax.broadcasted_iota(jnp.int32, (h, width), 0) // group)
        acc = jnp.where(own, acc_scr[...], 0.0)
        out = acc[:, :fold]
        for c in range(1, width // fold):
            out = out + acc[:, c * fold:(c + 1) * fold]
        acc_ref[0, 0] = out


def _auto_splits(max_blocks: int) -> int:
    """Largest divisor of the table width <= 4 (the split-KV parallelism
    knob; mb must split evenly so every program walks a static count)."""
    for ns in (4, 3, 2):
        if max_blocks % ns == 0:
            return ns
    return 1


def flash_decode(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    tables: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    n_splits: int = 0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-query attention over a paged KV cache (flash-decoding).

    - ``q``: ``[batch, heads, head_dim]`` — ONE query token per row.
    - ``pool_k/pool_v``: ``[num_blocks, block_size, kv_heads, head_dim]``
      block pool (float; or int8 with ``k_scale``/``v_scale``
      ``[num_blocks, block_size, kv_heads, d // quant_block_len(d)]``).
    - ``tables``: ``[batch, max_blocks]`` int32 — block ids per row, in
      position order; entries past a row's allocation should point at the
      reserved null block 0.
    - ``lengths``: ``[batch]`` int32 — valid tokens per row, INCLUDING the
      current one (so >= 1 for live rows; a length-0 row yields NaN).

    Returns f32 ``[batch, heads, head_dim]``. GQA: query head ``ih`` reads
    kv head ``ih // (heads // kv_heads)``. Compiled mode has no head_dim
    constraint of its own (d = 32/64/96/128, odd head counts and a single
    kv head all compile for a v5e — the programs only ever touch whole
    ``[block_size, kv_heads * d]`` slabs); interpret mode is the CPU
    serving and tier-1 path.
    """
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    nblk, bsz, kvh, dk = pool_k.shape
    assert dk == d and h % kvh == 0, (q.shape, pool_k.shape)
    group = h // kvh
    mb = tables.shape[1]
    int8 = pool_k.dtype == jnp.int8
    if int8 and (k_scale is None or v_scale is None):
        raise ValueError("int8 pools need k_scale/v_scale")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not n_splits:
        n_splits = _auto_splits(mb)
    if mb % n_splits != 0:
        raise ValueError(f"max_blocks {mb} % n_splits {n_splits} != 0")
    bps = mb // n_splits
    width = kvh * d
    # The flush folds the slab to one lane tile when it divides into them;
    # a narrower or ragged slab leaves whole and folds below.
    fold = _LANES if width % _LANES == 0 and _LANES % d == 0 else width

    # Block-diagonal query: head ih's (pre-scaled) vector on kv head
    # ih // group's lanes of the [kvh * d] slab, zeros elsewhere.
    qf = q.astype(jnp.float32) * (1.0 / math.sqrt(d))
    own = jnp.arange(h)[:, None] // group == jnp.arange(kvh)[None, :]
    q_bd = jnp.where(own[None, :, :, None], qf[:, :, None, :], 0.0)
    q_bd = q_bd.reshape(b, h, width)
    # Folded pool layouts: a page's rows are contiguous [bsz, kvh * d]
    # slabs (same no-transpose trick as the training kernels' [b, s, h*d]).
    k3 = pool_k.reshape(nblk, bsz, width)
    v3 = pool_v.reshape(nblk, bsz, width)

    def _page(last):
        return pl.BlockSpec(
            (1, bsz, last),
            lambda ib, isp, jb, tr, lr: (tr[ib, isp * bps + jb], 0, 0))

    in_specs = [
        pl.BlockSpec((1, h, width), lambda ib, isp, jb, tr, lr: (ib, 0, 0)),
        _page(width),
        _page(width),
    ]
    ops = [q_bd, k3, v3]
    if int8:
        nsc = kvh * k_scale.shape[-1]
        in_specs += [_page(nsc), _page(nsc)]
        ops += [k_scale.reshape(nblk, bsz, nsc),
                v_scale.reshape(nblk, bsz, nsc)]

    def _out(last):
        return pl.BlockSpec(
            (1, 1, h, last), lambda ib, isp, jb, tr, lr: (ib, isp, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_splits, bps),
        in_specs=in_specs,
        out_specs=[_out(_LANES), _out(_LANES), _out(fold)],
        scratch_shapes=[
            pltpu.VMEM((h, _LANES), jnp.float32),
            pltpu.VMEM((h, _LANES), jnp.float32),
            pltpu.VMEM((h, width), jnp.float32),
        ],
    )
    m, l, acc = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=bsz, bps=bps,
                          int8=int8, d=d, group=group, fold=fold),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, n_splits, h, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, n_splits, h, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, n_splits, h, fold), jnp.float32),
        ],
        interpret=interpret,
    )(tables, lengths, *ops)
    m, l = m[..., 0], l[..., 0]                              # [b, S, h]
    # Finish the lane fold: [.., fold] holds fold // d head slots, one live.
    acc = acc.reshape(b, n_splits, h, fold // d, d).sum(axis=3)
    # Split merge: renormalize each split's accumulator by the global max
    # and combine (empty splits carry m = -inf -> weight exp(-inf) = 0).
    w = jnp.exp(m - jnp.max(m, axis=1, keepdims=True))       # [b, S, h]
    l_tot = jnp.sum(l * w, axis=1)                           # [b, h]
    return jnp.einsum("bsh,bshd->bhd", w, acc) / l_tot[:, :, None]


def paged_attention_reference(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    tables: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Pure-jnp ``flash_decode``: gather the whole table view, mask past
    each row's length, plain f32 softmax. Same operands/result contract.
    The CPU serving path (a [b, mb*bsz] gather beats an interpreted grid
    walk by orders of magnitude) and the oracle the kernel tests pin
    against."""
    b, h, d = q.shape
    nblk, bsz, kvh, _ = pool_k.shape
    group = h // kvh
    mb = tables.shape[1]
    if pool_k.dtype == jnp.int8:
        nbq = k_scale.shape[-1]
        blkq = d // nbq
        deq = lambda p, s: (  # noqa: E731
            p.astype(jnp.float32).reshape(nblk, bsz, kvh, nbq, blkq)
            * s[..., None]).reshape(nblk, bsz, kvh, d)
        pool_k = deq(pool_k, k_scale)
        pool_v = deq(pool_v, v_scale)
    k = pool_k[tables].reshape(b, mb * bsz, kvh, d).astype(jnp.float32)
    v = pool_v[tables].reshape(b, mb * bsz, kvh, d).astype(jnp.float32)
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), k)
    s = s * (1.0 / math.sqrt(d))
    pos = jnp.arange(mb * bsz)[None, None]
    s = jnp.where(pos < lengths[:, None, None], s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", w, v)


def paged_attention_sharded(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    tables: jax.Array,
    lengths: jax.Array,
    *,
    mesh,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Tensor-parallel paged decode dispatch: ``flash_decode`` (or the
    reference) under a full-manual ``shard_map`` over a single-axis
    device mesh, Q heads split along the mesh axis.

    Two KV layouts, matching the pool placement the serving engine
    commits (serving/sharding.py):

    - ``kv_heads % tp == 0``: pools arrive sharded on their kv-heads
      axis; each device runs the stock kernel on its ``h/tp`` Q heads x
      ``kvh/tp`` kv heads slice (the per-device GQA group size is
      unchanged, so the kernel's ``ih // group`` indexing needs no
      adjustment).
    - ``tp % kv_heads == 0`` (GQA, kv_heads < tp): pools arrive
      replicated; each device's contiguous Q-head slice falls inside ONE
      kv group, so the body slices kv head ``axis_index // (tp // kvh)``
      and runs the kernel with a single kv head.

    Either way each device owns a disjoint contiguous slice of the
    output's heads axis; the final ``psum`` all-reduce of zero-padded
    slices is therefore an exact concatenation (every output element has
    exactly one non-zero contributor — no floating-point reassociation),
    which is what keeps the sharded engine bit-identical to the
    single-device one. Returns f32 ``[batch, heads, head_dim]``, same
    contract as ``flash_decode``.
    """
    from jax.sharding import PartitionSpec as P

    from tpu_trainer.utils.jax_compat import shard_map

    if impl == "auto":
        impl = "kernel" if jax.default_backend() == "tpu" else "reference"
    fn = (functools.partial(flash_decode, interpret=interpret)
          if impl == "kernel" else paged_attention_reference)

    axis = mesh.axis_names[0]
    tp = int(mesh.devices.size)
    b, h, d = q.shape
    kvh = pool_k.shape[2]
    scales = () if k_scale is None else (k_scale, v_scale)
    if tp == 1:
        kw = ({"k_scale": k_scale, "v_scale": v_scale} if scales else {})
        return fn(q, pool_k, pool_v, tables, lengths, **kw)
    if h % tp:
        raise ValueError(f"heads {h} % tp {tp} != 0")
    hl = h // tp
    kv_shard = kvh % tp == 0
    if not kv_shard and tp % kvh:
        raise ValueError(f"kv_heads {kvh} vs tp {tp}: neither divides")

    pool_spec = P(None, None, axis, None) if kv_shard else P()
    in_specs = [P(None, axis, None), pool_spec, pool_spec, P(), P()]
    in_specs += [pool_spec] * len(scales)

    def body(q_l, pk, pv, tb, ln, *sc):
        i = jax.lax.axis_index(axis)
        if not kv_shard:
            def one_kv(x):
                return jax.lax.dynamic_slice_in_dim(
                    x, i // (tp // kvh), 1, axis=2)
            pk, pv = one_kv(pk), one_kv(pv)
            sc = tuple(one_kv(s) for s in sc)
        kw = {"k_scale": sc[0], "v_scale": sc[1]} if sc else {}
        out_l = fn(q_l, pk, pv, tb, ln, **kw)            # [b, h/tp, d]
        full = jnp.zeros((b, h, d), out_l.dtype)
        full = jax.lax.dynamic_update_slice(full, out_l, (0, i * hl, 0))
        return jax.lax.psum(full, axis)

    return shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=P(), check_vma=False)(
        q, pool_k, pool_v, tables, lengths, *scales)
