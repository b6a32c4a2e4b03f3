"""The Mamba-2 selective state-space scan in its chunked (state-space
duality) form, arXiv:2405.21060 section 6.

Per head ``h`` of group ``g`` the layer computes the linear recurrence

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T        ([P, N] state)
    y_t = S_t C_t

which unrolls to ``y_t = sum_{j <= t} exp(sum_{j < r <= t} dt_r A_h) dt_j
(C_t . B_j) x_j``. Token by token that is ``seq`` dependent steps of vector
work; here the sequence is cut into chunks of ``chunk`` tokens and every
piece is a batched matrix product:

1. inside a chunk, the masked, decay-weighted scores ``M[i, j] = (C_i . B_j)
   exp(cum_i - cum_j) dt_j`` for ``j <= i`` (``cum`` the in-chunk cumulative
   sum of ``dt A``) times the chunk's ``x``: ``[Q, Q] x [Q, P]``;
2. each chunk's contribution to the state at its end, ``sum_j exp(cum_Q -
   cum_j) dt_j x_j B_j^T``: ``[P, Q] x [Q, N]``;
3. the states at the chunk boundaries from those, through the ``[chunks + 1,
   chunks + 1]`` matrix of decays between boundaries (one small matmul a
   head; never a loop over tokens);
4. what the state a chunk starts from adds to its outputs, ``exp(cum_i) C_i
   S``: ``[Q, N] x [N, P]``.

Every decay is ``exp`` of a DIFFERENCE of cumulative sums of ``dt A`` in
float32 (all differences taken are <= 0, so nothing overflows and a long
decay underflows to the zero it is); a ratio of cumulative products would
divide by an underflowed product after a few hundred strongly-decaying
tokens. The matmul operands are in the compute type, accumulation is f32,
the boundary states are f32 throughout.

Two implementations, chosen by ``ssd`` from what it can observe
(``kernel_path``: the platform or the interpret hook, the static shapes, the
published mesh), no option:

- ``_chunked``: the four steps above in plain XLA, every shape, the path off
  a TPU. Its backward is autodiff; its ``[Q, Q]`` scores and decays (64 x the
  size of ``x``) live in HBM.
- the Pallas kernels below, on a TPU (or under ``TPU_TRAINER_FLASH_INTERPRET``)
  for the shapes ``fits`` takes: a forward and a ``jax.custom_vjp`` backward
  that keep a chunk's scores, decays and weighted scores and the ``[P, N]``
  state carried between chunks in VMEM. HBM sees ``x``, ``B``, ``C``, ``dt``
  read and ``y`` written once a forward; their cotangents once a backward;
  and the float32 state each chunk starts from, which the forward leaves for
  the backward (as large as ``y``). The same mathematics, rounded where
  ``_chunked`` rounds.

``models/gpt.py`` ``Mamba2Mixer`` recomputes the scan with the vector work
round it in every backward (``jax.checkpoint``), so neither form's residuals
live between the passes.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tpu_trainer.ops.flash import _LANES

F32 = jnp.float32


def _segment_decay(cum):
    """``exp(cum_i - cum_j)`` for ``j <= i``, else 0, over the last axis:
    ``[..., Q] -> [..., Q, Q]``. The difference is masked BEFORE the exp, so
    the upper triangle (positive differences) never overflows."""
    q = cum.shape[-1]
    diff = cum[..., :, None] - cum[..., None, :]
    lower = jnp.tril(jnp.ones((q, q), bool))
    return jnp.exp(jnp.where(lower, diff, -jnp.inf))


def _boundary_states(total, chunk_states):
    """The state each chunk starts from: boundary ``z``'s state is the sum of
    the earlier chunks' contributions, each decayed over the chunks between
    (a ``[chunks, chunks]`` strictly lower-triangular matrix a head, in f32
    at the highest matmul precision). ``total [b, g, h, chunks]``: each
    chunk's whole log-decay; ``chunk_states [b, chunks, g, h, P, N]``."""
    nc = total.shape[-1]
    through = jnp.cumsum(total, axis=-1)
    # between[z, k]: decay from the end of chunk k to the start of chunk z
    # (k < z) = exp(through[z-1] - through[k]).
    before = jnp.pad(through, [(0, 0)] * 3 + [(1, 0)])[..., :-1]
    diff = before[..., :, None] - through[..., None, :]
    earlier = jnp.tril(jnp.ones((nc, nc), bool), k=-1)
    between = jnp.exp(jnp.where(earlier, diff, -jnp.inf))   # [b,g,h,z,k]
    return jnp.einsum("bghzk,bkghpm->bzghpm", between, chunk_states,
                      precision=jax.lax.Precision.HIGHEST)


def _chunked(x, dt, a, b, c, chunk, dtype):
    """``ssd`` on a sequence that is a whole number of chunks. Returns ``y``
    (f32) and the most negative in-chunk cumulative ``dt A``."""
    batch, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per = heads // groups
    nc = seq // chunk
    dot = functools.partial(jnp.einsum, preferred_element_type=F32)

    # [b, c, Q, g, h, .]: a group's heads side by side, so that B and C
    # (one a group) batch with them.
    x = x.reshape(batch, nc, chunk, groups, per, p)
    dt = dt.astype(F32).reshape(batch, nc, chunk, groups, per)
    b = b.reshape(batch, nc, chunk, groups, n).astype(dtype)
    c = c.reshape(batch, nc, chunk, groups, n).astype(dtype)
    log_decay = dt * a.astype(F32).reshape(groups, per)     # <= 0
    cum = jnp.cumsum(log_decay, axis=2)                     # inclusive
    cum_t = jnp.moveaxis(cum, 2, -1)                        # [b, c, g, h, Q]

    # 1. Inside the chunks.
    scores = dot("bcigm,bcjgm->bcgij", c, b)                # [b, c, g, Q, Q]
    weights = (scores[:, :, :, None] * _segment_decay(cum_t)
               * jnp.moveaxis(dt, 2, -1)[..., None, :])     # [b,c,g,h,Q,Q]
    y = dot("bcghij,bcjghp->bcighp", weights.astype(dtype), x.astype(dtype))

    # 2. Each chunk's own contribution to the state at its end.
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dt             # [b, c, Q, g, h]
    chunk_states = dot("bcjghp,bcjgm->bcghpm",
                       (x.astype(F32) * to_end[..., None]).astype(dtype), b)

    # 3. The state each chunk starts from.
    starts = _boundary_states(jnp.moveaxis(cum[:, :, -1], 1, -1),
                              chunk_states)

    # 4. What the carried state adds inside each chunk.
    carried = dot("bcigm,bcghpm->bcighp", c, starts.astype(dtype))
    y = y + carried * jnp.exp(cum)[..., None]
    return y.reshape(batch, seq, heads, p), jnp.min(cum)



# --------------------------------------------------------------------------
# The same scan as Pallas TPU kernels. One program owns one GROUP's heads of
# one batch row (B and C are one a group) and walks the chunks in order: the
# grid's last axis is ``arbitrary`` and the state between chunks, float32,
# stays in VMEM scratch (step 3 above, the matrix of decays between
# boundaries, has no place here). The group's lanes ``[Q, heads * P]`` are cut
# into 128-lane SLABS (two heads of 64, one of 128): what differs a head
# (the ``[Q, Q]`` decay-weighted scores) is one dot a head against the slab
# with the other head's lanes zeroed, which costs the MXU what a 64-lane dot
# costs and needs no lane slicing; what does not (``C S^T``, ``B^T (x te)``)
# is one dot a slab. The state is kept transposed, ``[N, 128]`` a slab, so
# that every dot a head or slab is in its natural form; B (forward), C and
# the summed score gradient (backward) are transposed once a chunk and group.
#
# The in-chunk cumulative sums of ``dt a`` are products with a triangle of
# ones, as a row ``[heads, Q]`` and as a column ``[Q, heads]`` (``dt`` comes
# in both layouts; no cross-lane work a score element, PERF.md section 6, PR
# 31). The float32 operand goes through the MXU as three bf16 pieces, which
# is exact against a matrix of ones.
#
# The backward walks the chunks in reverse and carries the state's
# cotangent. It works on TRANSPOSED scores ``[j, i]``, so that ``dx = W^T dy``
# and ``dB = dG^T C`` are natural dots and a per-token reduction runs along
# the lanes into a column. B and C receive the heads' score gradients summed
# in float32 first. The gradient through the cumulative sums is what is left
# of sums that cancel: every product ``dW_ij W_ij`` adds to ``cum_i``'s and
# takes from ``cum_j``'s, so both are sums (over the sublanes, over the lanes)
# of ONE float32 matrix, added before the reverse cumulative sum. (Attention's
# shortcut, ``dy_i . y_i`` for the row sums, reads the rounded weights on one
# side only and left ``dA`` 21% off at bf16.)
# --------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))          # contract both operands' lanes


def fits(x_shape, b_shape, chunk: int) -> bool:
    """Whether the kernels take these shapes (static): the sequence a whole
    number of chunks, ``chunk`` and the state's lanes multiples of 128, heads
    of 64 or 128 lanes, a whole number of groups whose lanes fill whole
    128-lane slabs."""
    _, seq, heads, p = x_shape
    groups, n = b_shape[2], b_shape[3]
    return (chunk % _LANES == 0 and chunk <= 512 and seq >= chunk
            and seq % chunk == 0 and n % _LANES == 0 and p in (64, 128)
            and groups > 0 and heads % groups == 0
            and (heads // groups * p) % _LANES == 0)


def _exact_sum(ones, v, form: str):
    """``ones @ v`` (``"left"``), ``v @ ones`` (``"right"``) or ``ones @ v^T``
    (``"transposed"``) for a 0/1 matrix and float32 ``v``, through bf16 passes
    of the MXU: ``v`` as three bf16 pieces, each product exact, accumulated
    in float32. (A row ``[heads, Q]`` turned into a column by the vector
    unit's transpose read 12% slower a kernel on the chip: PERF.md, PR 36.)"""
    total = None
    rest = v
    for _ in range(3):
        piece = rest.astype(jnp.bfloat16)
        rest = rest - piece.astype(F32)
        if form == "left":
            part = jnp.dot(ones, piece, preferred_element_type=F32)
        elif form == "right":
            part = jnp.dot(piece, ones, preferred_element_type=F32)
        else:
            part = jax.lax.dot_general(ones, piece, _NT,
                                       preferred_element_type=F32)
        total = part if total is None else total + part
    return total


def _decays(dtr_ref, dtc_ref, ac_ref, ar_ref):
    """A chunk's time steps and cumulative log-decays, as rows ``[heads, Q]``
    and as columns ``[Q, heads]``, and the ``[Q, Q]`` index grids."""
    dt_row, dt_col = dtr_ref[0], dtc_ref[0, 0]
    q = dt_row.shape[1]
    sub = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    upper = (sub <= lane).astype(jnp.bfloat16)
    lower = (sub >= lane).astype(jnp.bfloat16)
    cum_row = _exact_sum(upper, dt_row * ac_ref[0], "right")
    cum_col = _exact_sum(lower, dt_col * ar_ref[0], "left")
    return dt_row, dt_col, cum_row, cum_col, sub, lane


def _spread(cols, slab: int, p: int):
    """Per-head values ``[rows, heads]`` over a slab's lanes ``[rows, 128]``:
    each head's value on that head's lanes."""
    per, rows = _LANES // p, cols.shape[0]
    first = cols[:, slab * per:slab * per + 1]
    if per == 1:
        return jnp.broadcast_to(first, (rows, _LANES))
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    return jnp.where(lane < p, first, cols[:, slab * per + 1:slab * per + 2])


def _heads_of(slab: int, p: int, like):
    """``(head, mask)`` of the heads in a slab: ``mask`` keeps that head's
    lanes of a ``[rows, 128]`` value (None where the slab is one head)."""
    per = _LANES // p
    if per == 1:
        return [(slab, None)]
    lane = jax.lax.broadcasted_iota(jnp.int32, like.shape, 1)
    return [(slab * per, lane < p), (slab * per + 1, lane >= p)]


def _keep(mask, v):
    return v if mask is None else jnp.where(mask, v, jnp.zeros_like(v))


def _fwd_kernel(x_ref, dtr_ref, dtc_ref, ac_ref, ar_ref, b_ref, c_ref, y_ref,
                *rest, p: int):
    starts_ref, state = rest if len(rest) == 2 else (None, rest[0])

    @pl.when(pl.program_id(2) == 0)
    def _from_zero():
        state[...] = jnp.zeros_like(state)

    dt_row, dt_col, cum_row, cum_col, sub, lane = _decays(
        dtr_ref, dtc_ref, ac_ref, ar_ref)
    q = dt_row.shape[1]
    dtype = x_ref.dtype
    total = cum_col[q - 1:q, :]                              # [1, heads]
    through = jnp.exp(cum_col)                               # from the start
    to_end = jnp.exp(total - cum_col) * dt_col
    whole = jnp.exp(total)
    bm, cm = b_ref[0], c_ref[0]
    scores = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=F32)
    b_t = bm.T
    causal = sub >= lane
    for slab in range(x_ref.shape[2] // _LANES):
        lanes = slice(slab * _LANES, (slab + 1) * _LANES)
        xs, start = x_ref[0, :, lanes], state[slab]
        if starts_ref is not None:
            starts_ref[0, 0, 0, slab] = start
        # 4. What the carried state adds; 1. inside the chunk, a head a dot.
        y = jnp.dot(cm, start.astype(dtype), preferred_element_type=F32
                    ) * _spread(through, slab, p)
        for head, mask in _heads_of(slab, p, xs):
            decay = jnp.exp(jnp.where(
                causal, cum_col[:, head:head + 1] - cum_row[head:head + 1, :],
                -jnp.inf))
            weights = scores * decay * dt_row[head:head + 1, :]
            y = y + jnp.dot(weights.astype(dtype), _keep(mask, xs),
                            preferred_element_type=F32)
        y_ref[0, :, lanes] = y
        # 2. and 3. The state the next chunk starts from.
        scaled = (xs.astype(F32) * _spread(to_end, slab, p)).astype(dtype)
        state[slab] = start * _spread(whole, slab, p) + jnp.dot(
            b_t, scaled, preferred_element_type=F32)


def _bwd_kernel(x_ref, dtr_ref, dtc_ref, ac_ref, ar_ref, b_ref, c_ref,
                starts_ref, dy_ref, dx_ref, db_ref, dc_ref, ddt_ref,
                dlog_ref, dstate, *, p: int):
    @pl.when(pl.program_id(2) == 0)
    def _from_zero():
        dstate[...] = jnp.zeros_like(dstate)

    dt_row, dt_col, cum_row, cum_col, sub, lane = _decays(
        dtr_ref, dtc_ref, ac_ref, ar_ref)
    q, heads = dt_col.shape
    dtype = x_ref.dtype
    total = cum_col[q - 1:q, :]
    through = jnp.exp(cum_col)
    rest = jnp.exp(total - cum_col)                          # to the end
    to_end = rest * dt_col
    whole = jnp.exp(total)
    bm, cm = b_ref[0], c_ref[0]
    scores_t = jax.lax.dot_general(bm, cm, _NT, preferred_element_type=F32)
    c_t = cm.T
    causal_t = sub <= lane                                   # [j, i], j <= i
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (q, heads), 1)
    head_lane_row = head_lane[:1]
    head_sub = jax.lax.broadcasted_iota(jnp.int32, (heads, q), 0)
    dscores_t = jnp.zeros((q, q), F32)
    db = jnp.zeros(db_ref.shape[1:], F32)
    dc = jnp.zeros(dc_ref.shape[1:], F32)
    ddt = jnp.zeros((q, heads), F32)        # through dt where it multiplies
    # Through the cumulative sums: what a score's row index i receives comes
    # as a row (a sum over the sublanes j of the SAME float32 products whose
    # sum over the lanes i its column index j loses: the two cancel in the
    # sum to a_h, so they must agree to the last bit), the rest as columns.
    dcum_row = jnp.zeros((heads, q), F32)
    dcum = jnp.zeros((q, heads), F32)
    dtotal = jnp.zeros((1, heads), F32)
    for slab in range(x_ref.shape[2] // _LANES):
        lanes = slice(slab * _LANES, (slab + 1) * _LANES)
        xs, dy = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        start, dend = starts_ref[0, 0, 0, slab], dstate[slab]
        dy_low, dend_low = dy.astype(dtype), dend.astype(dtype)
        to_end_s = _spread(to_end, slab, p)
        whole_s = _spread(whole, slab, p)
        # 4. The carried state's part of y.
        start_low = start.astype(dtype)
        dcarried = dy * _spread(through, slab, p)
        dthrough = dcarried * jnp.dot(cm, start_low,
                                      preferred_element_type=F32)
        dcarried = dcarried.astype(dtype)
        dc = dc + jax.lax.dot_general(dcarried, start_low, _NT,
                                      preferred_element_type=F32)
        dstart = jnp.dot(c_t, dcarried, preferred_element_type=F32)
        # 2. and 3. The state at the chunk's end.
        dscaled = jnp.dot(bm, dend_low, preferred_element_type=F32)
        scaled = (xs.astype(F32) * to_end_s).astype(dtype)
        db = db + jax.lax.dot_general(scaled, dend_low, _NT,
                                      preferred_element_type=F32)
        dx = dscaled * to_end_s
        dto_end = dscaled * xs.astype(F32)                   # summed a head
        kept = jnp.sum(dend * start, axis=0, keepdims=True)  # [1, 128]
        dstate[slab] = dend * whole_s + dstart
        # 1. Inside the chunk, on transposed scores [j, i].
        for head, mask in _heads_of(slab, p, xs):
            col = slice(head, head + 1)
            dweights_t = jax.lax.dot_general(
                _keep(mask, xs), dy_low, _NT, preferred_element_type=F32)
            decay_t = jnp.exp(jnp.where(
                causal_t, cum_row[col, :] - cum_col[:, col], -jnp.inf))
            r = dweights_t * decay_t
            dscores_t = dscores_t + r * dt_col[:, col]
            weights_t = (scores_t * decay_t * dt_col[:, col]).astype(dtype)
            dx = dx + jnp.dot(weights_t, _keep(mask, dy_low),
                              preferred_element_type=F32)
            by_dt = r * scores_t                    # dW_ij W_ij / dt_j
            dcum_row = dcum_row + jnp.where(
                head_sub == head,
                jnp.sum(by_dt * dt_col[:, col], axis=0, keepdims=True), 0.0)
            by_dt = jnp.sum(by_dt, axis=1, keepdims=True)            # [Q, 1]
            dte = jnp.sum(_keep(mask, dto_end), axis=1, keepdims=True)
            here = head_lane == head
            ddt = ddt + jnp.where(here, by_dt + dte * rest[:, col], 0.0)
            dcum = dcum + jnp.where(
                here, jnp.sum(_keep(mask, dthrough), axis=1, keepdims=True)
                - dt_col[:, col] * by_dt - dte * to_end[:, col], 0.0)
            dtotal = dtotal + jnp.where(
                head_lane_row == head,
                whole[:, col] * jnp.sum(_keep(mask, kept), axis=1,
                                        keepdims=True)
                + jnp.sum(dte * to_end[:, col], axis=0, keepdims=True), 0.0)
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
    # B and C are the group's: its heads' score gradients summed, then two
    # dots.
    dscores_low = dscores_t.astype(dtype)
    db = db + jnp.dot(dscores_low, cm, preferred_element_type=F32)
    dc = dc + jnp.dot(dscores_low.T, bm, preferred_element_type=F32)
    db_ref[0] = db.astype(db_ref.dtype)
    dc_ref[0] = dc.astype(dc_ref.dtype)
    # Back through the cumulative sum: dlog_k = sum_{i >= k} dcum_i, and the
    # chunk's whole decay is its last entry. The row part is turned into a
    # column (a product with the identity) and added BEFORE the sum.
    dcum = dcum + _exact_sum((sub == lane).astype(jnp.bfloat16), dcum_row,
                             "transposed")
    ddt_ref[0, 0] = ddt
    dlog_ref[0, 0] = _exact_sum((sub <= lane).astype(jnp.bfloat16), dcum,
                                "left") + dtotal


def _specs(batch, seq, heads, p, groups, n, chunk, reverse: bool):
    """BlockSpecs of a grid ``(batch, groups, chunks)`` by operand kind; the
    backward walks the chunks from the last."""
    per, nc = heads // groups, seq // chunk
    at = (lambda ic: nc - 1 - ic) if reverse else (lambda ic: ic)
    return dict(
        lanes=pl.BlockSpec((1, chunk, per * p),              # [b, s, H P]
                           lambda ib, ig, ic: (ib, at(ic), ig)),
        state=pl.BlockSpec((1, chunk, n),                    # [b, s, G N]
                           lambda ib, ig, ic: (ib, at(ic), ig)),
        row=pl.BlockSpec((1, per, chunk),                    # [b, H, s]
                         lambda ib, ig, ic: (ib, ig, at(ic))),
        col=pl.BlockSpec((1, 1, chunk, per),                 # [b, G, s, h]
                         lambda ib, ig, ic: (ib, ig, at(ic), 0)),
        a_col=pl.BlockSpec((1, per, 1), lambda ib, ig, ic: (ig, 0, 0)),
        a_row=pl.BlockSpec((1, 1, per), lambda ib, ig, ic: (ig, 0, 0)),
        starts=pl.BlockSpec((1, 1, 1, per * p // _LANES, n, _LANES),
                            lambda ib, ig, ic: (ib, ig, at(ic), 0, 0, 0)))


def _operands(x, dt, a, b, c):
    """The kernels' layouts: lanes folded; ``dt`` as rows ``[b, H, s]`` and
    as columns; ``a`` a column and a row a group."""
    batch, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    dt = dt.astype(F32)
    a = a.astype(F32).reshape(groups, heads // groups)
    return (x.reshape(batch, seq, heads * p), jnp.swapaxes(dt, 1, 2),
            _columns(dt, groups), a[:, :, None], a[:, None, :],
            b.reshape(batch, seq, groups * n),
            c.reshape(batch, seq, groups * n))


def _columns(v, groups):
    """``[b, s, H] -> [b, G, s, heads of the group]``."""
    batch, seq, heads = v.shape
    return jnp.swapaxes(v.reshape(batch, seq, groups, heads // groups), 1, 2)


def _rows(v):
    """``_columns`` back: ``[b, G, s, h] -> [b, s, H]``."""
    batch, groups, seq, per = v.shape
    return jnp.swapaxes(v, 1, 2).reshape(batch, seq, groups * per)


def _params(interpret):
    from jax.experimental.pallas import tpu as pltpu

    return dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)


def _forward(x, dt, a, b, c, *, chunk, interpret, keep_starts):
    """``y [b, s, H, P]`` float32 and, for a backward, the state every chunk
    starts from (``[b, G, chunks, slabs, N, 128]`` float32, transposed)."""
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    spec = _specs(batch, seq, heads, p, groups, n, chunk, reverse=False)
    slabs = heads // groups * p // _LANES
    outs = [(spec["lanes"],
             jax.ShapeDtypeStruct((batch, seq, heads * p), F32))]
    if keep_starts:
        outs.append((spec["starts"], jax.ShapeDtypeStruct(
            (batch, groups, seq // chunk, slabs, n, _LANES), F32)))
    y, *starts = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        grid=(batch, groups, seq // chunk),
        in_specs=[spec[k] for k in ("lanes", "row", "col", "a_col", "a_row",
                                    "state", "state")],
        out_specs=[s for s, _ in outs], out_shape=[s for _, s in outs],
        scratch_shapes=[pltpu.VMEM((slabs, n, _LANES), F32)],
        **_params(interpret))(*_operands(x, dt, a, b, c))
    return y.reshape(x.shape), *starts


def _backward(x, dt, a, b, c, starts, dy, *, chunk, interpret):
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    spec = _specs(batch, seq, heads, p, groups, n, chunk, reverse=True)
    cols = jax.ShapeDtypeStruct((batch, groups, seq, heads // groups), F32)
    dx, db, dc, ddt, dlog = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=(batch, groups, seq // chunk),
        in_specs=[spec[k] for k in ("lanes", "row", "col", "a_col", "a_row",
                                    "state", "state", "starts", "lanes")],
        out_specs=[spec[k] for k in ("lanes", "state", "state", "col", "col")],
        out_shape=[jax.ShapeDtypeStruct((batch, seq, heads * p), x.dtype),
                   jax.ShapeDtypeStruct((batch, seq, groups * n), b.dtype),
                   jax.ShapeDtypeStruct((batch, seq, groups * n), c.dtype),
                   cols, cols],
        scratch_shapes=[pltpu.VMEM((heads // groups * p // _LANES, n, _LANES),
                                   F32)],
        **_params(interpret))(
            *_operands(x, dt, a, b, c), starts,
            # The cotangent as it comes, f32: rounded to the operands' type
            # outside, it cost a pass of its own (PERF.md section 6, PR 36).
            dy.astype(F32).reshape(batch, seq, heads * p))
    # The gradient with respect to log-decay dt a, token by token; dA is its
    # sum over batch and sequence against dt: XLA's.
    dlog = _rows(dlog)
    ddt = _rows(ddt) + dlog * a.astype(F32)
    da = jnp.sum(dlog * dt.astype(F32), axis=(0, 1))
    return (dx.reshape(x.shape), ddt.astype(dt.dtype), da.astype(a.dtype),
            db.reshape(b.shape), dc.reshape(c.shape))


@functools.lru_cache(maxsize=None)
def _kernels(chunk: int, interpret: bool):
    """The scan through the kernels, differentiable in all five operands.
    The bodies are jitted, so that a step's call sites (four blocks, each
    forward, recomputed and backward) trace and lower each once a shape."""
    kw = dict(chunk=chunk, interpret=interpret)

    @jax.jit
    def ssd_fwd(x, dt, a, b, c):
        return _forward(x, dt, a, b, c, keep_starts=False, **kw)[0]

    @jax.jit
    def ssd_fwd_keeping(x, dt, a, b, c):
        return _forward(x, dt, a, b, c, keep_starts=True, **kw)

    @jax.jit
    def ssd_bwd(x, dt, a, b, c, starts, dy):
        return _backward(x, dt, a, b, c, starts, dy, **kw)

    @jax.custom_vjp
    def scan(x, dt, a, b, c):
        return ssd_fwd(x, dt, a, b, c)

    def fwd(x, dt, a, b, c):
        y, starts = ssd_fwd_keeping(x, dt, a, b, c)
        return y, (x, dt, a, b, c, starts)

    def bwd(res, dy):
        return ssd_bwd(*res, dy)

    scan.defvjp(fwd, bwd)
    return scan


def kernel_path(x_shape, b_shape, chunk: int):
    """How a call of these shapes runs, decided from what can be observed (the
    platform, the test hook, the shapes, the published mesh): ``None`` for the
    plain XLA form, else ``(interpret, mesh, batch axes)`` for the kernels,
    under ``shard_map`` over the batch where a mesh is published."""
    from tpu_trainer.ops.attention import _INTERPRET_ENV, _flash_mesh

    interpret = os.environ.get(_INTERPRET_ENV, "0") == "1"
    if not (interpret or any(d.platform == "tpu" for d in jax.devices())
            ) or not fits(x_shape, b_shape, chunk):
        return None
    mesh = _flash_mesh(None)
    if mesh is None:
        return interpret, None, None
    from tpu_trainer.parallel.mesh import attention_shard_spec

    # The scan is independent across the batch and nothing else; a mesh whose
    # batch axes do not divide it gets no un-sharded kernel by accident.
    b_spec, _ = attention_shard_spec(mesh, x_shape[0], 1, 1)
    return None if b_spec is None else (interpret, mesh, b_spec)


def _kernel_scan(x, dt, a, b, c, chunk, path):
    interpret, mesh, b_spec = path
    scan = _kernels(chunk, interpret)
    if mesh is None:
        return scan(x, dt, a, b, c)
    from jax.sharding import PartitionSpec as P

    from tpu_trainer.parallel.context import kernel_manual_axes
    from tpu_trainer.utils.jax_compat import shard_map

    rows4, rows3 = P(b_spec, None, None, None), P(b_spec, None, None)
    return shard_map(
        scan, mesh=mesh, in_specs=(rows4, rows3, P(None), rows4, rows4),
        out_specs=rows4, axis_names=kernel_manual_axes(mesh, set(b_spec)),
        check_vma=False)(x, dt, a, b, c)

def _ssd(x, dt, a, b, c, chunk, dtype):
    path = kernel_path(x.shape, b.shape, chunk)
    if path is not None:
        # Decays only shrink (dt >= 0, a < 0): a chunk's most negative
        # cumulative sum is its whole one, a plain sum.
        low = jnp.min(jnp.sum(
            (dt.astype(F32) * a.astype(F32)).reshape(
                x.shape[0], -1, chunk, x.shape[2]), axis=2))
        y = _kernel_scan(x.astype(dtype), dt, a, b.astype(dtype),
                         c.astype(dtype), chunk, path)
        return y, low
    seq = x.shape[1]
    pad = -seq % chunk
    if pad:
        # A padded step has dt = 0: it decays nothing and adds nothing.
        x, dt, b, c = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, b, c))
    y, low = _chunked(x, dt, a, b, c, chunk, dtype)
    return y[:, :seq], low


def ssd(x, dt, a, b, c, *, chunk: int = 128, dtype=None):
    """The state-space scan ``y_t = C_t . S_t``, ``S_t = exp(dt_t a) S_{t-1}
    + dt_t x_t B_t^T`` from a zero state.

    ``x [batch, seq, heads, P]``; ``dt [batch, seq, heads]`` (after its
    softplus, >= 0); ``a [heads]`` (negative); ``b``, ``c`` ``[batch, seq,
    groups, N]`` (head ``h`` reads group ``h // (heads / groups)``).
    ``dtype``: the matmul operands' type (``x``'s if not given); decays and
    accumulation are float32. Returns ``(y [batch, seq, heads, P] in float32,
    the most negative in-chunk cumulative dt a of the call)``; the skip term
    ``D x`` is the caller's.
    """
    dtype = jnp.dtype(x.dtype if dtype is None else dtype)
    with jax.named_scope("ssd"):
        return _ssd(x, dt, a, b, c, chunk, dtype)
