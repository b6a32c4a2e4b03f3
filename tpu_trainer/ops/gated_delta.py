"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) in its chunked
form.

Per value head, with ``S_0 = 0`` and a ``[d_k, d_v]`` state,

    S'  = exp(g_t) S_{t-1}
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
    o_t = S_t^T q_t

The update reads the state it updates (the "delta": what the state already
predicts for ``k_t`` is taken off ``v_t``), so unlike a scalar-decay scan
(``ops/ssd.py``) a chunk's tokens are not independent given the state the
chunk starts from: within a chunk they are tied by a triangular system.
Here the sequence is cut into chunks of ``chunk`` tokens (the UT / WY
transform, arXiv:2412.06464 section 3):

1. inside each chunk: ``gamma`` the in-chunk cumulative sum of ``g``; ``A =
   strict_lower(diag(beta) (K K^T o exp(gamma_i - gamma_j)))``; ``T = (I +
   A)^-1``; ``U = T diag(beta) V``; ``W = T diag(beta exp(gamma)) K``;
2. across the chunks, in order (the state in f32): ``V_new = U - W S``, then
   ``S <- exp(gamma_C) S + (K o exp(gamma_C - gamma))^T V_new``;
3. the outputs, from the state the chunk started from: ``O = (Q o
   exp(gamma)) S + lower(Q K^T o exp(gamma_i - gamma_j)) V_new``.

Every decay is ``exp`` of a difference of in-chunk cumulative sums taken
where it is <= 0, so nothing overflows and a steep decay underflows to the
zero it is. ``T`` is exact: ``A`` is strictly lower triangular, so ``(I +
A)^-1 = sum_{j < C} (-A)^j = (I - A)(I + A^2)(I + A^4)...`` with ``C`` the
chunk, a few ``[C, C]`` products in f32 (``_unit_lower_inverse``). Its
gradient is the inverse's own, ``-T^T dT T^T`` from ``T`` alone, so that the
series' powers are never kept for a backward. A key head serves ``H_v /
H_k`` value heads (value head ``j`` reads key head ``j // (H_v / H_k)``):
``K K^T`` and ``Q K^T`` are computed once a key head, never for a repeated
key. Matmul operands are in the compute type; the decays, ``T``, the state
and every accumulation are f32.

Two implementations, chosen by ``gated_delta`` from what it can observe
(``kernel_path``: the platform or the interpret hook, the static shapes, the
published mesh), no option:

- the Pallas kernels below, on a TPU (or under
  ``TPU_TRAINER_FLASH_INTERPRET``) for the shapes ``fits`` takes: a forward
  and a ``jax.custom_vjp`` backward over a grid ``(batch, key head, chunk)``
  that keep a chunk's decays, ``A``, ``T``, ``U``, ``W``, ``V_new`` and the
  ``[d_k, d_v]`` f32 state of each of the key head's value heads, carried
  from chunk to chunk, in VMEM. HBM sees ``q``, ``k``, ``v`` in the layout
  the mixer makes them, ``gamma`` and ``beta`` read and ``o`` written once a
  forward; their cotangents once a backward; and, for the backward, the
  state each chunk starts from and each chunk's ``T``, which the forward
  leaves there (the backward walks the chunks in reverse carrying the
  state's cotangent). The same mathematics, rounded where ``_chunked``
  rounds.
- ``_chunked``: the three steps in plain XLA (the pass over the chunks a
  ``lax.scan``, ``_across_chunks``), every other shape and off a TPU. Its
  backward is autodiff's but for ``T``'s; its ``[C, C]`` chunk matrices live
  in HBM.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tpu_trainer.ops.ssd import _NT, _params, _segment_decay

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
_mm = functools.partial(jnp.matmul, precision=HIGHEST)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + A)^-1`` for ``A [..., C, C]`` strictly lower triangular, in
    f32: the series ``sum_{j < C} (-A)^j`` (``A^C = 0``) as ``(I - A)(I +
    A^2)(I + A^4)...(I + A^(2^(m-1)))`` with ``2^m >= C``. Its gradient is
    the inverse's own, ``-T^T dT T^T``, from ``T`` alone: the series' powers
    are not kept for the backward."""
    size = a.shape[-1]
    eye = jnp.eye(size, dtype=F32)
    inverse, power, reach = eye - a, a, 2
    while reach < size:
        power = _mm(power, power)
        inverse = _mm(inverse, eye + power)
        reach *= 2
    return inverse


def _inverse_fwd(a):
    t = _unit_lower_inverse(a)
    return t, t


def _inverse_bwd(t, cotangent):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_mm(_mm(tt, cotangent), tt),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _across_chunks(u, w, to_end, whole, dtype):
    """The state each chunk starts from ``[b, n, hk, per, d_k, d_v]`` (f32)
    and the chunk's corrected values ``V_new = U - W S``, in order over the
    chunks from a zero state: ``S <- whole S + to_end^T V_new``, with
    ``whole`` the decay over a chunk ``[b, n, hk, per]``."""
    dot = functools.partial(jnp.einsum, preferred_element_type=F32)
    batch, _, hk, per, _, dk = w.shape
    dv = u.shape[-1]

    def step(state, xs):
        u_c, w_c, to_end_c, whole_c = xs
        v_new = u_c - dot("bhrid,bhrde->bhrie", w_c, state.astype(dtype))
        nxt = whole_c[..., None, None] * state + dot(
            "bihrd,bhrie->bhrde", to_end_c, v_new.astype(dtype))
        return nxt, (state, v_new)

    first = jnp.zeros((batch, hk, per, dk, dv), F32)
    over = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    _, (starts, v_new) = jax.lax.scan(
        step, first, (over(u), over(w), over(to_end), over(whole)))
    return jnp.moveaxis(starts, 0, 1), jnp.moveaxis(v_new, 0, 1)


def _chunked(q, k, v, g, beta, chunk, dtype):
    """``gated_delta`` on a sequence that is a whole number of chunks."""
    batch, seq, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    per = hv // hk
    nc = seq // chunk
    dot = functools.partial(jnp.einsum, preferred_element_type=F32)

    # [b, n, C, hk, (per,) .]: a key head's value heads side by side.
    q = q.reshape(batch, nc, chunk, hk, dk).astype(dtype)
    k = k.reshape(batch, nc, chunk, hk, dk).astype(dtype)
    v = v.reshape(batch, nc, chunk, hk, per, dv)
    g = g.astype(F32).reshape(batch, nc, chunk, hk, per)
    beta = beta.astype(F32).reshape(batch, nc, chunk, hk, per)
    cum = jnp.cumsum(g, axis=2)                              # gamma, inclusive
    decay = _segment_decay(jnp.moveaxis(cum, 2, -1))         # [b,n,hk,per,C,C]

    # 1. Inside the chunks.
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)
    kk = dot("bnihd,bnjhd->bnhij", k, k)                     # [b, n, hk, C, C]
    a = jnp.where(strict, kk[:, :, :, None] * decay, 0.0) * jnp.moveaxis(
        beta, 2, -1)[..., None]                              # beta_i, row i
    t = _unit_lower_inverse(a)
    u = dot("bnhrij,bnjhrd->bnhrid", t.astype(dtype),
            (v.astype(F32) * beta[..., None]).astype(dtype))
    w = dot("bnhrij,bnjhrd->bnhrid", t.astype(dtype),
            (k.astype(F32)[:, :, :, :, None]
             * (beta * jnp.exp(cum))[..., None]).astype(dtype))
    to_end = (k.astype(F32)[:, :, :, :, None]
              * jnp.exp(cum[:, :, -1:] - cum)[..., None]).astype(dtype)
    whole = jnp.exp(cum[:, :, -1])                           # [b, n, hk, per]

    # 2. Across the chunks.
    starts, v_new = _across_chunks(u, w, to_end, whole, dtype)

    # 3. The outputs.
    qk = dot("bnihd,bnjhd->bnhij", q, k)
    scores = (qk[:, :, :, None] * decay).astype(dtype)      # [b,n,hk,per,C,C]
    o = dot("bnhrij,bnhrje->bnihre", scores, v_new.astype(dtype))
    q_in = (q.astype(F32)[:, :, :, :, None]
            * jnp.exp(cum)[..., None]).astype(dtype)         # [b,n,C,hk,per,dk]
    o = o + dot("bnihrd,bnhrde->bnihre", q_in, starts.astype(dtype))
    return o.reshape(batch, seq, hv, dv), jnp.min(cum)




# --------------------------------------------------------------------------
# The same rule as Pallas TPU kernels. One program owns one KEY head of one
# batch row (its ``per = H_v / H_k`` value heads read the same q and k) and
# walks the chunks in order: the grid's last axis is ``arbitrary`` and the
# value heads' f32 states stay in VMEM scratch from chunk to chunk.
#
# A chunk's value heads are STACKED along the sublanes: rows ``[r C, (r + 1)
# C)`` are value head ``r``'s tokens, ``R = per C`` rows in all (128 in the
# Qwen3-Next cell). What differs a value head inside a chunk (the decays,
# ``A``, ``T``, the masked scores) is then ONE block-diagonal ``[R, R]``
# matrix: ``K K^T`` and ``Q K^T`` of the repeated keys and queries are the
# key head's ``[C, C]`` products in every block, the masks keep the diagonal
# blocks, and the inverse of a block-diagonal matrix is each block's
# inverse, so the series' products are ``[R, R]`` f32 dots that fill the
# MXU. Only the state differs a head in a way no block holds: ``W S``, ``(Q
# e^gamma) S`` and the state's update are a dot a head; where a head's rows
# are contracted into a ``[d_k, d_v]`` product (the update and its
# cotangent), the other heads' rows are zeroed before the transpose.
#
# A grid step takes up to four chunks (``_steps``): their inverses, which do
# not wait for the state, are computed first, so that their chains of small
# f32 products overlap, and the backward's chunks likewise; the state is
# carried through them as values.
#
# ``gamma`` and ``beta`` come a row ``[1, R]`` a chunk (value head ``r``'s
# tokens on lanes ``[r C, (r + 1) C)``); their columns are taken inside by a
# masked sum over the lanes, which is exact (one term is not zero). The
# backward returns ``dgamma`` and ``dbeta`` the same way; the chain to ``g``
# (a reverse in-chunk cumulative sum) and the layouts are XLA's, outside.
# --------------------------------------------------------------------------

CHUNK = 64
_LANES = 128


def fits(q_shape, v_shape, chunk: int = CHUNK) -> bool:
    """Whether the kernels take these shapes (static): key and value heads
    of whole 128-lane blocks, a whole number of value heads a key head, and
    a chunk of a multiple of 16 tokens (the bf16 tile) whose value heads,
    stacked, fill at most 128 rows. Any sequence: it is padded to whole
    chunks first."""
    hk, dk = q_shape[2], q_shape[3]
    hv, dv = v_shape[2], v_shape[3]
    return (hk > 0 and hv % hk == 0 and dk % _LANES == 0
            and dv % _LANES == 0 and chunk % 16 == 0
            and 0 < hv // hk * chunk <= _LANES)


def _of_head(shape, axis: int, chunk: int, r: int):
    """Whether each row (``axis`` 0) or lane (1) of a stack is value head
    ``r``'s."""
    at = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return (at >= r * chunk) & (at < (r + 1) * chunk)


def _stack(block, per: int):
    """``[C, per d]`` (value heads side by side on the lanes) -> ``[per C,
    d]`` (stacked on the sublanes)."""
    d = block.shape[1] // per
    return jnp.concatenate([block[:, r * d:(r + 1) * d] for r in range(per)],
                           axis=0) if per > 1 else block


def _column(row, eye):
    """A row ``[1, R]`` as a column ``[R, 1]``, exactly."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    """A column ``[R, 1]`` as a row ``[1, R]``, exactly."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _f32_dot(a, b):
    return jnp.dot(a, b, precision=HIGHEST, preferred_element_type=F32)


def _block_inverse(a, chunk: int):
    """``_unit_lower_inverse`` of a block-diagonal ``[R, R]`` whose blocks of
    ``chunk`` are strictly lower triangular (``A^chunk = 0``), in f32."""
    eye = jnp.eye(a.shape[0], dtype=F32)
    inverse, power, reach = eye - a, a, 2
    while reach < chunk:
        power = _f32_dot(power, power)
        inverse = _f32_dot(inverse, eye + power)
        reach *= 2
    return inverse


class _Chunk:
    """What forward and backward both compute of chunk ``j`` of a grid step's
    block, one key head, the value heads stacked: ``q``, ``k`` (the key
    head's, repeated) and ``v`` ``[R, d]`` in the compute type; ``beta`` as a
    column ``[R, 1]``; each head's whole
    decay ``e^gamma_C`` ``[1, 1]``; the decays from the chunk's start
    (``through``) and to its end (``rest``), columns; the decays
    ``exp(gamma_i - gamma_j)`` on each diagonal block's lower triangle, else
    0; ``K K^T``, ``Q K^T``; ``M = strict(K K^T o decay)``, of which ``A =
    diag(beta) M``; the compute-type operands of ``U = T (beta V)``, ``W = T
    (beta e^gamma K)``, the outputs' ``Q e^gamma`` and the update's ``K
    e^(gamma_C - gamma)``; the masked scores ``lower(Q K^T o decay)``."""

    def __init__(self, refs, j: int, per: int, chunk: int):
        q_ref, k_ref, v_ref, g_ref, b_ref = refs
        self.dtype = dtype = q_ref.dtype
        self.per, self.chunk = per, chunk
        rows = per * chunk
        at = slice(j * chunk, (j + 1) * chunk)
        self.q = jnp.concatenate([q_ref[0, at]] * per, axis=0)
        self.k = jnp.concatenate([k_ref[0, at]] * per, axis=0)
        self.v = _stack(v_ref[0, at], per)
        g_row = g_ref[0, 0, j]                               # [1, R]
        square = (rows, rows)
        sub = jax.lax.broadcasted_iota(jnp.int32, square, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, square, 1)
        self.eye = eye = sub == lane
        g_col = _column(g_row, eye)
        self.b_col = b_col = _column(b_ref[0, 0, j], eye)
        same = functools.reduce(jnp.logical_or, [
            _of_head(square, 0, chunk, r) & _of_head(square, 1, chunk, r)
            for r in range(per)])
        self.strict = same & (sub > lane)
        self.decay = jnp.exp(jnp.where(same & (sub >= lane), g_col - g_row,
                                       -jnp.inf))
        ends = [g_row[:, (r + 1) * chunk - 1:(r + 1) * chunk]
                for r in range(per)]                         # [1, 1] a head
        self.wholes = [jnp.exp(e) for e in ends]
        end_col = sum(jnp.where(_of_head((rows, 1), 0, chunk, r), e, 0.0)
                      for r, e in enumerate(ends))
        self.through = jnp.exp(g_col)
        self.rest = jnp.exp(end_col - g_col)
        self.kk = jax.lax.dot_general(self.k, self.k, _NT,
                                      preferred_element_type=F32)
        self.qk = jax.lax.dot_general(self.q, self.k, _NT,
                                      preferred_element_type=F32)
        self.masked = jnp.where(self.strict, self.kk * self.decay, 0.0)
        k32 = self.k.astype(F32)
        self.vb = (self.v.astype(F32) * b_col).astype(dtype)
        self.kw = (k32 * (b_col * self.through)).astype(dtype)
        self.qe = (self.q.astype(F32) * self.through).astype(dtype)
        self.ke = (k32 * self.rest).astype(dtype)
        self.scores = (self.qk * self.decay).astype(dtype)

    def rows(self, r: int):
        return slice(r * self.chunk, (r + 1) * self.chunk)

    def only(self, r: int, x):
        """``x^T [d, R]`` of a stack ``x [R, d]`` with the rows of heads
        other than ``r`` zeroed: the operand that contracts head ``r``'s rows
        alone against a ``[R, d']`` stack."""
        if self.per > 1:
            x = jnp.where(_of_head(x.shape, 0, self.chunk, r), x,
                          jnp.zeros_like(x))
        return x.T

    def inverse(self):
        """``T = (I + A)^-1``."""
        return _block_inverse(self.masked * self.b_col, self.chunk)

    def corrected(self, t, starts):
        """``T`` and ``W`` in the compute type and ``V_new = U - W S`` (f32),
        from ``T`` and each head's starting state."""
        tb = t.astype(self.dtype)
        u = jnp.dot(tb, self.vb, preferred_element_type=F32)
        w = jnp.dot(tb, self.kw, preferred_element_type=F32).astype(self.dtype)
        v_new = jnp.concatenate([
            u[self.rows(r)] - jnp.dot(w[self.rows(r)], s.astype(self.dtype),
                                      preferred_element_type=F32)
            for r, s in enumerate(starts)], axis=0)
        return tb, w, v_new


def _compact(t, per: int, chunk: int):
    """A block-diagonal ``[R, R]`` as ``[C, R]``: head ``r``'s block on the
    lanes ``[r C, (r + 1) C)``. Exact (the rows added hold zeros there)."""
    return sum(t[r * chunk:(r + 1) * chunk] for r in range(per))


def _expand(compact, per: int, chunk: int):
    """``_compact`` back."""
    if per == 1:
        return compact
    return jnp.concatenate(
        [jnp.where(_of_head(compact.shape, 1, chunk, r), compact, 0.0)
         for r in range(per)], axis=0)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *refs, per: int,
                chunk: int, steps: int, keep: bool):
    """``steps`` chunks of one key head, in order. Refs: ``q, k`` ``[steps
    C, d_k]``, ``v [steps C, per d_v]``, the rows of ``gamma`` and ``beta``;
    out: ``o [steps C, per d_v]`` (f32), [the state each head starts each
    chunk from, each chunk's ``T`` compacted]; scratch: the heads' carried
    states. The chunks' inverses come first: they do not wait for the
    state, so their chains of small products overlap."""
    starts_ref, t_ref, state = refs if keep else (None, None, refs[0])

    @pl.when(pl.program_id(2) == 0)
    def _from_zero():
        state[...] = jnp.zeros_like(state)

    inputs = (q_ref, k_ref, v_ref, g_ref, b_ref)
    chunks = [_Chunk(inputs, j, per, chunk) for j in range(steps)]
    inverses = [c.inverse() for c in chunks]
    states = [state[r] for r in range(per)]
    dv = o_ref.shape[2] // per
    for j, (c, t) in enumerate(zip(chunks, inverses)):
        _, _, v_new = c.corrected(t, states)
        v_low = v_new.astype(c.dtype)
        o = jnp.dot(c.scores, v_low, preferred_element_type=F32)
        at = slice(j * chunk, (j + 1) * chunk)
        for r, s in enumerate(states):
            rows = c.rows(r)
            o_ref[0, at, r * dv:(r + 1) * dv] = o[rows] + jnp.dot(
                c.qe[rows], s.astype(c.dtype), preferred_element_type=F32)
            if keep:
                starts_ref[0, 0, j, r] = s
        states = [c.wholes[r] * s + jnp.dot(c.only(r, c.ke), v_low,
                                            preferred_element_type=F32)
                  for r, s in enumerate(states)]
        if keep:
            t_ref[0, 0, j] = _compact(t, per, chunk)
    for r, s in enumerate(states):
        state[r] = s


def _bwd_chunk(c, t, starts, dends, do):
    """The backward of one chunk: from ``o``'s cotangent ``do [R, d_v]``
    (compute type, stacked) and the heads' states' cotangents at the chunk's
    end, ``(dq, dk, dv`` stacked ``[R, .]``, the columns of ``dgamma`` and
    ``dbeta``, what each head's whole decay receives ``[1, 1]``, the states'
    cotangents at the chunk's start``)``."""
    dtype = c.dtype
    tb, w, v_new = c.corrected(t, starts)
    v_low = v_new.astype(dtype)
    # The outputs and the state at the chunk's end: V_new's cotangent, Q
    # e^gamma's, K e^(gamma_C - gamma)'s, W's; each head's state's cotangent
    # at the chunk's start; what the whole decay receives.
    dv_new = jnp.dot(c.scores.T, do, preferred_element_type=F32)
    d_parts, dqe, dke, dwholes = [], [], [], []
    for r, (s, dend) in enumerate(zip(starts, dends)):
        rows, s_low, dend_low = c.rows(r), s.astype(dtype), dend.astype(dtype)
        d_parts.append(jnp.dot(c.ke[rows], dend_low,
                               preferred_element_type=F32))
        dke.append(jax.lax.dot_general(v_low[rows], dend_low, _NT,
                                       preferred_element_type=F32))
        dqe.append(jax.lax.dot_general(do[rows], s_low, _NT,
                                       preferred_element_type=F32))
        kept = jnp.sum(jnp.sum(dend * s, axis=0, keepdims=True), axis=1,
                       keepdims=True)                        # [1, 1]
        dwholes.append(c.wholes[r] * kept)
    dv_new = dv_new + jnp.concatenate(d_parts, axis=0)
    dv_low = dv_new.astype(dtype)
    dw, dstarts = [], []
    for r, (s, dend) in enumerate(zip(starts, dends)):
        dw.append(-jax.lax.dot_general(dv_low[c.rows(r)], s.astype(dtype),
                                       _NT, preferred_element_type=F32))
        dstarts.append(c.wholes[r] * dend
                       + jnp.dot(c.only(r, c.qe), do,
                                 preferred_element_type=F32)
                       - jnp.dot(c.only(r, w), dv_low,
                                 preferred_element_type=F32))
    dqe = jnp.concatenate(dqe, axis=0)                       # [R, d_k]
    dke = jnp.concatenate(dke, axis=0)
    dw_low = jnp.concatenate(dw, axis=0).astype(dtype)
    # U = T (beta V), W = T (beta e^gamma K): T's cotangent, then A's (the
    # inverse's own gradient, -T^T dT T^T, on the strict blocks).
    dt = (jax.lax.dot_general(dv_low, c.vb, _NT, preferred_element_type=F32)
          + jax.lax.dot_general(dw_low, c.kw, _NT,
                                preferred_element_type=F32))
    tb_t = tb.T
    dvb = jnp.dot(tb_t, dv_low, preferred_element_type=F32)  # [R, d_v]
    dkw = jnp.dot(tb_t, dw_low, preferred_element_type=F32)  # [R, d_k]
    t_t = t.T
    da = jnp.where(c.strict, -_f32_dot(_f32_dot(t_t, dt), t_t), 0.0)
    dm = da * c.b_col
    # The scores' and A's cotangents into Q K^T, K K^T and the decays.
    dscores = jax.lax.dot_general(do, v_low, _NT, preferred_element_type=F32)
    dqk = (dscores * c.decay).astype(dtype)
    dkk = dm * c.decay
    dkk = (dkk + dkk.T).astype(dtype)
    by_decay = (dm * c.kk + dscores * c.qk) * c.decay
    q32, k32 = c.q.astype(F32), c.k.astype(F32)
    dq = jnp.dot(dqk, c.k, preferred_element_type=F32) + dqe * c.through
    dk = (jnp.dot(dqk.T, c.q, preferred_element_type=F32)
          + jnp.dot(dkk, c.k, preferred_element_type=F32)
          + dke * c.rest + dkw * (c.b_col * c.through))
    # beta: A's rows, V's and W's operands. gamma: every decay (a difference
    # of the row's and the column's: the sums over the lanes and over the
    # sublanes of ONE f32 matrix), e^gamma in W's and Q's operands,
    # e^(gamma_C - gamma) in K's, and the whole decays.
    by_w = jnp.sum(dkw * k32, axis=1, keepdims=True) * c.through
    by_end = jnp.sum(dke * k32, axis=1, keepdims=True) * c.rest
    db = (jnp.sum(da * c.masked, axis=1, keepdims=True) + by_w
          + jnp.sum(dvb * c.v.astype(F32), axis=1, keepdims=True))
    dg = (jnp.sum(by_decay, axis=1, keepdims=True) + by_w * c.b_col
          + jnp.sum(dqe * q32, axis=1, keepdims=True) * c.through - by_end)
    dg_row = _row(dg, c.eye) - jnp.sum(by_decay, axis=0, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, dg_row.shape, 1)
    for r in range(c.per):
        to_end = jnp.sum(jnp.where(_of_head(by_end.shape, 0, c.chunk, r),
                                   by_end, 0.0),
                         axis=0, keepdims=True) + dwholes[r]  # [1, 1]
        dg_row = dg_row + jnp.where(lane == (r + 1) * c.chunk - 1, to_end,
                                    0.0)
    return dq, dk, dvb * c.b_col, dg_row, _row(db, c.eye), dstarts


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, starts_ref, t_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, *, per: int,
                chunk: int, steps: int):
    """The backward of ``_fwd_kernel`` for ``steps`` chunks, the chunks in
    reverse. Refs: the forward's operands, the starting states and ``T`` it
    kept, the cotangent of ``o`` (f32); out: ``dq, dk, dv`` and the rows of
    ``dgamma`` and ``dbeta``; scratch: the heads' states' cotangents."""
    @pl.when(pl.program_id(2) == 0)
    def _from_zero():
        dstate[...] = jnp.zeros_like(dstate)

    inputs = (q_ref, k_ref, v_ref, g_ref, b_ref)
    dends = [dstate[r] for r in range(per)]
    dv = dv_ref.shape[2] // per
    for j in reversed(range(steps)):
        c = _Chunk(inputs, j, per, chunk)
        at = slice(j * chunk, (j + 1) * chunk)
        dq, dk, dvv, dg_row, db_row, dends = _bwd_chunk(
            c, _expand(t_ref[0, 0, j], per, chunk),
            [starts_ref[0, 0, j, r] for r in range(per)], dends,
            _stack(do_ref[0, at], per).astype(c.dtype))
        dq_ref[0, at] = sum(dq[c.rows(r)] for r in range(per)).astype(
            dq_ref.dtype)
        dk_ref[0, at] = sum(dk[c.rows(r)] for r in range(per)).astype(
            dk_ref.dtype)
        for r in range(per):
            dv_ref[0, at, r * dv:(r + 1) * dv] = dvv[c.rows(r)].astype(
                dv_ref.dtype)
        dg_ref[0, 0, j] = dg_row
        db_ref[0, 0, j] = db_row
    for r, d in enumerate(dends):
        dstate[r] = d


def _steps(chunks: int) -> int:
    """Chunks a grid step: four where they divide the sequence's, else two,
    else one. More than one lets a step's independent chains of small
    products (the inverses; a chunk's backward beside the next one's) overlap
    and spreads a step's fixed cost."""
    return next(n for n in (4, 2, 1) if chunks % n == 0)


def _specs(seq, dk, per, dv, chunk, reverse: bool):
    """BlockSpecs of a grid ``(batch, key heads, blocks of chunks)`` by
    operand kind; the backward walks the blocks from the last."""
    steps = _steps(seq // chunk)
    blocks, rows = seq // (chunk * steps), per * chunk
    at = (lambda ic: blocks - 1 - ic) if reverse else (lambda ic: ic)
    return dict(
        keys=pl.BlockSpec((1, steps * chunk, dk),            # [b, s, H_k d_k]
                          lambda ib, ih, ic: (ib, at(ic), ih)),
        values=pl.BlockSpec((1, steps * chunk, per * dv),    # [b, s, H_v d_v]
                            lambda ib, ih, ic: (ib, at(ic), ih)),
        row=pl.BlockSpec((1, 1, steps, 1, rows),          # [b, H_k, n, 1, R]
                         lambda ib, ih, ic: (ib, ih, at(ic), 0, 0)),
        starts=pl.BlockSpec((1, 1, steps, per, dk, dv),
                            lambda ib, ih, ic: (ib, ih, at(ic), 0, 0, 0)),
        t=pl.BlockSpec((1, 1, steps, chunk, rows),        # [b, H_k, n, C, R]
                       lambda ib, ih, ic: (ib, ih, at(ic), 0, 0)))


_OPERAND_SPECS = ("keys", "keys", "values", "row", "row")


def _sizes(q, v, g_rows, chunk):
    """``(batch, seq, key heads, d_k, per, d_v)`` of the kernels' operands."""
    batch, seq, _ = q.shape
    hk, rows = g_rows.shape[1], g_rows.shape[-1]
    per = rows // chunk
    return batch, seq, hk, q.shape[2] // hk, per, v.shape[2] // (hk * per)


def _forward(q, k, v, g_rows, b_rows, *, chunk, interpret, keep):
    """``o [b, s, H_v d_v]`` float32 and, for a backward, the state each
    chunk starts from (``[b, H_k, n, per, d_k, d_v]`` float32) and each
    chunk's ``T`` (``[b, H_k, n, C, per C]`` float32, compacted)."""
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, hk, dk, per, dv = _sizes(q, v, g_rows, chunk)
    nc = seq // chunk
    spec = _specs(seq, dk, per, dv, chunk, reverse=False)
    outs = [(spec["values"], jax.ShapeDtypeStruct(v.shape, F32))]
    if keep:
        outs += [(spec["starts"], jax.ShapeDtypeStruct(
                     (batch, hk, nc, per, dk, dv), F32)),
                 (spec["t"], jax.ShapeDtypeStruct(
                     (batch, hk, nc, chunk, per * chunk), F32))]
    o, *saved = pl.pallas_call(
        functools.partial(_fwd_kernel, per=per, chunk=chunk,
                          steps=_steps(nc), keep=keep),
        grid=(batch, hk, nc // _steps(nc)),
        in_specs=[spec[name] for name in _OPERAND_SPECS],
        out_specs=[s for s, _ in outs], out_shape=[s for _, s in outs],
        scratch_shapes=[pltpu.VMEM((per, dk, dv), F32)],
        **_params(interpret))(q, k, v, g_rows, b_rows)
    return o, tuple(saved)


def _backward(q, k, v, g_rows, b_rows, saved, do, *, chunk, interpret):
    """The cotangents of ``q, k, v`` and of the rows of ``gamma`` and
    ``beta``, from ``o``'s and what ``_forward`` kept."""
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, hk, dk, per, dv = _sizes(q, v, g_rows, chunk)
    nc = seq // chunk
    spec = _specs(seq, dk, per, dv, chunk, reverse=True)
    rows = jax.ShapeDtypeStruct(g_rows.shape, F32)
    outs = [(spec["keys"], jax.ShapeDtypeStruct(q.shape, q.dtype)),
            (spec["keys"], jax.ShapeDtypeStruct(k.shape, k.dtype)),
            (spec["values"], jax.ShapeDtypeStruct(v.shape, v.dtype)),
            (spec["row"], rows), (spec["row"], rows)]
    return tuple(pl.pallas_call(
        functools.partial(_bwd_kernel, per=per, chunk=chunk,
                          steps=_steps(nc)),
        grid=(batch, hk, nc // _steps(nc)),
        in_specs=[spec[name] for name in _OPERAND_SPECS]
        + [spec["starts"], spec["t"], spec["values"]],
        out_specs=[s for s, _ in outs], out_shape=[s for _, s in outs],
        scratch_shapes=[pltpu.VMEM((per, dk, dv), F32)],
        **_params(interpret))(q, k, v, g_rows, b_rows, *saved,
                              do.astype(F32)))


@functools.lru_cache(maxsize=None)
def _kernels(chunk: int, interpret: bool):
    """The rule through the kernels, differentiable in ``q, k, v`` and the
    rows of ``gamma`` and ``beta``. The bodies are jitted, so that a step's
    call sites (three layers, each forward and backward) trace and lower
    each once a shape."""
    kw = dict(chunk=chunk, interpret=interpret)

    @jax.jit
    def gated_delta_fwd(*operands):
        return _forward(*operands, keep=False, **kw)[0]

    @jax.jit
    def gated_delta_fwd_keeping(*operands):
        return _forward(*operands, keep=True, **kw)

    @jax.jit
    def gated_delta_bwd(operands, saved, do):
        return _backward(*operands, saved, do, **kw)

    @jax.custom_vjp
    def rule(*operands):
        return gated_delta_fwd(*operands)

    def fwd(*operands):
        o, saved = gated_delta_fwd_keeping(*operands)
        return o, (operands, saved)

    def bwd(res, do):
        operands, saved = res
        return gated_delta_bwd(operands, saved, do)

    rule.defvjp(fwd, bwd)
    return rule


def kernel_path(q_shape, v_shape, chunk: int = CHUNK):
    """How a call of these shapes runs, decided from what can be observed (the
    platform, the test hook, the shapes, the published mesh): ``None`` for the
    plain XLA form, else ``(interpret, mesh, batch axes)`` for the kernels,
    under ``shard_map`` over the batch where a mesh is published."""
    from tpu_trainer.ops.attention import _INTERPRET_ENV, _flash_mesh

    interpret = os.environ.get(_INTERPRET_ENV, "0") == "1"
    if not (interpret or any(d.platform == "tpu" for d in jax.devices())
            ) or not fits(q_shape, v_shape, chunk):
        return None
    mesh = _flash_mesh(None)
    if mesh is None:
        return interpret, None, None
    from tpu_trainer.parallel.mesh import attention_shard_spec

    # The rule is independent across the batch and nothing else; a mesh
    # whose batch axes do not divide it gets no un-sharded kernel by accident.
    b_spec, _ = attention_shard_spec(mesh, q_shape[0], 1, 1)
    return None if b_spec is None else (interpret, mesh, b_spec)


def _kernel_rule(q, k, v, gamma, beta, chunk, path):
    """The kernels on whole chunks: ``q, k [b, s, H_k, d_k]`` and ``v [b, s,
    H_v, d_v]`` in the compute type, ``gamma`` (the in-chunk cumulative sum
    of ``g``) and ``beta [b, s, H_v]`` float32; ``o [b, s, H_v, d_v]``
    float32."""
    batch, seq, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    per, nc = hv // hk, seq // chunk

    def rows(x):                            # [b, s, H_v] -> [b, H_k, n, 1, R]
        x = jnp.transpose(x.reshape(batch, nc, chunk, hk, per),
                          (0, 3, 1, 4, 2))
        return x.reshape(batch, hk, nc, 1, per * chunk)

    operands = (q.reshape(batch, seq, hk * dk), k.reshape(batch, seq, hk * dk),
                v.reshape(batch, seq, hv * dv), rows(gamma), rows(beta))
    interpret, mesh, b_spec = path
    rule = _kernels(chunk, interpret)
    if mesh is None:
        o = rule(*operands)
    else:
        from jax.sharding import PartitionSpec as P

        from tpu_trainer.parallel.context import kernel_manual_axes
        from tpu_trainer.utils.jax_compat import shard_map

        lanes, rows5 = P(b_spec, None, None), P(b_spec, None, None, None, None)
        o = shard_map(
            rule, mesh=mesh, in_specs=(lanes,) * 3 + (rows5,) * 2,
            out_specs=lanes, axis_names=kernel_manual_axes(mesh, set(b_spec)),
            check_vma=False)(*operands)
    return o.reshape(batch, seq, hv, dv)


def gated_delta(q, k, v, g, beta, *, chunk: int = CHUNK, dtype=None):
    """The gated delta rule over a sequence, from a zero state.

    ``q``, ``k`` ``[batch, seq, H_k, d_k]`` (as the rule reads them: a
    Gated DeltaNet normalises and scales them first); ``v [batch, seq, H_v,
    d_v]`` with ``H_v`` a multiple of ``H_k``; ``g`` (the log decay, <= 0)
    and ``beta`` ``[batch, seq, H_v]``. ``dtype``: the matmul operands' type
    (``q``'s if not given); decays, ``T`` and the state are float32, every
    accumulation is. Returns ``(o [batch, seq, H_v, d_v] in float32, the
    most negative in-chunk cumulative g of the call)``. A sequence that is
    not a whole number of chunks is padded with steps that change nothing
    (``g = 0``, ``beta = 0``). Through the kernels where ``kernel_path``
    takes the shapes, else ``_chunked``."""
    dtype = jnp.dtype(q.dtype if dtype is None else dtype)
    with jax.named_scope("gated_delta"):
        seq = q.shape[1]
        pad = -seq % chunk
        if pad:
            q, k, v, g, beta = (
                jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
                for x in (q, k, v, g, beta))
        path = kernel_path(q.shape, v.shape, chunk)
        if path is None:
            o, low = _chunked(q, k, v, g, beta, chunk, dtype)
        else:
            batch, padded, hv = g.shape
            cum = jnp.cumsum(g.astype(F32).reshape(
                batch, padded // chunk, chunk, hv), axis=2)
            low = jnp.min(cum)
            o = _kernel_rule(q.astype(dtype), k.astype(dtype), v.astype(dtype),
                             cum.reshape(g.shape), beta.astype(F32), chunk,
                             path)
        return o[:, :seq], low
