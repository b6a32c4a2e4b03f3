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

1. inside each chunk, all chunks at once: ``gamma`` the in-chunk cumulative
   sum of ``g``; ``A = strict_lower(diag(beta) (K K^T o exp(gamma_i -
   gamma_j)))``; ``T = (I + A)^-1``; ``U = T diag(beta) V``; ``W = T
   diag(beta exp(gamma)) K``;
2. across the chunks, in order (``lax.scan``, the state in f32):
   ``V_new = U - W S``, then ``S <- exp(gamma_C) S + (K o exp(gamma_C -
   gamma))^T V_new``;
3. the outputs, all chunks at once, from the state each chunk started from:
   ``O = (Q o exp(gamma)) S + lower(Q K^T o exp(gamma_i - gamma_j)) V_new``.

Every decay is ``exp`` of a difference of in-chunk cumulative sums taken
where it is <= 0, so nothing overflows and a steep decay underflows to the
zero it is. ``T`` is exact: ``A`` is strictly lower triangular, so ``(I +
A)^-1 = sum_{j < C} (-A)^j = (I - A)(I + A^2)(I + A^4)...`` with ``C`` the
chunk, a few ``[C, C]`` products in f32 (``_unit_lower_inverse``).

Plain XLA; the backward is autodiff's, but for ``T``, whose gradient is the
inverse's own (``-T^T dT T^T``, from ``T`` alone), so that the series' powers
are not kept for it. A key head serves ``H_v / H_k``
value heads (value head ``j`` reads key head ``j // (H_v / H_k)``): ``K
K^T`` and ``Q K^T`` are computed once a key head, never for a repeated key.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_trainer.ops.ssd import _segment_decay

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


def gated_delta(q, k, v, g, beta, *, chunk: int = 64, dtype=None):
    """The gated delta rule over a sequence, from a zero state.

    ``q``, ``k`` ``[batch, seq, H_k, d_k]`` (as the rule reads them: a
    Gated DeltaNet normalises and scales them first); ``v [batch, seq, H_v,
    d_v]`` with ``H_v`` a multiple of ``H_k``; ``g`` (the log decay, <= 0)
    and ``beta`` ``[batch, seq, H_v]``. ``dtype``: the matmul operands' type
    (``q``'s if not given); decays, ``T`` and the state are float32, every
    accumulation is. Returns ``(o [batch, seq, H_v, d_v] in float32, the
    most negative in-chunk cumulative g of the call)``. A sequence that is
    not a whole number of chunks is padded with steps that change nothing
    (``g = 0``, ``beta = 0``)."""
    dtype = jnp.dtype(q.dtype if dtype is None else dtype)
    with jax.named_scope("gated_delta"):
        seq = q.shape[1]
        pad = -seq % chunk
        if pad:
            q, k, v, g, beta = (
                jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
                for x in (q, k, v, g, beta))
        o, low = _chunked(q, k, v, g, beta, chunk, dtype)
        return o[:, :seq], low
