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

The backward is autodiff through the same decomposition. Its residuals (the
in-chunk ``[Q, Q]`` scores and decays, 64 x the size of ``x``) are the
caller's to keep or to recompute: ``models/gpt.py`` ``Mamba2Mixer``
recomputes the scan with the vector work round it (``jax.checkpoint``), so
that they never live between the passes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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


def _ssd(x, dt, a, b, c, chunk, dtype):
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
