"""Mixture-of-Experts feed-forward with expert parallelism.

Beyond-reference capability (the reference has a single dense model family;
SURVEY.md §2 lists EP as absent): a Switch-Transformer-style top-1 routed
MoE that drops into the TransformerBlock in place of the dense SwiGLU when
``GPTConfig.num_experts > 0``.

TPU-native shape: experts are *stacked* (``[E, ...]`` parameter leaves, like
the layer stack). Token routing has two interchangeable implementations
(``GPTConfig.moe_dispatch``; identical semantics pinned by oracle tests):

- **gather** (default off expert-parallel meshes, round 4): each expert
  slot gathers its token's row and each token gathers its k expert
  outputs back — O(T*k) integer bookkeeping plus two [E*C, H]-volume
  gathers (AD transposes to the matching scatter-adds). Measured: the
  one-hot alternative burned 30% of the MoE step multiplying by zeros.
- **einsum** (default under expert parallelism): dispatch/combine as
  one-hot matmuls — the MXU does the routing, and sharding the expert
  leaves over the ``expert`` mesh axis makes GSPMD emit the token
  all-to-all between data-sharded tokens and expert-sharded FFNs
  automatically (a lowering the gather formulation does not offer it).

No collective appears in this file either way.

Mechanics (Switch Transformer, arXiv:2101.03961; top-2 per GShard/ST-MoE):

- router: ``logits [T, E]`` in f32; top-k experts per token
  (``moe_top_k``: 1 = Switch, gate = router prob; 2 = GShard, gates
  renormalized over the chosen pair).
- capacity ``C = ceil(k*T/E * capacity_factor)``; per-expert positions
  come from a cumsum over the one-hot assignments in *choice-major* order
  (every token's first choice queues before any second choice — at
  capacity, second choices drop first); tokens beyond capacity are
  dropped (contribute zero, like the papers).
- aux losses, returned PRE-WEIGHTED as one scalar the model adds
  directly: ``moe_aux_weight * (E * sum_e f_e * p_e)`` (load balance,
  over first-choice assignment fractions) plus ``router_z_weight *
  mean(logsumexp(logits)^2)`` (ST-MoE z-loss, arXiv:2202.08906 — keeps
  router logits from drifting into softmax saturation).

``GPTConfig.moe_impl="dropless"`` replaces the capacity machinery above
with MegaBlocks-style token-dropless routing (arXiv:2211.15841): the
``T*k`` token-choice rows are permuted into expert order (one stable
argsort), per-expert group sizes come from a bincount of the routing
(no capacity ``C`` exists, so ``drop_frac`` is 0 by construction), and
all three SwiGLU projections run as grouped matmuls
(``ops/grouped_matmul.gmm``) whose compute scales with the tokens each
expert actually received. The inverse permutation gathers rows back and
the top-k gates weight the combine. Router, gates, and aux/z losses are
shared with the capacity path; telemetry reports the TRUE post-routing
load (the bincount) rather than pre-capacity first-choice fractions,
plus a ``max_group_frac`` collapse indicator.

A layer that holds a share of the experts (``GPTConfig.moe_experts_held``:
one chip of an expert-parallel deployment) sizes every row buffer of the
dropless path by that share: ``R`` rows, twice what an even load sends it,
as the exchange would size its receive buffer (``_receive_rows``). The sort
stays over the ``k*T`` integers; rows are gathered, multiplied, activated
and brought back over ``[R, .]`` buffers. A pass in which more than ``R``
rows chose a held expert goes through ALL the sorted rows instead, a chunk
at a time (``lax.cond``, counted as ``moe_overflow_passes``), so no row is
dropped at any load and the result is the same either way; only the bounded
path runs the Pallas kernels, the overflow the compiler's own ragged dots.
An expert is the gated three-matrix FFN ``down(act(gate x) * up x)`` or,
with ``GPTConfig.ffn_kind = "relu2"``, the two-matrix ``down(relu(up x)^2)``:
every formulation takes the weights as they come (``_split``, ``_mid``), two
``gmm`` forward and two ``gmm`` + two ``tgmm`` backward where the gated one
has three of each.
Holding half the experts or more, or all of them as every uniform model
does, gives ``R == k*T``: one formulation over all rows, no ``cond``.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_trainer.models.config import GPTConfig
from tpu_trainer.ops.dropout import residual_dropout
from tpu_trainer.ops.grouped_matmul import gmm, gmm_reference, tgmm
from tpu_trainer.utils import telemetry


# --- all-gather dispatch/combine (custom_vjp) ------------------------------
#
# The dispatch map is a BIJECTION between kept token-choices (t, j) and
# expert slots s (dropped choices hit a trailing trash slot / trash row on
# either side). AD's transpose of a row gather is a row scatter-add, and on
# v5e those scatter-adds measured ~45 GB/s (row-serial read-modify-write at
# sub-sublane granularity) against ~420 GB/s for the matching gathers —
# 14.1 ms of the 179 ms top-2 step (round-5 xplane). The bijection lets
# every transpose be re-expressed as a gather through the INVERSE map, so
# both passes of both movements run at gather speed:
#
#   dispatch fwd:  expert_in[s] = x[token(s)]                 (gather)
#   dispatch bwd:  dx[t]        = sum_j d_ein[slot(t, j)]     (k gathers)
#   combine  fwd:  out[t]       = sum_j g[t,j] * eo[slot(t,j)] (k gathers)
#   combine  bwd:  d_eo[s]      = g[tc(s)] * dout[token(s)]    (one gather)
#                  d_g[t,j]     = <dout[t], eo[slot(t,j)]>     (k gathers)
#
# ``slot_token`` maps slot -> source token (trash slots -> T, the zero pad
# row); ``flat_ids`` maps (t, j) -> slot (dropped -> S, the zero pad row);
# ``slot_tc`` maps slot -> flat token-choice in CHOICE-MAJOR order
# (j*T + t; trash -> k*T). Choice-major is load-bearing twice: the k
# per-choice gathers address clean [T, H] panels (token-major produced a
# [T, k, H] intermediate whose T(2,128) tile layout cost ~2 ms/step of
# relayout), and the combine backward's gate-scaled rows concatenate as
# ``[dout * g_0; dout * g_1; ...]`` — a [k*T, H] buffer in natural layout,
# so d_eo is ONE row gather instead of a row gather times a 1-D gate
# gather (1-D gathers run element-serial on TPU; measured ~1 ms/step).


@jax.custom_vjp
def _dispatch_rows(x, slot_token, flat_ids):
    """Gather token rows into expert slots: ``x [T, H] -> [S, H]``."""
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)], axis=0)
    return x_pad[slot_token]


def _dispatch_rows_fwd(x, slot_token, flat_ids):
    return _dispatch_rows(x, slot_token, flat_ids), flat_ids


def _dispatch_rows_bwd(flat_ids, d_ein):
    d_pad = jnp.concatenate(
        [d_ein, jnp.zeros((1, d_ein.shape[1]), d_ein.dtype)], axis=0
    )
    dx = d_pad[flat_ids[:, 0]]
    for j in range(1, flat_ids.shape[1]):
        dx = dx + d_pad[flat_ids[:, j]]
    return dx, None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(eo, gates, flat_ids, slot_tc):
    """Weighted gather-back: ``out[t] = sum_j gates[t, j] * eo[slot(t, j)]``.

    ``eo [S, H]`` expert outputs, ``gates [T, k]`` f32, ``slot_tc [S]`` the
    inverse map in CHOICE-MAJOR order (slot -> j*T + t; trash -> k*T) used
    only by the backward.
    """
    eo_pad = jnp.concatenate(
        [eo, jnp.zeros((1, eo.shape[1]), eo.dtype)], axis=0
    )
    out = None
    for j in range(flat_ids.shape[1]):
        contrib = eo_pad[flat_ids[:, j]] * gates[:, j:j + 1].astype(eo.dtype)
        out = contrib if out is None else out + contrib
    return out


def _combine_rows_fwd(eo, gates, flat_ids, slot_tc):
    return _combine_rows(eo, gates, flat_ids, slot_tc), (
        eo, gates, flat_ids, slot_tc
    )


def _combine_rows_bwd(res, dout):
    eo, gates, flat_ids, slot_tc = res
    T, k = flat_ids.shape
    H = eo.shape[1]
    # Pre-scale dout by each choice's gate and stack choice-major: row
    # j*T + t = dout[t] * gates[t, j]. One clean-layout buffer, one row
    # gather through the inverse map; the trailing zero row absorbs trash
    # slots (slot_tc = k*T).
    dout_scaled = jnp.concatenate(
        [dout * gates[:, j:j + 1].astype(dout.dtype) for j in range(k)]
        + [jnp.zeros((1, H), dout.dtype)],
        axis=0,
    )
    d_eo = dout_scaled[slot_tc]
    eo_pad = jnp.concatenate(
        [eo, jnp.zeros((1, H), eo.dtype)], axis=0
    )
    d_gates = jnp.stack(
        [jnp.sum((eo_pad[flat_ids[:, j]] * dout).astype(jnp.float32), axis=-1)
         for j in range(k)],
        axis=1,
    ).astype(gates.dtype)
    return d_eo, d_gates, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


# --- dropless row movement (custom_vjp) ----------------------------------------
#
# The sort is a PERMUTATION of the T*k token-choice rows, so the transpose of
# each gather is a gather through the inverse permutation. AD does not know
# that and emits row scatter-adds (see the measurements above: an order of
# magnitude slower than the matching gathers on the chip).


@jax.custom_vjp
def _sorted_rows(x, perm, inv_perm, mine):
    """Token rows into sorted order: ``x [T, H] -> [k*T, H]``, row ``r`` is
    token ``perm[r] % T`` (token-choices are numbered CHOICE-MAJOR, ``j*T +
    t``, so that ``[k*T, H] <-> [k, T, H]`` splits the leading dim: the
    token-major ``[T, k, H]`` costs a relayout of the whole buffer on the
    chip). ``mine [k, T]`` marks the choices whose expert lives here; the
    gradient of the others' rows is left out (those rows are past every
    group: what a grouped matmul returns for them is not a result)."""
    return x[perm % x.shape[0]]


def _sorted_rows_fwd(x, perm, inv_perm, mine):
    return _sorted_rows(x, perm, inv_perm, mine), (inv_perm, mine)


def _sorted_rows_bwd(res, g):
    inv_perm, mine = res
    rows = g[inv_perm].reshape(*mine.shape, g.shape[-1])        # [k, T, H]
    dx = jnp.sum(jnp.where(mine[..., None], rows, 0), axis=0)
    return dx, None, None, None


_sorted_rows.defvjp(_sorted_rows_fwd, _sorted_rows_bwd)


@jax.custom_vjp
def _unsorted_rows(y, perm, inv_perm):
    """Sorted rows back into token-choice order: ``y[inv_perm]``."""
    return y[inv_perm]


def _unsorted_rows_fwd(y, perm, inv_perm):
    return y[inv_perm], perm


def _unsorted_rows_bwd(perm, g):
    return g[perm], None, None


_unsorted_rows.defvjp(_unsorted_rows_fwd, _unsorted_rows_bwd)


# --- the dropless expert FFN, and its row buffers ---------------------------
#
# A layer that holds ``held`` of ``E`` experts receives, at an even load,
# ``k*T * held / E`` of the ``k*T`` token-choice rows. Its sorted buffers are
# sized as an expert-parallel exchange sizes its receive buffer: twice that
# share. Whatever the load, no row is dropped: a pass in which more rows
# chose a held expert visits them all, a buffer's worth at a time
# (``lax.cond`` in ``_bounded_ffn``).

_RECEIVE_OVER_EVEN_SHARE = 2


def _receive_rows(choices: int, held: int, experts: int) -> int:
    """Rows of the sorted buffers, ``R``: twice the even share of the
    ``choices`` (``k*T``) rows, a multiple of the row tile ``gmm`` takes at
    that size (``ops/grouped_matmul._resolve_opts``: 512 from 4,096 rows on,
    128 below), at most all of them. Holding half the experts or more gives
    ``choices``: one formulation, the worst case."""
    share = _RECEIVE_OVER_EVEN_SHARE * choices * held / experts
    tile = 512 if share >= 4096 else 128
    return min(choices, tile * math.ceil(share / tile))


class _FFN(NamedTuple):
    """What is static about one expert FFN (hashable: it rides the
    ``custom_vjp`` as a non-differentiable argument)."""

    act: Callable
    use_kernel: Optional[bool]
    subset: bool        # a share of the experts is held
    rows: int           # R, the receive bound on the sorted buffers
    # The grouped matmuls as one ``lax.ragged_dot`` each (the overflow).
    plain: bool = False


def _relu2(x):
    return jnp.square(nn.relu(x))


def _mid(how: _FFN, pre):
    """What the down projection reads, from the first projections' results:
    ``act(gate) * up`` of a gated FFN's two, ``act(up)`` of an ungated one's
    (``GPTConfig.ffn_kind``)."""
    return how.act(pre[0]) * pre[1] if len(pre) == 2 else how.act(pre[0])


def _split(args):
    """``(xt, gates, weights, (perm, inv_perm, mine, counts))`` of an expert
    FFN's arguments: the weights are the three matrices of a gated FFN or
    the two of an ungated one, the last of them the down projection."""
    return args[0], args[1], args[2:-4], args[-4:]


def _worst_case_ffn(how: _FFN, *args):
    """The expert FFN over ALL ``k*T`` sorted rows in one set of buffers:
    what a layer runs whose receive bound is ``k*T`` (every expert held, or
    half of them and more). ``args`` as ``_split`` reads them. The grouped
    matmuls' schedule skips the rows past ``sum(counts)``, the row movement
    and the elementwise passes do not."""
    xt, gates, weights, (perm, inv_perm, mine, counts) = _split(args)
    k, T = mine.shape
    with jax.named_scope("route"):
        grouped_in = _sorted_rows(xt, perm, inv_perm, mine)     # [k*T, H]

    def grouped(lhs, w):
        return gmm(lhs, w, counts, use_kernel=how.use_kernel)

    with jax.named_scope("experts"):
        mid = _mid(how, [grouped(grouped_in, w) for w in weights[:-1]])
        grouped_out = grouped(mid, weights[-1])                 # [k*T, H]
    with jax.named_scope("route"):
        rows = _unsorted_rows(grouped_out, perm, inv_perm).reshape(k, T, -1)
        weighted = rows * gates.T[..., None].astype(xt.dtype)
        if how.subset:
            weighted = jnp.where(mine[..., None], weighted, 0)
        return jnp.sum(weighted, axis=0)


def _chunk(how: _FFN, perm, inv_perm, mine, counts, chunk):
    """Sorted rows ``[chunk*R, (chunk+1)*R)`` as one ``[R, .]`` buffer sees
    them: the token-choice of each row (``[R]``, choice-major ``j*T + t``),
    the group sizes inside the chunk, and for each token-choice its row in
    the chunk with whether it is there at all (``[k, T]`` both). ``chunk``
    is 0 on the bounded path (its rows start where the whole sort's do, so
    its ``gmm`` tiles are the worst case's) and a loop's index past it."""
    rows, start = how.rows, chunk * how.rows
    ends = jnp.cumsum(counts)
    sizes = (jnp.clip(ends - start, 0, rows)
             - jnp.clip(ends - counts - start, 0, rows))
    # The last chunk may pass the end of the sort: rows of no group.
    first = jax.lax.dynamic_slice(
        jnp.pad(perm, (0, -perm.shape[0] % rows)), (start,), (rows,))
    local = inv_perm.reshape(mine.shape) - start
    here = mine & (local >= 0) & (local < rows)
    return first, sizes, jnp.clip(local, 0, rows - 1), here


def _to_tokens(source, slots, here, scale=None):
    """The way back from an ``[R, H]`` buffer of sorted rows to the tokens:
    ``sum_j source[slots[j]] (* scale[j])`` over the choices that are
    ``here``, ``[T, H]`` in f32. The token side still has ``k*T`` choices,
    but their source is the small buffer and each choice is its own
    ``[T, H]`` gather, so nothing of ``k*T`` rows is written: on the chip
    the k gathers and the masked sum took 0.93 ms where one scatter-add of
    the ``R`` gate-scaled rows into ``[T, H]`` took 2.57 in f32 and 1.93 in
    bf16 (PERF.md, PR 29)."""
    out = 0.0
    for j in range(slots.shape[0]):
        rows = source[slots[j]]
        if scale is not None:
            rows = rows * scale[j][:, None].astype(rows.dtype)
        out = out + jnp.where(here[j][:, None], rows, 0).astype(jnp.float32)
    return out


def _bounded_forward(how: _FFN, *args_and_chunk):
    """The FFN over one chunk of ``how.rows`` sorted rows. With
    ``sum(counts) <= R`` chunk 0 holds every row that chose a held expert,
    in the worst case's order, so the groups and the ``gmm`` tiling over
    them are the worst case's. Returns the chunk's part of the layer's sum
    (f32) and what the backward needs of the ``[R, .]`` intermediates."""
    *args, chunk = args_and_chunk
    xt, gates, weights, (perm, inv_perm, mine, counts) = _split(args)
    first, sizes, slots, here = _chunk(
        how, perm, inv_perm, mine, counts, chunk)

    def grouped(lhs, w):
        if how.plain:
            return gmm_reference(lhs, w, sizes)
        return gmm(lhs, w, sizes, use_kernel=how.use_kernel)

    with jax.named_scope("route"):
        grouped_in = xt[first % xt.shape[0]]                    # [R, H]
    with jax.named_scope("experts"):
        pre = tuple(grouped(grouped_in, w) for w in weights[:-1])
        grouped_out = grouped(_mid(how, pre), weights[-1])
    with jax.named_scope("route"):
        out = _to_tokens(grouped_out, slots, here, gates.T)
    return out, (*pre, grouped_out)


def _bounded_backward(how: _FFN, args, saved, g, chunk):
    """Transpose of ``_bounded_forward``, with every row buffer ``[R, .]``:
    the gate-scaled output gradient is gathered in sorted order (``R`` rows
    of ``g``), the grouped matmuls' transposes are ``gmm`` against the
    transposed weights and ``tgmm``, and the way back to the tokens is the
    forward's. Every gradient in f32: the caller rounds them."""
    xt, gates, weights, (perm, inv_perm, mine, counts) = _split(args)
    *pre, grouped_out = saved
    k, T = mine.shape
    first, sizes, slots, here = _chunk(
        how, perm, inv_perm, mine, counts, chunk)
    kernel = dict(use_kernel=how.use_kernel)

    def dgrad(dout, w):
        return gmm(dout, jnp.swapaxes(w, 1, 2), sizes, **kernel)

    with jax.named_scope("route"):
        tok = first % T
        grouped_in = xt[tok]            # gathered again: cheaper than kept
        g_rows = g[tok].astype(jnp.float32)                     # [R, H]
        scale = gates.T.reshape(-1)[first]                      # [R]
        # Past the chunk's groups grouped_out is zero, so the gate's
        # gradient is; d_out is not, and no grouped matmul reads it there.
        d_scale = jnp.sum(g_rows * grouped_out.astype(jnp.float32), axis=-1)
        d_out = (g_rows * scale[:, None]).astype(grouped_out.dtype)
    with jax.named_scope("experts"):
        mid, act_vjp = jax.vjp(lambda *p: _mid(how, p), *pre)
        d_pre = act_vjp(dgrad(d_out, weights[-1]))
        d_in = functools.reduce(
            jnp.add, [dgrad(d, w) for d, w in zip(d_pre, weights)])
        d_weights = (*[tgmm(grouped_in, d, sizes, **kernel) for d in d_pre],
                     tgmm(mid, d_out, sizes, **kernel))
    with jax.named_scope("route"):
        dx = _to_tokens(d_in, slots, here)
        d_gates = jnp.zeros((k * T,), jnp.float32).at[first].add(
            d_scale).reshape(k, T).T
    return (dx, d_gates, *d_weights)


def _sum_over_chunks(how: _FFN, choices: int, chunk_fn, like):
    """``sum(chunk_fn(c))`` over every chunk of the ``choices`` sorted rows
    (f32 trees shaped as ``like``), one chunk's buffers at a time."""
    zeros = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), like)
    return jax.lax.fori_loop(
        0, -(-choices // how.rows),
        lambda c, acc: jax.tree_util.tree_map(jnp.add, acc, chunk_fn(c)),
        zeros)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bounded_ffn(how: _FFN, *args):
    """The expert FFN with row buffers of ``how.rows`` rows; ``args`` as
    ``_worst_case_ffn`` takes them. ``sum(counts) <= R``: one pass over the
    first ``R`` sorted rows (``_bounded_forward`` / ``_bounded_backward``).
    More: every chunk of the sorted rows in turn (``_overflow_part``), which
    visits all ``k*T`` rows, so the result is exact either way. One
    ``custom_vjp`` round both so that what lives between the passes is the
    bounded path's ``[R, .]`` intermediates and nothing of the overflow (AD
    of ``lax.cond`` keeps both branches' residuals and copies every operand
    a branch saved): the backward's overflow branch starts again from the
    layer's inputs."""
    return _bounded_ffn_fwd(how, *args)[0]


def _plain(how: _FFN) -> _FFN:
    """The overflow's side of ``how``. A branch costs its tracing, lowering,
    compiling and its room in the step's memory plan whether or not it ever
    runs, and in the cell this one never does: the worst-case formulation
    there kept the plan rematerialising elsewhere (23 ms a step), the Pallas
    kernels there added 10 s to 60 s of set-up (PERF.md, PR 29). So: a
    quarter of ``R`` rows a chunk, and each grouped matmul one
    ``lax.ragged_dot``, which plain AD differentiates."""
    return how._replace(plain=True, rows=max(1, how.rows // 4))


def _overflow_part(how: _FFN, floats, ints, chunk):
    """One chunk's part of the layer's sum (f32) on the overflow's side."""
    return _bounded_forward(_plain(how), *floats, *ints, chunk)[0]


def _bounded_ffn_fwd(how: _FFN, *args):
    xt, *_, mine, counts = args

    def bounded():
        out, saved = _bounded_forward(how, *args, 0)
        return out.astype(xt.dtype), saved

    def overflow():
        out = _sum_over_chunks(
            _plain(how), mine.size,
            lambda c: _overflow_part(how, args[:-4], args[-4:], c), xt)
        saved = jax.eval_shape(bounded)[1]
        return out.astype(xt.dtype), tuple(
            jnp.zeros(s.shape, s.dtype) for s in saved)

    out, saved = jax.lax.cond(
        jnp.sum(counts) <= how.rows, bounded, overflow)
    return out, (args, saved)


def _bounded_ffn_bwd(how: _FFN, res, g):
    args, saved = res
    floats, ints = args[:-4], args[-4:]     # ints: perm ... mine, counts
    *_, mine, counts = ints

    def chunk_grads(c):
        _, vjp = jax.vjp(
            lambda *fl: _overflow_part(how, fl, ints, c), *floats)
        return tuple(d.astype(jnp.float32)
                     for d in vjp(g.astype(jnp.float32)))

    grads = jax.lax.cond(
        jnp.sum(counts) <= how.rows,
        lambda: _bounded_backward(how, args, saved, g, 0),
        lambda: _sum_over_chunks(_plain(how), mine.size, chunk_grads, floats))
    return (*(d.astype(x.dtype) for d, x in zip(grads, floats)),
            *[None] * len(ints))


_bounded_ffn.defvjp(_bounded_ffn_fwd, _bounded_ffn_bwd)


# The router's Dense. Its module computes in f32 from an f32 kernel, and says
# so here for whoever keeps a compute-dtype copy of the parameters.
ROUTER = "router"


def computed_in_f32(path_keys) -> bool:
    """Whether a parameter leaf (the keys of its path) is one an expert
    layer consumes in float32: a rounded copy of it would make tokens near
    a tie choose other experts than the same parameters choose elsewhere."""
    return ROUTER in path_keys


class SharedExpert(nn.Module):
    """The shared experts beside the routed ones: one FFN of the experts'
    kind (``GPTConfig.ffn_kind``) of width ``shared_expert_width``
    (``moe_shared_experts x expert_width`` unless stated) over every token,
    no routing."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=cfg.compute_dtype,
            param_dtype=cfg.params_dtype,
            kernel_init=nn.initializers.normal(cfg.initializer_range))
        width = cfg.shared_expert_width
        if cfg.ffn_kind == "relu2":
            mid = _relu2(dense(width, name="up_proj")(x))
        else:
            act = {"silu": nn.silu, "gelu": nn.gelu}[cfg.activation]
            mid = act(dense(width, name="gate_proj")(x)) * dense(
                width, name="up_proj")(x)
        return dense(cfg.hidden_size, name="down_proj")(mid)


class MoEMLP(nn.Module):
    """Top-k routed expert SwiGLU (replaces ``MLP`` when experts are on)."""

    config: GPTConfig

    @nn.compact
    def __call__(
        self, x: jax.Array, deterministic: bool = True
    ) -> Tuple[jax.Array, jax.Array]:
        cfg = self.config
        E = cfg.num_experts
        k = cfg.moe_top_k
        b, s, H = x.shape
        T = b * s
        I = cfg.expert_width
        held = cfg.experts_held[1]

        xt = x.reshape(T, H)

        with jax.named_scope("route"):
            # Router in f32 (standard for stability).
            router_logits = nn.Dense(
                E, use_bias=False, dtype=jnp.float32, param_dtype=jnp.float32,
                kernel_init=nn.initializers.normal(cfg.initializer_range),
                name=ROUTER,
            )(xt.astype(jnp.float32))
            if cfg.moe_router == "sigmoid":
                # Scores are independent sigmoids; the bias only SELECTS
                # (a buffer: no gradient reaches it, and with no published
                # update rule it stays at its zeros); the chosen scores are
                # normalised over the choice.
                scores = jax.nn.sigmoid(router_logits)
                bias = jax.lax.stop_gradient(self.param(
                    "expert_bias", nn.initializers.zeros, (E,), jnp.float32))
                _, gate_idx = jax.lax.top_k(scores + bias, k)
                gate_vals = jnp.take_along_axis(scores, gate_idx, axis=-1)
                gates = gate_vals / (jnp.sum(gate_vals, axis=-1,
                                             keepdims=True) + cfg.moe_gate_eps)
                if cfg.moe_routed_scale != 1.0:
                    gates = gates * cfg.moe_routed_scale
                probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
            else:
                probs = jax.nn.softmax(router_logits, axis=-1)      # [T, E]
                gate_vals, gate_idx = jax.lax.top_k(probs, k)       # [T, k]
                # Gates: Switch keeps the raw router prob at k=1; at k>1
                # the chosen probs renormalize to sum 1 (GShard/Mixtral).
                gates = gate_vals if k == 1 else (
                    gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
                )
        assign_k = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # [T,k,E]

        # Aux load-balance loss uses pre-capacity FIRST-choice fractions.
        frac = jnp.mean(assign_k[:, 0], axis=0)                 # [E]
        mean_prob = jnp.mean(probs, axis=0)                     # [E]
        aux = jnp.zeros((), jnp.float32)
        if cfg.moe_aux_weight > 0.0:
            aux = cfg.moe_aux_weight * E * jnp.sum(frac * mean_prob)
        if cfg.router_z_weight > 0.0:
            z = jax.nn.logsumexp(router_logits, axis=-1)        # [T]
            aux = aux + cfg.router_z_weight * jnp.mean(z * z)

        dtype = cfg.compute_dtype
        entropy = -jnp.sum(mean_prob * jnp.log(mean_prob + 1e-9))

        def ffn_param(name, shape):
            return self.param(
                name, nn.initializers.normal(cfg.initializer_range), shape,
                cfg.params_dtype,
            ).astype(dtype)

        # Only the experts held here have weights here.
        gated = cfg.ffn_kind != "relu2"
        if gated:
            w_gate = ffn_param("experts_gate", (held, H, I))
        w_up = ffn_param("experts_up", (held, H, I))
        w_down = ffn_param("experts_down", (held, I, H))
        act = ({"silu": nn.silu, "gelu": nn.gelu}[cfg.activation] if gated
               else _relu2)

        if cfg.moe_impl == "dropless":
            out = self._dropless_ffn(
                xt, gate_idx, gates, entropy,
                (w_gate, w_up, w_down) if gated else (w_up, w_down), act,
            ).reshape(b, s, H)
            if cfg.moe_shared_experts:
                out = out + SharedExpert(cfg, name="shared_expert")(x)
            out = residual_dropout(self, out, cfg.dropout, deterministic)
            return out, aux.astype(jnp.float32)

        if T <= 2 * E:
            # Tiny-token regime (single-token KV decode: T = batch): the
            # statistical capacity rule degenerates (C~1 would zero out any
            # token colliding on an expert). Give every token a slot.
            C = T
        else:
            C = max(1, math.ceil(k * T / E * cfg.expert_capacity_factor))

        # Position of each token-choice in its expert's queue, counted in
        # choice-major order (all first choices precede any second choice,
        # so capacity overflow drops second choices first); drop past C.
        assign_flat = assign_k.transpose(1, 0, 2).reshape(k * T, E)
        pos_flat = jnp.cumsum(assign_flat, axis=0) - assign_flat
        pos_k = pos_flat.reshape(k, T, E).transpose(1, 0, 2)    # [T, k, E]
        keep_k = (pos_k < C).astype(jnp.float32) * assign_k
        pos_idx = jnp.sum(pos_k * assign_k, axis=-1).astype(jnp.int32)

        kept = jnp.sum(keep_k, axis=-1) > 0                     # [T, k]
        if telemetry.capturing():
            # Router health (Switch-Transformer diagnostics), popped by the
            # enclosing TransformerBlock into its per-layer telemetry:
            # first-choice load fractions (sum to 1 by construction),
            # entropy of the mean routing distribution (log E when the
            # router is uniform, 0 when it collapses onto one expert), the
            # fraction of token-choices dropped at capacity, and the
            # heaviest expert's share of KEPT token-choices (collapse
            # shows up here before the drops do). ``dropless`` marks the
            # impl so the analyzer can gate drop_frac > 0 as a bug on
            # dropless runs but expected behavior here.
            kept_counts = jnp.sum(keep_k, axis=(0, 1))          # [E]
            telemetry.record("router", {
                "load": frac,
                "entropy": entropy,
                "drop_frac": 1.0 - jnp.mean(kept.astype(jnp.float32)),
                "max_group_frac": (jnp.max(kept_counts)
                                   / jnp.maximum(jnp.sum(kept_counts), 1.0)),
                "dropless": jnp.zeros((), jnp.float32),
            })
        mode = cfg.moe_dispatch
        if mode == "auto":
            # Trace-time mesh introspection: gathers are far cheaper on a
            # chip, but only the einsum form hands GSPMD a one-hot matmul
            # it can lower to the EP token all-to-all.
            from tpu_trainer.parallel import context as ctx_lib

            mesh = ctx_lib.current_mesh()
            ep = mesh.shape.get("expert", 1) if mesh is not None else 1
            mode = "einsum" if ep > 1 else "gather"
        if mode == "gather":
            # Gather dispatch (round 4, re-formulated round 5): the one-hot
            # dispatch/combine einsums cost 2*T*E*C*H FLOPs EACH — at
            # E=8/capacity 1.25 that is ~129 GF per einsum per layer vs
            # ~145 GF for all three expert FFN einsums combined, and the
            # [T, k, E, C] slot tensor is a ~335 MB f32 buffer. Measured
            # on v5e (xplane): dispatch/combine = 47.4 ms of a 156 ms
            # step (30%). Round 4 replaced them with gathers whose AD
            # transposes were scatter-adds (still 14.1 ms of the 179 ms
            # top-2 step); round 5's custom_vjp pair above re-expresses
            # those transposes as gathers through the inverse slot map.
            # Dropped token-choices route to a trailing trash slot that
            # reads as a zero row — identical semantics to the einsum
            # path (pinned by tests/test_moe.py oracles either way).
            flat_ids = jnp.where(kept, gate_idx * C + pos_idx, E * C)
            # One scatter builds the inverse map: slot -> flat token-choice
            # in choice-major order (j*T + t, trash -> k*T; see the
            # custom_vjp comment for why choice-major).
            tc_vals = (jnp.arange(T, dtype=jnp.int32)[:, None]
                       + T * jnp.arange(k, dtype=jnp.int32)[None, :])
            slot_tc = jnp.full((E * C + 1,), k * T, jnp.int32)
            slot_tc = slot_tc.at[flat_ids.reshape(-1)].set(
                tc_vals.reshape(-1)
            )[:E * C]
            slot_token = jnp.where(slot_tc == k * T, T, slot_tc % T)
            expert_in = _dispatch_rows(
                xt.astype(dtype), slot_token, flat_ids
            ).reshape(E, C, H)
        else:
            # One-hot einsum dispatch (rounds 2-3): the routing rides the
            # MXU, and under expert parallelism GSPMD lowers the einsums
            # to a clean token all-to-all — which is why "auto" selects
            # this form whenever the mesh has a non-trivial expert axis.
            slot = (keep_k[..., None]
                    * jax.nn.one_hot(pos_idx, C,
                                     dtype=jnp.float32)[:, :, None, :])
            dispatch = jnp.sum(slot, axis=1)                    # [T, E, C]
            expert_in = jnp.einsum(
                "tec,th->ech", dispatch.astype(dtype), xt.astype(dtype)
            )  # [E, C, H]

        hmid = jnp.einsum("ech,ehi->eci", expert_in, w_gate)
        hmid = act(hmid) * jnp.einsum("ech,ehi->eci", expert_in, w_up)
        expert_out = jnp.einsum("eci,eih->ech", hmid, w_down)   # [E, C, H]

        if mode == "gather":
            out = _combine_rows(
                expert_out.reshape(E * C, H), gates, flat_ids, slot_tc
            ).reshape(b, s, H)
        else:
            combine = jnp.sum(
                slot * gates[:, :, None, None], axis=1
            )                                                   # [T, E, C]
            out = jnp.einsum(
                "tec,ech->th", combine.astype(dtype), expert_out
            ).reshape(b, s, H)
        out = residual_dropout(self, out, cfg.dropout, deterministic)
        return out, aux.astype(jnp.float32)

    def _dropless_ffn(self, xt, gate_idx, gates, entropy, weights, act):
        """Token-dropless expert FFN over grouped matmuls.

        One stable argsort of the ``T*k`` token-choice rows by expert id
        builds the grouped layout (stability makes the permutation a pure
        function of the routing — exact-resume replays it bit-identically);
        ``bincount`` gives the true per-expert group sizes. Each SwiGLU
        projection is one ``gmm`` whose compute is exactly
        ``sum(counts)`` rows — no capacity padding, no drops. The
        inverse permutation is a second argsort (of the first), and the
        gates weight the per-choice rows back into token order.

        With a share of the experts held (``GPTConfig.moe_experts_held``)
        the choices of experts that live elsewhere sort behind the held
        experts' rows, ``sum(counts)`` is the rows held, and the row
        buffers are sized by the share, not by the worst case: ``R =
        _receive_rows(k*T, held, E)`` rows, twice the even share
        (``_bounded_ffn``). The first ``R`` sorted rows are gathered, the
        three ``gmm`` and the activation see ``[R, .]`` operands, and each
        token gathers its held choices back from the ``[R, H]`` result.
        Every choice of every token may still be a held expert: a pass
        with ``sum(counts) > R`` runs under ``lax.cond`` over all ``k*T``
        sorted rows, a chunk at a time, the same computation with the
        compiler's ragged dots for the kernels. Either way every row that
        chose a held expert goes through its expert, in f32 accumulation:
        none is ever dropped, whatever the imbalance, and no capacity or
        approximation enters; an overflow costs more time than the bound
        saves, and ``moe_overflow_passes`` counts it. With ``held == E``
        (or half of them and more) ``R == k*T`` and ``_worst_case_ffn``
        over all rows is the only formulation traced. What the absent
        experts would add is left out: the result is this chip's part of
        the layer's sum.

        Mesh composition: on a multi-device mesh the jnp twin runs
        (``use_kernel=False``) so GSPMD partitions the ragged dot like any
        other op; the Pallas kernel drives the single-device TPU path. A
        shard_mapped gmm with an explicit EP all-to-all is the planned
        follow-up (ROADMAP item 4).
        """
        cfg = self.config
        k = cfg.moe_top_k
        T = xt.shape[0]
        dtype = cfg.compute_dtype
        first, held = cfg.experts_held
        subset = held < cfg.num_experts

        from tpu_trainer.parallel import context as ctx_lib

        mesh = ctx_lib.current_mesh()
        if subset and mesh is not None and mesh.shape.get("expert", 1) > 1:
            raise NotImplementedError(
                "moe_experts_held names this chip's share of the experts; "
                "an 'expert' mesh axis > 1 would share them a second time")
        use_kernel = False if (mesh is not None and mesh.size > 1) else None

        with jax.named_scope("route"):
            local = gate_idx.astype(jnp.int32) - first
            # A choice of an expert that lives elsewhere goes to a trailing
            # group that is nobody's: its rows sort behind the held
            # experts' rows, no grouped matmul visits them, and the
            # combine leaves them out.
            mine = ((local >= 0) & (local < held)).T            # [k, T]
            flat_expert = (jnp.where(mine, local.T, held) if subset
                           else local.T).reshape(-1)            # [k*T]
            counts = jnp.bincount(flat_expert, length=held + 1)[:held]
            perm = jnp.argsort(flat_expert)                     # stable
            inv_perm = jnp.argsort(perm)

        how = _FFN(act, use_kernel, subset,
                   _receive_rows(k * T, held, cfg.num_experts))
        args = (xt.astype(dtype), gates, *weights,
                perm, inv_perm, mine, counts)
        bounded = how.rows < k * T
        out = (_bounded_ffn if bounded else _worst_case_ffn)(how, *args)

        # Rows the experts here computed (whichever way), the busiest one's
        # share, and whether this pass outgrew the bounded buffers.
        rows_held = jnp.sum(counts).astype(jnp.float32)
        telemetry.count("moe_rows_held", rows_held)
        telemetry.count("moe_max_load", jnp.max(counts).astype(jnp.float32)
                        / jnp.maximum(rows_held, 1.0), reduce="max")
        if bounded:
            telemetry.count("moe_overflow_passes",
                            (rows_held > how.rows).astype(jnp.float32))

        if telemetry.capturing():
            # True post-routing load (the bincount — what each expert
            # actually computed), not pre-capacity first-choice fractions;
            # max_group_frac is the collapse indicator (1/E when balanced,
            # -> 1.0 as the router collapses onto one expert). drop_frac
            # is structurally zero — the analyzer FAILs a dropless run
            # that ever reports otherwise.
            load = counts.astype(jnp.float32) / float(k * T)
            router = {
                "load": load,
                "entropy": entropy,
                "drop_frac": jnp.zeros((), jnp.float32),
                "max_group_frac": jnp.max(load),
                "dropless": jnp.ones((), jnp.float32),
            }
            if telemetry.capturing(deep=True):
                # Which experts each token chose, for whoever compares the
                # routing with a reference's (never a periodic telemetry
                # step: [T, k] integers a layer).
                router["choice"] = gate_idx.astype(jnp.int32)
            telemetry.record("router", router)
        return out
