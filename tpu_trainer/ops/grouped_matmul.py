"""Grouped (ragged) matmul for dropless MoE — Pallas kernel + jnp twin.

``gmm(lhs [G, H], rhs [E, H, N], group_sizes [E]) -> [G, N]`` multiplies
each contiguous row-group of ``lhs`` by its own expert weight block:
rows ``[offsets[e], offsets[e+1])`` (``offsets = cumsum(group_sizes)``)
hit ``rhs[e]``. This is the MegaBlocks grouped-GEMM primitive (arXiv:
2211.15841): expert FFN compute scales with the tokens actually routed
(``sum(group_sizes) <= G``: rows past the sum are nobody's), not with a
capacity-padded ``[E, C]`` buffer, so no token is ever dropped and no
expert pays for an empty queue.

Kernel shape (same dispatch contract as ``ops/flash.py`` — compiled on
TPU, the ``jnp`` reference twin off-TPU, ``interpret=True`` under
tests):

- The token dimension is cut into ``tile_tokens`` blocks and the kernel
  runs one grid step per (token-tile, group) *overlap* — a tile fully
  inside one group is visited once; a tile straddling ``b`` group
  boundaries is visited ``b + 1`` times, so the static grid bound is
  ``num_tiles + E - 1`` steps (ragged tails cost steps, not a second
  kernel).
- The schedule (which tile, which group, is this step live) is computed
  at trace time from ``group_sizes`` with O(E + steps) jnp work and
  **scalar-prefetched** into SMEM (``PrefetchScalarGridSpec``): the
  BlockSpec index maps read it to point each step's lhs/out blocks at
  the right token tile and its rhs block at the right expert — the
  weight block for step ``s`` is streaming into VMEM while step
  ``s - 1`` computes.
- Ragged boundaries are masked *in-block*: a boundary tile zeroes the
  rows outside ``[offsets[g], offsets[g+1])`` before the dot, and
  consecutive steps on the same output tile accumulate in VMEM (the
  revisit pattern — the block stays resident because the schedule
  orders steps by tile).

``tgmm`` is the transposed/dgrad variant (``lhs^T @ dout`` per group ->
``[E, H, N]``, the weight gradient); ``gmm``'s ``custom_vjp`` routes
d(lhs) through ``gmm`` against the transposed weights and d(rhs)
through ``tgmm``, so both backward passes reuse the same two kernels.

Two jnp twins exist. ``gmm_reference``/``tgmm_reference`` are the
*oracles* — ``jax.lax.ragged_dot`` / ``segment_sum``, the simplest
correct spelling, used by the tests as ground truth. The *dispatch*
twin (``_gmm_blocked``/``_tgmm_blocked``) replays the kernel's own tile
schedule in pure jnp — gather the scheduled (token-tile, expert-weight)
block pairs, one batched matmul over the steps, scatter-add back — which
XLA turns into a single dense batched GEMM plus cheap gathers
(~4x faster than ``ragged_dot``'s CPU lowering at bench shapes, and the
same masked-tile numerics as the kernel). The blocked twin is what runs
off-TPU (tier-1, the dropless bench lane) and under multi-device meshes,
where GSPMD can partition the jnp formulation but would treat an
un-shard_mapped ``pallas_call`` as an opaque replicated primitive.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

try:  # pragma: no cover - import guard mirrors ops/flash.py
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _PALLAS_OK = True
except Exception:  # pragma: no cover
    _PALLAS_OK = False


class _GmmOpts(NamedTuple):
    """Static (hashable) dispatch knobs carried through the custom_vjp."""

    use_kernel: bool
    interpret: bool
    tile_tokens: int
    tile_cols: int


def _resolve_opts(use_kernel, interpret, tile_tokens, tile_cols,
                  rows: int, cols: int) -> _GmmOpts:
    # Tiles from the shapes, where the caller names none: every grid step
    # costs its overhead whether it is live or padding, and the static step
    # bound grows with rows / tile_tokens, so a large buffer takes large
    # tiles (a [tile, H] x [H, tile] step also reads less per FLOP).
    if tile_tokens is None:
        tile_tokens = 512 if rows >= 4096 else 128
    if tile_cols is None:
        tile_cols = 512 if cols % 512 == 0 else 128
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if use_kernel is None:
        # Same contract as ops/flash.py: the compiled kernel drives TPU,
        # everything else gets the reference twin (tests opt into the
        # kernel explicitly with use_kernel=True + interpret=True).
        use_kernel = jax.default_backend() == "tpu"
    return _GmmOpts(bool(use_kernel), bool(interpret),
                    int(tile_tokens), int(tile_cols))


# --- reference twin ---------------------------------------------------------

def _group_ids(group_sizes: jax.Array, num_rows: int) -> jax.Array:
    """Row -> group id, [G] int32 (rows past sum(group_sizes) get E)."""
    ends = jnp.cumsum(group_sizes)
    return jnp.searchsorted(
        ends, jnp.arange(num_rows, dtype=group_sizes.dtype), side="right"
    ).astype(jnp.int32)


def gmm_reference(lhs: jax.Array, rhs: jax.Array,
                  group_sizes: jax.Array) -> jax.Array:
    """jnp twin of ``gmm`` — ``jax.lax.ragged_dot``.

    Accumulates in f32 and returns ``lhs.dtype`` (the kernel contract).
    """
    out = jax.lax.ragged_dot(
        lhs, rhs, group_sizes.astype(jnp.int32),
        preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype)


def tgmm_reference(lhs: jax.Array, dout: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """jnp twin of ``tgmm``: per-group ``lhs^T @ dout -> [E, H, N]``."""
    E = group_sizes.shape[0]
    gid = _group_ids(group_sizes, lhs.shape[0])
    prod = (lhs.astype(jnp.float32)[:, :, None]
            * dout.astype(jnp.float32)[:, None, :])        # [G, H, N]
    return jax.ops.segment_sum(prod, gid, num_segments=E)


# --- blocked jnp twin (the off-TPU dispatch path) ---------------------------

def _blocked_inputs(lhs, group_sizes, tile):
    """Shared setup: pad to tiles, build the schedule, mask the scheduled
    lhs blocks. Returns ``(x [S, tile, H] masked, tiles, gids, num_tiles)``.
    """
    G, H = lhs.shape
    num_tiles = max(1, -(-G // tile))
    lhs_p = _pad_to(lhs, 0, tile)
    tiles, gids, lives, offs = _schedule(group_sizes, num_tiles, tile)
    blocks = lhs_p.reshape(num_tiles, tile, H)[tiles]       # [S, tile, H]
    rows = tiles[:, None] * tile + jnp.arange(tile)[None, :]
    mask = ((rows >= offs[gids][:, None]) & (rows < offs[gids + 1][:, None])
            & (lives[:, None] > 0))
    x = jnp.where(mask[..., None], blocks, jnp.zeros((), lhs.dtype))
    return x, tiles, gids, num_tiles


def _gmm_blocked(lhs, rhs, group_sizes, tile):
    """Kernel-schedule gmm in jnp: one batched GEMM over the grid steps.

    Step ``s`` multiplies the masked token tile ``tiles[s]`` by expert
    block ``rhs[gids[s]]``; tiles revisited across a group boundary are
    summed by the scatter-add exactly as the kernel's VMEM accumulation
    does. Padding steps are fully masked and add zero.
    """
    G = lhs.shape[0]
    x, tiles, gids, num_tiles = _blocked_inputs(lhs, group_sizes, tile)
    y = jnp.einsum("sth,shn->stn", x, rhs[gids],
                   preferred_element_type=jnp.float32)       # [S, tile, N]
    out = jnp.zeros((num_tiles, tile, rhs.shape[2]), jnp.float32)
    out = out.at[tiles].add(y)
    return out.reshape(num_tiles * tile, -1)[:G].astype(lhs.dtype)


def _tgmm_blocked(lhs, dout, group_sizes, tile):
    """Kernel-schedule tgmm in jnp: per-step ``x^T @ dout`` scatter-added
    into the owning expert's ``[H, N]`` block (f32, the tgmm contract).
    Avoids ``tgmm_reference``'s materialized ``[G, H, N]`` outer-product
    temp — the batched contraction keeps the temp at ``[S, H, N]``.
    """
    E = group_sizes.shape[0]
    x, tiles, gids, num_tiles = _blocked_inputs(lhs, group_sizes, tile)
    dout_p = _pad_to(dout, 0, tile)
    dblocks = dout_p.reshape(num_tiles, tile, -1)[tiles]     # [S, tile, N]
    y = jnp.einsum("sth,stn->shn", x, dblocks,
                   preferred_element_type=jnp.float32)       # [S, H, N]
    out = jnp.zeros((E, lhs.shape[1], dout.shape[1]), jnp.float32)
    return out.at[gids].add(y)


# --- the schedule -----------------------------------------------------------

def _schedule(group_sizes: jax.Array, num_tiles: int,
              tile_tokens: int) -> Tuple[jax.Array, ...]:
    """Trace-time (tile, group, live) arrays for the static step bound.

    Step ``s`` processes token tile ``tiles[s]`` against group
    ``gids[s]``; ``lives[s] == 0`` marks padding steps past the real
    work (the bound ``num_tiles + E - 1`` is only reached when every
    group boundary lands mid-tile). Both ``tiles`` and ``gids`` are
    nondecreasing — group e+1 starts where group e ends — which is what
    lets BOTH output indexings (by tile in gmm, by group in tgmm) see
    their revisits consecutively and accumulate in VMEM.
    """
    E = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    nonempty = sizes > 0
    first_tile = jnp.where(nonempty, starts // tile_tokens, 0)
    visits = jnp.where(
        nonempty, (ends - 1) // tile_tokens - first_tile + 1, 0)
    cum_visits = jnp.cumsum(visits)
    num_steps = num_tiles + E - 1
    s = jnp.arange(num_steps, dtype=jnp.int32)
    gid = jnp.searchsorted(cum_visits, s, side="right").astype(jnp.int32)
    live = (gid < E).astype(jnp.int32)
    gid_c = jnp.minimum(gid, E - 1)
    prev = jnp.where(gid_c > 0, cum_visits[jnp.maximum(gid_c - 1, 0)], 0)
    tile = first_tile[gid_c] + (s - prev.astype(jnp.int32))
    # Padding steps park on the last (tile, group) pair; the live mask
    # zeroes their contribution and — tiles/gids being clamped to the
    # maxima — they can never look like a fresh first visit of a block
    # that real work wrote.
    tile = jnp.where(live > 0, tile, num_tiles - 1)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), ends.astype(jnp.int32)])
    return tile.astype(jnp.int32), gid_c, live, offsets


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


# --- kernels ----------------------------------------------------------------

def _gmm_kernel(tiles, gids, lives, offs, lhs_ref, rhs_ref, out_ref, acc_ref,
                *, tile_tokens):
    s = pl.program_id(1)
    g = gids[s]
    rows = (tiles[s] * tile_tokens
            + jax.lax.broadcasted_iota(jnp.int32, (tile_tokens, 1), 0))
    mask = ((rows >= offs[g]) & (rows < offs[g + 1])
            & (lives[s] > 0))
    first = jnp.logical_or(s == 0, tiles[s] != tiles[jnp.maximum(s - 1, 0)])

    # A padding step computes nothing: with ``sum(group_sizes) < G`` most of
    # the static step bound is padding, and compute follows the rows that
    # belong to a group. The tiles no live step visits are never written;
    # the wrapper zeroes their rows.
    @pl.when(lives[s] > 0)
    def _live():
        x = jnp.where(mask, lhs_ref[...], jnp.zeros_like(lhs_ref[...]))
        contrib = jnp.dot(x, rhs_ref[0],
                          preferred_element_type=jnp.float32)

        # f32 accumulation across the visits of one tile in scratch; the
        # result leaves in the operands' type (no f32 [G, N] round trip
        # through HBM and no cast pass outside the kernel).
        @pl.when(first)
        def _init():
            acc_ref[...] = contrib

        @pl.when(jnp.logical_not(first))
        def _acc():
            acc_ref[...] = acc_ref[...] + contrib

        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tgmm_kernel(tiles, gids, lives, offs, lhs_ref, dout_ref, out_ref, *,
                 tile_tokens):
    s = pl.program_id(1)
    g = gids[s]
    rows = (tiles[s] * tile_tokens
            + jax.lax.broadcasted_iota(jnp.int32, (tile_tokens, 1), 0))
    mask = ((rows >= offs[g]) & (rows < offs[g + 1])
            & (lives[s] > 0))
    first = jnp.logical_or(s == 0, g != gids[jnp.maximum(s - 1, 0)])

    @pl.when(lives[s] > 0)      # padding steps compute nothing (see gmm)
    def _live():
        x = jnp.where(mask, lhs_ref[...], jnp.zeros_like(lhs_ref[...]))
        # Contract the token dim: [tile, H]^T @ [tile, N] -> [H, N].
        contrib = jax.lax.dot_general(
            x, dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(first)
        def _init():
            out_ref[0] = contrib

        @pl.when(jnp.logical_not(first))
        def _acc():
            out_ref[0] = out_ref[0] + contrib


# Column blocks are independent; the steps of one column block accumulate in
# order. 512-wide tiles of a [tile, 2048] x [2048, tile] step, double
# buffered, pass the 16 MiB default scope (the wgrad's f32 [2048, 512] result
# block: 17.9 MiB); the chip has 128 MiB.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=48 * 1024 * 1024) if _PALLAS_OK else None


def _gmm_pallas(lhs, rhs, group_sizes, opts: _GmmOpts):
    G, H = lhs.shape
    E, _, N = rhs.shape
    tm, tn = opts.tile_tokens, min(opts.tile_cols, max(N, 1))
    lhs_p = _pad_to(_pad_to(lhs, 0, tm), 1, 128)
    rhs_p = _pad_to(_pad_to(rhs, 1, 128), 2, tn)
    Gp, Hp = lhs_p.shape
    Np = rhs_p.shape[2]
    num_tiles = Gp // tm
    tiles, gids, lives, offs = _schedule(group_sizes, num_tiles, tm)
    grid = (Np // tn, tiles.shape[0])
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tile_tokens=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, Hp),
                             lambda n, s, tiles, gids, lives, offs:
                             (tiles[s], 0)),
                pl.BlockSpec((1, Hp, tn),
                             lambda n, s, tiles, gids, lives, offs:
                             (gids[s], 0, n)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, s, tiles, gids, lives, offs:
                                   (tiles[s], n)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Gp, Np), lhs.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=opts.interpret,
    )(tiles, gids, lives, offs, lhs_p, rhs_p)
    # Rows past sum(group_sizes) belong to no group: tiles wholly past it
    # were never visited, so what the buffer holds there is not a result.
    out = jnp.where(jnp.arange(Gp)[:, None] < offs[-1], out,
                    jnp.zeros((), out.dtype))
    return out[:G, :N]


def _tgmm_pallas(lhs, dout, group_sizes, opts: _GmmOpts):
    G, H = lhs.shape
    N = dout.shape[1]
    E = group_sizes.shape[0]
    tm, tn = opts.tile_tokens, min(opts.tile_cols, max(N, 1))
    lhs_p = _pad_to(_pad_to(lhs, 0, tm), 1, 128)
    dout_p = _pad_to(_pad_to(dout, 0, tm), 1, tn)
    Gp, Hp = lhs_p.shape
    Np = dout_p.shape[1]
    num_tiles = Gp // tm
    tiles, gids, lives, offs = _schedule(group_sizes, num_tiles, tm)
    grid = (Np // tn, tiles.shape[0])
    out = pl.pallas_call(
        functools.partial(_tgmm_kernel, tile_tokens=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, Hp),
                             lambda n, s, tiles, gids, lives, offs:
                             (tiles[s], 0)),
                pl.BlockSpec((tm, tn),
                             lambda n, s, tiles, gids, lives, offs:
                             (tiles[s], n)),
            ],
            out_specs=pl.BlockSpec((1, Hp, tn),
                                   lambda n, s, tiles, gids, lives, offs:
                                   (gids[s], 0, n)),
        ),
        out_shape=jax.ShapeDtypeStruct((E, Hp, Np), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=opts.interpret,
    )(tiles, gids, lives, offs, lhs_p, dout_p)
    # Empty groups own no grid step, so their output blocks are never
    # written — replace whatever the backing buffer held with zeros.
    out = jnp.where(group_sizes[:, None, None] > 0, out, 0.0)
    return out[:, :H, :N]


# --- custom_vjp entry -------------------------------------------------------

def _gmm_dispatch(opts: _GmmOpts, lhs, rhs, group_sizes):
    if opts.use_kernel and _PALLAS_OK:
        return _gmm_pallas(lhs, rhs, group_sizes, opts)
    return _gmm_blocked(lhs, rhs, group_sizes, opts.tile_tokens)


def _tgmm_dispatch(opts: _GmmOpts, lhs, dout, group_sizes):
    if opts.use_kernel and _PALLAS_OK:
        return _tgmm_pallas(lhs, dout, group_sizes, opts)
    return _tgmm_blocked(lhs, dout, group_sizes, opts.tile_tokens)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gmm(opts: _GmmOpts, lhs, rhs, group_sizes):
    return _gmm_dispatch(opts, lhs, rhs, group_sizes)


def _gmm_fwd(opts, lhs, rhs, group_sizes):
    return _gmm_dispatch(opts, lhs, rhs, group_sizes), (
        lhs, rhs, group_sizes)


def _gmm_bwd(opts, res, dout):
    lhs, rhs, group_sizes = res
    # dgrad: the same grouped matmul against the transposed weights
    # ([E, N, H] blocks); wgrad: the transposed variant.
    dlhs = _gmm_dispatch(
        opts, dout, jnp.swapaxes(rhs, 1, 2), group_sizes).astype(lhs.dtype)
    drhs = _tgmm_dispatch(opts, lhs, dout, group_sizes).astype(rhs.dtype)
    return dlhs, drhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
        use_kernel: Optional[bool] = None,
        interpret: Optional[bool] = None,
        tile_tokens: Optional[int] = None,
        tile_cols: Optional[int] = None) -> jax.Array:
    """Grouped matmul: row-groups of ``lhs`` times per-group weights.

    - ``lhs``: ``[G, H]`` rows SORTED by group (group e's rows are the
      contiguous slice ``[offsets[e], offsets[e+1])``).
    - ``rhs``: ``[E, H, N]`` stacked per-group weight blocks.
    - ``group_sizes``: ``[E]`` int, ``sum <= G``. Rows past the sum
      belong to no group (an expert layer that holds a share of the experts
      sorts the other choices there): they produce zeros, get a zero
      gradient, and cost no matmul — the kernel's padding steps skip their
      compute and its schedule never visits a tile wholly past the sum.

    Returns ``[G, N]`` in ``lhs.dtype`` (f32 accumulation either path).
    Differentiable via ``custom_vjp``: d(lhs) is a ``gmm`` against
    ``rhs^T``, d(rhs) a ``tgmm`` — ``group_sizes`` gets no gradient.

    ``use_kernel=None`` picks the Pallas kernel exactly on TPU (the
    reference twin elsewhere); ``interpret=True`` runs the kernel
    under the Pallas interpreter (the CPU test path).
    """
    opts = _resolve_opts(use_kernel, interpret, tile_tokens, tile_cols,
                         lhs.shape[0], rhs.shape[2])
    if lhs.shape[0] == 0:
        return jnp.zeros((0, rhs.shape[2]), lhs.dtype)
    return _gmm(opts, lhs, rhs, group_sizes)


def tgmm(lhs: jax.Array, dout: jax.Array, group_sizes: jax.Array, *,
         use_kernel: Optional[bool] = None,
         interpret: Optional[bool] = None,
         tile_tokens: Optional[int] = None,
         tile_cols: Optional[int] = None) -> jax.Array:
    """Transposed grouped matmul (the wgrad): per-group
    ``lhs[slice]^T @ dout[slice]`` stacked to ``[E, H, N]`` f32.
    """
    opts = _resolve_opts(use_kernel, interpret, tile_tokens, tile_cols,
                         lhs.shape[0], dout.shape[1])
    if lhs.shape[0] == 0:
        return jnp.zeros(
            (group_sizes.shape[0], lhs.shape[1], dout.shape[1]),
            jnp.float32)
    return _tgmm_dispatch(opts, lhs, dout, group_sizes)
