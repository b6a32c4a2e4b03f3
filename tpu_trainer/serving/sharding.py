"""Tensor-parallel (head-sharded) layout helpers for the serving engine.

One replica = one mesh: a single-axis ``("tp",)`` device mesh (built by
``parallel/mesh.tp_mesh``, which the model's paged step shares) over which
the paged KV pools are sharded on their *kv-heads* axis with
``NamedSharding``, while block tables, lengths and offsets stay
replicated host mirrors (scheduling never syncs the device — unchanged).
Params are committed to the mesh *sharded* on each leaf's largest
tp-divisible axis (persistent per-device bytes ~ P/tp) and gathered back
to replicated *inside* the jitted step with a sharding constraint: the
all-gather is an exact concatenation, so every matmul downstream sees
bit-identical operands to the single-device engine — which is what makes
the sharded greedy streams token-identical by construction rather than
by tolerance.

GQA composes the same way ``generate_kv``'s TP path does: when
``kv_heads < tp`` the KV pools are replicated (every device holds all kv
heads) and only the Q heads are sharded — each device's contiguous
Q-head slice attends to exactly one kv head, selected inside the
``shard_map`` body by ``axis_index // (tp // kv_heads)``.

All helpers are no-ops / identities at ``tp == 1`` so the single-device
engine never pays for them.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_trainer.parallel.mesh import TP_AXIS

# Cache-collection leaves sharded on their kv-heads axis (axis 2). Every
# other cache leaf (tables / lengths / offsets) replicates — they are the
# host-mirror scheduling state.
_POOL_LEAVES = ("pool_k", "pool_v", "scale_k", "scale_v")


def kv_sharded(kv_heads: int, tp: int) -> bool:
    """True when the KV pools shard over heads (the capacity win); False
    in GQA-replicate mode (``tp % kv_heads == 0``), where every device
    holds the full pools."""
    return tp > 1 and kv_heads % tp == 0


def shard_factor(kv_heads: int, tp: int) -> int:
    """Pool capacity multiplier: with kv-head-sharded pools each block
    costs 1/tp of its single-device bytes per device, so a per-device
    block budget B affords B*tp pool blocks. Replicated (GQA) pools gain
    nothing."""
    return tp if kv_sharded(kv_heads, tp) else 1


def _cache_spec(key: Optional[str], kv_heads: int, tp: int) -> P:
    if key in _POOL_LEAVES and kv_sharded(kv_heads, tp):
        return P(None, None, TP_AXIS, None)
    return P()


def shard_cache(cache, mesh: Mesh, kv_heads: int):
    """Commit a freshly initialized cache collection to the mesh: pools
    (and int8 scales) sharded on their kv-heads axis when divisible,
    everything replicated otherwise. Committed placement is what lets
    jit leave uncommitted per-step inputs (tables, ids, ...) to implicit
    replication."""
    tp = mesh.devices.size

    def put(path, leaf):
        spec = _cache_spec(getattr(path[-1], "key", None), kv_heads, tp)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(put, cache)


def constrain_cache(cache, mesh: Mesh, kv_heads: int):
    """The in-jit twin of ``shard_cache``: pin the step's output cache to
    the same layout so the pool scatter's result stays sharded instead of
    drifting to whatever GSPMD infers."""
    tp = mesh.devices.size

    def pin(path, leaf):
        spec = _cache_spec(getattr(path[-1], "key", None), kv_heads, tp)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(pin, cache)


def pick_shard_axis(shape: Sequence[int], tp: int) -> Optional[int]:
    """Device-placement rule for a param leaf: the largest axis ``tp``
    divides evenly (ties -> lowest axis index), or None to replicate.
    Deterministic so every engine in a fleet commits the same layout."""
    best = None
    for ax, n in enumerate(shape):
        if n % tp == 0 and (best is None or n > shape[best]):
            best = ax
    return best


def param_spec(shape: Sequence[int], tp: int) -> P:
    ax = pick_shard_axis(shape, tp) if tp > 1 else None
    if ax is None:
        return P()
    spec = [None] * len(shape)
    spec[ax] = TP_AXIS
    return P(*spec)


def shard_params(params, mesh: Mesh):
    """Commit params to the mesh sharded per ``param_spec`` — the
    persistent-HBM side of the capacity story (~P/tp resident bytes per
    device; the step's gather is transient)."""
    tp = mesh.devices.size
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, param_spec(np.shape(x), tp))),
        params)


def gather_params(params, mesh: Mesh):
    """Inside the jitted step: constrain every param leaf to replicated.
    GSPMD lowers this to an all-gather of contiguous shards — an exact
    concatenation, no arithmetic — so the compute that follows is
    bitwise the single-device compute."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P())),
        params)
