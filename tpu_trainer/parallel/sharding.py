"""Parameter / optimizer-state sharding rules (GSPMD).

The TPU-native equivalent of the reference's two parallelism strategies
(SURVEY.md C9/C10), plus tensor parallelism (absent upstream — an
aspirational README bullet, ``README.md:9``; here a working mesh axis):

- **DDP** (reference ``ddp_trainer.py:167-172``): params and optimizer state
  replicated; the batch sharded over the data axes. XLA's SPMD partitioner
  inserts the gradient all-reduce that DDP's bucket hooks perform.
- **FSDP** (reference ``fsdp_trainer.py:236-310``): the ``sharding_strategy``
  modes map onto NamedShardings instead of wrapper classes:

  | reference mode  | ZeRO | params    | grads     | optimizer state |
  |-----------------|------|-----------|-----------|-----------------|
  | FULL_SHARD      | 3    | sharded   | sharded   | sharded         |
  | SHARD_GRAD_OP   | 2    | replicated| sharded   | sharded         |
  | NO_SHARD        | -    | replicated| replicated| replicated      |
  | HYBRID_SHARD    | 3*   | sharded over fsdp, replicated over data |

  (HYBRID_SHARD is docstring-only/broken in the reference —
  ``fsdp_trainer.py:258-261`` vs the strategy dict ``:269-273``; here it is
  simply ``data > 1 and fsdp > 1``.)
- **TP (Megatron-style)**: when the mesh's ``tensor`` axis is > 1, the
  per-layer projections split column-/row-parallel via path rules
  (``_TENSOR_RULES``); GSPMD emits the all-reduce after each row-parallel
  matmul. No explicit collectives appear anywhere — TP is purely a change of
  PartitionSpec, composable with every ZeRO mode.

The all-gather (param use) and reduce-scatter (grad reduction) that torch
FSDP issues per wrapped module are emitted automatically by the XLA SPMD
partitioner, with overlap handled by the latency-hiding scheduler — the
analogue of ``backward_prefetch``/``limit_all_gathers``
(``fsdp_trainer.py:296,304-307``).

FSDP rule: for each array leaf, shard the **largest** dimension that is
divisible by the fsdp axis size and not already tensor-sharded (ties → later
dim). Shape-driven, so one rule covers params, grads, and Adam's mu/nu
(whose trees mirror params — path matching uses suffix match, which survives
the optax state nesting).
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_trainer.parallel.mesh import (
    DATA_AXIS, EXPERT_AXIS, FSDP_AXIS, STAGE_AXIS, TENSOR_AXIS,
)

# Strategy names: ours (zero3/zero2/replicated) with the reference's
# FSDP spellings accepted as aliases.
STRATEGY_ALIASES = {
    "FULL_SHARD": "zero3",
    "SHARD_GRAD_OP": "zero2",
    "NO_SHARD": "replicated",
    "HYBRID_SHARD": "zero3",  # hybrid = zero3 rules + data axis > 1
    "zero3": "zero3",
    "zero2": "zero2",
    "replicated": "replicated",
    "ddp": "replicated",
}

# Megatron-style tensor-parallel placement, by parameter-path suffix.
# Column-parallel = shard the output dim (last); row-parallel = shard the
# input dim (second to last); the row-parallel matmuls (o_proj, down_proj)
# are where GSPMD inserts the TP all-reduce. The tied embedding shards its
# hidden dim (vocab 50257 is not divisible by practical axis sizes), making
# ``embed.attend`` a row-parallel matmul too.
_TENSOR_RULES: List[Tuple[Tuple[str, ...], int]] = [
    (("attention", "q_proj", "kernel"), -1),
    (("attention", "k_proj", "kernel"), -1),
    (("attention", "v_proj", "kernel"), -1),
    (("attention", "o_proj", "kernel"), -2),
    (("mlp", "gate_proj", "kernel"), -1),
    (("mlp", "up_proj", "kernel"), -1),
    (("mlp", "down_proj", "kernel"), -2),
    (("embed_tokens", "embedding"), -1),
    # Expert FFN weights ([.., E, H, I] / [.., E, I, H]): the same
    # column/row-parallel split as the dense MLP, per expert — composes
    # with the expert-dim sharding (_expert_dim) into EP x TP.
    (("experts_gate",), -1),
    (("experts_up",), -1),
    (("experts_down",), -2),
]


# A Mamba-2 mixer (models/gpt.py Mamba2Mixer) has no tensor-parallel rule:
# ``in_proj``'s columns are ``[z, xBC, dt]`` back to back, the taps and the
# gated norm run over lanes that a column split would cut through a group,
# and ``A_log`` / ``dt_bias`` / ``D`` are a scalar a head. Every leaf under
# ``mamba`` REPLICATES over ``tensor`` (the Trainer and the model refuse a
# tensor axis > 1 for such a model in words); under zero3 / zero2 the
# shape-driven fsdp rule shards them like any other leaf (stacked ``[count,
# ...]``: ``in_proj`` and ``out_proj`` on their largest dim, ``conv_weight``
# and ``conv_bias`` on the channels, the ``[count, heads]`` leaves on the
# heads where the axis divides them).
_TENSOR_REPLICATED_SCOPES = ("mamba",)


def canonical_strategy(name: str) -> str:
    if name not in STRATEGY_ALIASES:
        raise ValueError(
            f"unknown sharding strategy {name!r}; choose from {sorted(STRATEGY_ALIASES)}"
        )
    return STRATEGY_ALIASES[name]


def _path_keys(path) -> Tuple[str, ...]:
    return tuple(
        str(getattr(p, "key", getattr(p, "name", getattr(p, "idx", "")))) for p in path
    )


# Expert-parallel placement: stacked expert FFN weights ([E, H, I] — or
# [L, E, H, I] under the layer scan) shard their expert dim, which sits at
# ndim-3. The router stays replicated (it is tiny).
_EXPERT_PARAM_PREFIX = "experts_"


def _expert_dim(path_keys: Tuple[str, ...], shape, expert_size: int) -> Optional[int]:
    if expert_size <= 1 or not path_keys or len(shape) < 3:
        return None
    if not path_keys[-1].startswith(_EXPERT_PARAM_PREFIX):
        return None
    d = len(shape) - 3
    return d if shape[d] % expert_size == 0 else None


def _tensor_dim(path_keys: Tuple[str, ...], shape, tensor_size: int) -> Optional[int]:
    """Dim to shard over the tensor axis for this param path, or None."""
    if tensor_size <= 1 or any(
            scope in path_keys for scope in _TENSOR_REPLICATED_SCOPES):
        return None
    for suffix, dim in _TENSOR_RULES:
        if path_keys[-len(suffix):] == suffix:
            d = dim % len(shape)
            if shape[d] % tensor_size == 0:
                return d
            return None
    return None


def fsdp_spec(shape, fsdp_size: int) -> P:
    """Shape-only FSDP rule: shard the largest fsdp-divisible dim (ties →
    later dim); replicate when nothing divides."""
    return _leaf_spec((), shape, fsdp_size=fsdp_size, tensor_size=1,
                      shard_fsdp=True)


def _leaf_spec(path_keys, shape, *, fsdp_size: int, tensor_size: int,
               shard_fsdp: bool, expert_size: int = 1,
               stage_size: int = 1) -> P:
    """Combined PP + EP + TP + FSDP PartitionSpec for one array leaf."""
    if not shape:
        return P()
    dims: List[Optional[str]] = [None] * len(shape)
    if (
        stage_size > 1
        and "layers" in path_keys
        and shape
        and shape[0] % stage_size == 0
    ):
        # Pipeline parallelism: the nn.scan stacked [num_layers, ...]
        # leading dim splits into contiguous stages. Everything outside the
        # layer stack (embedding, final norm) replicates over `stage`.
        dims[0] = STAGE_AXIS
    edim = _expert_dim(path_keys, shape, expert_size)
    if edim is not None and dims[edim] is None:
        dims[edim] = EXPERT_AXIS
    tdim = _tensor_dim(path_keys, shape, tensor_size)
    if tdim is not None and dims[tdim] is None:
        dims[tdim] = TENSOR_AXIS
    if shard_fsdp and fsdp_size > 1:
        best: Optional[int] = None
        for i, d in enumerate(shape):
            if dims[i] is None and d % fsdp_size == 0 and d >= fsdp_size:
                if best is None or d >= shape[best]:
                    best = i
        if best is not None:
            dims[best] = FSDP_AXIS
    if all(d is None for d in dims):
        return P()
    return P(*dims)


def _specs_for_sizes(tree: Any, axis_sizes, *, shard_fsdp: bool) -> Any:
    """Spec tree from axis sizes alone (a ``{axis_name: size}`` mapping).

    The placement rules are pure shape/path arithmetic — no live ``Mesh``
    required — which is what lets the mesh auto-planner score candidate
    meshes that were never materialized. ``mesh.shape`` is such a mapping,
    so the Mesh entry points below just delegate here.
    """
    fsdp_size = axis_sizes.get(FSDP_AXIS, 1)
    tensor_size = axis_sizes.get(TENSOR_AXIS, 1)
    expert_size = axis_sizes.get(EXPERT_AXIS, 1)
    stage_size = axis_sizes.get(STAGE_AXIS, 1)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: _leaf_spec(
            _path_keys(path), getattr(x, "shape", ()),
            fsdp_size=fsdp_size, tensor_size=tensor_size,
            shard_fsdp=shard_fsdp, expert_size=expert_size,
            stage_size=stage_size,
        ),
        tree,
    )


def _specs_for_tree(tree: Any, mesh: Mesh, *, shard_fsdp: bool) -> Any:
    return _specs_for_sizes(tree, mesh.shape, shard_fsdp=shard_fsdp)


def params_specs_from_sizes(params: Any, axis_sizes, strategy: str) -> Any:
    """``params_specs`` from a ``{axis: size}`` mapping instead of a Mesh."""
    strategy = canonical_strategy(strategy)
    return _specs_for_sizes(params, axis_sizes, shard_fsdp=strategy == "zero3")


def params_specs(params: Any, mesh: Mesh, strategy: str) -> Any:
    """PartitionSpec tree for model parameters under a strategy.

    TP placement applies in every strategy (a TP-sharded param is never
    replicated over ``tensor``); the fsdp axis applies only under zero3.
    """
    return params_specs_from_sizes(params, mesh.shape, strategy)


def opt_state_specs_from_sizes(opt_state: Any, axis_sizes, strategy: str) -> Any:
    """``opt_state_specs`` from a ``{axis: size}`` mapping instead of a Mesh."""
    strategy = canonical_strategy(strategy)
    return _specs_for_sizes(
        opt_state, axis_sizes, shard_fsdp=strategy in ("zero2", "zero3")
    )


def opt_state_specs(opt_state: Any, mesh: Mesh, strategy: str) -> Any:
    """PartitionSpec tree for optimizer state.

    zero2 and zero3 both shard the (param-shaped) Adam moments; scalars (step
    counts) stay replicated. The moments live nested inside optax state, but
    suffix-matching the param path still applies the TP rules correctly.
    ``opt_state`` may be a tree of arrays or of ShapeDtypeStructs.
    """
    return opt_state_specs_from_sizes(opt_state, mesh.shape, strategy)


def grads_specs_from_sizes(params: Any, axis_sizes, strategy: str) -> Any:
    """``grads_specs`` from a ``{axis: size}`` mapping instead of a Mesh."""
    strategy = canonical_strategy(strategy)
    return _specs_for_sizes(
        params, axis_sizes, shard_fsdp=strategy in ("zero2", "zero3")
    )


def grads_specs(params: Any, mesh: Mesh, strategy: str) -> Any:
    """PartitionSpec tree for gradients (reduce-scatter target under ZeRO).

    Gradients of TP-sharded params carry the same tensor dims in every
    strategy; the fsdp axis applies under zero2/zero3.
    """
    return grads_specs_from_sizes(params, mesh.shape, strategy)


def to_shardings(spec_tree: Any, mesh: Mesh) -> Any:
    """PartitionSpec tree → NamedSharding tree."""
    return jax.tree_util.tree_map(lambda spec: NamedSharding(mesh, spec), spec_tree)


def constrain(tree: Any, spec_tree: Any) -> Any:
    """Apply ``with_sharding_constraint`` leaf-wise (inside jit)."""
    return jax.tree_util.tree_map(
        lambda x, spec: jax.lax.with_sharding_constraint(x, spec), tree, spec_tree
    )
