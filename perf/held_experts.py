"""Which of an expert layer's experts this chip holds, so that every seed
draws work of one difficulty.

A configuration that holds a share of the experts (``experts_held_first``,
the family's ``held(cfg)``) gets its load from the seeded weights: at their
initial values the router's ``E`` columns are exchangeable draws, the rows a
token brings to the ``held`` of them that live here swing from 0.17 to 0.79
a token and layer between seeds where an even load gives ``k x held / E``
(PERF.md section 6, PR 37), and a deployment's router is held near the even
load by its selection bias, for which there is no published rule here. A
configuration with ``"experts_held_pick": "nearest_mean_load"`` relabels the
experts after the weights are made from the seed: layer by layer, in the
order the layers run, the program's own forward counts the first batch's
choices by expert, and the ``held`` experts whose load is nearest the mean
load move into the held range (the router's columns and the selection
bias's entries change places; nothing else moves, and a later layer is
counted after the earlier ones were relabelled, since what it reads depends
on them). The weights are still a draw from the program's own initialiser
(columns drawn alike, in another order) and a function of the seed alone.
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

PICKS = ("nearest_mean_load",)


def order(load, first: int, count: int) -> List[int]:
    """The experts' new order: ``order[j]`` is the expert whose column goes
    to place ``j``. The ``count`` experts whose ``load`` is nearest the mean
    (ties: one that is held already, then the lower id) take the places
    ``first .. first + count``, by id; the others keep their order around
    them."""
    mean = sum(load) / len(load)
    nearest = sorted(range(len(load)), key=lambda e: (
        abs(load[e] - mean), not first <= e < first + count, e))
    held = sorted(nearest[:count])
    rest = [e for e in range(len(load)) if e not in set(held)]
    return rest[:first] + held + rest[first:]


def router_sites(trainer) -> List[Tuple[str, int]]:
    """``(parameter group, index in its stack)`` of each expert layer, in the
    order the layers run: the order of ``program_side``'s choices."""
    seen, sites = {}, []
    for kind in trainer.model_config.layer_kinds():
        if kind[1] == "moe":
            i = seen.get(kind, 0)
            seen[kind] = i + 1
            sites.append(("layers_" + "_".join(kind), i))
    return sites


@functools.lru_cache(maxsize=None)
def _relabel():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda leaf, i, new_order: leaf.at[i].set(
        jnp.take(leaf[i], new_order, axis=-1)))


def relabel_tree(tree, group: str, i: int, new_order: Sequence[int]):
    """``tree`` with layer ``i`` of ``group``'s router columns and selection
    bias in ``new_order``; every other leaf is the same array."""
    import jax
    import jax.numpy as jnp

    new_order = jnp.asarray(new_order, jnp.int32)
    relabel = _relabel()

    def leaf(path, value):
        keys = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        routed = (keys[-2:] == ["router", "kernel"]
                  or keys[-1] == "expert_bias")
        if keys[0] != group or not routed:
            return value
        return relabel(value, i, new_order)

    return jax.tree_util.tree_map_with_path(leaf, tree)


def apply(trainer, state, family, cfg, job, batch, side, place_rows):
    """``state`` with the experts relabelled as ``cfg["experts_held_pick"]``
    says, and what was done, for the run's note: by layer the experts now
    held (their ids at the seed's draw) and the rows a token of ``batch``
    brought to the held range before and after."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    pick = cfg["experts_held_pick"]
    if pick not in PICKS:
        raise ValueError(f"experts_held_pick {pick!r}: have {PICKS}")
    first, count = family.held(cfg)
    experts = family.router_width(cfg)
    rows = job["micro_batch"]

    @jax.jit
    def loads(params, toks):
        _, choices = side(params, toks)
        return jnp.stack([jnp.bincount(c.reshape(-1), length=experts)
                          for c in choices])

    def batch_loads(params):
        return sum(np.asarray(loads(params, place_rows(
            trainer, batch[lo:lo + rows]))) for lo in range(0, len(batch),
                                                            rows))

    params, params_c = state.params, state.params_c
    done = []
    for layer, (group, i) in enumerate(router_sites(trainer)):
        load = batch_loads(params)[layer]
        new_order = order(load.tolist(), first, count)
        params = relabel_tree(params, group, i, new_order)
        if params_c is not None:
            params_c = relabel_tree(params_c, group, i, new_order)
        done.append({
            "group": group, "layer": i,
            "held": new_order[first:first + count],
            "rows_per_token_before":
                float(load[first:first + count].sum()) / batch.size,
            "rows_per_token_after":
                float(load[new_order[first:first + count]].sum())
                / batch.size})
    return state.replace(params=params, params_c=params_c), {
        "pick": pick, "even_rows_per_token": family.even_rows_per_token(cfg),
        "layers": done}
