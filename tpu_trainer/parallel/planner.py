"""Mesh auto-planner: enumerate, score, and pick the parallelism split.

Users shouldn't hand-pick ``data x fsdp x sequence x tensor x expert x
stage`` for every model/pod/HBM combination (ROADMAP item 2). Alpa
(Zheng et al., OSDI '22) and FlexFlow (Jia et al., MLSys '19) showed an
analytic cost model searched over a constrained plan space matches
hand-tuned parallelization; the analytic half already exists here
(``parallel/comms_model.py`` per-axis collective bytes + ICI roofline).
This module is the search half:

1. **enumerate**: every ordered factorization of the device count over
   the six mesh axes (the divisor lattice);
2. **prune**: divisibility feasibility (:func:`feasibility_error` — the
   same predicate the CLI uses for early mesh validation, so CLI errors
   and planner pruning can never disagree) and a per-device HBM budget
   from an analytic params + optimizer + gradient + activation memory
   estimate (:func:`estimate_memory`);
3. **score**: ``comms_model.build_core`` bytes -> ICI roofline seconds,
   plus the 6N-FLOPs compute estimate, summed serially (the comms model's
   stated no-overlap assumption) into a predicted step time;
4. **rank**: argmin predicted step time, deterministic tiebreak on the
   axis tuple; emit the ``kind:"mesh_plan"`` record with top-k
   alternatives for ``--mesh auto`` and ``tools/plan``.

The search holds the GLOBAL batch fixed (``global_rows`` rows per
micro-step) and derives each candidate's per-shard batch as
``global_rows // (data*fsdp)`` — otherwise a tensor-heavy mesh would
"win" simply by doing less work per step than a data-parallel one.

Everything is pure shape arithmetic on an abstract param tree: nothing
compiles, no mesh is materialized, and plans for a different device kind
(``--hbm_gb`` + ``--device-kind``) cost the same as plans for this host.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_trainer.parallel import comms_model as comms_lib
from tpu_trainer.parallel import mesh as mesh_lib
from tpu_trainer.parallel import sharding as shard_lib
from tpu_trainer.utils.logging import SCHEMA_VERSION

GiB = float(2**30)


# --- feasibility (shared with CLI mesh validation) --------------------------

def feasibility_error(
    axis_sizes,
    model_config,
    *,
    n_devices: int,
    global_rows: int,
    max_seq_len: int,
) -> Optional[str]:
    """Why this mesh can't run this model, or ``None`` if it can.

    Mirrors every divisibility check ``Trainer.__init__`` enforces (plus
    the planner's fixed-global-batch row split), so a mesh this predicate
    accepts constructs a Trainer and one it rejects fails there with the
    same arithmetic. The CLI calls it at parse/startup time for explicit
    ``--mesh_*`` splits; the enumerator uses it to prune — one predicate,
    so the two can never disagree.
    """
    d = axis_sizes.get(mesh_lib.DATA_AXIS, 1)
    f = axis_sizes.get(mesh_lib.FSDP_AXIS, 1)
    sp = axis_sizes.get(mesh_lib.SEQUENCE_AXIS, 1)
    tp = axis_sizes.get(mesh_lib.TENSOR_AXIS, 1)
    ep = axis_sizes.get(mesh_lib.EXPERT_AXIS, 1)
    st = axis_sizes.get(mesh_lib.STAGE_AXIS, 1)
    sizes = (d, f, sp, tp, ep, st)
    if any(s < 1 for s in sizes):
        return f"mesh axes must be >= 1, got {sizes}"
    product = int(np.prod(sizes))
    if product != n_devices:
        return (f"mesh {sizes} uses {product} devices but {n_devices} "
                f"are available")
    if sp > 1 and max_seq_len % sp != 0:
        return (f"max_seq_len {max_seq_len} not divisible by sequence "
                f"axis size {sp}")
    if ep > 1:
        if model_config.num_experts <= 0:
            return ("expert mesh axis > 1 requires a MoE model "
                    "(GPTConfig.num_experts > 0)")
        if model_config.num_experts % ep != 0:
            return (f"num_experts {model_config.num_experts} not divisible "
                    f"by expert axis size {ep}")
    if tp > 1:
        if model_config.num_heads % tp != 0:
            return (f"num_heads {model_config.num_heads} not divisible by "
                    f"tensor axis size {tp}")
        if model_config.kv_heads % tp != 0:
            return (f"num_kv_heads {model_config.kv_heads} not divisible by "
                    f"tensor axis size {tp} (each tensor shard must own "
                    f"whole K/V-head groups)")
    dp = d * f
    if global_rows % dp != 0:
        return (f"global batch of {global_rows} rows not divisible by "
                f"{dp} data shards (data {d} x fsdp {f})")
    if st > 1:
        if model_config.num_layers % st != 0:
            return (f"num_layers {model_config.num_layers} not divisible by "
                    f"stage axis size {st}")
        microbatches = model_config.pipeline_microbatches or st
        if model_config.pipeline_schedule == "interleaved":
            vst = model_config.pipeline_virtual_stages
            if model_config.num_layers % (st * vst):
                return (f"num_layers {model_config.num_layers} not divisible "
                        f"by stages*virtual ({st}*{vst})")
            if microbatches % st:
                return (f"interleaved schedule needs pipeline_microbatches "
                        f"({microbatches}) divisible by the stage count "
                        f"({st})")
        if global_rows % microbatches != 0:
            return (f"global batch {global_rows} rows not divisible by "
                    f"pipeline_microbatches {microbatches}")
    return None


def validate_mesh_config(
    mesh_config: mesh_lib.MeshConfig,
    model_config,
    *,
    n_devices: int,
    global_rows: int,
    max_seq_len: int,
) -> Dict[str, int]:
    """Resolve + feasibility-check an explicit MeshConfig; raise ValueError
    with an actionable message on any split the Trainer would reject.

    The CLI's early mesh validation: the same arithmetic errors the Trainer
    raises mid-startup surface at parse time instead, with a pointer to
    ``--mesh auto``. Returns the resolved ``{axis: size}`` dict.
    """
    resolved = mesh_config.resolve(n_devices)  # raises on bad product
    sizes = dict(zip(mesh_lib.MESH_AXES, resolved))
    err = feasibility_error(
        sizes, model_config, n_devices=n_devices,
        global_rows=global_rows, max_seq_len=max_seq_len)
    if err:
        raise ValueError(
            f"infeasible mesh {tuple(resolved)} "
            f"({'x'.join(mesh_lib.MESH_AXES)}): {err} — pick a split whose "
            f"axes divide the model, or let `--mesh auto` choose one")
    return sizes


# --- enumeration ------------------------------------------------------------

def enumerate_meshes(n_devices: int) -> Iterator[Dict[str, int]]:
    """Every ordered factorization of ``n_devices`` over the six mesh axes.

    The full divisor lattice, deterministically ordered (each axis walks
    its divisors ascending, data-axis outermost). For n = 2^k this is
    C(k+5, 5) candidates — 56 at n=8, 462 at n=64 — cheap enough that no
    search heuristics are needed below pod scale.
    """
    def factorize(remaining: int, n_axes: int) -> Iterator[Tuple[int, ...]]:
        if n_axes == 1:
            yield (remaining,)
            return
        for div in range(1, remaining + 1):
            if remaining % div == 0:
                for rest in factorize(remaining // div, n_axes - 1):
                    yield (div,) + rest

    for sizes in factorize(n_devices, len(mesh_lib.MESH_AXES)):
        yield dict(zip(mesh_lib.MESH_AXES, sizes))


# --- per-device memory estimate ---------------------------------------------

def estimate_memory(
    param_shapes,
    axis_sizes,
    strategy: str,
    *,
    model_config,
    batch_size: int,
    max_seq_len: int,
    opt_state_bytes: int = 4,
    carry_cast: bool = True,
) -> Dict[str, float]:
    """Analytic per-device peak-HBM estimate (bytes) for one candidate mesh.

    Exact for the persistent state — every param/grad/optimizer leaf is
    divided by its PartitionSpec's shard factor, the same specs the trainer
    will install — and approximate for activations (flash attention keeps
    the S^2 matrix out of HBM, so the dominant saved-for-backward terms are
    the per-layer residual/MLP streams):

    - master params: f32 / params spec
    - compute-dtype param copy (``carry_cast_params``): only when compute
      dtype is narrower than f32
    - Adam mu+nu: ``opt_state_bytes`` each / grads spec (the optimizer
      moments shard like grads under zero2/zero3)
    - grads: f32 / grads spec (persists across the accumulation loop)
    - activations per micro-batch:
      ``rows * seq_local * layers_local * (4*hidden + 2*inter_local)``
      in compute dtype, plus a 4x-hidden embed/head working set; the MoE
      FFN term scales by ``top_k * capacity_factor`` (capacity routing)
      or by ``top_k`` alone (dropless routing — no slot padding).

    Cross-check the winner against the XLA ``memory_analysis`` numbers in
    the ``cost_analysis`` record — this estimate is for *pruning*
    infeasible plans, not for capacity planning to the last megabyte.
    """
    strategy = shard_lib.canonical_strategy(strategy)
    mc = model_config
    sizes = {ax: axis_sizes.get(ax, 1) for ax in mesh_lib.MESH_AXES}
    sp = sizes[mesh_lib.SEQUENCE_AXIS]
    tp = sizes[mesh_lib.TENSOR_AXIS]
    st = sizes[mesh_lib.STAGE_AXIS]
    act_bytes = jnp.dtype(mc.compute_dtype).itemsize

    p_specs = shard_lib.params_specs_from_sizes(param_shapes, sizes, strategy)
    g_specs = shard_lib.grads_specs_from_sizes(param_shapes, sizes, strategy)

    mem = {"params": 0.0, "opt": 0.0, "grads": 0.0}

    def per_leaf(leaf, pspec, gspec):
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        p_shard = size / comms_lib._shard_factor(pspec, sizes)
        g_shard = size / comms_lib._shard_factor(gspec, sizes)
        mem["params"] += p_shard * 4
        if carry_cast and act_bytes < 4:
            mem["params"] += p_shard * act_bytes
        mem["opt"] += 2.0 * g_shard * opt_state_bytes
        mem["grads"] += g_shard * 4

    jax.tree_util.tree_map(per_leaf, param_shapes, p_specs, g_specs)

    seq_local = max_seq_len // sp
    layers_local = mc.num_layers // st if st > 1 else mc.num_layers
    inter_local = (mc.intermediate_size // tp
                   if mc.intermediate_size % tp == 0 else mc.intermediate_size)
    if mc.num_experts > 0:
        # Capacity routing materialises the padded E*C slot buffer;
        # dropless holds exactly the k*T routed rows.
        mlp_scale = (mc.moe_top_k if mc.moe_impl == "dropless"
                     else mc.moe_top_k * mc.expert_capacity_factor)
    else:
        mlp_scale = 1.0
    per_token = 4 * mc.hidden_size + 2 * inter_local * mlp_scale
    activations = act_bytes * batch_size * seq_local * (
        layers_local * per_token + 4 * mc.hidden_size)
    mem["activations"] = activations
    mem["total"] = sum(mem.values())
    return mem


def hbm_budget_bytes(hbm_gb: Optional[float] = None) -> Optional[float]:
    """Per-device HBM budget in bytes: explicit ``--hbm_gb`` override, else
    the local device's ``memory_stats()['bytes_limit']``, else ``None``
    (no budget — CPU hosts planning for themselves don't prune on HBM)."""
    if hbm_gb is not None:
        return float(hbm_gb) * GiB
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:
        return None
    limit = stats.get("bytes_limit")
    return float(limit) if limit else None


# --- scoring ----------------------------------------------------------------

def score_mesh(
    param_shapes,
    axis_sizes,
    strategy: str,
    *,
    model_config,
    global_rows: int,
    max_seq_len: int,
    grad_accum: int,
    device_kind: str = "",
    peak_flops: Optional[float] = None,
    opt_state_bytes: int = 4,
    carry_cast: bool = True,
) -> Dict[str, Any]:
    """One ranked-table entry for one feasible mesh.

    Predicted step time is the comms model's serial roofline — analytic
    compute seconds (6N FLOPs at the device's peak) plus per-device
    collective bytes over the ICI bandwidth, no overlap — so the score
    inherits exactly the assumptions the ``comms_model`` record documents.
    """
    sizes = {ax: axis_sizes.get(ax, 1) for ax in mesh_lib.MESH_AXES}
    dp = sizes[mesh_lib.DATA_AXIS] * sizes[mesh_lib.FSDP_AXIS]
    batch_per_shard = global_rows // dp
    rec = comms_lib.build_core(
        param_shapes, sizes, strategy,
        model_config=model_config, batch_size=batch_per_shard,
        max_seq_len=max_seq_len, grad_accum=grad_accum,
        device_kind=device_kind, peak_flops=peak_flops)
    mem = estimate_memory(
        param_shapes, sizes, strategy,
        model_config=model_config, batch_size=batch_per_shard,
        max_seq_len=max_seq_len,
        opt_state_bytes=opt_state_bytes, carry_cast=carry_cast)
    compute_ms = rec["compute_seconds_est"] * 1e3
    comms_ms = rec["comms_seconds_est"] * 1e3
    # Pipeline bubble: under GPipe, each of the (st-1) ramp-up/down slots
    # idles relative to the m microbatches of useful work — compute
    # stretches by (1 + (st-1)/m). The comms model doesn't see idleness
    # (it counts bytes), so the scorer must, or stage meshes win on cheap
    # boundary transfers alone.
    st = sizes[mesh_lib.STAGE_AXIS]
    bubble = 1.0
    if st > 1:
        micro = model_config.pipeline_microbatches or st
        bubble = 1.0 + (st - 1) / micro
    return {
        "mesh": sizes,
        "batch_per_shard": batch_per_shard,
        "predicted_step_ms": compute_ms * bubble + comms_ms,
        "compute_ms": compute_ms,
        "comms_ms": comms_ms,
        "bubble_factor": bubble,
        "bytes_per_device": rec["total_bytes_per_device_per_step"],
        "peak_hbm_gb": mem["total"] / GiB,
        "bound": rec["bound"],
    }


# --- the planner ------------------------------------------------------------

class NoFeasiblePlanError(ValueError):
    """No mesh factorization of the device count can run this model."""


def plan(
    model_config,
    n_devices: int,
    *,
    global_rows: int,
    max_seq_len: int,
    grad_accum: int,
    strategy: str = "zero3",
    device_kind: str = "",
    hbm_gb: Optional[float] = None,
    peak_flops: Optional[float] = None,
    opt_state_bytes: int = 4,
    carry_cast: bool = True,
    top_k: int = 5,
    exclude_axes: Sequence[str] = (),
) -> Dict[str, Any]:
    """Enumerate, prune, score, rank; return the ``mesh_plan`` record.

    ``device_kind`` drives both the ICI-bandwidth table and (when
    ``peak_flops`` is not given) the peak-FLOPs table — so
    ``--device-kind v5e`` plans consistently for hardware this process
    doesn't own. An unknown kind raises; an empty or CPU kind plans for
    ``utils.logging.OFF_CHIP_MODEL_KIND`` like the live comms model.

    ``exclude_axes`` drops candidates that split the named axes — for
    platform capability gaps rather than model arithmetic (e.g. the CPU
    SPMD partitioner cannot lower the GPipe stage shard_map, so CPU
    correctness-mode callers exclude ``"stage"``).

    Raises :class:`NoFeasiblePlanError` when every factorization is pruned
    (message includes the per-candidate reasons, capped).
    """
    strategy = shard_lib.canonical_strategy(strategy)
    param_shapes = comms_lib.abstract_params(model_config)
    budget = hbm_budget_bytes(hbm_gb)

    scored: List[Dict[str, Any]] = []
    reasons: List[str] = []
    hbm_reasons: List[str] = []
    n_enumerated = 0
    pruned = {"divisibility": 0, "hbm": 0}
    if exclude_axes:
        pruned["excluded"] = 0
    for sizes in enumerate_meshes(n_devices):
        n_enumerated += 1
        if any(sizes.get(ax, 1) > 1 for ax in exclude_axes):
            pruned["excluded"] += 1
            if len(reasons) < 8:
                reasons.append(
                    f"mesh {tuple(sizes.values())} splits excluded axis "
                    f"({', '.join(exclude_axes)})")
            continue
        err = feasibility_error(
            sizes, model_config, n_devices=n_devices,
            global_rows=global_rows, max_seq_len=max_seq_len)
        if err:
            pruned["divisibility"] += 1
            if len(reasons) < 8:
                reasons.append(err)
            continue
        entry = score_mesh(
            param_shapes, sizes, strategy,
            model_config=model_config, global_rows=global_rows,
            max_seq_len=max_seq_len, grad_accum=grad_accum,
            device_kind=device_kind, peak_flops=peak_flops,
            opt_state_bytes=opt_state_bytes, carry_cast=carry_cast)
        if budget is not None and entry["peak_hbm_gb"] * GiB > budget:
            pruned["hbm"] += 1
            if len(hbm_reasons) < 4:
                hbm_reasons.append(
                    f"mesh {tuple(sizes.values())} needs "
                    f"{entry['peak_hbm_gb']:.2f} GiB/device "
                    f"> budget {budget / GiB:.2f} GiB")
            continue
        scored.append(entry)

    if not scored:
        # HBM reasons first: "everything divisible got memory-pruned" is the
        # actionable story (raise --hbm_gb / shrink the batch), and the
        # divisibility list alone would bury it under the 8-reason cap.
        raise NoFeasiblePlanError(
            f"no feasible mesh for {n_devices} devices "
            f"(global batch {global_rows}, seq {max_seq_len}): "
            + "; ".join((hbm_reasons + reasons)[:8]))

    # Deterministic rank: predicted step time, then the axis tuple so equal
    # scores (common on symmetric factorizations) break identically across
    # runs and hosts.
    scored.sort(key=lambda e: (e["predicted_step_ms"],
                               tuple(e["mesh"][ax] for ax in
                                     mesh_lib.MESH_AXES)))
    chosen = scored[0]
    return {
        "kind": "mesh_plan",
        "schema_version": SCHEMA_VERSION,
        "devices": n_devices,
        "strategy": strategy,
        "global_rows": global_rows,
        "seq_len": max_seq_len,
        "grad_accum": grad_accum,
        "device_kind": device_kind or "unknown",
        "hbm_budget_gb": (budget / GiB) if budget is not None else None,
        "n_enumerated": n_enumerated,
        "n_feasible": len(scored),
        "pruned": pruned,
        "chosen": chosen,
        "ranked": scored[:max(1, top_k)],
        "predicted_step_ms": chosen["predicted_step_ms"],
        "assumptions": {
            "score": "serial roofline: 6N-FLOPs compute + ring-collective "
                     "bytes / ICI, no overlap (comms_model assumptions)",
            "global_batch_held_fixed": True,
            "memory": "analytic params+opt+grads via PartitionSpec shard "
                      "factors; activations approximate (flash attention, "
                      "per-layer residual+MLP streams)",
        },
    }


def plan_single(
    model_config,
    axis_sizes,
    strategy: str,
    *,
    global_rows: int,
    max_seq_len: int,
    grad_accum: int,
    device_kind: str = "",
    peak_flops: Optional[float] = None,
    hbm_gb: Optional[float] = None,
    opt_state_bytes: int = 4,
    carry_cast: bool = True,
) -> Dict[str, Any]:
    """``mesh_plan`` record for ONE pinned mesh — no search.

    The predicted-vs-measured validation path: a caller scores the mesh it
    actually ran and writes the record with ``measured_step_ms`` filled, so
    ``tools/analyze.py`` can gate prediction error on real runs, not just
    on whatever ``auto`` happened to pick. Same record shape as
    :func:`plan` with a one-entry ranking (trivially its own argmin).
    No program in the tree calls it today (ROADMAP D12).
    """
    strategy = shard_lib.canonical_strategy(strategy)
    sizes = {ax: axis_sizes.get(ax, 1) for ax in mesh_lib.MESH_AXES}
    n_devices = int(np.prod(list(sizes.values())))
    param_shapes = comms_lib.abstract_params(model_config)
    entry = score_mesh(
        param_shapes, sizes, strategy,
        model_config=model_config, global_rows=global_rows,
        max_seq_len=max_seq_len, grad_accum=grad_accum,
        device_kind=device_kind, peak_flops=peak_flops,
        opt_state_bytes=opt_state_bytes, carry_cast=carry_cast)
    budget = hbm_budget_bytes(hbm_gb)
    return {
        "kind": "mesh_plan",
        "schema_version": SCHEMA_VERSION,
        "devices": n_devices,
        "strategy": strategy,
        "global_rows": global_rows,
        "seq_len": max_seq_len,
        "grad_accum": grad_accum,
        "device_kind": device_kind or "unknown",
        "hbm_budget_gb": (budget / GiB) if budget is not None else None,
        "n_enumerated": 1,
        "n_feasible": 1,
        "pruned": {"divisibility": 0, "hbm": 0},
        "chosen": entry,
        "ranked": [entry],
        "predicted_step_ms": entry["predicted_step_ms"],
        "assumptions": {
            "score": "serial roofline: 6N-FLOPs compute + ring-collective "
                     "bytes / ICI, no overlap (comms_model assumptions)",
            "global_batch_held_fixed": True,
            "memory": "analytic params+opt+grads via PartitionSpec shard "
                      "factors; activations approximate (flash attention, "
                      "per-layer residual+MLP streams)",
        },
    }


def mesh_config_for(entry: Dict[str, Any]) -> mesh_lib.MeshConfig:
    """A plan entry's mesh as a MeshConfig (for ``make_mesh``)."""
    m = entry["mesh"]
    return mesh_lib.MeshConfig(**{
        field.name: int(m.get(field.name, 1))
        for field in dataclasses.fields(mesh_lib.MeshConfig)
    })


def render_table(record: Dict[str, Any]) -> List[str]:
    """Human-readable ranked plan table for a ``mesh_plan`` record."""
    hdr = "x".join(mesh_lib.MESH_AXES)
    lines = [
        (f"mesh_plan | {record['devices']} devices, strategy "
         f"{record['strategy']}, global batch {record['global_rows']} rows, "
         f"seq {record['seq_len']}, accum {record['grad_accum']}"),
        (f"mesh_plan | {record['n_enumerated']} factorizations -> "
         f"{record['n_feasible']} feasible "
         f"(pruned: {record['pruned']['divisibility']} divisibility, "
         f"{record['pruned']['hbm']} HBM"
         + (f" @ {record['hbm_budget_gb']:.1f} GiB/device"
            if record.get("hbm_budget_gb") else "")
         + (f", {record['pruned']['excluded']} axis-excluded"
            if record["pruned"].get("excluded") else "") + ")"),
        (f"| rank | {hdr} | batch/shard | pred ms | compute ms | comms ms "
         f"| HBM GiB | bound |"),
        "|---|---|---|---|---|---|---|---|",
    ]
    for i, e in enumerate(record["ranked"]):
        m = "x".join(str(e["mesh"][ax]) for ax in mesh_lib.MESH_AXES)
        marker = " *" if i == 0 else ""
        lines.append(
            f"| {i + 1}{marker} | {m} | {e['batch_per_shard']} "
            f"| {e['predicted_step_ms']:.2f} | {e['compute_ms']:.2f} "
            f"| {e['comms_ms']:.2f} | {e['peak_hbm_gb']:.2f} "
            f"| {e['bound']} |")
    return lines
