"""Static per-step collective-traffic model for a Trainer's mesh + sharding.

Answers "why does a step cost what it costs" *before* the profiler runs:
from the mesh shape, the sharding strategy, and the abstract parameter
tree alone, predict the bytes each device moves per optimizer step on
every mesh axis — DP grad all-reduce, FSDP param all-gather / grad
reduce-scatter, TP activation all-reduces, ring-attention K/V rotation,
MoE all-to-all dispatch/combine, and pipeline stage boundary transfers —
then put that next to the analytic FLOPs as a comms-vs-compute roofline.
The MegaScale-style production question ("is this config interconnect-
bound?") becomes a one-time ``kind:"comms_model"`` JSONL record instead
of a profile-reading session.

The model is *analytic*: every formula assumes bidirectional-ring
collectives (the TPU ICI native algorithm) and no compute/comms overlap,
so the time estimates are upper bounds for classification, not step-time
predictions. ``crosscheck`` counts the collective ops GSPMD actually
inserted in the compiled HLO and flags axes the model charges traffic to
that show no matching collective (soft notes — the partitioner may
legally substitute op forms, e.g. an all-reduce for a reduce-scatter +
all-gather pair).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpu_trainer.parallel import mesh as mesh_lib
from tpu_trainer.parallel import sharding as shard_lib
from tpu_trainer.utils.logging import (
    cost_model_kind, flops_per_token, lookup_by_kind, peak_flops_for_kind)

# Gradients accumulate and reduce in float32 regardless of compute dtype.
GRAD_BYTES = 4

# Assumed per-device interconnect bandwidth (bytes/s) by device_kind
# substring, for the roofline estimate only. Aggregate ICI figures good to
# a factor of ~2 — enough to classify a config as comms- or compute-bound,
# not to predict step time. Matched like the peak-FLOPs table
# (utils.logging.lookup_by_kind); an unknown kind raises.
_ICI_BYTES_PER_SEC = {
    "v6": 1.8e11,
    "v5p": 1.2e11,
    "v5lite": 4.5e10,
    "v5e": 4.5e10,
    "v4": 1.2e11,
    "v3": 7.0e10,
    "v2": 5.0e10,
}

_HLO_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)
# Which compiled collectives each modeled axis may legitimately appear as.
_AXIS_EXPECTED_OPS = {
    "data": ("all-reduce", "reduce-scatter"),
    "fsdp": ("all-gather", "reduce-scatter", "all-reduce"),
    "tensor": ("all-reduce", "reduce-scatter", "all-gather"),
    "sequence": ("collective-permute", "all-to-all"),
    "expert": ("all-to-all", "all-gather"),
    "stage": ("collective-permute",),
}


# --- ring-collective per-device byte costs ---------------------------------

def ring_all_reduce_bytes(payload: float, n: int) -> float:
    """Ring all-reduce of ``payload`` bytes over ``n`` devices: a
    reduce-scatter then an all-gather, each moving (n-1)/n of the payload
    through every device."""
    return 2.0 * (n - 1) / n * payload if n > 1 else 0.0


def ring_all_gather_bytes(payload: float, n: int) -> float:
    """All-gather whose *result* is ``payload`` bytes: each device
    receives the (n-1)/n of it that it doesn't already hold."""
    return (n - 1) / n * payload if n > 1 else 0.0


def ring_reduce_scatter_bytes(payload: float, n: int) -> float:
    """Reduce-scatter of a ``payload``-byte addend per device: (n-1)/n of
    it leaves each device."""
    return (n - 1) / n * payload if n > 1 else 0.0


def all_to_all_bytes(payload: float, n: int) -> float:
    """All-to-all of a ``payload``-byte per-device buffer: (n-1)/n of it is
    destined for other devices."""
    return (n - 1) / n * payload if n > 1 else 0.0


def ring_sendrecv_bytes(shard_bytes: float, n: int) -> float:
    """Full ring rotation (ring attention): every device forwards its
    ``shard_bytes`` neighbour block ``n-1`` times."""
    return (n - 1) * shard_bytes if n > 1 else 0.0


# --- the model -------------------------------------------------------------

def _spec_axes(spec) -> tuple:
    axes: List[str] = []
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            axes.extend(entry)
        else:
            axes.append(entry)
    return tuple(axes)


def _shard_factor(spec, axis_sizes, exclude=()) -> int:
    f = 1
    for ax in _spec_axes(spec):
        if ax not in exclude:
            f *= axis_sizes[ax]
    return f


def _ici_bytes_per_sec(device_kind: str) -> float:
    return lookup_by_kind(_ICI_BYTES_PER_SEC, device_kind, "ICI bandwidth")


def build_core(
    param_shapes,
    axis_sizes,
    strategy: str,
    *,
    model_config,
    batch_size: int,
    max_seq_len: int,
    grad_accum: int,
    device_kind: str = "",
    peak_flops: Optional[float] = None,
) -> dict:
    """Trainer-independent core of the comms model.

    Everything the model needs is shape arithmetic over an abstract param
    tree plus the run's dimensions — no live ``Trainer`` or ``Mesh``:

    - ``param_shapes``: abstract parameter tree (``jax.eval_shape`` output);
    - ``axis_sizes``: ``{axis_name: size}`` for the six mesh axes (missing
      axes default to 1) — ``mesh.shape`` or a planner candidate;
    - ``strategy``: sharding strategy (aliases accepted);
    - ``batch_size``: per-data-shard rows per micro-batch;
    - ``device_kind`` / ``peak_flops``: roofline hardware constants, looked
      up by kind (an unknown kind raises; off-TPU the roofline is drawn for
      ``utils.logging.OFF_CHIP_MODEL_KIND`` and says so in
      ``assumptions.roofline_kind``). ``peak_flops`` overrides the table.

    This is what the mesh auto-planner (``parallel/planner.py``) scores
    candidate meshes with; :func:`build` is the thin trainer wrapper and its
    output is byte-for-byte what it always was.
    """
    strategy = shard_lib.canonical_strategy(strategy)
    mc = model_config
    d = axis_sizes.get(mesh_lib.DATA_AXIS, 1)
    f = axis_sizes.get(mesh_lib.FSDP_AXIS, 1)
    sp = axis_sizes.get(mesh_lib.SEQUENCE_AXIS, 1)
    tp = axis_sizes.get(mesh_lib.TENSOR_AXIS, 1)
    ep = axis_sizes.get(mesh_lib.EXPERT_AXIS, 1)
    st = axis_sizes.get(mesh_lib.STAGE_AXIS, 1)
    sizes = {ax: axis_sizes.get(ax, 1) for ax in mesh_lib.MESH_AXES}
    n_devices = d * f * sp * tp * ep * st
    accum = grad_accum
    rows = batch_size                         # per-data-shard rows per micro
    seq_local = max_seq_len // sp
    act_bytes = jnp.dtype(mc.compute_dtype).itemsize
    hidden = mc.hidden_size
    layers = mc.num_layers

    p_specs = shard_lib.params_specs_from_sizes(param_shapes, sizes, strategy)
    g_specs = shard_lib.grads_specs_from_sizes(param_shapes, sizes, strategy)
    params_total = int(sum(
        int(np.prod(x.shape)) if x.shape else 1
        for x in jax.tree_util.tree_leaves(param_shapes)))

    # Param-tree traffic: DP grad all-reduce + FSDP gathers/scatters.
    acc = {"data": 0.0, "fsdp_gather": 0.0, "fsdp_scatter": 0.0}
    zero2_regather = strategy == "zero2"

    def per_leaf(leaf, pspec, gspec):
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        # data axis: all-reduce of the per-device f32 grad shard (for
        # ZeRO meshes this runs on the post-reduce-scatter shard).
        gshard = size * GRAD_BYTES / _shard_factor(gspec, sizes)
        acc["data"] += ring_all_reduce_bytes(gshard, d)
        if f > 1 and mesh_lib.FSDP_AXIS in _spec_axes(gspec):
            # fsdp grad reduce-scatter, on the pre-scatter f32 payload.
            pre = size * GRAD_BYTES / _shard_factor(
                gspec, sizes, exclude=(mesh_lib.FSDP_AXIS,))
            acc["fsdp_scatter"] += ring_reduce_scatter_bytes(pre, f)
            if zero2_regather and mesh_lib.FSDP_AXIS not in _spec_axes(pspec):
                # zero2: params stay replicated, so the fsdp-sharded
                # update is all-gathered back once per step (f32).
                acc["fsdp_gather"] += ring_all_gather_bytes(pre, f)
        if f > 1 and mesh_lib.FSDP_AXIS in _spec_axes(pspec):
            # zero3 param all-gather in compute dtype (>=2-D leaves are
            # cast; scalars/vectors stay f32), once for the forward and
            # once for the backward re-gather (no full-tree liveness).
            itemsize = act_bytes if len(leaf.shape) >= 2 else 4
            pre = size * itemsize / _shard_factor(
                pspec, sizes, exclude=(mesh_lib.FSDP_AXIS,))
            acc["fsdp_gather"] += 2.0 * ring_all_gather_bytes(pre, f)

    jax.tree_util.tree_map(per_leaf, param_shapes, p_specs, g_specs)

    # tensor axis: 2 forward + 2 backward activation all-reduces per layer
    # per micro-batch (row-parallel o_proj and down_proj outputs, and their
    # grads w.r.t. the column-parallel inputs). The vocab-sharded fused
    # head reduces scalars only — excluded.
    act_payload = rows * seq_local * hidden * act_bytes
    tensor_bytes = (
        accum * layers * 4 * ring_all_reduce_bytes(act_payload, tp))

    # sequence axis: ring attention rotates each device's K/V shard around
    # the ring once per layer forward and twice backward (K/V again plus
    # the dK/dV accumulators riding the reverse ring).
    kv_shard = (2 * rows * seq_local * mc.kv_heads * mc.head_dim * act_bytes)
    seq_bytes = accum * layers * 3 * ring_sendrecv_bytes(kv_shard, sp)

    # expert axis: dispatch + combine all-to-alls, forward and backward
    # (4 total per layer per micro).  Capacity routing moves the padded
    # E*C slot buffer (top_k * capacity_factor rows per token); dropless
    # routing moves exactly the k*T routed rows — no padding factor.
    expert_bytes = 0.0
    if mc.num_experts > 0 and ep > 1:
        routed_scale = (mc.moe_top_k if mc.moe_impl == "dropless"
                        else mc.moe_top_k * mc.expert_capacity_factor)
        tok_payload = (rows * seq_local * routed_scale
                       * hidden * act_bytes)
        expert_bytes = (
            accum * layers * 4 * all_to_all_bytes(tok_payload, ep))

    # stage axis: every microbatch's activations cross each stage boundary
    # forward and backward; per device that is (st-1)/st of the per-micro
    # activation rows (the microbatch split cancels out of the total).
    stage_bytes = 0.0
    if st > 1:
        stage_bytes = (accum * 2.0 * (st - 1) / st
                       * rows * seq_local * hidden * act_bytes)

    per_axis = {
        "data": {
            "size": d,
            "collective": "grad all-reduce (ring)",
            "bytes": acc["data"],
        },
        "fsdp": {
            "size": f,
            "collective": "param all-gather + grad reduce-scatter (ring)",
            "bytes": acc["fsdp_gather"] + acc["fsdp_scatter"],
            "gather_bytes": acc["fsdp_gather"],
            "scatter_bytes": acc["fsdp_scatter"],
        },
        "tensor": {
            "size": tp,
            "collective": "activation all-reduce (ring)",
            "bytes": tensor_bytes,
        },
        "sequence": {
            "size": sp,
            "collective": "ring-attention K/V sendrecv",
            "bytes": seq_bytes,
        },
        "expert": {
            "size": ep,
            "collective": "MoE dispatch/combine all-to-all",
            "bytes": expert_bytes,
        },
        "stage": {
            "size": st,
            "collective": "pipeline boundary transfer",
            "bytes": stage_bytes,
        },
    }
    total = sum(v["bytes"] for v in per_axis.values())

    # Roofline: serial (no-overlap) comms time vs analytic compute time.
    # Drawn for the mesh's chip; off-TPU for the named model target.
    roofline_kind = cost_model_kind(device_kind)
    peak = (peak_flops if peak_flops is not None
            else peak_flops_for_kind(roofline_kind))
    ici = _ici_bytes_per_sec(roofline_kind)
    tokens_per_step = rows * accum * d * f * max_seq_len
    flops_step = flops_per_token(mc, seq_len=max_seq_len) * tokens_per_step
    per_device_flops = flops_step / n_devices
    compute_s = per_device_flops / peak
    comms_s = total / ici
    ratio = comms_s / compute_s if compute_s > 0 else float("inf")

    return {
        "kind": "comms_model",
        "mesh": sizes,
        "strategy": strategy,
        "params": params_total,
        "per_axis": per_axis,
        "total_bytes_per_device_per_step": total,
        "compute_seconds_est": compute_s,
        "comms_seconds_est": comms_s,
        "comms_compute_ratio": ratio,
        "bound": "comms" if comms_s > compute_s else "compute",
        "assumptions": {
            "collectives": "bidirectional ring, no compute/comms overlap",
            "grad_bytes": GRAD_BYTES,
            "activation_bytes": act_bytes,
            "fsdp_param_gathers_per_step": 2,
            "tp_head_excluded": "vocab-sharded fused head reduces scalars",
            "peak_flops_per_device": peak,
            "ici_bytes_per_sec": ici,
            "device_kind": device_kind or "unknown",
            "roofline_kind": roofline_kind,
        },
    }


def abstract_params(model_config):
    """Abstract parameter tree for a model config (no weights allocated).

    Exactly the tree :func:`build` derives from a live trainer — valid for
    planning because nothing mesh-dependent changes the parameter *shapes*
    (TP/FSDP change PartitionSpecs only, and the fused-projections toggle
    the Trainer flips under TP keeps the tree identical: fusion is disabled
    whenever ``tensor > 1``, and the planner follows the same rule via the
    model config it is handed).
    """
    from tpu_trainer.models.gpt import GPT

    model = GPT(model_config)
    return jax.eval_shape(
        lambda rng: model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )


def build(trainer) -> dict:
    """Analytic per-device bytes/step for every mesh axis of ``trainer``.

    Pure shape arithmetic — evaluates no step, compiles nothing (parameter
    shapes come from ``jax.eval_shape`` on ``model.init``). Returns the
    ``kind:"comms_model"`` record; the caller stamps ``step`` and logs it.

    Thin wrapper over :func:`build_core` (same output, byte for byte): it
    only extracts the trainer's abstract param tree, mesh axis sizes, and
    run dimensions.
    """
    mesh = trainer.mesh
    tc = trainer.training_config
    param_shapes = jax.eval_shape(
        lambda rng: trainer.model.init(
            rng, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    device = next(iter(mesh.devices.flat))
    return build_core(
        param_shapes,
        dict(mesh.shape),
        trainer.strategy,
        model_config=trainer.model_config,
        batch_size=tc.batch_size,
        max_seq_len=tc.max_seq_len,
        grad_accum=tc.gradient_accumulation_steps,
        device_kind=getattr(device, "device_kind", ""),
    )


def summary_lines(record: dict) -> List[str]:
    """Two human-readable stdout lines for a comms_model record."""
    active = {k: v for k, v in record["per_axis"].items() if v["bytes"] > 0}
    parts = ", ".join(
        f"{k}[{v['size']}] {v['bytes'] / 1e6:.1f} MB" for k, v in active.items()
    ) or "none (single-device or fully replicated compute)"
    lines = [
        f"comms_model | per-device traffic/step: {parts}",
        (f"comms_model | roofline: comms {record['comms_seconds_est'] * 1e3:.2f} ms"
         f" vs compute {record['compute_seconds_est'] * 1e3:.2f} ms"
         f" -> {record['bound']}-bound"
         f" (ratio {record['comms_compute_ratio']:.2f})"),
    ]
    mism = record.get("hlo_mismatches")
    if mism:
        lines.extend(f"comms_model | HLO cross-check: {m}" for m in mism)
    return lines


# --- HLO cross-check -------------------------------------------------------

_HLO_OP_RE = re.compile(
    r"(?<![%\w-])(" + "|".join(_HLO_COLLECTIVES) + r")(?:-start)?\("
)


def hlo_collective_counts(hlo_text: str) -> Dict[str, int]:
    """Count collective *instructions* in compiled HLO text.

    Matches the opcode position (``= <type> all-reduce(...)`` or the async
    ``-start`` form) and not operand references (``%all-reduce.1``) or the
    paired ``-done`` ops, so each collective is counted once.
    """
    counts = {op: 0 for op in _HLO_COLLECTIVES}
    for m in _HLO_OP_RE.finditer(hlo_text):
        counts[m.group(1)] += 1
    return counts


def crosscheck(record: dict, hlo_text: str) -> dict:
    """Compare the model against the collectives GSPMD actually inserted.

    Soft validation: for every axis the model charges bytes to, at least
    one of the collective forms that axis can legally compile to must
    appear in the HLO. Returns ``{"hlo_collective_counts", "hlo_mismatches"}``
    for the caller to merge into the record.
    """
    counts = hlo_collective_counts(hlo_text)
    mismatches = []
    for axis, info in record["per_axis"].items():
        if info["bytes"] <= 0:
            continue
        expected = _AXIS_EXPECTED_OPS[axis]
        if not any(counts.get(op, 0) > 0 for op in expected):
            mismatches.append(
                f"modeled {info['bytes']:.3g} B/step on axis '{axis}' but "
                f"none of {expected} appear in the compiled HLO")
    return {"hlo_collective_counts": counts, "hlo_mismatches": mismatches}
