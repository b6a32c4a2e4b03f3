"""Device mesh and multi-host communication backend.

The TPU-native replacement for the reference's NCCL/`torch.distributed` layer
(SURVEY.md C12; reference ``ddp_trainer.py:93-113``, ``fsdp_trainer.py:125-138``):

- rendezvous: ``jax.distributed.initialize()`` (↔ ``init_process_group("nccl")``)
- rank/world discovery: ``jax.process_index()/process_count()`` (↔ RANK/WORLD_SIZE env)
- the collective fabric: a ``jax.sharding.Mesh`` over ICI (intra-slice) and DCN
  (inter-slice); gradients/params move via XLA-inserted collectives, not
  explicit NCCL calls
- barrier: ``multihost_utils.sync_global_devices`` (↔ ``dist.barrier()``)
- broadcast: ``multihost_utils.broadcast_one_to_all``
  (↔ ``dist.broadcast_object_list``)

Mesh axes:

- ``data``  — pure data parallelism (DDP replica axis; grads all-reduced).
- ``fsdp``  — parameter/optimizer sharding axis (ZeRO); also carries data
  (batch is sharded over ``data × fsdp`` jointly, exactly like torch FSDP
  where every rank is both a data rank and a shard rank).
- ``tensor`` — tensor-parallel axis (op sharding inside a layer).

``data > 1`` with ``fsdp > 1`` gives HYBRID_SHARD — documented-but-broken in
the reference (docstring-only, ``fsdp_trainer.py:258-261``; SURVEY.md §2) and
a real mode here.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.experimental import multihost_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
SEQUENCE_AXIS = "sequence"
TENSOR_AXIS = "tensor"
EXPERT_AXIS = "expert"
STAGE_AXIS = "stage"
MESH_AXES = (
    DATA_AXIS, FSDP_AXIS, SEQUENCE_AXIS, TENSOR_AXIS, EXPERT_AXIS, STAGE_AXIS,
)
# The one axis of a serving replica's decode mesh (``tp_mesh`` below): the
# paged model step and ``serving/sharding.py`` shard heads and KV pools over
# it. Not one of the training mesh's axes.
TP_AXIS = "tp"


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """How to carve the device fleet into parallelism axes.

    ``-1`` means "all remaining devices" (at most one axis may be -1).
    """

    data: int = -1
    fsdp: int = 1
    sequence: int = 1
    tensor: int = 1
    expert: int = 1
    stage: int = 1

    def resolve(self, n_devices: int) -> tuple:
        sizes = [self.data, self.fsdp, self.sequence, self.tensor,
                 self.expert, self.stage]
        n_auto = sum(1 for s in sizes if s == -1)
        if n_auto > 1:
            raise ValueError("at most one mesh axis may be -1")
        fixed = int(np.prod([s for s in sizes if s != -1]))
        if n_auto == 1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes = [n_devices // fixed if s == -1 else s for s in sizes]
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} wants {fixed} devices but {n_devices} are available"
            )
        return tuple(sizes)


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    auto: Optional[bool] = None,
) -> None:
    """Multi-host rendezvous (↔ reference ``dist.init_process_group``).

    Three modes:

    - explicit: pass coordinator/num_processes/process_id (or set the
      ``COORDINATOR_ADDRESS`` / ``NUM_PROCESSES`` / ``PROCESS_ID`` env vars);
    - ``auto=True`` (the CLI's ``--multihost`` flag): call the no-arg
      ``jax.distributed.initialize()``, which autodetects the topology on
      Cloud TPU / SLURM / GKE;
    - default: autodetect is attempted only when a Cloud TPU multi-host
      environment is visible (so single-host runs stay zero-config no-ops).
    """
    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    num_processes = num_processes or _int_env("NUM_PROCESSES")
    process_id = process_id if process_id is not None else _int_env("PROCESS_ID")
    if coordinator_address or num_processes:
        if (num_processes or 0) > 1:
            # CPU cross-process collectives default to "none" on this jax,
            # which makes any multi-process computation fail with
            # "Multiprocess computations aren't implemented on the CPU
            # backend". Selecting gloo before backend init turns the
            # supervisor's N-process CPU rendezvous (and the elastic chaos
            # tests) into a real collective fabric. Must happen before the
            # first backend instantiation; harmless on TPU (ignored).
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        kwargs = {}
        timeout_s = _int_env("COORDINATOR_TIMEOUT_S")
        if timeout_s is not None:
            # Bounded rendezvous: a peer that died before reaching
            # initialize() must surface as an error the run supervisor can
            # see, not an indefinite hang of the surviving processes.
            kwargs["initialization_timeout"] = timeout_s
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            **kwargs,
        )
        return
    if auto is None:
        hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
        auto = len([h for h in hostnames.split(",") if h]) > 1
    if auto:
        jax.distributed.initialize()


def _int_env(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v is not None else None


def make_mesh(
    config: MeshConfig = MeshConfig(),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the device mesh.

    ``mesh_utils.create_device_mesh`` lays ranks out so that the innermost
    axes map onto physically adjacent devices — collectives on ``tensor`` and
    ``fsdp`` ride ICI; ``data`` (outermost) crosses DCN on multi-slice.
    """
    devices = list(devices if devices is not None else jax.devices())
    shape = config.resolve(len(devices))
    if len(devices) == 1:
        device_array = np.array(devices).reshape(shape)
    else:
        device_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(device_array, MESH_AXES)


def batch_spec() -> P:
    """PartitionSpec for a ``[accum, batch, seq]`` micro-batched step input:
    batch is sharded over data × fsdp jointly (every device holds a distinct
    slice of the global batch — the FSDP world is also the data world, as in
    torch FSDP); the sequence dim shards over the ring-attention axis."""
    return P(None, (DATA_AXIS, FSDP_AXIS), SEQUENCE_AXIS)


def batch_spec_2d() -> P:
    """PartitionSpec for a plain ``[batch, seq]`` batch (eval/inference)."""
    return P((DATA_AXIS, FSDP_AXIS), SEQUENCE_AXIS)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec())


def dp_size(mesh: Mesh) -> int:
    """Number of distinct data shards (data × fsdp axes)."""
    return mesh.shape[DATA_AXIS] * mesh.shape[FSDP_AXIS]


def host_feed_info(sharding, global_shape, row_dim: int,
                   process_of_device=None, process_index=None):
    """Which batch-row slice this host must load: ``(feed_rank, feed_world)``.

    Derived from the sharding's device->index map, so it is correct for any
    mesh topology: hosts whose devices address the same global row range
    form one feed group and must load IDENTICAL rows (e.g. a sequence or
    tensor axis spanning hosts — the long-context pod layout); hosts with
    disjoint ranges get consecutive ranks ordered by row start. For the
    common dp%hosts==0 layout this degenerates to
    ``(process_index, process_count)``.

    ``process_of_device`` / ``process_index`` are injectable for tests
    (simulating a multi-host device->process assignment on one real
    process).

    Raises if the distinct host row-coverages do not form an ordered
    equal-size partition of the rows — then no consistent loader sharding
    exists for this mesh layout.
    """
    pod = process_of_device or (lambda d: d.process_index)
    pidx = jax.process_index() if process_index is None else process_index
    rows_total = global_shape[row_dim]
    cover = {}
    for dev, idx in sharding.devices_indices_map(tuple(global_shape)).items():
        sl = idx[row_dim]
        start = 0 if sl.start is None else sl.start
        stop = rows_total if sl.stop is None else sl.stop
        cover.setdefault(pod(dev), set()).add((int(start), int(stop)))

    def span(ranges):
        # Each host's covered rows must be one contiguous run.
        rs = sorted(ranges)
        lo, hi = rs[0][0], rs[0][1]
        for a, b in rs[1:]:
            if a > hi:
                raise ValueError(
                    f"host row coverage {rs} is not contiguous — this mesh "
                    f"device layout interleaves data shards within a host; "
                    f"no consistent data feeding order exists"
                )
            hi = max(hi, b)
        return (lo, hi)

    spans = {p: span(rngs) for p, rngs in cover.items()}
    groups = sorted(set(spans.values()))
    size = groups[0][1] - groups[0][0]
    for g, (lo, hi) in enumerate(groups):
        if lo != g * size or hi - lo != size:
            raise ValueError(
                f"host row spans {groups} do not partition {rows_total} rows "
                f"into equal ordered slices; no consistent data feeding "
                f"order exists for this mesh layout"
            )
    if pidx not in spans:
        raise ValueError(f"process {pidx} holds no addressable batch rows")
    return groups.index(spans[pidx]), len(groups)


def attention_shard_spec(mesh: Mesh, batch: int, heads: int,
                         kv_heads: Optional[int] = None):
    """PartitionSpec components for ``[b, s, h, d]`` attention operands.

    Attention is independent across batch and heads, so those dims shard
    losslessly: batch over ``data x fsdp`` (every device is both a data and
    a shard rank, as in torch FSDP) and heads over ``tensor``. An axis whose
    size doesn't divide the dim (tiny test batches) falls back to
    replicated. Shared by the flash-kernel shard_map wrapper
    (``ops/attention.py``) and ring attention (``ops/ring.py``).

    Under GQA pass ``kv_heads``: heads shard over ``tensor`` only when the
    K/V heads divide too — a manual region whose q-head shard doesn't own
    its group's K/V head would read the wrong one.

    Returns ``(b_spec, h_spec)`` — each an axis (tuple) or None.
    """
    dp = mesh.shape[DATA_AXIS] * mesh.shape[FSDP_AXIS]
    b_spec = (DATA_AXIS, FSDP_AXIS) if (dp > 1 and batch % dp == 0) else None
    tp = mesh.shape[TENSOR_AXIS]
    kv_heads = heads if kv_heads is None else kv_heads
    h_spec = (
        TENSOR_AXIS
        if (tp > 1 and heads % tp == 0 and kv_heads % tp == 0)
        else None
    )
    return b_spec, h_spec


def attention_shard_coord(mesh: Mesh, b_spec, h_spec):
    """Linearized coordinate of this shard along the axes that actually
    shard the attention inputs (0 when none). Must be called inside the
    shard_map body. Folding this into a dropout PRNG key decorrelates masks
    across shards — and *only* across sharded axes: folding a replicated
    axis's coordinate would make devices along it compute different outputs
    for identical data, breaking the replicated out_spec.
    """
    coord = 0
    if b_spec is not None:
        for ax in (DATA_AXIS, FSDP_AXIS):
            coord = coord * mesh.shape[ax] + jax.lax.axis_index(ax)
    if h_spec is not None:
        coord = coord * mesh.shape[TENSOR_AXIS] + jax.lax.axis_index(
            TENSOR_AXIS
        )
    return coord


def validate_tp(num_heads: int, kv_heads: int, tp: int) -> None:
    """The head-sharding feasibility rule: Q heads split evenly over the
    mesh, and KV heads either split evenly too or are replicated with
    whole Q-head groups per device (``tp % kv_heads == 0``)."""
    if tp < 1:
        raise ValueError(f"paged_tp={tp} < 1")
    if tp == 1:
        return
    if num_heads % tp:
        raise ValueError(
            f"paged_tp={tp} does not divide num_heads={num_heads}")
    if kv_heads % tp and tp % kv_heads:
        raise ValueError(
            f"paged_tp={tp} vs kv_heads={kv_heads}: need kv_heads % tp "
            f"== 0 (sharded KV) or tp % kv_heads == 0 (replicated KV, "
            f"GQA)")


def resolve_devices(tp: int,
                    device_ids: Optional[Sequence[int]] = None) -> tuple:
    """The device set backing a tp-way mesh: explicit ids when the worker
    spec names them (one fleet, disjoint meshes), else the first ``tp``
    visible devices."""
    devs = jax.devices()
    if device_ids:
        by_id = {d.id: d for d in devs}
        missing = [i for i in device_ids if i not in by_id]
        if missing:
            raise ValueError(
                f"device ids {missing} not visible (have "
                f"{sorted(by_id)}); is XLA_FLAGS="
                f"--xla_force_host_platform_device_count set?)")
        devs = [by_id[int(i)] for i in device_ids]
    if len(devs) < tp:
        raise ValueError(f"paged_tp={tp} > {len(devs)} visible devices")
    return tuple(devs[:tp])


@functools.lru_cache(maxsize=None)
def tp_mesh(tp: int,
            device_ids: Optional[Tuple[int, ...]] = None) -> Mesh:
    """The (cached) single-axis decode mesh. Caching matters twice over:
    mesh construction is not free, and the jitted-step memo keys on the
    config's ``(paged_tp, paged_tp_devices)`` — one mesh object per key
    keeps placements stable across steps."""
    return Mesh(np.array(resolve_devices(tp, device_ids)), (TP_AXIS,))


def barrier(name: str = "barrier") -> None:
    """Cross-host barrier (↔ ``dist.barrier()``, reference fsdp_trainer.py:465)."""
    if jax.process_count() > 1:
        multihost_utils.sync_global_devices(name)


def global_any(flag: bool) -> bool:
    """True on every host iff ``flag`` is True on any host — the coordination
    primitive for preemption (one host's SIGTERM must make *all* hosts enter
    the collective checkpoint save together, or the save deadlocks)."""
    if jax.process_count() <= 1:
        return flag
    votes = multihost_utils.process_allgather(np.asarray([bool(flag)]))
    return bool(np.any(votes))


def shutdown_distributed() -> None:
    """Best-effort clean exit from the rendezvous — the proactive-drain path
    (``utils/preemption.py`` notice → checkpoint → deregister → exit) calls
    this so the coordinator sees an orderly departure instead of a dropped
    connection. Failures are swallowed: the process is exiting either way,
    and a drain must never turn into a crash over coordinator teardown."""
    try:
        if jax.process_count() > 1:
            jax.distributed.shutdown()
    except Exception:
        pass


def broadcast_from_host0(pytree):
    """Host-0 → all hosts value broadcast
    (↔ ``dist.broadcast_object_list``, reference fsdp_trainer.py:469-478)."""
    if jax.process_count() > 1:
        return multihost_utils.broadcast_one_to_all(pytree)
    return pytree
