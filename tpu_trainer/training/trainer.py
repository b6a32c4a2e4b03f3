"""The trainer: one jitted train_step for every parallelism strategy.

TPU-native re-design of the reference's two trainer classes
(``DistributedTrainer``, ``ddp_trainer.py:66-456``; ``FSDPTrainer``,
``fsdp_trainer.py:53-505``). The load-bearing property of the reference —
*the model is parallelism-blind; the runtime layer decides placement*
(SURVEY.md §1) — becomes literal here: DDP and FSDP are the **same**
``train_step``, differing only in the NamedShardings handed to ``jax.jit``.

Key mappings (SURVEY.md C9/C10/C15/C16):

- DDP's ``no_sync`` + final-micro-step all-reduce (``ddp_trainer.py:329-342``)
  → ``lax.scan`` over micro-batches accumulating local grads, one reduction
  at the end (the no_sync equivalent is free — XLA reduces once, after the
  scan, because that's where the grads are first consumed).
- FSDP's per-module all-gather / reduce-scatter (``fsdp_trainer.py:369-384``)
  → GSPMD-inserted collectives from the param/grad shardings; overlap comes
  from XLA's latency-hiding scheduler (↔ ``backward_prefetch``).
- ``clip_grad_norm_`` (``ddp_trainer.py:347-350``, ``fsdp_trainer.py:386-388``)
  → ``optax.clip_by_global_norm`` inside the chain (global norm over sharded
  trees = partial norms + psum, emitted automatically).
- fp16 ``GradScaler`` (``ddp_trainer.py:152``) → dynamic loss scaling done
  functionally in-step (scale up on a run of finite steps, halve + skip the
  update on overflow). bf16 needs none of this (TPU-native recipe:
  fp32 params, bf16 compute).
- LR is applied per-step as a pure function of ``state.step`` inside the
  optimizer — fixing the reference's set-after-step off-by-one (§2.1 b1).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Optional, Tuple

import flax.struct as struct
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_trainer.models.config import GPTConfig
from tpu_trainer.models.gpt import GPT
from tpu_trainer.ops import ring
from tpu_trainer.parallel import context as ctx_lib
from tpu_trainer.parallel import mesh as mesh_lib
from tpu_trainer.parallel import sharding as shard_lib
from tpu_trainer.training.config import TrainingConfig
from tpu_trainer.training.optimizer import make_optimizer
from tpu_trainer.utils import profiling, telemetry

_MP_TO_DTYPE = {"fp32": "float32", "bf16": "bfloat16", "fp16": "float16"}


@jax.custom_vjp
def _linked_cast(master, casted):
    """Use a precomputed compute-dtype param copy, gradients to the master.

    Forward returns ``casted`` (== the compute-dtype cast of ``master``,
    produced inside the PREVIOUS step's optimizer-update fusion — see
    ``TrainState.params_c``); the backward converts the cotangents to the
    master's f32, which is exactly the transpose of the cast this replaces,
    so XLA fuses it into the dW producers the same way it fused the
    original cast-transpose. Numerics are identical to casting ``master``
    in-place.
    """
    return casted


def _linked_cast_fwd(master, casted):
    return casted, None


def _linked_cast_bwd(_, g):
    # master's cotangent: the cast-transpose (convert to f32). casted is a
    # derived constant at every call site; its zero cotangent is dead code
    # the compiler drops.
    return (
        jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), g),
        jax.tree_util.tree_map(jnp.zeros_like, g),
    )


_linked_cast.defvjp(_linked_cast_fwd, _linked_cast_bwd)


# Blockwise int8 quantization now lives in utils/quant.py (shared with the
# on-device quantized Adam state); re-exported here for its established
# import path (tests/test_offload.py, validate.py).
from tpu_trainer.utils.quant import (  # noqa: E402,F401
    QuantPack,
    dequantize_blockwise_int8,
    quantize_blockwise_int8,
)


def _split_packed(batch: jax.Array):
    """``[rows, seq]`` → ``(tokens, None)``; packed ``[rows, seq, 2]`` →
    ``(tokens, segment_ids)``. The channel-last convention: ``[..., 0]`` is
    token ids, ``[..., 1]`` is segment ids (0 = padding, docs 1..K)."""
    if batch.ndim >= 3 and batch.shape[-1] == 2:
        return batch[..., 0], batch[..., 1]
    return batch, None


def _path_keys(path) -> tuple:
    """Pytree path -> hashable tuple of key strings."""
    return tuple(
        str(getattr(p, "key", getattr(p, "name", getattr(p, "idx", ""))))
        for p in path
    )


def select_resident_moments(opt_shapes, budget_bytes: int,
                            shard_count: int = 1):
    """Partial-offload selection: which optimizer-state leaves stay on
    device under a byte budget (VERDICT r4 #3).

    Greedy largest-first over the float ndim>=1 leaves (the stream is
    volume-bound, so the biggest leaves buy the most link traffic per
    selection; Adam's mu/nu for one param are equal-sized and selected
    together or not at all only by budget coincidence — fine, each leaf
    streams independently). Scalars never stream anyway. Returns
    ``(frozenset of path-key tuples, bytes kept)``.

    ``shard_count`` is the fsdp axis size under zero2/zero3, where the
    moments are fsdp-sharded: a kept leaf then costs ``size /
    shard_count`` bytes of *per-device* HBM, which is what the
    ``--opt_resident_gb`` budget and the startup line describe. Leaves no
    dim of which divides the axis stay replicated and cost full size
    (same shape-only rule as ``shard_lib.fsdp_spec``; a leaf that is
    *additionally* tensor-sharded is counted conservatively at its
    fsdp-only shard size).
    """
    cands = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(opt_shapes)[0]:
        if (getattr(leaf, "ndim", 0) >= 1
                and jnp.issubdtype(leaf.dtype, jnp.floating)):
            size = leaf.size * jnp.dtype(leaf.dtype).itemsize
            if (shard_count > 1 and shard_lib.FSDP_AXIS
                    in tuple(shard_lib.fsdp_spec(leaf.shape, shard_count))):
                size = -(-size // shard_count)  # ceil: per-device bytes
            cands.append((_path_keys(path), size))
    cands.sort(key=lambda kv: (-kv[1], kv[0]))
    keep, used = set(), 0
    for pk, sz in cands:
        if used + sz <= budget_bytes:
            keep.add(pk)
            used += sz
    return frozenset(keep), used

_SCALE_GROWTH_INTERVAL = 2000  # steps of finite grads before doubling
_MAX_LOSS_SCALE = 2.0**16
_INIT_LOSS_SCALE = 2.0**15


class TrainState(struct.PyTreeNode):
    """Everything that evolves across steps (the checkpointable unit —
    reference checkpoint dict, ``ddp_trainer.py:408-415``)."""

    step: jax.Array            # int32 scalar
    params: Any
    opt_state: Any
    rng: jax.Array             # dropout PRNG key chain
    loss_scale: jax.Array      # float32 scalar (fp16 dynamic scaling; 1.0 else)
    good_steps: jax.Array      # int32: consecutive finite-grad steps (fp16)
    # Compute-dtype copy of the >=2-D params (None when inactive). The
    # f32->bf16 cast of the full parameter tree used to run as separate
    # convert passes at the top of every step (~1.7 ms at headline
    # geometry: the cast lives in the NEXT step's executable, so XLA
    # cannot fuse it into the optimizer-update fusions that produced the
    # params). Carrying the cast in the state moves it into the update
    # fusion's epilogue. Derived data: excluded from checkpoints
    # (utils/checkpoint.py strips it on save and rebuilds on restore), so
    # the checkpoint format is unchanged and pre-round-4 checkpoints
    # restore cleanly.
    params_c: Any = None


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """How to parallelize: mesh shape + ZeRO mode.

    - DDP (reference ddp_trainer): ``MeshConfig(data=-1)`` + ``"replicated"``.
    - FSDP (reference fsdp_trainer): ``MeshConfig(fsdp=-1)`` + one of
      ``zero3`` (FULL_SHARD) / ``zero2`` (SHARD_GRAD_OP) /
      ``replicated`` (NO_SHARD); reference spellings accepted.
    - HYBRID_SHARD: both axes > 1.
    - ``cpu_offload`` (reference ``FSDPConfig.cpu_offload``,
      ``fsdp_trainer.py:62-63,299-301``): optimizer state lives in host
      memory (``pinned_host``) and is streamed to the device inside the
      jitted step only for the update — the TPU analogue of torch FSDP's
      ``CPUOffload``, trading step time for 2x param-bytes of HBM.
    - ``offload_dtype``: storage dtype for the host-resident optimizer
      state. The offloaded step is host-link *volume* bound (measured:
      the f32 Adam m/v round trip, 16 bytes/param/step, runs at ~7 GB/s
      effective through this host link — over a second per step at 1B
      params — while the update compute is ~0.1 s; overlap alone cannot
      help when the stream is 10x the compute). ``"bfloat16"`` halves the
      stream: m/v are cast once after each update and reconstructed to
      f32 on device before the next (one rounding per step — the same
      tradeoff as 8-bit optimizer states, milder). ``"int8"`` quarters
      it: ndim>=2 moment leaves quantize to blockwise-absmax int8 along
      their last dim (block 256; ~0.4% relative error per block), with
      Adam's nonnegative second moment quantized in sqrt-space — v only
      enters the update through sqrt(v), so the 8 bits cover half the
      log-range (the bitsandbytes dynamic-quantization motivation).
      Default f32 keeps the offloaded step bitwise-identical to the
      on-device one.
    - ``offload_budget_gb`` (round 5, VERDICT r4 #3 — partial offload):
      GB of optimizer-moment leaves allowed to REMAIN device-resident,
      largest-first; only the overflow streams over the host link. The
      stream is volume-bound, so every resident GB is ~2 GB/step less
      link traffic at f32 (read + write) — resident leaves skip the
      storage transform entirely and keep the bitwise-f32 contract.
      0 = classic full offload.
    """

    mesh: mesh_lib.MeshConfig = mesh_lib.MeshConfig()
    sharding_strategy: str = "replicated"
    cpu_offload: bool = False
    offload_dtype: str = "float32"
    offload_budget_gb: float = 0.0


class Trainer:
    """Owns the mesh, the jitted step, and state initialization.

    Public interface mirrors the reference trainer (SURVEY.md §1 L4):
    ``init_state()``, ``train_step(state, batch) -> (state, metrics)``,
    ``put_batch``, plus ``process_index/process_count`` for rank discovery.
    """

    def __init__(
        self,
        model_config: GPTConfig,
        training_config: TrainingConfig = TrainingConfig(),
        parallel_config: ParallelConfig = ParallelConfig(),
        mesh: Optional[Mesh] = None,
    ):
        # Mixed-precision policy → model compute dtype (reference
        # ddp_trainer.py:115-156 autocast selection).
        dtype = _MP_TO_DTYPE[training_config.mixed_precision]
        self.model_config = dataclasses.replace(model_config, dtype=dtype)
        self.training_config = training_config
        self.parallel_config = parallel_config
        self.strategy = shard_lib.canonical_strategy(parallel_config.sharding_strategy)
        self.use_loss_scaling = training_config.mixed_precision == "fp16"

        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh(parallel_config.mesh)
        self.sp_size = self.mesh.shape[mesh_lib.SEQUENCE_AXIS]
        if self.sp_size > 1 and training_config.max_seq_len % self.sp_size != 0:
            raise ValueError(
                f"max_seq_len {training_config.max_seq_len} not divisible by "
                f"sequence axis size {self.sp_size}"
            )
        # Data feeding works on ANY mesh/host layout: each host's feed rank
        # is derived from which global batch rows its devices address
        # (mesh_lib.host_feed_info). Hosts under a sequence/tensor axis that
        # spans hosts share a feed rank and load identical rows; hosts under
        # data/fsdp axes get disjoint ranks — the round-2
        # dp_size-must-partition-hosts restriction is gone.
        self.ep_size = self.mesh.shape.get(mesh_lib.EXPERT_AXIS, 1)
        if self.ep_size > 1:
            if self.model_config.num_experts <= 0:
                raise ValueError(
                    "expert mesh axis > 1 requires a MoE model "
                    "(GPTConfig.num_experts > 0)"
                )
            if self.model_config.num_experts % self.ep_size != 0:
                raise ValueError(
                    f"num_experts {self.model_config.num_experts} not "
                    f"divisible by expert axis size {self.ep_size}"
                )
            if self.model_config.moe_experts_held is not None:
                raise ValueError(
                    "moe_experts_held names this chip's share of the "
                    "experts; it does not compose with an expert mesh "
                    f"axis of {self.ep_size}")
        if not self.model_config.uniform_layers:
            for axis in (mesh_lib.STAGE_AXIS, mesh_lib.SEQUENCE_AXIS):
                if self.mesh.shape.get(axis, 1) > 1:
                    raise ValueError(
                        f"a model whose layers differ (layer_types / "
                        f"num_dense_layers) does not run under a {axis!r} "
                        f"mesh axis > 1: the pipeline and the ring "
                        f"schedule one stacked block")
        if self.model_config.has_mamba and (
                self.mesh.shape.get(mesh_lib.TENSOR_AXIS, 1) > 1):
            raise ValueError(
                "mamba layers train under 'data', 'fsdp' and 'expert' mesh "
                "axes: in_proj's [z, xBC, dt] columns, the taps and the "
                "heads' A_log / dt_bias / D have no tensor-parallel rule "
                f"(got 'tensor' = {self.mesh.shape[mesh_lib.TENSOR_AXIS]})")
        if self.model_config.has_linear_attention and (
                self.mesh.shape.get(mesh_lib.TENSOR_AXIS, 1) > 1):
            raise ValueError(
                "linear_attention layers train under 'data', 'fsdp' and "
                "'expert' mesh axes: in_proj_qkvz's columns grouped by key "
                "head, the taps and the heads' A_log / dt_bias have no "
                "tensor-parallel rule (got 'tensor' = "
                f"{self.mesh.shape[mesh_lib.TENSOR_AXIS]})")
        if self.model_config.latent_attention:
            for axis in (mesh_lib.TENSOR_AXIS, mesh_lib.SEQUENCE_AXIS,
                         mesh_lib.STAGE_AXIS, mesh_lib.EXPERT_AXIS):
                if self.mesh.shape.get(axis, 1) > 1:
                    raise ValueError(
                        f"latent attention (kv_lora_rank) trains under "
                        f"'data' and 'fsdp' mesh axes only; got {axis!r} = "
                        f"{self.mesh.shape[axis]}")
        self.tp_size = self.mesh.shape[mesh_lib.TENSOR_AXIS]
        if self.tp_size > 1:
            if self.model_config.num_heads % self.tp_size != 0:
                raise ValueError(
                    f"num_heads {self.model_config.num_heads} not divisible "
                    f"by tensor axis size {self.tp_size}"
                )
            if self.model_config.kv_heads % self.tp_size != 0:
                raise ValueError(
                    f"num_kv_heads {self.model_config.kv_heads} not "
                    f"divisible by tensor axis size {self.tp_size} (each "
                    f"tensor shard must own whole K/V-head groups)"
                )
            # TP shards the q/k/v (and gate/up) kernels along their output
            # dim — the axis fused_projections concatenates. Fusing there
            # would force GSPMD to gather the shards; keep the narrow
            # per-projection matmuls, which shard cleanly.
            if self.model_config.fused_projections:
                self.model_config = dataclasses.replace(
                    self.model_config, fused_projections=False
                )
        self.stage_size = self.mesh.shape.get(mesh_lib.STAGE_AXIS, 1)
        if self.stage_size > 1:
            # Pipeline parallelism (parallel/pipeline.py): contiguous layer
            # blocks per stage, GPipe microbatches within each step.
            if self.model_config.num_layers % self.stage_size != 0:
                raise ValueError(
                    f"num_layers {self.model_config.num_layers} not divisible "
                    f"by stage axis size {self.stage_size}"
                )
            # SP x PP composes for BOTH schedules: the pipeline's shard_map
            # goes jointly manual over {stage, sequence} and the ring runs
            # unrolled inside it (models/gpt.py pipeline branch /
            # pipeline_1f1b_value_and_grad, ring.ring_attention_manual) —
            # the round-2 guard against Shardy's nested manual-region
            # binding and round-3's 1f1b-specific guards are gone. MoE
            # rides either schedule (the aux loss is threaded through the
            # manual backward under 1f1b).
            microbatches = (self.model_config.pipeline_microbatches
                            or self.stage_size)
            if self.model_config.pipeline_schedule == "interleaved":
                vst = self.model_config.pipeline_virtual_stages
                if self.model_config.num_layers % (self.stage_size * vst):
                    raise ValueError(
                        f"num_layers {self.model_config.num_layers} not "
                        f"divisible by stages*virtual "
                        f"({self.stage_size}*{vst})"
                    )
                if microbatches % self.stage_size:
                    raise ValueError(
                        f"interleaved schedule needs pipeline_microbatches "
                        f"({microbatches}) divisible by the stage count "
                        f"({self.stage_size})"
                    )
            global_rows = (training_config.batch_size
                           * mesh_lib.dp_size(self.mesh))
            if global_rows % microbatches != 0:
                raise ValueError(
                    f"global batch {global_rows} rows (batch_size "
                    f"{training_config.batch_size} x {mesh_lib.dp_size(self.mesh)} "
                    f"data shards) not divisible by pipeline_microbatches "
                    f"{microbatches}"
                )
        self.model = GPT(self.model_config)
        self.optimizer = make_optimizer(training_config)

        # Carry the compute-dtype param copy in the state (see
        # TrainState.params_c / TrainingConfig.carry_cast_params): only
        # meaningful when compute and param dtypes differ, and skipped
        # under cpu_offload — those configs run at the HBM edge and the
        # extra copy is the marginal GB while the stream dwarfs the cast.
        self._carry_cast = (
            training_config.carry_cast_params
            and self.model_config.compute_dtype
            != self.model_config.params_dtype
            and not parallel_config.cpu_offload
            # The pipeline's manual schedules take the f32 master and
            # manage their own stage-local casts; keep their param flow
            # unchanged.
            and self.stage_size == 1
        )

        # cpu_offload viability + host storage dtype must be known before
        # state shapes are traced (_make_state casts the stored state).
        self.cpu_offload = parallel_config.cpu_offload
        if (self.cpu_offload
                and training_config.optimizer_state_dtype != "float32"):
            raise ValueError(
                "cpu_offload streams the optimizer state from host storage "
                "(--offload_dtype controls its width there); combine it "
                "with optimizer_state_dtype=float32 — the on-device "
                "quantized state targets HBM traffic, which offloaded "
                "state does not generate"
            )
        if self.cpu_offload:
            kinds = {
                m.kind for d in self.mesh.devices.flat
                for m in d.addressable_memories()
            }
            platform = next(iter(self.mesh.devices.flat)).platform
            multi = self.mesh.size > 1
            if platform == "tpu" and "pinned_host" not in kinds:
                raise RuntimeError(
                    "cpu_offload requested but this TPU runtime exposes no "
                    f"pinned_host memory space (has: {sorted(kinds)})")
            if "pinned_host" not in kinds or (platform == "cpu" and multi):
                import warnings

                warnings.warn(
                    "cpu_offload requested but this backend cannot host-"
                    "offload here (no pinned_host memory space, or the CPU "
                    "SPMD partitioner's UNIMPLEMENTED multi-device "
                    "placement); keeping optimizer state on device",
                    stacklevel=2,
                )
                self.cpu_offload = False
        # Host-side storage for offloaded optimizer state: "bfloat16" halves
        # the host-link stream; "int8" quarters it via blockwise-absmax
        # quantization (mu symmetric, nu in sqrt-space) — see
        # ParallelConfig docstring.
        self._offload_quant = (
            self.cpu_offload and parallel_config.offload_dtype == "int8"
        )
        self._offload_cast = (
            jnp.dtype(parallel_config.offload_dtype)
            if self.cpu_offload and not self._offload_quant
            and parallel_config.offload_dtype != "float32" else None
        )
        # Partial offload (offload_budget_gb): leaves in _offload_keep stay
        # device-resident in exact f32. Selection needs the optimizer-state
        # shapes BEFORE _make_state is traced (its _offload_store consults
        # the keep set), hence this separate abstract trace.
        self._offload_keep = frozenset()
        self.offload_resident_bytes = 0  # surfaced in the CLI startup line
        if self.cpu_offload and parallel_config.offload_budget_gb > 0:
            p_shapes = jax.eval_shape(
                lambda rng: self.model.init(
                    rng, jnp.zeros((1, 8), jnp.int32))["params"],
                jax.random.PRNGKey(0),
            )
            opt_shapes = jax.eval_shape(self.optimizer.init, p_shapes)
            # Under zero2/zero3 the moments are fsdp-sharded: budget the
            # PER-DEVICE shard bytes, not the global leaf bytes, so
            # --opt_resident_gb and the startup line match actual HBM.
            fsdp_shards = (
                self.mesh.shape[shard_lib.FSDP_AXIS]
                if self.strategy in ("zero2", "zero3") else 1
            )
            self._offload_keep, self.offload_resident_bytes = (
                select_resident_moments(
                    opt_shapes,
                    int(parallel_config.offload_budget_gb * 2**30),
                    shard_count=fsdp_shards,
                )
            )

        # --- shardings, from shapes only (no allocation) -------------------
        state_shapes = jax.eval_shape(self._make_state, jax.random.PRNGKey(0))
        # Compute-side dtypes of the optimizer state (pre-storage-cast), for
        # reconstructing f32 state on device each step.
        self._opt_compute_dtypes = jax.tree_util.tree_map(
            lambda s: s.dtype,
            jax.eval_shape(self.optimizer.init, state_shapes.params),
        )
        replicated = P()
        param_specs = shard_lib.params_specs(
            state_shapes.params, self.mesh, self.strategy
        )
        self._state_specs = TrainState(
            step=replicated,
            params=param_specs,
            opt_state=shard_lib.opt_state_specs(
                state_shapes.opt_state, self.mesh, self.strategy
            ),
            rng=replicated,
            loss_scale=replicated,
            good_steps=replicated,
            # params_c mirrors the params' placement leaf for leaf (same
            # tree, same shapes, compute dtype).
            params_c=param_specs if self._carry_cast else None,
        )
        self.state_shardings = shard_lib.to_shardings(self._state_specs, self.mesh)
        self._grad_shardings = shard_lib.to_shardings(
            shard_lib.grads_specs(state_shapes.params, self.mesh, self.strategy),
            self.mesh,
        )
        self.batch_sharding = mesh_lib.batch_sharding(self.mesh)

        if self.cpu_offload:
            # Optimizer state is host-resident; the step streams it through
            # the device around the update (jax.device_put inside jit).
            # Scalar leaves (Adam's step count) stay on device — the SPMD
            # partitioner rejects placement annotations on scalars, and
            # they're bytes anyway.
            self._opt_device_shardings = self.state_shardings.opt_state
            # Partial offload: leaves in _offload_keep keep their device
            # sharding (their pre-pack paths survive because kept leaves
            # skip the storage transform, so pack-extended paths — 'q'/
            # 'scale' — are never in the keep set).
            self._opt_host_shardings = jax.tree_util.tree_map_with_path(
                lambda path, ns, shape: (
                    NamedSharding(self.mesh, ns.spec, memory_kind="pinned_host")
                    if getattr(shape, "ndim", 0) >= 1
                    and _path_keys(path) not in self._offload_keep else ns
                ),
                self.state_shardings.opt_state,
                state_shapes.opt_state,
            )
            self.state_shardings = self.state_shardings.replace(
                opt_state=self._opt_host_shardings
            )

        self._init_jit = jax.jit(self._make_state, out_shardings=self.state_shardings)
        self._step_jit = jax.jit(
            self._train_step,
            donate_argnums=(0,),
            in_shardings=(self.state_shardings, self.batch_sharding),
            out_shardings=(self.state_shardings, None),
        )
        # Telemetry step: a SECOND executable of the same math with the
        # per-layer stats as extra outputs (utils/telemetry). The training
        # loop calls it every --telemetry_interval steps; the steady-state
        # step above keeps its original graph and pays nothing. jax.jit is
        # lazy, so runs that never ask for telemetry never compile this.
        self._step_tel_jit = jax.jit(
            functools.partial(self._train_step, telemetry_on=True),
            donate_argnums=(0,),
            in_shardings=(self.state_shardings, self.batch_sharding),
            out_shardings=(self.state_shardings, None),
        )
        eval_batch_sharding = NamedSharding(
            self.mesh, mesh_lib.batch_spec_2d()
        )
        self._eval_jit = jax.jit(
            self._eval_step,
            in_shardings=(self.state_shardings, eval_batch_sharding),
            out_shardings=None,
        )
        self._eval_batch_sharding = eval_batch_sharding
        # Host-side count of train_step calls: the `step` the spans of one
        # step share (state.step lives on the device; reading it would sync).
        self._calls = 0
        # Abstract (shape, dtype, sharding) arguments of each step variant's
        # first call: compiled_step_text needs no live buffers after it.
        self._step_signature: dict = {}

    # --- rank discovery (↔ reference rank/world_size, ddp_trainer.py:101-103)
    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def process_count(self) -> int:
        return jax.process_count()

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def dp_size(self) -> int:
        return mesh_lib.dp_size(self.mesh)

    @functools.cached_property
    def _feed_info(self):
        """(feed_rank, feed_world) for this host's data loading — see
        mesh_lib.host_feed_info. Computed from the actual batch sharding, so
        sequence/tensor axes spanning hosts get replicated-row feeding."""
        c = self.training_config
        shape = (c.gradient_accumulation_steps,
                 c.batch_size * self.dp_size, c.max_seq_len)
        return mesh_lib.host_feed_info(self.batch_sharding, shape, row_dim=1)

    @property
    def data_feed_rank(self) -> int:
        return self._feed_info[0]

    @property
    def data_feed_world(self) -> int:
        return self._feed_info[1]

    @property
    def global_batch_size(self) -> int:
        """Sequences consumed per optimizer step, across all devices."""
        c = self.training_config
        return c.batch_size * c.gradient_accumulation_steps * self.dp_size

    @property
    def feed_signature(self) -> dict:
        """The quantities a persisted loader cursor's units depend on.
        Stamped into every checkpoint's ``data_state`` so an elastic
        restart on a differently-factored mesh can remap the cursor
        (``utils/checkpoint.remap_data_state``) instead of replaying the
        dataset from the start."""
        return {
            "global_batch_size": self.global_batch_size,
            "feed_world": self.data_feed_world,
        }

    @property
    def tokens_per_step(self) -> int:
        return self.global_batch_size * self.training_config.max_seq_len

    # --- state ------------------------------------------------------------

    @staticmethod
    def _is_packed(x) -> bool:
        # Type check, not a dict-key heuristic: QuantPack is a registered
        # pytree node, so a params subtree using the same keys can never
        # be misread as a quantized moment.
        return isinstance(x, QuantPack)

    @staticmethod
    def _path_nonneg(path) -> bool:
        """Adam's second moment (``nu`` in optax's ScaleByAdamState) is
        nonnegative and only consumed through sqrt — quantize it in
        sqrt-space."""
        return any(
            str(getattr(p, "name", getattr(p, "key", ""))) == "nu"
            for p in path
        )

    def _offload_store(self, opt_state):
        """Compute-dtype optimizer state -> host storage form (no-op unless
        ``offload_dtype`` narrows it; "int8" packs ndim>=2 float leaves
        into blockwise {q, scale}). Device-resident leaves under a partial
        offload budget (``self._offload_keep``) skip the transform — they
        never cross the link, so they stay exact f32."""
        keep = self._offload_keep
        if self._offload_quant:
            return jax.tree_util.tree_map_with_path(
                lambda path, x: x if self._is_packed(x)
                or _path_keys(path) in keep
                else quantize_blockwise_int8(
                    x, nonneg=self._path_nonneg(path))
                if getattr(x, "ndim", 0) >= 2
                and jnp.issubdtype(x.dtype, jnp.floating) else x,
                opt_state,
                is_leaf=self._is_packed,
            )
        if self._offload_cast is None:
            return opt_state
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x.astype(self._offload_cast)
            if getattr(x, "ndim", 0) >= 1
            and jnp.issubdtype(x.dtype, jnp.floating)
            and _path_keys(path) not in keep else x,
            opt_state,
        )

    def _offload_load(self, opt_state):
        """Host storage form -> the optimizer's compute dtypes (on device,
        after the h2d stream — the dequant/cast costs HBM ops, the narrow
        storage saved host-link bytes)."""
        if self._offload_quant:
            return jax.tree_util.tree_map_with_path(
                lambda path, x, dt: dequantize_blockwise_int8(
                    x,
                    x["q"].shape[:-2]
                    + (x["q"].shape[-2] * x["q"].shape[-1],),
                    dt,
                    nonneg=self._path_nonneg(path),
                ) if self._is_packed(x) else x,
                opt_state, self._opt_compute_dtypes,
                is_leaf=self._is_packed,
            )
        if self._offload_cast is None:
            return opt_state
        return jax.tree_util.tree_map(
            lambda x, dt: x.astype(dt) if getattr(x, "ndim", 0) >= 1 else x,
            opt_state, self._opt_compute_dtypes,
        )

    def _cast_params(self, params):
        """Compute-dtype copy of the >=2-D param leaves (exactly the cast
        the modules apply: Dense/Embed promote their matrices to the
        module dtype; 1-D leaves — RMSNorm weights — stay f32, and so does
        what a module says it computes in f32: an expert layer's router, a
        Mamba-2 mixer's ``A_log`` / ``dt_bias`` / ``D``
        (``models/gpt.computed_in_f32``))."""
        from tpu_trainer.models.gpt import computed_in_f32

        cd = self.model_config.compute_dtype
        return jax.tree_util.tree_map_with_path(
            lambda path, p: p.astype(cd)
            if p.ndim >= 2 and not computed_in_f32(_path_keys(path)) else p,
            params)

    def _apply_params(self, state: TrainState):
        """The param tree the model forward should consume: the carried
        compute-dtype copy (gradients linked to the f32 master via
        ``_linked_cast``), or the master itself when the carry is off."""
        if state.params_c is None:
            return state.params
        return _linked_cast(state.params, state.params_c)

    def _make_state(self, rng: jax.Array) -> TrainState:
        param_rng, dropout_rng = jax.random.split(rng)
        dummy = jnp.zeros((1, 8), jnp.int32)
        params = self.model.init(param_rng, dummy)["params"]
        opt_state = self._offload_store(self.optimizer.init(params))
        init_scale = _INIT_LOSS_SCALE if self.use_loss_scaling else 1.0
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt_state,
            rng=dropout_rng,
            loss_scale=jnp.asarray(init_scale, jnp.float32),
            good_steps=jnp.zeros((), jnp.int32),
            params_c=self._cast_params(params) if self._carry_cast else None,
        )

    def with_params_c(self, state: TrainState) -> TrainState:
        """Attach the derived compute-dtype param copy to a state that lacks
        it (checkpoint restore: ``params_c`` is stripped on save)."""
        if not self._carry_cast or state.params_c is not None:
            return state
        cast = jax.jit(
            self._cast_params,
            out_shardings=shard_lib.to_shardings(
                self._state_specs.params_c, self.mesh
            ),
        )
        return state.replace(params_c=cast(state.params))

    def init_state(self, seed: Optional[int] = None) -> TrainState:
        """Initialize (sharded directly on the mesh — params never exist
        unsharded, unlike the reference's build-on-CPU-then-wrap)."""
        seed = self.training_config.seed if seed is None else seed
        with profiling.span("trainer:init_state", step=self._calls):
            return self._init_jit(jax.random.PRNGKey(seed))

    # --- data placement -----------------------------------------------------

    def put_batch(self, local_batch: np.ndarray) -> jax.Array:
        """Host numpy ``[accum * local_bs, seq]`` → global sharded device array
        ``[accum, global_bs, seq]`` (↔ reference micro-batch slicing,
        ``ddp_trainer.py:320-326``, done once here instead of per micro-step).

        Packed batches arrive as ``[accum * local_bs, seq, 2]`` — channel 0
        tokens, channel 1 segment ids — and come out ``[accum, global_bs,
        seq, 2]``. The batch PartitionSpec is 3-D, so the trailing channel
        dim stays replicated without a second sharding.
        """
        accum = self.training_config.gradient_accumulation_steps
        packed = local_batch.ndim == 3
        n, seq = local_batch.shape[:2]
        if n % accum != 0:
            raise ValueError(f"batch rows {n} not divisible by accum {accum}")
        # Out-of-vocab ids make the embedding gather silently produce garbage
        # (NaN loss a few steps later); a host-side max over the batch is
        # ~free next to the device step. Typical trigger: byte tokenizer ids
        # (<= 50256) against a shrunken vocab_size.
        vocab = self.model_config.vocab_size
        tokens = local_batch[..., 0] if packed else local_batch
        top = int(tokens.max()) if tokens.size else 0
        if top >= vocab or int(tokens.min() if tokens.size else 0) < 0:
            raise ValueError(
                f"batch contains token id {top} outside [0, {vocab}) — "
                f"tokenizer/vocab_size mismatch (e.g. byte-tokenizer ids "
                f"with a reduced model vocab)"
            )
        tail = local_batch.shape[2:]
        local = local_batch.reshape(accum, n // accum, seq, *tail)
        # feed_world, not process_count: hosts sharing a data shard (a
        # sequence/tensor axis spanning hosts) each pass the same rows, and
        # the global row count scales with the number of DISTINCT slices.
        global_shape = (accum, (n // accum) * self.data_feed_world, seq, *tail)
        return jax.make_array_from_process_local_data(
            self.batch_sharding, local, global_shape
        )

    # --- the step -----------------------------------------------------------

    def place_batch(self, batch) -> jax.Array:
        """Host array ``[accum * local_bs, seq]`` (or ``[accum, local_bs,
        seq]``) → the sharded ``[accum, global_bs, seq]`` device array the
        jitted step expects; device arrays pass through. Packed batches
        carry a trailing ``2`` channel dim (tokens, segment ids) and are
        recognized by ``shape[-1] == 2`` (a real seq dim is never 2).
        Public: the device-prefetch feed (``data/device_prefetch.py``) uses
        this to enqueue H2D copies ahead of the step."""
        if not isinstance(batch, jax.Array):
            with profiling.span("trainer:place_batch", step=self._calls):
                batch = np.asarray(batch)
                packed = batch.shape[-1] == 2
                flat_ndim = 3 if packed else 2
                if batch.ndim == flat_ndim + 1:
                    batch = batch.reshape(-1, *batch.shape[2:])
                batch = self.put_batch(batch)
        return batch

    def train_step(self, state: TrainState, batch,
                   telemetry: bool = False) -> Tuple[TrainState, dict]:
        """One optimizer step over ``accum`` micro-batches.

        ``batch``: the sharded ``[accum, global_bs, seq]`` device array from
        ``put_batch``, or a **process-local** host array, which is placed
        automatically (``place_batch``).

        ``telemetry=True`` runs the telemetry variant of the step (separate
        executable, compiled on first use): the metrics dict gains a
        ``"telemetry"`` subtree of per-layer grad/param/update norms,
        activation RMS/absmax, and MoE router stats.
        """
        batch = self.place_batch(batch)
        variant = "telemetry" if telemetry else "plain"
        if variant not in self._step_signature:
            self._step_signature[variant] = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding), (state, batch))
            profiling.register_program(
                "train_step" if variant == "plain" else "train_step_telemetry",
                functools.partial(self.compiled_step_text,
                                  telemetry=telemetry))
        step_jit = self._step_tel_jit if telemetry else self._step_jit
        # The dispatch: it returns before the device is done.
        with profiling.span("trainer:train_step", step=self._calls,
                            variant=variant):
            out = step_jit(state, batch)
        self._calls += 1
        return out

    def step_memory_analysis(self, state: TrainState, batch) -> Optional[dict]:
        """Compiler-reported per-device HBM footprint of the compiled train
        step, in bytes.

        The XLA executable's own ``memory_analysis`` — what the compiler
        planned, next to what ``device.memory_stats()`` observed.
        ``peak_bytes`` ≈ arguments + outputs + temporaries − aliased (the
        donated train state aliases its output, so it is counted once).
        Returns None when the backend doesn't expose the analysis.
        """
        batch = self.place_batch(batch)
        # Same jit object + same shapes as the running step: this hits the
        # existing executable cache rather than recompiling.
        compiled = self._step_jit.lower(state, batch).compile()
        ma = compiled.memory_analysis()
        if ma is None:
            return None
        arg = ma.argument_size_in_bytes
        out = ma.output_size_in_bytes
        tmp = ma.temp_size_in_bytes
        alias = ma.alias_size_in_bytes
        return {
            "argument_bytes": arg,
            "output_bytes": out,
            "temp_bytes": tmp,
            "alias_bytes": alias,
            "peak_bytes": arg + out + tmp - alias,
        }

    def step_cost_analysis(self, state: TrainState, batch) -> Optional[dict]:
        """Compiler-predicted cost of one train step: FLOPs and HBM bytes
        accessed per the XLA cost model, plus the memory_analysis peak.

        This is the *computed ceiling* next to the observed rate: predicted
        FLOPs/step over device peak FLOPs gives the step time the chip
        cannot beat, and achieved/predicted FLOP throughput is an MFU that
        charges the model for padding and recompute the 6N estimate misses.
        Returns None when the backend reports no analysis.
        """
        batch = self.place_batch(batch)
        # Same jit object + shapes as the running step: hits the executable
        # cache (or warms it — this doubles as an explicit compile point the
        # goodput ledger can attribute to "compile").
        ca = self._step_jit.lower(state, batch).compile().cost_analysis()
        if not ca:
            return None
        out = {}
        if ca.get("flops"):
            out["flops_per_step"] = float(ca["flops"])
        if ca.get("bytes accessed"):
            out["bytes_accessed"] = float(ca["bytes accessed"])
        mem = self.step_memory_analysis(state, batch)
        if mem is not None:
            out["peak_bytes"] = mem["peak_bytes"]
        return out or None

    def compiled_step_text(self, state: Optional[TrainState] = None,
                           batch=None, telemetry: bool = False) -> str:
        """Post-optimization HLO of the compiled train step.

        Read by parallel/comms_model.crosscheck, which counts the collective
        ops GSPMD actually inserted against the analytic traffic model, and,
        through ``utils.profiling.program_texts``, by whoever attributes a
        device trace to the program (``perf/program_trace.py``): every
        instruction's ``metadata={op_name=...}`` is the path of scopes it
        came from. Without ``state`` and ``batch`` the step is lowered from
        the abstract arguments remembered at the variant's first call, so
        it needs no live buffers (the running state has been donated by
        then); ``jax.ShapeDtypeStruct`` arguments do as well (a compile
        for a described chip). Same jit object + shapes as the running
        step: the lowering and the executable come from jax's caches.
        """
        step_jit = self._step_tel_jit if telemetry else self._step_jit
        if state is None:
            variant = "telemetry" if telemetry else "plain"
            if variant not in self._step_signature:
                raise ValueError(
                    f"the {variant} train step has not run yet: pass state "
                    f"and batch")
            state, batch = self._step_signature[variant]
        elif not isinstance(batch, jax.ShapeDtypeStruct):
            batch = self.place_batch(batch)
        return step_jit.lower(state, batch).compile().as_text()

    def nan_scan(self, state: TrainState, batch) -> dict:
        """Forward-only activation scan: where does the first NaN/Inf appear?

        Runs one deterministic forward (micro-batch 0) with the telemetry
        capture active and bisects the per-layer absmax series host-side.
        Returns ``{"first_nan": {"layer", "site"} | None, "sites": [...],
        "stats": {flattened telemetry scalars}}`` — see
        utils/telemetry.nan_report. Debug tool (``--nan_scan``); the
        activation hooks don't run under pipeline schedules (stage > 1).
        """
        batch = self.place_batch(batch)

        def scan_fn(st, micro):
            tokens, segs = _split_packed(micro)
            with telemetry.capture(deep=True) as cap:
                with self._sp_context():
                    _, loss = self.model.apply(
                        {"params": st.params}, tokens, labels=tokens,
                        segment_ids=segs,
                    )
            stats = telemetry.assemble(cap.stats)
            stats["loss"] = loss
            return stats

        stats = jax.jit(scan_fn)(state, batch[0])
        stats = jax.device_get(stats)
        report = telemetry.nan_report(stats)
        report["stats"] = telemetry.flatten_scalars(
            {k: v for k, v in stats.items() if isinstance(v, dict)},
            prefix="nan_scan",
        )
        report["stats"]["nan_scan/loss"] = float(np.asarray(stats["loss"]))
        return report

    def eval_step(self, state: TrainState, batch) -> jax.Array:
        """Forward-only mean loss on one ``[rows, seq]`` batch (deterministic,
        no dropout) — the eval loop the reference's dead ``eval_interval``
        promised (``ddp_trainer.py:52``, SURVEY.md §0.1)."""
        if not isinstance(batch, jax.Array):
            local = np.asarray(batch)
            n, seq = local.shape[:2]
            batch = jax.make_array_from_process_local_data(
                self._eval_batch_sharding, local,
                (n * self.data_feed_world, seq) + local.shape[2:]
            )
        with profiling.span("trainer:eval_step", step=self._calls):
            return self._eval_jit(state, batch)

    def _eval_step(self, state: TrainState, batch: jax.Array):
        tokens, segs = _split_packed(batch)
        with self._sp_context():
            _, loss = self.model.apply(
                {"params": state.params}, tokens, labels=tokens,
                segment_ids=segs,
            )
        return loss

    def _sp_context(self):
        """Trace context for the model body: publishes the mesh so mesh-aware
        ops (the Pallas flash kernel) shard_map themselves over it
        (``parallel/context.py``), plus the ring-attention context when the
        mesh has a non-trivial ``sequence`` axis."""
        import contextlib

        stack = contextlib.ExitStack()
        stack.enter_context(ctx_lib.mesh_scope(self.mesh))
        if self.sp_size > 1:
            stack.enter_context(ring.sequence_parallel(self.mesh))
        return stack

    def _train_step(self, state: TrainState, batch: jax.Array,
                    telemetry_on: bool = False):
        cfg = self.training_config
        accum = cfg.gradient_accumulation_steps
        assert batch.ndim in (3, 4) and batch.shape[0] == accum

        def loss_fn(params, micro, rng, scale):
            tokens, segs = _split_packed(micro)
            # With the carried cast, the forward consumes the state's
            # compute-dtype copy; gradients still land on the f32 master
            # (_linked_cast routes the cotangents through the
            # cast-transpose). Identical numerics to casting here.
            if state.params_c is not None:
                params = _linked_cast(params, state.params_c)
            # Telemetry variant only: activate the trace-time capture so the
            # model routes per-layer activation/router stats out of the
            # forward; they ride the value_and_grad aux. The steady-state
            # trace (telemetry_on=False) is byte-identical to before.
            cap_cm = (telemetry.capture() if telemetry_on
                      else contextlib.nullcontext())
            # The model's step counters (the expert layers' rows and load;
            # empty for a model that counts nothing) ride the aux too.
            with cap_cm as cap, telemetry.counters() as counts:
                with self._sp_context():
                    _, loss = self.model.apply(
                        {"params": params},
                        tokens,
                        labels=tokens,
                        train=True,
                        rngs={"dropout": rng},
                        segment_ids=segs,
                    )
            if telemetry_on:
                return loss * scale, (loss, counts,
                                      telemetry.assemble(cap.stats))
            return loss * scale, (loss, counts)

        if (self.stage_size > 1
                and self.model_config.pipeline_schedule in (
                    "1f1b", "interleaved")):
            # Manual interleaved-backward schedule: the loss and gradients
            # come from one scheduled scan instead of AD over the GPipe
            # forward — the activation-memory cap 1F1B exists for
            # (models/gpt.py pipeline_1f1b_value_and_grad).
            from tpu_trainer.models.gpt import pipeline_1f1b_value_and_grad

            _raw_1f1b = pipeline_1f1b_value_and_grad(
                self.model, self.mesh,
                self.model_config.pipeline_microbatches or self.stage_size,
            )

            def grad_fn(p, micro, rng_, scale_):
                # Same trace context as loss_fn: publishes the mesh so the
                # flash dispatch shard_maps its batch/head axes — without
                # it the Pallas call inside the stage body would force
                # batch replication, the memory cliff 1F1B exists to avoid.
                with self._sp_context():
                    (scaled, loss_v), g = _raw_1f1b(p, micro, rng_, scale_)
                # 1f1b bypasses normal AD — no forward capture here;
                # grad/param/update norms below still apply.
                aux = (loss_v, {}, {}) if telemetry_on else (loss_v, {})
                return (scaled, aux), g
        else:
            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        fwd_stats = None
        if accum == 1:
            # No accumulation buffer — one backward, grads consumed in place.
            new_rng, sub = jax.random.split(state.rng)
            (_, aux), grads = grad_fn(
                state.params, batch[0], sub, state.loss_scale
            )
            loss_sum, counts = aux[:2]
            if telemetry_on:
                fwd_stats = aux[2]
        else:
            with jax.named_scope("grad_accum"):
                zero_grads = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), state.params
                )

            def micro_step(carry, micro):
                grads_acc, loss_acc, rng = carry
                rng, sub = jax.random.split(rng)
                (_, aux), grads = grad_fn(state.params, micro, sub, state.loss_scale)
                with jax.named_scope("grad_accum"):
                    grads_acc = jax.tree_util.tree_map(
                        jnp.add, grads_acc, grads)
                return (grads_acc, loss_acc + aux[0], rng), aux[1:]

            (grads, loss_sum, new_rng), stacked = jax.lax.scan(
                micro_step, (zero_grads, jnp.zeros((), jnp.float32), state.rng), batch
            )
            counts = telemetry.reduce_counts(stacked[0])
            if telemetry_on:
                # [accum, ...]-stacked forward stats → mean (max for absmax).
                fwd_stats = telemetry.reduce_micro(stacked[1])
        # Mean over micro-steps and undo the loss scale; then pin the grads to
        # their ZeRO sharding (the reduce-scatter point under zero2/zero3).
        with jax.named_scope("grad_finalize"):
            denom = accum * state.loss_scale
            grads = jax.tree_util.tree_map(lambda g: g / denom, grads)
            grads = shard_lib.constrain(grads, self._grad_shardings)
            grad_norm = optax.global_norm(grads)
        loss = loss_sum / accum

        # Schedule applied here, as a pure function of state.step (fixes b1;
        # also keeps logged LR == applied LR across fp16 overflow skips, where
        # the optimizer chain's internal count freezes but the schedule ticks).
        lr = cfg.lr_at(state.step)

        @jax.named_scope("optimizer")
        def apply_update(_):
            opt_in = state.opt_state
            if self.cpu_offload:
                opt_in = jax.device_put(opt_in, self._opt_device_shardings)
            updates, new_opt = self.optimizer.update(
                grads, self._offload_load(opt_in), state.params
            )
            new_opt = self._offload_store(new_opt)
            if self.cpu_offload:
                new_opt = jax.device_put(new_opt, self._opt_host_shardings)
            updates = jax.tree_util.tree_map(lambda u: u * lr, updates)
            new_p = optax.apply_updates(state.params, updates)
            # The compute-dtype copy is produced HERE, in the same
            # executable as the update — XLA fuses the cast into the
            # update fusions' epilogues (the point of params_c).
            new_c = self._cast_params(new_p) if self._carry_cast else None
            return new_p, new_opt, new_c

        if self.use_loss_scaling:
            finite = jnp.isfinite(grad_norm)
            new_params, new_opt, new_params_c = jax.lax.cond(
                finite, apply_update,
                lambda _: (state.params, state.opt_state, state.params_c),
                None,
            )
            grew = state.good_steps + 1 >= _SCALE_GROWTH_INTERVAL
            new_scale = jnp.where(
                finite,
                jnp.where(grew, jnp.minimum(state.loss_scale * 2.0, _MAX_LOSS_SCALE),
                          state.loss_scale),
                jnp.maximum(state.loss_scale * 0.5, 1.0),
            )
            new_good = jnp.where(finite, jnp.where(grew, 0, state.good_steps + 1), 0)
        else:
            new_params, new_opt, new_params_c = apply_update(None)
            new_scale, new_good = state.loss_scale, state.good_steps

        metrics = {
            "loss": loss,
            "lr": lr,
            "grad_norm": grad_norm,
            "loss_scale": state.loss_scale,
            **telemetry.flat_counts(counts),
        }
        if telemetry_on:
            telem = dict(fwd_stats or {})
            # Per-group norms from the trees the step already has in hand:
            # the stacked [num_layers, ...] leaves reduce to a per-layer
            # vector, embed/norm to scalars (telemetry.group_norms; the
            # recombination to optax.global_norm is pinned by tests).
            grad_norms = telemetry.group_norms(grads)
            param_norms = telemetry.group_norms(state.params)
            update_norms = telemetry.group_norms(jax.tree_util.tree_map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                new_params, state.params,
            ))
            telem["grad_norm"] = grad_norms
            telem["param_norm"] = param_norms
            telem["update_ratio"] = {
                k: update_norms[k] / (param_norms[k] + 1e-20)
                for k in update_norms
            }
            metrics["telemetry"] = telem
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt,
            rng=new_rng,
            params_c=new_params_c,
        )
        if self.use_loss_scaling:
            new_state = new_state.replace(loss_scale=new_scale, good_steps=new_good)
        return new_state, metrics


class RecompileWatchdog:
    """Detect steady-state recompilation of the jitted train step.

    ``jax.jit`` silently compiles a fresh executable for every new abstract
    input signature; a loader that churns shapes (ragged tails, bucketing
    bugs) turns each "cache miss" into a multi-second compile stall that
    telemetry otherwise books as ordinary step time. The watchdog reads the
    program's compile log (``profiling.compile_log()``) after every step:
    backend compiles under the ``trainer:train_step`` span since the last
    look that the training loop did not expect (first use of the plain or
    telemetry variant is expected) produce a ``kind:"recompile"`` record
    carrying the offending batch's abstract shape; ``warn_after`` unexpected
    events flips ``storm`` on, the loop's cue to warn loudly.
    """

    SPAN = "trainer:train_step"

    def __init__(self, warn_after: int = 3):
        self.warn_after = warn_after
        self.events: list = []
        self.executables = 0           # train-step compiles seen so far
        self._watermark = time.perf_counter()

    def observe(self, step: int, batch=None,
                expected: bool = False) -> Optional[dict]:
        """Read the compile log after ``step`` ran on ``batch``.

        ``expected=True`` moves the watermark silently (warmup compiles:
        the first use of each step variant). Returns the recompile record
        to log, or None when nothing unexpected happened.
        """
        since, self._watermark = self._watermark, time.perf_counter()
        grew = sum(1 for e in profiling.compile_log()
                   if e.kind == "compile" and e.span == self.SPAN
                   and since < e.end <= self._watermark)
        self.executables += grew
        if expected or not grew:
            return None
        shape = tuple(getattr(batch, "shape", ()) or ())
        dtype = getattr(batch, "dtype", None)
        record = {
            "kind": "recompile",
            "step": int(step),
            "executables": int(self.executables),
            "new_executables": int(grew),
            "batch_abstract": "{}[{}]".format(
                dtype if dtype is not None else "?",
                ",".join(str(d) for d in shape)),
        }
        self.events.append(record)
        record["recompiles_total"] = len(self.events)
        record["storm"] = len(self.events) >= self.warn_after
        return record
