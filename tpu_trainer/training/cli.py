"""Training CLI: the shared main behind ``train_ddp`` and ``train_fsdp``.

TPU-native re-design of the reference's two ``main()`` entry points
(``/root/reference/src/training/ddp_trainer.py:490-625``,
``.../fsdp_trainer.py:530-616``), unified into one driver (the
``trainer_utils`` layer the reference promised but never wrote —
SURVEY.md §0.1). Differences by design:

- **YAML configs are actually loaded.** The reference documents
  ``--config configs/small_model.yaml`` but defines no such flag
  (SURVEY.md §0.1); here ``--config`` parses the same YAML schema
  (``/root/reference/configs/small_model.yaml``) into the dataclasses, with
  CLI flags taking precedence over YAML over defaults.
- **Resume is wired.** ``--resume_from`` restores a checkpoint; with no flag,
  the latest checkpoint under ``--checkpoint_dir`` is auto-restored (the
  reference's ``resume_from`` was dead config and ``load_checkpoint`` was
  never called — SURVEY.md §5.3).
- **Preemption handling.** SIGTERM (routine on TPU pools) checkpoints at the
  next step boundary and exits cleanly.
- **A real eval loop.** ``eval_interval`` triggers forward-only loss
  evaluation (the reference declares the field but has no eval loop anywhere
  — SURVEY.md §0.1).

Flag parity: every reference flag is accepted (DDP set,
``ddp_trainer.py:494-510``; FSDP set incl. ``--sharding``/``--cpu_offload``/
``--no_activation_checkpointing``, ``fsdp_trainer.py:531-538``).
"""

from __future__ import annotations

import argparse
import dataclasses
import signal
import time
from typing import Optional

import numpy as np

from tpu_trainer.data.device_prefetch import DevicePrefetcher
from tpu_trainer.models.config import GPTConfig
from tpu_trainer.parallel import comms_model as comms_lib
from tpu_trainer.parallel import mesh as mesh_lib
from tpu_trainer.parallel import planner as planner_lib
from tpu_trainer.training.config import TrainingConfig
from tpu_trainer.training.trainer import (
    _MP_TO_DTYPE, ParallelConfig, RecompileWatchdog, Trainer,
)
from tpu_trainer.utils import checkpoint as ckpt_lib
from tpu_trainer.utils import faults, guards, profiling
from tpu_trainer.utils import preemption as preemption_lib
from tpu_trainer.utils import flight_recorder as flight_lib
from tpu_trainer.utils import telemetry as telemetry_lib
from tpu_trainer.utils.logging import MetricLogger, flops_per_token

# Steps between cross-host preemption votes (each vote is a collective, so
# it must run at a cadence every host reaches at the same step).
_PREEMPT_VOTE_INTERVAL = 10

# Steps a metric future may stay in flight before the host materializes it
# (utils/telemetry.py DeferredFetcher): by fetch time the device has long
# finished that step, so the device_get returns ~immediately; the spike
# detector and NaN guards see values this many steps late, which recovery
# (bounded by checkpoint cadence, not by the window) absorbs.
_DEFERRED_SYNC_WINDOW = 2


def _nan_loss_transform(metrics: dict) -> dict:
    """Injected-fault mutation, applied to the *fetched* host copy at
    maturity — the live metrics are still in flight on device when the
    fault fires."""
    metrics = dict(metrics)
    metrics["loss"] = float("nan")
    return metrics


def _loss_spike_transform(metrics: dict) -> dict:
    # Large but finite: the early-warning path must engage before anything
    # trips the NaN guard.
    metrics = dict(metrics)
    metrics["loss"] = float(metrics["loss"]) * 8.0 + 5.0
    return metrics

_SHARDING_CHOICES = [
    "FULL_SHARD", "SHARD_GRAD_OP", "NO_SHARD", "HYBRID_SHARD",
    "zero3", "zero2", "replicated", "ddp",
]

# Optimizer-state storage dtypes (host-offloaded: trainer.py _offload_*;
# on-device: optimizer.py scale_by_adam_quantized). Shared between the
# argparse choices and the YAML validation below — any other string would
# flow into jnp.dtype() as a silently-corrupting storage cast (e.g. int16
# truncates Adam moments to zero).
_OFFLOAD_DTYPES = ["float32", "bfloat16", "int8"]


def _require_choice(value, choices, name):
    if value not in choices:
        raise SystemExit(
            f"{name} {value!r} not supported; choose one of {choices}"
        )
    return value


def build_parser(mode: str) -> argparse.ArgumentParser:
    """Argument parser; defaults are ``None`` sentinels so that explicit CLI
    flags can be layered over YAML over dataclass defaults."""
    p = argparse.ArgumentParser(
        description=f"TPU-native GPT training ({mode})",
    )
    p.add_argument("--config", type=str, default=None,
                   help="YAML config (reference configs/*.yaml schema)")
    # model (reference ddp_trainer.py:495-499)
    p.add_argument("--model_size", type=str, default=None,
                   choices=["small", "medium", "large", "xl"])
    p.add_argument("--seq_len", type=int, default=None)
    p.add_argument("--gradient_checkpointing", action="store_true", default=None)
    p.add_argument("--no_flash_attention", action="store_true", default=None)
    # training (reference ddp_trainer.py:496-502)
    p.add_argument("--batch_size", type=int, default=None,
                   help="per-data-shard micro-batch size")
    p.add_argument("--max_steps", type=int, default=None)
    p.add_argument("--learning_rate", type=float, default=None)
    p.add_argument("--warmup_steps", type=int, default=None)
    p.add_argument("--grad_accum", "--gradient_accumulation_steps",
                   dest="grad_accum", type=int, default=None)
    p.add_argument("--mixed_precision", type=str, default=None,
                   choices=["fp32", "bf16", "fp16"])
    # data (reference ddp_trainer.py:503-510)
    p.add_argument("--dataset", type=str, default=None,
                   choices=["dummy", "tinystories", "openwebtext"])
    p.add_argument("--data_path", type=str, default=None)
    p.add_argument("--max_tokens", type=int, default=None)
    p.add_argument("--streaming", action="store_true", default=None)
    p.add_argument("--pack_sequences", action="store_true", default=None,
                   help="first-fit sequence packing: ragged documents share "
                        "rows, a segment-id channel keeps attention and "
                        "loss per-document (data/packing.py); batches are "
                        "[rows, seq, 2] (tokens, segment ids)")
    p.add_argument("--max_open_bins", type=int, default=None,
                   help="packing: max simultaneously open bins before the "
                        "oldest is flushed (default 8)")
    p.add_argument("--pack_strategy", type=str, default=None,
                   choices=("first_fit", "best_fit"),
                   help="packing bin selection: first_fit (stream order) or "
                        "best_fit (best-fit-decreasing over a lookahead "
                        "window — fewer stranded bin tails; default "
                        "first_fit)")
    p.add_argument("--mask_doc_boundaries", action="store_true", default=None,
                   help="concatenating text stream: derive segment ids from "
                        "EOS positions so attention/loss never leak across "
                        "documents (default off — bit-compat with runs "
                        "checkpointed on the leaky stream)")
    p.add_argument("--data_mixture", type=str, default=None,
                   help="weighted multi-source mixture "
                        "'name:weight[:path],...', e.g. 'tinystories:0.7:"
                        "ts.txt,dummy:0.3'; names from {dummy, tinystories, "
                        "openwebtext}; overrides --dataset (data/mixture.py)")
    p.add_argument("--cache_max_tokens", type=int, default=None)
    p.add_argument("--num_workers", type=int, default=None,
                   help="streaming tokenizer thread-pool size (0 = inline; "
                        "reference DataLoader num_workers)")
    p.add_argument("--prefetch", "--prefetch_depth", dest="prefetch",
                   type=int, default=None,
                   help="host-side prefetch depth: batches assembled ahead "
                        "on a background thread (0 disables the host "
                        "input/compute overlap)")
    p.add_argument("--device_prefetch_depth", type=int, default=None,
                   help="batches placed on device (with the batch sharding) "
                        "ahead of the step so H2D copies ride under compute "
                        "(default 2; 0 places inside the step)")
    p.add_argument("--no_async_checkpointing", action="store_true",
                   default=None,
                   help="commit interval checkpoints synchronously in the "
                        "step loop instead of snapshotting to host and "
                        "writing on a background thread")
    p.add_argument("--num_batches", type=int, default=None,
                   help="dummy-dataset corpus size in batches")
    p.add_argument("--tokenizer", type=str, default=None)
    # schedule / logging / checkpointing
    p.add_argument("--log_interval", type=int, default=None)
    p.add_argument("--eval_interval", type=int, default=None)
    p.add_argument("--eval_batches", type=int, default=None)
    p.add_argument("--eval_split", type=float, default=None,
                   help="held-out tail fraction of map-style text chunks "
                        "(default 0.02; 0 disables eval on text datasets)")
    p.add_argument("--eval_holdout_every", type=int, default=None,
                   help="streaming: reserve every N-th line for eval "
                        "(0 = no streaming eval)")
    p.add_argument("--save_interval", type=int, default=None)
    p.add_argument("--checkpoint_dir", type=str, default=None)
    p.add_argument("--resume_from", type=str, default=None)
    p.add_argument("--no_auto_resume", action="store_true", default=None)
    p.add_argument("--keep_last_n", type=int, default=None,
                   help="checkpoint GC: keep only the newest N completed "
                        "checkpoints (0 = keep all)")
    # fault tolerance (divergence rollback; utils/checkpoint.py hardening)
    p.add_argument("--max_rollbacks", type=int, default=None,
                   help="on a non-finite loss or cross-host divergence, "
                        "rewind to the last good checkpoint and retry up to "
                        "this many times before failing (0 = crash at once)")
    p.add_argument("--skip_batches_on_rollback", type=int, default=None,
                   help="on rollback, fast-forward the data stream this many "
                        "batches past the batch that diverged (0 = replay "
                        "the same data and rely on the LR backoff)")
    p.add_argument("--rollback_lr_backoff", type=float, default=None,
                   help="multiply the peak LR by this factor on each "
                        "rollback (1.0 disables the backoff)")
    p.add_argument("--inject_fault", type=str, default=None,
                   help="debug: deterministic fault injection, "
                        "'kind@step[,kind@step...]' — kinds: nan_loss, "
                        "loss_spike, kill, kill_in_save, truncate_meta, "
                        "corrupt_shard, sigterm, kill_host, hang_host, "
                        "preempt_notice, return_host (utils/faults.py)")
    p.add_argument("--preemption_grace_s", type=float, default=None,
                   help="hard deadline (seconds) for the SIGTERM exit path: "
                        "drain the in-flight async save and take the final "
                        "checkpoint within this budget, exiting 143 even if "
                        "the save had to be abandoned (0 = wait "
                        "indefinitely, the pre-elastic behavior)")
    p.add_argument("--preempt_notice", type=str, default=None,
                   help="proactive preemption notice source "
                        "(utils/preemption.py): 'file:<path>', an http(s) "
                        "GCE-metadata-shaped URL, or 'metadata' (the real "
                        "GCE endpoint). A received notice drains at the "
                        "next step boundary — checkpoint, deregister, exit "
                        "143 — before the kill lands. SIGTERM stays the "
                        "always-on fallback")
    p.add_argument("--preempt_notice_poll_s", type=float, default=None,
                   help="throttle for probing the notice source "
                        "(default 1.0s; the HTTP probe is a network "
                        "round-trip on the step path)")
    p.add_argument("--preempt_vote_interval", type=int, default=None,
                   help="steps between cross-host preemption/notice votes "
                        "on multi-process runs (each vote is a collective; "
                        "default 10). Single-process runs vote every step")
    p.add_argument("--metrics_jsonl", type=str, default=None)
    p.add_argument("--metrics_port", type=int, default=None,
                   help="serve live /metrics + /healthz + /statusz on this "
                        "port (0 = ephemeral; host 0 only; off by default "
                        "— records and streams are identical either way)")
    p.add_argument("--wandb_project", type=str, default=None,
                   help="log metrics to Weights & Biases (import-guarded)")
    p.add_argument("--tensorboard_dir", type=str, default=None,
                   help="log metrics to TensorBoard event files")
    p.add_argument("--seed", type=int, default=None)
    # profiling (SURVEY.md §5.1) and numerics/divergence guards (§5.2)
    p.add_argument("--profile_dir", type=str, default=None,
                   help="capture a jax.profiler trace window to this dir")
    p.add_argument("--profile_start", type=int, default=None,
                   help="first traced step (default 5; lets compile pass)")
    p.add_argument("--profile_steps", type=int, default=None,
                   help="number of steps to trace (default 5)")
    p.add_argument("--guard_interval", type=int, default=None,
                   help="steps between finite-loss + cross-host sync checks "
                        "(default 100; 0 disables)")
    # telemetry / goodput / early warning (utils/telemetry.py)
    p.add_argument("--telemetry_interval", type=int, default=None,
                   help="steps between in-graph telemetry steps (per-layer "
                        "grad/param/update norms, activation RMS/absmax, MoE "
                        "router health — a second compiled step variant, so "
                        "steps in between pay nothing; default 0 = off)")
    p.add_argument("--spike_sigma", type=float, default=None,
                   help="loss-spike early warning: raise (and roll back) when "
                        "the logged loss exceeds the rolling median by this "
                        "many MAD-sigmas (default 6; 0 disables)")
    # run anatomy (ISSUE 3): comms model, recompile watchdog, flight recorder
    p.add_argument("--no_comms_model", action="store_true", default=None,
                   help="skip the one-time kind:\"comms_model\" record "
                        "(analytic per-axis collective bytes/step + "
                        "comms-vs-compute roofline, cross-checked against "
                        "the compiled HLO)")
    p.add_argument("--flight_recorder_steps", type=int, default=None,
                   help="crash flight recorder: ring-buffer capacity of "
                        "recent JSONL records dumped (with a config/mesh/env "
                        "snapshot) as crash_report.json under "
                        "--checkpoint_dir on SIGTERM/rollback/crash "
                        "(default 256; 0 disables)")
    p.add_argument("--nan_scan", action="store_true", default=None,
                   help="debug: run one forward-only activation scan on the "
                        "first batch, report the first layer/site with a "
                        "non-finite value, and exit without training")
    # mesh / multi-host
    p.add_argument("--mesh", type=str, default=None, choices=["auto"],
                   help="'auto' runs the mesh auto-planner at startup "
                        "(parallel/planner.py): enumerate every feasible "
                        "data x fsdp x sequence x tensor x expert x stage "
                        "split, score with the analytic comms + roofline "
                        "model, log a kind:\"mesh_plan\" record, and train "
                        "on the winner. Mutually exclusive with explicit "
                        "--mesh_* flags.")
    p.add_argument("--hbm_gb", type=float, default=None,
                   help="per-device HBM budget in GiB for --mesh auto "
                        "pruning (default: the device's reported limit; "
                        "no pruning on CPU)")
    p.add_argument("--mesh_data", type=int, default=None)
    p.add_argument("--mesh_fsdp", type=int, default=None)
    p.add_argument("--mesh_sequence", type=int, default=None,
                   help="ring-attention sequence-parallel axis size")
    p.add_argument("--mesh_tensor", type=int, default=None)
    p.add_argument("--mesh_expert", type=int, default=None,
                   help="expert-parallel axis size (MoE models)")
    p.add_argument("--mesh_stage", type=int, default=None,
                   help="pipeline-parallel stage axis size (GPipe schedule)")
    p.add_argument("--pipeline_microbatches", type=int, default=None,
                   help="GPipe microbatches per step (default: one per stage)")
    p.add_argument("--num_experts", type=int, default=None,
                   help="> 0 turns every block's FFN into a routed MoE")
    p.add_argument("--moe_impl", type=str, default=None,
                   choices=["capacity", "dropless"],
                   help="MoE routing discipline: fixed-capacity slots with "
                        "token dropping, or dropless grouped-matmul experts")
    p.add_argument("--num_kv_heads", type=int, default=None,
                   help="grouped-query attention: K/V heads (< num_heads "
                        "shrinks the KV cache by the group factor)")
    p.add_argument("--optimizer_state_dtype", default=None,
                   choices=["float32", "bfloat16", "int8"],
                   help="on-device Adam moment storage; narrow dtypes cut "
                        "the HBM-bound optimizer update traffic (int8 = "
                        "blockwise-absmax, second moment in sqrt-space)")
    p.add_argument("--multihost", action="store_true", default=None,
                   help="force jax.distributed.initialize() autodetect")
    p.add_argument("--device", type=str, default=None,
                   choices=["cpu", "tpu"],
                   help="force a JAX platform (cpu works even when a TPU "
                        "plugin is registered; the TPU->CPU fallback chain "
                        "replaces the reference's cuda->mps->cpu)")
    if mode == "fsdp":
        # reference fsdp_trainer.py:531-538
        p.add_argument("--sharding", type=str, default=None,
                       choices=_SHARDING_CHOICES)
        p.add_argument("--cpu_offload", action="store_true", default=None)
        p.add_argument("--offload_dtype", default=None,
                       choices=_OFFLOAD_DTYPES,
                       help="host storage dtype for offloaded optimizer "
                            "state; bfloat16 halves the host-link stream, "
                            "int8 (blockwise-absmax moments) quarters it")
        p.add_argument("--offload_budget_gb", type=float, default=None,
                       help="partial offload: GB of the largest optimizer-"
                            "moment leaves kept device-resident (exact "
                            "f32); only the overflow streams to host")
        p.add_argument("--no_activation_checkpointing", action="store_true",
                       default=None)
    return p


def load_yaml(path: Optional[str]) -> dict:
    if not path:
        return {}
    import yaml

    with open(path) as f:
        loaded = yaml.safe_load(f) or {}
    if not isinstance(loaded, dict):
        raise ValueError(f"{path}: expected a mapping at top level")
    return loaded


def _pick(*values):
    """First non-None value (CLI > YAML > default layering)."""
    for v in values:
        if v is not None:
            return v
    return None


def _pickf(*values) -> Optional[float]:
    """_pick + float coercion: YAML 1.1 parses bare '6e-4' as a string."""
    v = _pick(*values)
    return None if v is None else float(v)


def _picki(*values) -> Optional[int]:
    v = _pick(*values)
    return None if v is None else int(v)


def _preset_from_name(name: Optional[str]) -> Optional[str]:
    """Map a YAML model name like 'gpt2-small' to a preset key."""
    if not name:
        return None
    for key in ("small", "medium", "large", "xl"):
        if key in name:
            return key
    return None


def resolve_configs(args, mode: str):
    """Layer CLI flags over YAML over dataclass defaults → config objects."""
    y = load_yaml(args.config)
    y_model = y.get("model", {}) or {}
    y_train = y.get("training", {}) or {}
    y_dist = y.get("distributed", {}) or {}
    y_fsdp = y.get("fsdp", {}) or {}
    y_data = y.get("data", {}) or {}
    y_ckpt = y.get("checkpoint", {}) or {}
    y_ft = y.get("fault_tolerance", {}) or {}

    # --- model ---------------------------------------------------------
    preset = _pick(args.model_size, _preset_from_name(y_model.get("name")), "small")
    model_config = GPTConfig.preset(preset)
    overrides = {}
    # Any GPTConfig field may appear under `model:` (yaml keys == field
    # names; the reference schema's keys are a subset). Unknown keys fail
    # loudly — a silently-dropped `pipeline_schedule: 1f1b` once trained a
    # different configuration than the yaml said.
    _model_fields = {f.name for f in dataclasses.fields(GPTConfig)}
    for yaml_key, val in y_model.items():
        if yaml_key == "name":
            continue  # preset selector, handled above
        if yaml_key not in _model_fields:
            raise SystemExit(
                f"unknown model config key {yaml_key!r} in {args.config}; "
                f"valid keys: name, {', '.join(sorted(_model_fields))}"
            )
        overrides[yaml_key] = val
    if "hidden_size" in overrides and "intermediate_size" not in overrides:
        # Re-derive 4*hidden in __post_init__ rather than inheriting the
        # preset's intermediate size for a different hidden size.
        overrides["intermediate_size"] = None
    if args.seq_len is not None:
        overrides["max_seq_len"] = args.seq_len
    if args.num_experts is not None:
        overrides["num_experts"] = args.num_experts
    if args.moe_impl is not None:
        overrides["moe_impl"] = args.moe_impl
    if args.num_kv_heads is not None:
        overrides["num_kv_heads"] = args.num_kv_heads
    if args.gradient_checkpointing:
        overrides["gradient_checkpointing"] = True
    if mode == "fsdp":
        # FSDP default: activation checkpointing ON unless disabled
        # (reference fsdp_trainer.py:312-328, --no_activation_checkpointing).
        no_ckpt = getattr(args, "no_activation_checkpointing", None)
        if no_ckpt:
            overrides["gradient_checkpointing"] = False
        elif "gradient_checkpointing" not in overrides and not args.gradient_checkpointing:
            overrides["gradient_checkpointing"] = True
    if args.no_flash_attention:
        overrides["use_flash_attention"] = False
    elif "use_flash_attention" not in overrides:
        overrides["use_flash_attention"] = True
    if args.pipeline_microbatches is not None:
        overrides["pipeline_microbatches"] = args.pipeline_microbatches
    model_config = dataclasses.replace(model_config, **overrides)

    # --- training ------------------------------------------------------
    defaults = TrainingConfig()
    training_config = TrainingConfig(
        batch_size=_picki(args.batch_size, y_train.get("batch_size"),
                          defaults.batch_size),
        max_seq_len=model_config.max_seq_len,
        learning_rate=_pickf(args.learning_rate, y_train.get("learning_rate"),
                             defaults.learning_rate),
        weight_decay=_pickf(y_train.get("weight_decay"), defaults.weight_decay),
        beta1=_pickf(y_train.get("beta1"), defaults.beta1),
        beta2=_pickf(y_train.get("beta2"), defaults.beta2),
        grad_clip=_pickf(y_train.get("grad_clip"), defaults.grad_clip),
        max_steps=_picki(args.max_steps, y_train.get("max_steps"),
                         defaults.max_steps),
        warmup_steps=_picki(args.warmup_steps, y_train.get("warmup_steps"),
                            defaults.warmup_steps),
        log_interval=_picki(args.log_interval, y_train.get("log_interval"),
                            defaults.log_interval),
        eval_interval=_picki(args.eval_interval, y_train.get("eval_interval"),
                             defaults.eval_interval),
        save_interval=_picki(args.save_interval, y_train.get("save_interval"),
                             defaults.save_interval),
        mixed_precision=_pick(args.mixed_precision,
                              y_dist.get("mixed_precision"),
                              y_train.get("mixed_precision"),
                              defaults.mixed_precision),
        optimizer_state_dtype=_require_choice(
            _pick(args.optimizer_state_dtype,
                  y_train.get("optimizer_state_dtype"),
                  defaults.optimizer_state_dtype),
            _OFFLOAD_DTYPES, "optimizer_state_dtype"),
        gradient_accumulation_steps=_picki(
            args.grad_accum, y_train.get("gradient_accumulation_steps"),
            defaults.gradient_accumulation_steps),
        checkpoint_dir=_pick(args.checkpoint_dir, y_ckpt.get("dir"),
                             defaults.checkpoint_dir),
        resume_from=_pick(args.resume_from, y_ckpt.get("resume_from")),
        seed=_picki(args.seed, y_train.get("seed"), defaults.seed),
        # Step overlap (ISSUE 4): one resolved value each, read from here by
        # both the loaders and the startup summary.
        prefetch_depth=_picki(args.prefetch, y_data.get("prefetch"),
                              defaults.prefetch_depth),
        device_prefetch_depth=_picki(args.device_prefetch_depth,
                                     y_data.get("device_prefetch"),
                                     defaults.device_prefetch_depth),
        async_checkpointing=bool(_pick(
            False if args.no_async_checkpointing else None,
            y_ckpt.get("async"), defaults.async_checkpointing)),
    )

    # --- parallelism ---------------------------------------------------
    cpu_offload = False
    offload_dtype = "float32"
    offload_budget_gb = 0.0
    if mode == "fsdp":
        strategy = _pick(getattr(args, "sharding", None),
                         y_fsdp.get("sharding_strategy"), "FULL_SHARD")
        cpu_offload = bool(
            _pick(getattr(args, "cpu_offload", None),
                  y_fsdp.get("cpu_offload"), False)
        )
        offload_dtype = _require_choice(
            _pick(getattr(args, "offload_dtype", None),
                  y_fsdp.get("offload_dtype"), "float32"),
            _OFFLOAD_DTYPES, "offload_dtype")
        offload_budget_gb = _pickf(
            getattr(args, "offload_budget_gb", None),
            y_fsdp.get("offload_budget_gb"), 0.0)
        default_mesh = mesh_lib.MeshConfig(data=1, fsdp=-1)
    else:
        strategy = "replicated"
        default_mesh = mesh_lib.MeshConfig(data=-1, fsdp=1)
    mesh_auto = args.mesh == "auto"
    explicit_mesh = [
        flag for flag in ("mesh_data", "mesh_fsdp", "mesh_sequence",
                          "mesh_tensor", "mesh_expert", "mesh_stage")
        if getattr(args, flag) is not None
    ]
    if mesh_auto and explicit_mesh:
        raise SystemExit(
            "--mesh auto and explicit --" + "/--".join(explicit_mesh) +
            " are mutually exclusive: the planner picks every axis. Drop "
            "the explicit split, or drop --mesh auto to pin it yourself."
        )
    if strategy == "HYBRID_SHARD" and not mesh_auto \
            and args.mesh_data is None and args.mesh_fsdp is None:
        raise SystemExit(
            "HYBRID_SHARD needs an explicit mesh split: pass --mesh_data and "
            "--mesh_fsdp (data replicas x fsdp shards), or --mesh auto. (In "
            "the reference this mode is documented but unselectable — "
            "SURVEY.md §2.)"
        )
    mesh_config = mesh_lib.MeshConfig(
        data=_pick(args.mesh_data, default_mesh.data),
        fsdp=_pick(args.mesh_fsdp, default_mesh.fsdp),
        sequence=_pick(args.mesh_sequence, default_mesh.sequence),
        tensor=_pick(args.mesh_tensor, default_mesh.tensor),
        expert=_pick(args.mesh_expert, 1),
        stage=_pick(args.mesh_stage, 1),
    )
    parallel_config = ParallelConfig(
        mesh=mesh_config, sharding_strategy=strategy,
        cpu_offload=cpu_offload, offload_dtype=offload_dtype,
        offload_budget_gb=offload_budget_gb,
    )

    data_opts = {
        "dataset": _pick(args.dataset, y_data.get("dataset"), "dummy"),
        "data_path": _pick(args.data_path, y_data.get("path")),
        "max_tokens": _pick(args.max_tokens, y_data.get("max_tokens")),
        "streaming": bool(_pick(args.streaming, y_data.get("streaming"), False)),
        "pack_sequences": bool(_pick(args.pack_sequences,
                                     y_data.get("pack_sequences"), False)),
        "max_open_bins": _picki(args.max_open_bins,
                                y_data.get("max_open_bins"), 8),
        "pack_strategy": _pick(args.pack_strategy,
                               y_data.get("pack_strategy")) or "first_fit",
        "mask_doc_boundaries": bool(_pick(args.mask_doc_boundaries,
                                          y_data.get("mask_doc_boundaries"),
                                          False)),
        "data_mixture": _pick(args.data_mixture, y_data.get("mixture")),
        "cache_max_tokens": _pick(args.cache_max_tokens,
                                  y_data.get("cache_max_tokens")),
        "num_workers": _pick(args.num_workers, y_data.get("num_workers"), 0),
        "prefetch": training_config.prefetch_depth,
        "device_prefetch": training_config.device_prefetch_depth,
        "num_batches": _pick(args.num_batches, 100),
        "tokenizer": _pick(args.tokenizer, y_data.get("tokenizer"), "gpt2"),
        "metrics_jsonl": args.metrics_jsonl,
        "metrics_port": args.metrics_port,
        "wandb_project": args.wandb_project,
        "tensorboard_dir": args.tensorboard_dir,
        "eval_batches": _pick(args.eval_batches, 8),
        "eval_split": _pick(args.eval_split, y_data.get("eval_split"), 0.02),
        "eval_holdout_every": _pick(args.eval_holdout_every,
                                    y_data.get("eval_holdout_every"), 0),
        "auto_resume": not args.no_auto_resume,
        "profile_dir": args.profile_dir,
        "profile_start": _pick(args.profile_start, 5),
        "profile_steps": _pick(args.profile_steps, 5),
        "guard_interval": _pick(args.guard_interval, 100),
        # Fault tolerance (YAML: checkpoint.keep_last_n + fault_tolerance.*).
        # Defaults favor surviving a multi-day run: two rollbacks with
        # half-LR backoff, skipping one batch past the offending window.
        "keep_last_n": _picki(args.keep_last_n, y_ckpt.get("keep_last_n"), 0),
        "max_rollbacks": _picki(args.max_rollbacks,
                                y_ft.get("max_rollbacks"), 2),
        "skip_batches_on_rollback": _picki(
            args.skip_batches_on_rollback,
            y_ft.get("skip_batches_on_rollback"), 1),
        "rollback_lr_backoff": _pickf(args.rollback_lr_backoff,
                                      y_ft.get("rollback_lr_backoff"), 0.5),
        "inject_fault": args.inject_fault,
        "preemption_grace_s": _pickf(args.preemption_grace_s,
                                     y_ft.get("preemption_grace_s"), 0.0),
        "preempt_notice": _pick(args.preempt_notice,
                                y_ft.get("preempt_notice")),
        "preempt_notice_poll_s": _pickf(args.preempt_notice_poll_s,
                                        y_ft.get("preempt_notice_poll_s"),
                                        1.0),
        "preempt_vote_interval": _picki(args.preempt_vote_interval,
                                        y_ft.get("preempt_vote_interval"),
                                        _PREEMPT_VOTE_INTERVAL),
        # Telemetry / goodput / early warning (utils/telemetry.py).
        "telemetry_interval": _picki(args.telemetry_interval, None, 0),
        "spike_sigma": _pickf(args.spike_sigma, None, 6.0),
        "nan_scan": bool(_pick(args.nan_scan, False)),
        # Run anatomy (ISSUE 3).
        "comms_model": not bool(_pick(args.no_comms_model, False)),
        "flight_recorder_steps": _picki(args.flight_recorder_steps,
                                        None, 256),
        # Mesh auto-planner (--mesh auto; parallel/planner.py).
        "mesh_auto": mesh_auto,
        "hbm_gb": args.hbm_gb,
    }
    return model_config, training_config, parallel_config, data_opts


def parse_mixture_spec(spec: str) -> dict:
    """``'name:weight[:path],...'`` → ``{name: (weight, path)}``. Names must
    be distinct dataset kinds from {dummy, tinystories, openwebtext} (the
    mixture cursor keys per-source state by name)."""
    out = {}
    for part in spec.split(","):
        fields = part.strip().split(":", 2)
        if len(fields) < 2:
            raise SystemExit(
                f"bad --data_mixture entry {part.strip()!r}: expected "
                f"name:weight[:path]"
            )
        name = fields[0].strip()
        if name not in ("dummy", "tinystories", "openwebtext"):
            raise SystemExit(f"unknown mixture source {name!r}")
        if name in out:
            raise SystemExit(f"duplicate mixture source {name!r}")
        try:
            weight = float(fields[1])
        except ValueError:
            raise SystemExit(
                f"bad mixture weight {fields[1]!r} for source {name!r}"
            ) from None
        path = fields[2].strip() if len(fields) > 2 else None
        out[name] = (weight, path)
    return out


def _packed_synthetic_loader(rows, seq_len, vocab_size, num_batches, seed,
                             feed_rank, feed_world, max_open_bins, pack=True,
                             strategy="first_fit"):
    """Packed loader over a deterministic synthetic ragged corpus — the
    dummy dataset's packed counterpart (and the bench's --packed input).
    Documents stride across feed ranks so hosts pack disjoint rows."""
    from tpu_trainer.data.packing import (PackedDataLoader,
                                          synthetic_documents)

    mean_len = max(8, seq_len // 4)
    # Enough documents that every host can fill its num_batches * rows
    # rows: ~seq/mean docs land per packed row, plus slack for pad waste
    # in the pad-to-seq baseline lane (pack=False needs one row per doc).
    per_row = max(1, seq_len // mean_len) if pack else 1
    total_docs = num_batches * rows * feed_world * (per_row + 2)

    def doc_fn():
        docs = synthetic_documents(total_docs, mean_len, vocab_size,
                                   seed=seed)
        return (d for i, d in enumerate(docs)
                if i % feed_world == feed_rank)

    return PackedDataLoader(
        doc_fn, rows, seq_len, max_open_bins=max_open_bins, pack=pack,
        strategy=strategy, seed=seed, num_batches=num_batches,
    )


def _packed_text_loader(data_opts, rows, seq_len, feed_rank, feed_world,
                        seed):
    """Packed loader binning a text file's documents (lines) into full rows
    via ``StreamingTextDataset.iter_documents`` — shard/holdout/budget rules
    identical to the concatenating stream."""
    from tpu_trainer.data.packing import PackedDataLoader
    from tpu_trainer.data.text import StreamingTextDataset, TextDataLoader

    holdout_every = (data_opts["eval_holdout_every"]
                     if data_opts["streaming"] else 0)
    common = dict(
        tokenizer_name=data_opts["tokenizer"],
        max_tokens=data_opts["max_tokens"],
        cache_max_tokens=data_opts["cache_max_tokens"],
        shard_id=feed_rank,
        num_shards=feed_world,
        tokenizer_on_fallback="error",
    )
    ds = StreamingTextDataset(
        data_opts["data_path"], seq_len,
        num_workers=data_opts["num_workers"],
        holdout=("train", holdout_every) if holdout_every else None,
        **common,
    )
    train = PackedDataLoader(
        ds.iter_documents, rows, seq_len,
        max_open_bins=data_opts["max_open_bins"],
        strategy=data_opts.get("pack_strategy", "first_fit"), seed=seed,
    )
    eval_loader = None
    if holdout_every:
        # Held-out eval stays on the plain concatenating stream ([rows,
        # seq]): eval_step handles both formats, and eval loss on unpacked
        # rows is comparable across packed/unpacked training runs.
        eval_ds = StreamingTextDataset(
            data_opts["data_path"], seq_len,
            holdout=("eval", holdout_every), **common,
        )
        eval_loader = TextDataLoader(
            eval_ds, rows, process_index=feed_rank,
            process_count=feed_world, seed=seed, prefetch=0,
        )
    return train, eval_loader


def build_dataloaders(data_opts, trainer: Trainer, model_config: GPTConfig):
    """Train + (optional) eval loaders yielding per-host ``[rows, seq]``
    (or ``[rows, seq, 2]`` with a segment-id channel when packing or
    boundary masking is on).

    rows = grad_accum x micro_batch x (local data shards) — the reference's
    loader-batch semantics (``ddp_trainer.py:538``) applied per host.
    """
    c = trainer.training_config
    # Feed ranks come from the mesh's row coverage (Trainer.data_feed_*):
    # hosts sharing a data shard (sequence/tensor axes spanning hosts) get
    # the same rank and load identical rows.
    feed_rank, feed_world = trainer.data_feed_rank, trainer.data_feed_world
    rows = (c.gradient_accumulation_steps * c.batch_size * trainer.dp_size
            ) // feed_world
    if data_opts.get("data_mixture"):
        return _build_mixture(data_opts, trainer, model_config, rows,
                              feed_rank, feed_world)
    name = data_opts["dataset"]
    pack = data_opts.get("pack_sequences")
    if pack and name != "dummy":
        if not data_opts["data_path"]:
            raise SystemExit(f"--data_path is required for dataset {name!r}")
        return _packed_text_loader(data_opts, rows, c.max_seq_len,
                                   feed_rank, feed_world, c.seed)
    if name == "dummy":
        if pack:
            train = _packed_synthetic_loader(
                rows, c.max_seq_len, model_config.vocab_size,
                data_opts["num_batches"], c.seed + 1234, feed_rank,
                feed_world, data_opts["max_open_bins"],
                strategy=data_opts.get("pack_strategy", "first_fit"),
            )
            eval_loader = _packed_synthetic_loader(
                rows, c.max_seq_len, model_config.vocab_size,
                data_opts["eval_batches"], c.seed + 4321, feed_rank,
                feed_world, data_opts["max_open_bins"],
                strategy=data_opts.get("pack_strategy", "first_fit"),
            )
            return train, eval_loader
        from tpu_trainer.data.dummy import create_dummy_dataloader

        train = create_dummy_dataloader(
            batch_size=rows * feed_world,
            seq_len=c.max_seq_len,
            vocab_size=model_config.vocab_size,
            num_batches=data_opts["num_batches"],
            seed=c.seed + 1234,
            process_index=feed_rank,
            process_count=feed_world,
        )
        eval_loader = create_dummy_dataloader(
            batch_size=rows * feed_world,
            seq_len=c.max_seq_len,
            vocab_size=model_config.vocab_size,
            num_batches=data_opts["eval_batches"],
            seed=c.seed + 4321,   # disjoint synthetic eval corpus
            process_index=feed_rank,
            process_count=feed_world,
        )
        return train, eval_loader
    if name == "tinystories":
        from tpu_trainer.data.tinystories import create_tinystories_dataloader as factory
    elif name == "openwebtext":
        from tpu_trainer.data.openwebtext import create_openwebtext_dataloader as factory
    else:
        raise ValueError(f"unknown dataset {name!r}")
    if not data_opts["data_path"]:
        raise SystemExit(f"--data_path is required for dataset {name!r}")
    train = factory(
        data_opts["data_path"],
        batch_size=rows,
        seq_len=c.max_seq_len,
        tokenizer_name=data_opts["tokenizer"],
        max_tokens=data_opts["max_tokens"],
        streaming=data_opts["streaming"],
        cache_max_tokens=data_opts["cache_max_tokens"],
        process_index=feed_rank,
        process_count=feed_world,
        seed=trainer.training_config.seed,
        num_workers=data_opts["num_workers"],
        prefetch=data_opts["prefetch"],
        # Tokenizer guardrail (VERDICT r1 weak #6): training never falls
        # back to byte-level ids silently — choose it as --tokenizer byte.
        tokenizer_on_fallback="error",
        # Held-out eval (VERDICT r1 weak #5: the old "eval" re-read the
        # training data): map-style carves the tail eval_split fraction of
        # chunks; streaming reserves every eval_holdout_every-th line.
        # Train/eval rows are disjoint by construction (data/text.py).
        eval_split=0.0 if data_opts["streaming"] else data_opts["eval_split"],
        eval_holdout_every=(data_opts["eval_holdout_every"]
                            if data_opts["streaming"] else 0),
        # Cross-document loss-leak fix (streaming only; map-style chunks
        # have no in-chunk boundary metadata to derive segments from).
        mask_doc_boundaries=(data_opts["mask_doc_boundaries"]
                             if data_opts["streaming"] else False),
    )
    return train, train.eval_loader


def _build_mixture(data_opts, trainer, model_config, rows, feed_rank,
                   feed_world):
    """Weighted multi-source mixture (``--data_mixture``). Every source
    yields the same per-host batch shape — plain ``[rows, seq]``, or
    ``[rows, seq, 2]`` when ``--pack_sequences`` puts all sources (dummy
    included, via the synthetic ragged corpus) on the packed format."""
    from tpu_trainer.data.mixture import MixtureDataLoader

    c = trainer.training_config
    spec = parse_mixture_spec(data_opts["data_mixture"])
    pack = data_opts.get("pack_sequences")
    mask = data_opts.get("mask_doc_boundaries")
    if mask and not pack and "dummy" in spec:
        raise SystemExit(
            "--data_mixture with --mask_doc_boundaries cannot include the "
            "'dummy' source (its batches carry no segment channel, so the "
            "shapes would disagree); add --pack_sequences or drop dummy"
        )
    sources, weights = {}, {}
    for idx, nm in enumerate(sorted(spec)):
        weight, path = spec[nm]
        weights[nm] = weight
        sub_seed = c.seed + 1000 * (idx + 1)   # disjoint per-source streams
        if nm == "dummy":
            if pack:
                sources[nm] = _packed_synthetic_loader(
                    rows, c.max_seq_len, model_config.vocab_size,
                    data_opts["num_batches"], sub_seed, feed_rank,
                    feed_world, data_opts["max_open_bins"],
                    strategy=data_opts.get("pack_strategy", "first_fit"),
                )
            else:
                from tpu_trainer.data.dummy import create_dummy_dataloader

                sources[nm] = create_dummy_dataloader(
                    batch_size=rows * feed_world, seq_len=c.max_seq_len,
                    vocab_size=model_config.vocab_size,
                    num_batches=data_opts["num_batches"], seed=sub_seed,
                    process_index=feed_rank, process_count=feed_world,
                )
            continue
        if not path:
            raise SystemExit(
                f"mixture source {nm!r} needs a path "
                f"('{nm}:<weight>:<path>')"
            )
        if pack:
            opts = dict(data_opts, data_path=path, streaming=True,
                        eval_holdout_every=0)
            train, _ = _packed_text_loader(opts, rows, c.max_seq_len,
                                           feed_rank, feed_world, sub_seed)
            sources[nm] = train
        else:
            from tpu_trainer.data.text import create_text_dataloader

            sources[nm] = create_text_dataloader(
                path, batch_size=rows, seq_len=c.max_seq_len,
                tokenizer_name=data_opts["tokenizer"],
                max_tokens=data_opts["max_tokens"], streaming=True,
                cache_max_tokens=data_opts["cache_max_tokens"],
                process_index=feed_rank, process_count=feed_world,
                seed=sub_seed, num_workers=data_opts["num_workers"],
                # Sub-loaders draw on demand; background prefetch threads
                # would race the mixture's deterministic draw order for no
                # overlap win (the mixture itself sits behind feed prefetch).
                prefetch=0, tokenizer_on_fallback="error",
                mask_doc_boundaries=bool(mask),
            )
    train = MixtureDataLoader(sources, weights, seed=c.seed)
    # No held-out eval across a mixture (per-source holdouts would need
    # per-source eval weighting to mean anything); eval stays available via
    # single-source runs.
    return train, None


def run_training(argv=None, mode: str = "ddp") -> int:
    args = build_parser(mode).parse_args(argv)
    import os

    import jax

    # --- standby host (elastic supervisor's warm spares) ---------------
    # A standby has paid the cold-start bill — interpreter, imports (jax is
    # the multi-second item), arg parsing — and parks HERE, before the
    # jax.distributed rendezvous binds coordinator/world/rank. Promotion
    # (the supervisor writing the activation file) hands it the same env a
    # fresh child would get, and it proceeds down the normal path.
    standby_file = os.environ.get("TPU_TRAINER_STANDBY_FILE")
    if standby_file:
        from tpu_trainer.training import elastic as elastic_lib
        print(f"standby: parked before rendezvous ({standby_file})",
              flush=True)
        activation = elastic_lib.hold_standby(standby_file)
        if activation is None:
            print("standby: supervisor gone; retiring unpromoted",
                  flush=True)
            return 0
        os.environ.update(activation)
        print(f"standby: promoted to rank {activation.get('PROCESS_ID')} "
              f"(world {activation.get('NUM_PROCESSES')})", flush=True)

    if args.device:
        # --device picks the platform; without it jax's own choice stands
        # (JAX_PLATFORMS, or an embedding harness's jax.config setting).
        jax.config.update("jax_platforms", args.device)
    from tpu_trainer.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # Partitionable threefry, same as tests/conftest.py: without it the
    # pipeline stage shard_map lowers per-step RNG to a PartitionId
    # instruction the SPMD partitioner rejects — stage>1 meshes (the
    # planner picks them freely) would crash at the first train step.
    jax.config.update("jax_threefry_partitionable", True)
    mesh_lib.initialize_distributed(auto=args.multihost)

    model_config, training_config, parallel_config, data_opts = resolve_configs(
        args, mode
    )

    # --- mesh auto-planner / early mesh validation ---------------------
    # Both paths share planner_lib.feasibility_error, so a split the CLI
    # accepts here is exactly one the Trainer's own divisibility checks
    # accept below — the predicate can't disagree with the pruning.
    plan_record = None
    n_devices = jax.device_count()
    plan_mc = dataclasses.replace(
        model_config, dtype=_MP_TO_DTYPE[training_config.mixed_precision])
    plan_opt_bytes = {"float32": 4, "bfloat16": 2, "int8": 1}.get(
        training_config.optimizer_state_dtype, 4)
    if data_opts["mesh_auto"]:
        # Hold the global batch a pure-DP run would have (per-shard
        # batch_size on every device) fixed across candidates; the winner's
        # per-shard batch is global_rows / its data*fsdp world.
        global_rows = training_config.batch_size * n_devices
        # The CPU SPMD partitioner cannot lower the GPipe stage shard_map
        # (PartitionId rejection), so correctness-mode planning must not
        # hand back a mesh the Trainer then crashes on. Real TPUs plan
        # all six axes.
        exclude = (() if jax.devices()[0].platform == "tpu"
                   else ("stage",))
        try:
            plan_record = planner_lib.plan(
                plan_mc, n_devices,
                global_rows=global_rows,
                max_seq_len=training_config.max_seq_len,
                grad_accum=training_config.gradient_accumulation_steps,
                strategy=parallel_config.sharding_strategy,
                hbm_gb=data_opts["hbm_gb"],
                opt_state_bytes=plan_opt_bytes,
                carry_cast=training_config.carry_cast_params,
                exclude_axes=exclude)
        except planner_lib.NoFeasiblePlanError as plan_err:
            raise SystemExit(f"--mesh auto: {plan_err}") from plan_err
        plan_record["auto"] = True
        chosen = plan_record["chosen"]
        parallel_config = dataclasses.replace(
            parallel_config, mesh=planner_lib.mesh_config_for(chosen))
        if chosen["batch_per_shard"] != training_config.batch_size:
            training_config = dataclasses.replace(
                training_config, batch_size=chosen["batch_per_shard"])
        if jax.process_index() == 0:
            for line in planner_lib.render_table(plan_record):
                print(line, flush=True)
    else:
        try:
            resolved = parallel_config.mesh.resolve(n_devices)
        except ValueError as mesh_err:
            raise SystemExit(f"mesh: {mesh_err}") from mesh_err
        sizes = dict(zip(mesh_lib.MESH_AXES, resolved))
        feas_err = planner_lib.feasibility_error(
            sizes, plan_mc, n_devices=n_devices,
            global_rows=training_config.batch_size
            * sizes[mesh_lib.DATA_AXIS] * sizes[mesh_lib.FSDP_AXIS],
            max_seq_len=training_config.max_seq_len)
        if feas_err:
            raise SystemExit(
                f"mesh: infeasible split {tuple(resolved)} "
                f"({'x'.join(mesh_lib.MESH_AXES)}): {feas_err} — fix the "
                f"--mesh_* split, or let --mesh auto pick one")

    trainer = Trainer(model_config, training_config, parallel_config)
    main = trainer.is_main_process
    if main:
        print(f"mode={mode} strategy={trainer.strategy} "
              f"mesh={dict(trainer.mesh.shape)} devices={jax.device_count()} "
              f"x {jax.devices()[0].device_kind} "
              f"processes={trainer.process_count}")
        print(f"model: {model_config.num_parameters():,} params | "
              f"global batch {trainer.global_batch_size} seqs x "
              f"{training_config.max_seq_len} tokens")
        print(f"overlap: host_prefetch={training_config.prefetch_depth} "
              f"device_prefetch={training_config.device_prefetch_depth} "
              f"async_checkpointing="
              f"{'on' if training_config.async_checkpointing else 'off'}")
        if trainer.cpu_offload and trainer.offload_resident_bytes:
            print(f"partial offload: "
                  f"{trainer.offload_resident_bytes / 2**30:.2f} GB of "
                  f"optimizer moments device-resident (exact f32), "
                  f"overflow streams to host")

    # --- fault injection (--inject_fault debug flag; utils/faults.py) --
    installed_plan = None
    if data_opts["inject_fault"]:
        # process_count makes install validate TPU_TRAINER_FAULT_HOST once,
        # up front — a typo'd target rank must fail the run loudly, not
        # quietly neuter the chaos fault it was meant to aim.
        installed_plan = faults.install(data_opts["inject_fault"],
                                        process_count=trainer.process_count)

    # --- goodput ledger: attribute every second of the run -------------
    ledger = telemetry_lib.GoodputLedger()

    # --- resume (SURVEY.md §5.3: actually wired) -----------------------
    state = None
    tokens_seen = 0
    data_state = None
    resume_path = training_config.resume_from
    if resume_path:
        # Explicit --resume_from: failures raise — the user asked for this
        # exact checkpoint, silently substituting another would be worse.
        with ledger.track("checkpoint_restore"):
            state, meta = ckpt_lib.restore_checkpoint(resume_path, trainer)
        tokens_seen = meta.get("tokens_seen", 0)
        data_state = meta.get("data_state")
        if main:
            print(f"resumed from {resume_path} at step {int(state.step)}")
    elif data_opts["auto_resume"]:
        # Auto-resume hardening: a corrupt/partial latest checkpoint is
        # quarantined and the previous valid step restores instead — one
        # bad save must never brick the restart loop of a multi-day run.
        with ledger.track("checkpoint_restore"):
            restored = ckpt_lib.restore_latest(
                training_config.checkpoint_dir, trainer, verify=True
            )
        if restored is not None:
            state, meta, resume_path = restored
            tokens_seen = meta.get("tokens_seen", 0)
            data_state = meta.get("data_state")
            if main:
                print(f"resumed from {resume_path} at step {int(state.step)}")
    if state is None:
        state = trainer.init_state()

    train_loader, eval_loader = build_dataloaders(data_opts, trainer, model_config)
    if data_state is not None and hasattr(train_loader, "load_state_dict"):
        # Exact data resume: continue at the consumed-batch cursor saved in
        # the checkpoint instead of re-reading the dataset from the start.
        # If this run's mesh resized the global batch or feed world since
        # the save (elastic restart on fewer hosts), remap the cursor onto
        # the new batch granularity first — at-least-once semantics, never
        # skipping data.
        data_state, replayed = ckpt_lib.remap_data_state(
            data_state,
            new_global_batch_size=trainer.global_batch_size,
            new_feed_world=trainer.data_feed_world,
        )
        if replayed and main:
            print(f"data cursor remapped for the resized mesh: replaying "
                  f"{replayed} already-seen sequences (at-least-once window, "
                  f"batch granularity)", flush=True)
        try:
            train_loader.load_state_dict(data_state)
        except ValueError as e:
            if main:
                print(f"data state not restored ({e}); reading the dataset "
                      f"from the start", flush=True)

    # --- crash flight recorder (ISSUE 3): ring of emitted records ------
    recorder = None
    if data_opts["flight_recorder_steps"] > 0:
        recorder = flight_lib.FlightRecorder(
            capacity=data_opts["flight_recorder_steps"],
            snapshot=flight_lib.env_snapshot(
                trainer=trainer, model_config=model_config,
                training_config=training_config, argv=argv),
        )

    # --- heartbeats for the elastic run supervisor ---------------------
    # The supervisor (training/elastic.py) exports TPU_TRAINER_HEARTBEAT_DIR
    # to its children; standalone runs skip this entirely. One beat per
    # completed step — the supervisor's staleness check is how a hung (not
    # dead) host gets caught.
    heartbeat = None
    hb_dir = os.environ.get("TPU_TRAINER_HEARTBEAT_DIR")
    if hb_dir:
        heartbeat = flight_lib.HeartbeatWriter(
            hb_dir, host=trainer.process_index,
            min_interval_s=float(
                os.environ.get("TPU_TRAINER_HEARTBEAT_INTERVAL_S", "0")),
            recorder=recorder,
            # Every beat carries the step this attempt resumed at: the
            # supervisor computes rolled-back work as (dead attempt's last
            # beat) - (new attempt's start_step) — exactly 0 for a
            # proactive notice drain, whose exit checkpoint IS the resume
            # point.
            start_step=int(state.step),
        )

    def dump_flight(reason: str, exc: Optional[BaseException] = None):
        """Best-effort crash_report.json — never masks the real failure."""
        if recorder is None:
            return
        try:
            path = recorder.dump(
                training_config.checkpoint_dir, reason=reason, exc=exc,
                step=int(state.step) if state is not None else None)
            if main:
                print(f"flight recorder: wrote {path} ({reason})", flush=True)
        except Exception as dump_err:
            if main:
                print(f"flight recorder dump failed: {dump_err}", flush=True)

    # Live metrics plane (ISSUE 18): registry + bridge + HTTP endpoint,
    # host 0 only. The bridge rides the MetricLogger observer hook, so
    # every record the sinks see also updates the scrapeable registry —
    # and nothing else changes: with --metrics_port unset this whole
    # block is skipped and the run is bit-identical.
    metrics_server = None
    metrics_bridge = None
    if data_opts["metrics_port"] is not None and main:
        from tpu_trainer.obs.http import MetricsServer
        from tpu_trainer.obs.metrics import MetricsRegistry

        metrics_bridge = telemetry_lib.MetricsBridge(MetricsRegistry())
        metrics_server = MetricsServer(
            metrics_bridge.registry, port=data_opts["metrics_port"],
            statusz_fn=metrics_bridge.statusz)
        # Ready once the run has produced its first record — before
        # that the process is alive but still compiling/restoring.
        metrics_server.health.add_probe(
            "first_record", lambda: metrics_bridge.n_records > 0)
        print(f"metrics: serving {metrics_server.url}/metrics", flush=True)

    logger = MetricLogger(
        model_config,
        tokens_per_step=trainer.tokens_per_step,
        log_interval=training_config.log_interval,
        jsonl_path=data_opts["metrics_jsonl"],
        is_main_process=main,
        wandb_project=data_opts["wandb_project"],
        tensorboard_dir=data_opts["tensorboard_dir"],
        run_config={
            "model": dataclasses.asdict(model_config),
            "training": dataclasses.asdict(training_config),
        },
        seq_len=training_config.max_seq_len,
        recorder=recorder,
        observer=metrics_bridge,
    )
    logger.tokens_seen = tokens_seen

    if plan_record is not None:
        # The ranked table already printed at plan time (before the mesh
        # existed); this persists the record to the JSONL sinks.
        logger.log_record(plan_record)

    # --- nan_scan debug mode: bisect the first non-finite layer, exit --
    if data_opts["nan_scan"]:
        try:
            batch = next(iter(train_loader))
            report = trainer.nan_scan(state, batch)
            first = report["first_nan"]
            verdict = (
                "no non-finite activations in the forward" if first is None
                else f"first non-finite value at layer {first['layer']}, "
                     f"site '{first['site']}'"
            )
            if main:
                print(f"nan_scan | {verdict}")
                stats = report["stats"]
                layers = sorted({k.rsplit("/L", 1)[1]
                                 for k in stats if "/L" in k})
                for li in layers:
                    row = " ".join(
                        f"{site}={stats.get(f'nan_scan/act/{site}_absmax/L{li}', float('nan')):.3e}"
                        for site in ("attn", "ffn", "block")
                    )
                    print(f"nan_scan | layer {li} absmax: {row}")
                head = " ".join(
                    f"{site}={stats[f'nan_scan/act/{site}_absmax']:.3e}"
                    for site in ("embed_out", "final_norm", "logits")
                    if f"nan_scan/act/{site}_absmax" in stats
                )
                print(f"nan_scan | head absmax: {head}")
                print(f"nan_scan | loss: {stats['nan_scan/loss']:.6g}")
            logger.log_record({
                "kind": "nan_scan", "step": int(state.step),
                "first_nan": first, "sites": report["sites"],
                **report["stats"],
            })
            return 0
        finally:
            logger.close()
            if metrics_server is not None:
                metrics_server.close()
            if installed_plan is not None:
                faults.clear()

    # --- preemption handler (TPU maintenance SIGTERM) ------------------
    # "at" anchors the --preemption_grace_s deadline at signal receipt, not
    # at the (cadenced) vote that notices it.
    preempted = {"hit": False, "at": None}

    def _on_sigterm(signum, frame):
        preempted["hit"] = True
        if preempted["at"] is None:
            preempted["at"] = time.monotonic()

    old_handler = signal.signal(signal.SIGTERM, _on_sigterm)

    # --- proactive preemption notice (utils/preemption.py) -------------
    # The polled notice arrives BEFORE the kill deadline starts running
    # (SIGTERM is the fallback that arrives after). A noticed host drains
    # at the next vote boundary: checkpoint, write a drain marker
    # (deregister — the supervisor reforms without counting a crash), exit.
    notice_source = preemption_lib.build_notice_source(
        data_opts["preempt_notice"]
        or os.environ.get("TPU_TRAINER_PREEMPT_NOTICE"),
        poll_interval_s=data_opts["preempt_notice_poll_s"])
    notice = {"rec": None}

    def check_notice(step: int) -> bool:
        """Poll the notice source (and the preempt_notice fault) once per
        step; sticky. Logs on first receipt."""
        if notice["rec"] is not None:
            return True
        if faults.fire("preempt_notice", step) and faults.targets_host(
                trainer.process_index, trainer.process_count):
            grace = data_opts["preemption_grace_s"]
            notice["rec"] = preemption_lib.PreemptionNotice(
                source="fault:preempt_notice",
                received_unix=time.time(),
                deadline_unix=(time.time() + grace) if grace else None)
        elif notice_source is not None:
            notice["rec"] = notice_source.poll()
        if notice["rec"] is not None:
            remaining = notice["rec"].remaining_s()
            print(f"preemption notice received ({notice['rec'].source})"
                  + (f": {remaining:.1f}s to the kill deadline"
                     if remaining is not None else "")
                  + "; draining at the next step boundary", flush=True)
            return True
        return False

    # Async checkpointing (ISSUE 4): the periodic save snapshots to host and
    # returns; shards + meta commit on the saver's writer thread. At most one
    # commit is in flight — the next save, a rollback, SIGTERM, and exit all
    # drain it first, and that wait is attributed to checkpoint_commit_wait
    # (in steady state the commit finishes under the following steps' compute
    # and the drain costs ~nothing).
    saver = ckpt_lib.AsyncSaver() if training_config.async_checkpointing else None

    def drain_save(timeout: Optional[float] = None) -> bool:
        """Drain the in-flight async commit; False when ``timeout`` expired
        with the commit still running (daemon writer — it dies with the
        process, leaving the usual crash-safe meta-less tree)."""
        if saver is not None and saver.in_flight:
            with ledger.track("checkpoint_commit_wait"):
                saver.wait(timeout)
            return not saver.in_flight
        return True

    def save(tag: str = "", wait: bool = False,
             deadline: Optional[float] = None):
        if deadline is not None:
            # Preemption grace: both drains are bounded by the remaining
            # budget; an expired budget abandons the save rather than
            # outliving the scheduler's kill.
            if not drain_save(max(0.0, deadline - time.monotonic())):
                if main:
                    print("preemption grace spent draining the in-flight "
                          "commit; skipping the final checkpoint", flush=True)
                return
        else:
            drain_save()
        with ledger.track("checkpoint_save"):
            # The feed's cursor, not the raw loader's: with device prefetch
            # the loader runs up to depth batches ahead of what the trainer
            # consumed, and resuming from its cursor would skip the
            # buffered batches. The feed signature (global batch size, feed
            # world) rides along so an elastic restart on a resized mesh
            # can remap the cursor's units.
            data_sd = feed.state_dict()
            if data_sd is not None:
                data_sd = dict(data_sd, **trainer.feed_signature)
            save_fn = saver.save if saver is not None else ckpt_lib.save_checkpoint
            path = save_fn(
                training_config.checkpoint_dir, state,
                model_config=model_config, training_config=training_config,
                tokens_seen=logger.tokens_seen,
                data_state=data_sd,
                keep_last_n=data_opts["keep_last_n"],
            )
        if wait:
            # Terminal saves (final/preempt/crash): the process is about to
            # exit, so the checkpoint must be durable before we return.
            if not drain_save(None if deadline is None
                              else max(0.0, deadline - time.monotonic())):
                if main:
                    print("preemption grace expired before the final commit "
                          "landed; exiting with the commit in flight",
                          flush=True)
                return
        if main:
            print(f"saved checkpoint{' (' + tag + ')' if tag else ''}: {path}")

    eval_warned = {"hit": False}

    def run_eval():
        if eval_loader is None:
            return
        losses = []
        with ledger.track("eval"):
            for i, batch in enumerate(eval_loader):
                if i >= data_opts["eval_batches"]:
                    break
                # Device value: each eval step dispatches async and the
                # loop keeps feeding; the single device_get below is the
                # only host sync for the whole eval pass (the old
                # per-batch float() serialized host and device).
                losses.append(trainer.eval_step(state, batch))
            losses = [float(x) for x in jax.device_get(losses)]
        if losses and main:
            logger.log_eval(int(state.step), float(np.mean(losses)),
                            len(losses))
        elif not losses and main and not eval_warned["hit"]:
            eval_warned["hit"] = True
            print(
                "eval | no full eval batch (held-out rows < batch rows x "
                "hosts); grow --eval_split / --eval_holdout_every or the "
                "dataset", flush=True,
            )

    # --- the step loop (reference ddp_trainer.py:582-616) --------------
    data_iter = iter(train_loader)
    # Per-source loss telemetry (mixture loaders): sources are recorded at
    # PULL time in a FIFO — the device prefetcher pulls ahead of the
    # consuming step, but pull order == consume order, so popping one entry
    # per consumed batch re-aligns them exactly. source_by_step then feeds
    # the (window-lagged) metric log as the `data_source` extra.
    source_fifo = []
    source_by_step = {}

    def next_batch():
        nonlocal data_iter
        try:
            b = next(data_iter)
        except StopIteration:
            data_iter = iter(train_loader)  # new epoch
            try:
                b = next(data_iter)
            except StopIteration:
                raise SystemExit(
                    "the dataset yields zero batches for this configuration: "
                    "it is smaller than one global batch stride "
                    f"(batch_size x grad_accum x data shards = "
                    f"{trainer.global_batch_size} sequences of "
                    f"{training_config.max_seq_len} tokens). Use a larger "
                    "dataset or reduce batch_size/grad_accum."
                ) from None
        src = getattr(train_loader, "last_source", None)
        if src is not None:
            source_fifo.append(src)
        return b

    # Device prefetch (ISSUE 4): the feed owns the trainer-consumed cursor
    # (data/device_prefetch.py docstring) — every checkpoint/rollback reads
    # feed.state_dict(), never the raw loader's. place binds late so an LR
    # backoff's rebuilt trainer is picked up without respawning the feed.
    def make_feed():
        # Batches buffered in a discarded feed were pulled but never
        # consumed; their FIFO entries would desync the source alignment.
        source_fifo.clear()
        return DevicePrefetcher(
            next_batch,
            place=lambda b: trainer.place_batch(b),
            cursor_fn=(train_loader.state_dict
                       if hasattr(train_loader, "state_dict") else None),
            depth=data_opts["device_prefetch"],
        )

    feed = make_feed()

    profiler = profiling.WindowedTrace(
        data_opts["profile_dir"],
        start=int(state.step) + data_opts["profile_start"],
        num_steps=data_opts["profile_steps"],
    )
    guard_interval = data_opts["guard_interval"]

    # Rollback budget (divergence recovery): on a non-finite loss or
    # cross-host divergence, rewind to the last good checkpoint, skip the
    # offending data window, shrink the LR, and retry — bounded by
    # --max_rollbacks so a deterministic failure still fails loudly.
    max_rollbacks = data_opts["max_rollbacks"]
    rollbacks = 0
    steps_this_run = 0
    base_lr = training_config.learning_rate

    # Telemetry cadence + loss-spike early warning (ISSUE 2). The spike
    # check reads only records the logger actually emitted, and (ISSUE 4)
    # only on matured — window-lagged — host values.
    telemetry_interval = data_opts["telemetry_interval"]
    spike = (telemetry_lib.SpikeDetector(sigma=data_opts["spike_sigma"])
             if data_opts["spike_sigma"] > 0 else None)
    # Deferred host sync (ISSUE 4): each step's metrics go into a bounded
    # window of in-flight futures instead of being read back immediately;
    # the logger/spike/guard consumers below run on matured (lagged) host
    # values, so the host never blocks on the step it just dispatched.
    deferred = telemetry_lib.DeferredFetcher(window=_DEFERRED_SYNC_WINDOW)

    def consume(entries, check: bool = True):
        """Log, spike-check, and guard each matured metric entry.
        ``check=False`` (exit paths) logs without raising."""
        for mstep, mmetrics, pushed_at in entries:
            src = source_by_step.pop(mstep, None)
            rec = logger.log(
                mstep, mmetrics,
                extra=None if src is None else {"data_source": src},
                at=pushed_at)
            if not check:
                continue
            if spike is not None and rec is not None:
                is_spike, z = spike.update(rec["loss"])
                if is_spike:
                    if main:
                        print(
                            f"loss spike at step {mstep}: loss "
                            f"{rec['loss']:.4f} is z={z:.1f} above "
                            f"the rolling median (sigma="
                            f"{data_opts['spike_sigma']:g}); rolling "
                            "back before divergence", flush=True)
                    raise guards.LossSpikeError(
                        f"loss spike (z={z:.1f}) at step {mstep}")
            if guard_interval and (mstep + 1) % guard_interval == 0:
                loss = (rec or {}).get("loss")
                if loss is None:
                    loss = float(mmetrics["loss"])
                guards.check_finite(mstep, loss)
                guards.check_hosts_in_sync(mstep, loss)
    # Goodput attribution: the first execution of each jitted step variant
    # pays tracing + XLA compilation, so its wall-clock goes to "compile";
    # later executions go to "step" (or "rollback_replay" while re-covering
    # ground rewound by a rollback). Reset on LR backoff — rebuilding the
    # trainer recompiles both variants.
    jit_warm = {"step": False, "telemetry": False}
    cost_emitted = False
    replay_until = -1   # steps <= this are rollback replay, not fresh work
    # Recompile watchdog (ISSUE 3): executable-cache growth after warmup
    # means XLA recompiled the step — log it; repeated growth is a storm
    # (loader shape churn) and warns loudly.
    watchdog = RecompileWatchdog(trainer)

    try:
        while True:
            try:
                start_step = int(state.step)
                step = start_step
                if heartbeat is not None:
                    # Entry beat: start_step steps ARE completed (resumed)
                    # when the loop starts, so this is a true beat — and it
                    # marks the host live for the supervisor before the
                    # first step's multi-second compile, which would
                    # otherwise be silent. The recovery window (death →
                    # first beat of the new attempt) therefore measures
                    # time-to-resumed-and-ready, not compile time the dead
                    # host would have paid too.
                    heartbeat.beat(start_step)
                for step in range(start_step, training_config.max_steps):
                    if faults.fire("kill", step):
                        faults.kill()
                    if faults.fire("sigterm", step):
                        # A preemption notice that DID arrive: deliver a real
                        # SIGTERM to ourselves so the drain/grace exit path
                        # is exercised through the actual handler.
                        os.kill(os.getpid(), signal.SIGTERM)
                    if faults.fire("kill_host", step) and faults.targets_host(
                            trainer.process_index, trainer.process_count):
                        # Chaos lane: this rank dies hard; the others keep
                        # running until the supervisor reforms the mesh.
                        faults.kill()
                    if faults.fire("hang_host", step) and faults.targets_host(
                            trainer.process_index, trainer.process_count):
                        # Chaos lane: look dead without dying — only the
                        # supervisor's heartbeat-staleness check catches it.
                        if heartbeat is not None:
                            heartbeat.stop()
                    if (faults.fire("return_host", step)
                            and trainer.process_index == 0
                            and int(os.environ.get("TPU_TRAINER_ATTEMPT",
                                                   "0")) > 0):
                        # Chaos lane: the cluster re-grants a host. Not
                        # host-targeted — rank 0 plays the granting agent,
                        # and it must stay live at world 1, where a shrunk
                        # run is exactly the one that needs to grow back.
                        # Armed only on attempt > 0: a "returned" host only
                        # exists after a death, and async dispatch lets the
                        # first attempt's Python loop run steps ahead of the
                        # collective a dying peer just abandoned — an
                        # attempt-0 grant would regrow the reform straight
                        # into the re-armed kill fault.
                        cap_file = os.environ.get("TPU_TRAINER_CAPACITY_FILE")
                        if cap_file:
                            total = preemption_lib.grant_capacity(cap_file, 1)
                            print(f"fault return_host@{step}: capacity grant "
                                  f"written ({total} host(s) available)",
                                  flush=True)
                    has_notice = check_notice(step)
                    # profiler.step returns a StepTraceAnnotation context
                    # inside the trace window (per-step grouping in the
                    # viewer), a nullcontext outside it.
                    with profiler.step(step):
                        with ledger.track("data_wait"):
                            # Device-resident (or at least enqueued) already
                            # when device_prefetch_depth > 0 — the H2D copy
                            # ran under the previous step's compute.
                            batch = feed.next()
                        if source_fifo:
                            source_by_step[step] = source_fifo.pop(0)
                        tel_step = bool(
                            telemetry_interval
                            and (step + 1) % telemetry_interval == 0)
                        variant = "telemetry" if tel_step else "step"
                        expected_compile = not jit_warm[variant]
                        category = ("compile" if expected_compile
                                    else "rollback_replay"
                                    if step <= replay_until else "step")
                        # The matured metric fetches are the device sync
                        # point, so they stay inside the tracked block —
                        # otherwise async dispatch would bank the real
                        # compute under "untracked".
                        with ledger.track(category):
                            state, metrics = trainer.train_step(
                                state, batch, telemetry=tel_step)
                            if not jit_warm[variant]:
                                jax.block_until_ready(metrics["loss"])
                                jit_warm[variant] = True
                            steps_this_run += 1
                            transform = None
                            if faults.fire("nan_loss", step):
                                transform = _nan_loss_transform
                            if faults.fire("loss_spike", step):
                                transform = _loss_spike_transform
                            # Padding-waste accounting: loaders that pack
                            # (or segment) expose the cumulative non-pad
                            # fraction; the logger turns it into
                            # effective_tokens_per_sec, the ledger into the
                            # run-level non-pad goodput numbers. Loaders
                            # without the stat count as fully dense.
                            npf = getattr(train_loader, "non_pad_frac", None)
                            if npf is not None:
                                logger.non_pad_frac = float(npf)
                            ledger.add_tokens(
                                trainer.tokens_per_step,
                                None if npf is None else int(round(
                                    trainer.tokens_per_step * float(npf))),
                            )
                            consume(deferred.push(step, metrics, transform))
                    if heartbeat is not None:
                        heartbeat.beat(step + 1)
                    wd_rec = watchdog.observe(step, batch,
                                              expected=expected_compile)
                    if wd_rec is not None:
                        wd_lines = [
                            f"recompile | step {step}: train step recompiled"
                            f" for {wd_rec['batch_abstract']} (executables: "
                            f"{wd_rec['executables']})"]
                        if wd_rec.get("storm"):
                            wd_lines.append(
                                "recompile | WARNING: steady-state "
                                f"recompilation ({wd_rec['recompiles_total']}"
                                " events after warmup) — input shapes are "
                                "churning; check the loader/bucketing")
                        logger.log_record(wd_rec, stdout_lines=wd_lines)
                    if not cost_emitted:
                        # One-time XLA cost model vs analytic FLOPs. Runs
                        # after the first step so .lower().compile() hits the
                        # executable cache instead of recompiling.
                        cost_emitted = True
                        cost = trainer.step_cost_analysis(state, batch)
                        if cost is not None:
                            analytic = (flops_per_token(
                                model_config, training_config.max_seq_len)
                                * trainer.tokens_per_step)
                            rec = {"kind": "cost_analysis", "step": step}
                            rec.update(cost)
                            rec["analytic_flops_per_step"] = analytic
                            lines = []
                            if cost.get("flops_per_step"):
                                rec["analytic_over_xla"] = (
                                    analytic / cost["flops_per_step"])
                                lines.append(
                                    "cost_analysis | xla "
                                    f"{cost['flops_per_step']:.3e} flops/step"
                                    f" | analytic {analytic:.3e}"
                                    f" (x{rec['analytic_over_xla']:.2f})")
                            if cost.get("peak_bytes"):
                                lines.append(
                                    "cost_analysis | predicted peak HBM "
                                    f"{cost['peak_bytes'] / 2**30:.2f} GiB")
                            logger.log_record(rec, stdout_lines=lines)
                        if data_opts["comms_model"]:
                            # One-time analytic collective-traffic record,
                            # cross-checked against the collectives GSPMD
                            # actually put in the compiled step's HLO.
                            try:
                                comms = comms_lib.build(trainer)
                                comms["step"] = step
                                comms.update(comms_lib.crosscheck(
                                    comms, trainer.compiled_step_text(
                                        state, batch)))
                                logger.log_record(
                                    comms,
                                    stdout_lines=comms_lib.summary_lines(
                                        comms))
                            except Exception as comms_err:
                                if main:
                                    print("comms_model failed: "
                                          f"{type(comms_err).__name__}: "
                                          f"{comms_err}", flush=True)
                    if tel_step:
                        logger.log_record(ledger.record(step=step))
                    eval_now = (training_config.eval_interval > 0
                                and (step + 1) % training_config.eval_interval == 0)
                    save_now = (training_config.save_interval > 0
                                and (step + 1) % training_config.save_interval == 0)
                    if eval_now or save_now:
                        # Boundary: materialize outstanding metric futures
                        # so eval records order after the train records and
                        # the checkpoint's tokens_seen count is exact (the
                        # eval/snapshot sync pays the device wait anyway).
                        consume(deferred.drain())
                    if eval_now:
                        run_eval()
                    if save_now:
                        save()
                    # The preempt decision must be unanimous: the checkpoint
                    # save is a collective, so one host's SIGTERM pulls every
                    # host in. The cross-host vote is itself a collective, so
                    # on pods it runs at a fixed cadence every host hits at
                    # the same step (not on the local flag, which would
                    # desynchronize the allgather).
                    vote_now = (trainer.process_count == 1
                                or (step + 1)
                                % data_opts["preempt_vote_interval"] == 0)
                    if vote_now and mesh_lib.global_any(
                            preempted["hit"] or has_notice):
                        proactive = not preempted["hit"]
                        if main:
                            print("proactive drain: checkpointing and "
                                  "exiting before the kill lands"
                                  if proactive else
                                  "SIGTERM received: checkpointing and "
                                  "exiting")
                        consume(deferred.drain(), check=False)
                        grace = data_opts["preemption_grace_s"]
                        deadline = None
                        rec = notice["rec"]
                        if rec is not None and rec.deadline_unix is not None:
                            # The notice named the kill time; anchor the
                            # drain budget there, not at vote time.
                            deadline = (time.monotonic()
                                        + (rec.deadline_unix - time.time()))
                        elif grace and grace > 0:
                            deadline = (preempted["at"] or time.monotonic()
                                        ) + grace
                        save("preempt", wait=True, deadline=deadline)
                        if rec is not None and hb_dir:
                            # Deregister: the supervisor treats a drain
                            # marker as a planned departure (reform without
                            # this host), not a crash.
                            flight_lib.write_drain(
                                hb_dir, trainer.process_index,
                                step=int(state.step), cause=rec.source,
                                deadline_unix=rec.deadline_unix)
                        dump_flight("preempt_notice" if proactive
                                    else "sigterm")
                        if proactive:
                            mesh_lib.shutdown_distributed()
                        return 143
                consume(deferred.drain())
                save("final", wait=True)
                if not (training_config.eval_interval > 0
                        and step + 1 == training_config.max_steps
                        and (step + 1) % training_config.eval_interval == 0):
                    run_eval()  # skip only when the last step just ran eval
                break
            except (FloatingPointError, guards.DivergenceError) as err:
                if rollbacks >= max_rollbacks:
                    if main:
                        print(f"divergence persisted after {rollbacks} "
                              f"rollback(s); giving up", flush=True)
                    raise
                # The feed's cursor at failure points just past the last
                # batch the trainer consumed (with deferred sync, up to the
                # window past the batch that actually diverged); capture it
                # before the restore below rewinds the loader.
                failure_cursor = feed.state_dict()
                rollbacks += 1
                backoff = data_opts["rollback_lr_backoff"] ** rollbacks
                if backoff != 1.0:
                    # The LR schedule is traced into the jitted step as a
                    # pure function of the config, so backing off means
                    # rebuilding the trainer (a recompile — acceptable for
                    # an event this rare).
                    training_config = dataclasses.replace(
                        training_config, learning_rate=base_lr * backoff)
                    trainer = Trainer(model_config, training_config,
                                      parallel_config)
                    jit_warm = {"step": False, "telemetry": False}
                if spike is not None:
                    # The restored loss level predates the whole window;
                    # stale history would re-fire on the first post-rollback
                    # loss and burn the rollback budget.
                    spike.reset()
                # Un-matured metric futures predate the rollback: reading
                # them now would log pre-failure steps after the rollback
                # record; drop them (the window is a few steps of logs).
                deferred = telemetry_lib.DeferredFetcher(
                    window=_DEFERRED_SYNC_WINDOW)
                replay_until = step  # re-covered ground is not fresh goodput
                # An in-flight async commit may be writing (and GC-ing) the
                # very tree restore_latest is about to scan: drain it first.
                drain_save()
                with ledger.track("checkpoint_restore"):
                    restored = ckpt_lib.restore_latest(
                        training_config.checkpoint_dir, trainer, verify=True)
                if restored is None:
                    if main:
                        print("rollback impossible: no valid checkpoint to "
                              "rewind to", flush=True)
                    raise
                state, meta, ckpt_path = restored
                logger.tokens_seen = meta.get("tokens_seen", 0)
                skip = data_opts["skip_batches_on_rollback"]
                if hasattr(train_loader, "load_state_dict"):
                    if skip > 0 and failure_cursor is not None:
                        # Resume the data just past the diverging batch
                        # (failure cursor - 1 + skip) instead of replaying it.
                        cursor = dict(failure_cursor)
                        cursor["batch_index"] += skip - 1
                        train_loader.load_state_dict(cursor)
                    elif meta.get("data_state") is not None:
                        train_loader.load_state_dict(meta["data_state"])
                if hasattr(data_iter, "close"):
                    data_iter.close()
                data_iter = iter(train_loader)
                # Buffered batches belong to the abandoned timeline; a fresh
                # feed re-bases its cursor on the rewound loader.
                feed = make_feed()
                # The rebuilt trainer (LR backoff) has a fresh executable
                # cache; re-arm the watchdog on it either way so the
                # watermark matches the trainer actually stepping.
                watchdog = RecompileWatchdog(trainer)
                logger.log_record({
                    "kind": "rollback",
                    "step": int(step),
                    "rollback": rollbacks,
                    "max_rollbacks": max_rollbacks,
                    "cause": type(err).__name__,
                    "restored_step": int(state.step),
                    "lr_backoff": backoff,
                })
                dump_flight(f"rollback:{type(err).__name__}", exc=err)
                if main:
                    print(f"rollback {rollbacks}/{max_rollbacks}: "
                          f"{type(err).__name__} at step {step}; rewound to "
                          f"{ckpt_path} (step {int(state.step)}), lr x "
                          f"{backoff:g}, skipping {skip} batch(es)",
                          flush=True)
        logger.log_record(ledger.record(step=int(state.step), final=True),
                          stdout_lines=ledger.summary_lines())
    except (FloatingPointError, guards.DivergenceError) as err:
        dump_flight("divergence", exc=err)
        raise  # poisoned state: never crash-save it
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as err:
        dump_flight("crash", exc=err)
        # Best-effort crash checkpoint: only after real progress this run
        # (an immediate failure would just overwrite good state with noise).
        if steps_this_run >= 1:
            try:
                save("crash", wait=True)
            except Exception as save_err:
                if main:
                    print(f"crash checkpoint failed: {save_err}", flush=True)
        raise
    finally:
        if saver is not None and saver.in_flight:
            # Exception exits that skipped the terminal-save drains: let the
            # last scheduled checkpoint land rather than orphaning it
            # (best-effort — an incomplete tree is crash-safe regardless).
            try:
                drain_save()
            except Exception as commit_err:
                if main:
                    print(f"async checkpoint commit failed: {commit_err}",
                          flush=True)
        signal.signal(signal.SIGTERM, old_handler)
        profiler.close()
        logger.close()
        if metrics_server is not None:
            metrics_server.close()
        if installed_plan is not None:
            faults.clear()
    if main:
        print(f"done: {steps_this_run} steps this run, "
              f"{logger.tokens_seen:,} tokens total")
    return 0
