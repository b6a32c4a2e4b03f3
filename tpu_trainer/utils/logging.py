"""Metrics / logging / observability (SURVEY.md §5.5, C30/C31).

The reference promised ``src/utils/logging.py`` in its README structure but
never wrote it (``README.md:51``, SURVEY.md §0.1); its real observability is
rank-0 ``print`` with a cumulative-average tokens/sec (``ddp_trainer.py:600-609``
— SURVEY.md §2.1 b6) plus CUDA memory stats (``fsdp_trainer.py:496-505``).

This module is the real thing, TPU-native:

- **windowed** tokens/sec (rate since the last log line, not since t0 — fixes
  b6) plus tokens/sec/chip;
- **MFU** against the chip's peak bf16 FLOPs (the ≥40% north star, BASELINE.md);
- device memory stats via ``device.memory_stats()`` (↔ ``torch.cuda.memory_*``);
- pluggable sinks: stdout table + JSONL file; emission is host-0 only, like
  the reference's rank-0 gating.
"""

from __future__ import annotations

import json
import os
import time
from typing import IO, Optional

import jax

from tpu_trainer.models.config import GPTConfig
from tpu_trainer.utils import telemetry as telemetry_lib

# Version stamp carried by every JSONL record this process emits. The
# offline analyzer (tpu_trainer.tools.analyze) refuses records whose stamp
# is missing or different, so schema drift fails loudly at analysis time
# instead of silently misparsing old runs. Bump on any breaking change to
# record field semantics.
SCHEMA_VERSION = 1

# Peak dense bf16 FLOP/s per chip, by device_kind substring (public figures;
# v5e: Google Cloud "TPU v5e" page). jax reports a v5e as "TPU v5 lite",
# which normalizes to "tpuv5lite". Matched longest key first.
_PEAK_FLOPS = {
    "v6": 918e12,        # Trillium (v6e)
    "v5p": 459e12,
    "v5e": 197e12,       # aka v5 lite
    "v5lite": 197e12,    # device_kind "TPU v5 lite"
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}
# The chip a cost MODEL (comms roofline, mesh planner, KV-migration pricer)
# is drawn for when the process holds no TPU: an estimate for a named
# target, recorded as such — never a utilization of the device it ran on.
OFF_CHIP_MODEL_KIND = "v5e"


def lookup_by_kind(table: dict, device_kind: str, what: str):
    """``table`` entry whose key occurs in ``device_kind`` (case and spaces
    ignored, longest key first). A kind that is not in the table is an
    error, never a default."""
    kind = (device_kind or "").lower().replace(" ", "")
    for key in sorted(table, key=len, reverse=True):
        if key in kind:
            return table[key]
    raise ValueError(
        f"no {what} on record for device_kind {device_kind!r}; add it to "
        f"the table (known: {', '.join(table)})")


def peak_flops_for_kind(device_kind: str) -> float:
    """Peak bf16 FLOP/s for a ``device_kind`` string.

    Split out of :func:`device_peak_flops` so offline consumers — the mesh
    auto-planner planning for a device kind the process doesn't own
    (``tools/plan --device-kind``) — share the exact lookup the live
    telemetry uses. A kind that is not in the table is an error: a
    utilization against a guessed peak is worse than none.
    """
    return lookup_by_kind(_PEAK_FLOPS, device_kind, "peak FLOP/s")


def cost_model_kind(device_kind: str) -> str:
    """The kind a cost model is drawn for: ``device_kind`` itself, or
    ``OFF_CHIP_MODEL_KIND`` when the process holds no accelerator."""
    kind = (device_kind or "").strip()
    return OFF_CHIP_MODEL_KIND if kind.lower() in ("", "cpu") else kind


def device_peak_flops(device: Optional[jax.Device] = None) -> Optional[float]:
    """Peak bf16 FLOP/s of one chip; ``None`` off-TPU (no peak, no MFU).

    Defaults to ``jax.local_devices()[0]`` — same accessor as
    ``memory_stats`` — so multi-host processes describe a chip they
    actually own (``jax.devices()[0]`` is host 0's first chip everywhere).
    An unrecognized TPU kind raises (``peak_flops_for_kind``).
    """
    device = device or jax.local_devices()[0]
    if device.platform != "tpu":
        return None
    return peak_flops_for_kind(device.device_kind)


def flops_per_token(config: GPTConfig, seq_len: Optional[int] = None) -> float:
    """Training FLOPs per token: 6*N for parameter matmuls (fwd + bwd) plus
    12*S*W for the attention score/value matmuls of each ATTENTION layer
    (PaLM-appendix convention, full S^2 — not halved for causality; W the
    query heads' lanes, ``hidden_size`` unless a head's width is stated; a
    conv or state-space layer has no such term) plus, for each state-space
    layer, the chunked scan's own matrix products (``ops/ssd.py``: 3 x the
    forward's ``2 (Q N G + Q P H + 2 P N H)`` at chunk Q). N is the ACTIVE
    parameter count: for MoE only the top-k routed experts' FFNs do work per
    token, so MFU against total params would overstate utilization by
    ~E/top_k on the FFN share (VERDICT r3 item 8).

    ``seq_len`` is the sequence length the run actually trains at; it
    defaults to ``config.max_seq_len`` but the attention term scales with
    the REAL S — a run at S=512 under a 1024-context model does half the
    attention FLOPs, and charging it for the model max overstates MFU.
    """
    n = config.num_active_parameters()
    s = seq_len if seq_len else config.max_seq_len
    operators = [op for op, _ in config.layer_kinds()]
    attn = 12 * operators.count("attention") * s * config.attention_width
    q, heads = config.mamba_chunk_size, config.mamba_num_heads
    state = config.mamba_head_dim * config.ssm_state_size * heads
    scan = operators.count("mamba") * 6 * (
        q * config.ssm_state_size * config.mamba_n_groups
        + q * config.mamba_head_dim * heads + 2 * state)
    return 6.0 * n + attn + scan


def mfu(
    tokens_per_sec: float,
    config: GPTConfig,
    n_chips: Optional[int] = None,
    peak_flops: Optional[float] = None,
    seq_len: Optional[int] = None,
) -> Optional[float]:
    """Model FLOPs utilization: achieved model FLOP/s over peak hardware
    FLOP/s. ``None`` where there is no peak to divide by (off-TPU)."""
    n_chips = n_chips if n_chips is not None else jax.device_count()
    peak = peak_flops if peak_flops is not None else device_peak_flops()
    if peak is None:
        return None
    return tokens_per_sec * flops_per_token(config, seq_len) / (n_chips * peak)


def memory_stats(device: Optional[jax.Device] = None) -> dict:
    """Per-device HBM stats in bytes (↔ reference ``get_memory_stats``,
    ``fsdp_trainer.py:496-505``). Empty dict where the backend has none (CPU)."""
    device = device or jax.local_devices()[0]
    stats = device.memory_stats() or {}
    return {
        "bytes_in_use": stats.get("bytes_in_use", 0),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
        "bytes_limit": stats.get("bytes_limit", 0),
    }


class MetricLogger:
    """Step-metrics logger with windowed rates and pluggable sinks.

    Usage::

        logger = MetricLogger(model_config, tokens_per_step=..., jsonl_path=...)
        for step ...:
            state, metrics = trainer.train_step(...)
            logger.log(step, metrics)     # emits every log_interval steps

    Only host 0 emits (reference rank-0 gating, ``ddp_trainer.py:600``);
    other hosts keep counters but write nothing.
    """

    def __init__(
        self,
        model_config: Optional[GPTConfig] = None,
        *,
        tokens_per_step: int = 0,
        log_interval: int = 1,
        jsonl_path: Optional[str] = None,
        stdout: bool = True,
        is_main_process: Optional[bool] = None,
        wandb_project: Optional[str] = None,
        tensorboard_dir: Optional[str] = None,
        run_config: Optional[dict] = None,
        seq_len: Optional[int] = None,
        recorder=None,
        observer=None,
    ):
        # Crash flight recorder (utils/flight_recorder.FlightRecorder):
        # every record emitted to the sinks is also observed by the ring
        # buffer, so a crash report carries the tail of the metrics stream.
        self._recorder = recorder
        # Live-metrics observer (utils/telemetry.MetricsBridge): same
        # observe(record) contract as the recorder, mapping records onto
        # the obs registry a /metrics endpoint scrapes. Sink-side only —
        # record contents are identical with or without one.
        self._observer = observer
        self.model_config = model_config
        self.tokens_per_step = tokens_per_step
        # Sequence length the run trains at, for the MFU attention term;
        # None = the model's max_seq_len (flops_per_token docstring).
        self.seq_len = seq_len
        self.log_interval = max(1, log_interval)
        self.is_main = (
            is_main_process if is_main_process is not None else jax.process_index() == 0
        )
        self.stdout = stdout and self.is_main
        self._jsonl: Optional[IO[str]] = None
        if jsonl_path and self.is_main:
            os.makedirs(os.path.dirname(os.path.abspath(jsonl_path)), exist_ok=True)
            self._jsonl = open(jsonl_path, "a", buffering=1)
        # Optional sinks (declared deps / README milestones the reference
        # never wired — requirements.txt:12-13, README.md:215; SURVEY.md
        # §5.5). Import-guarded: a missing package degrades to a one-line
        # warning, never a crash. Host 0 only, like every other sink.
        self._wandb = None
        if wandb_project and self.is_main:
            try:
                import wandb

                self._wandb = wandb.init(
                    project=wandb_project, config=run_config or {}
                )
            except Exception as e:  # missing package, no login, offline...
                import warnings

                warnings.warn(f"wandb sink disabled: {type(e).__name__}: {e}")
        self._tb = None
        if tensorboard_dir and self.is_main:
            try:
                from tensorboardX import SummaryWriter

                self._tb = SummaryWriter(tensorboard_dir)
            except Exception as e:
                import warnings

                warnings.warn(
                    f"tensorboard sink disabled: {type(e).__name__}: {e}"
                )
        self.tokens_seen = 0
        # Non-pad token fraction of the batches fed (sequence packing);
        # set by the run loop from the dataloader's accounting. None =
        # padding untracked → the effective-throughput fields stay absent
        # and old JSONL records are byte-identical.
        self.non_pad_frac: Optional[float] = None
        self._t0 = time.perf_counter()
        self._window_t = self._t0
        self._window_tokens = 0
        self._n_chips = jax.device_count()
        self._peak = device_peak_flops()

    def log(self, step: int, metrics: dict, extra: Optional[dict] = None,
            at: Optional[float] = None) -> Optional[dict]:
        """Record one step; emit (and return) a record every ``log_interval``.

        ``at`` is the ``time.perf_counter()`` reading at which the step was
        handed to the device, for a caller that reads its metrics later
        (``telemetry.DeferredFetcher``): the window's rate is then taken
        between dispatches, not between the calls of ``log``, which come
        ``window`` steps late and, at a drain, several at once.

        A ``metrics["telemetry"]`` subtree (the trainer's telemetry-step
        output) forces emission regardless of the interval — telemetry
        steps are rare and already paid for the stats — and is flattened
        into ``telemetry/*`` scalars across every sink.
        """
        self.tokens_seen += self.tokens_per_step
        self._window_tokens += self.tokens_per_step
        if (step + 1) % self.log_interval != 0 and "telemetry" not in metrics:
            return None

        now = time.perf_counter() if at is None else at
        window_s = max(now - self._window_t, 1e-9)
        tok_per_sec = self._window_tokens / window_s   # windowed, not cumulative (b6)
        record = {
            "kind": "train",
            "schema_version": SCHEMA_VERSION,
            "step": int(step),
            "loss": float(metrics.get("loss", float("nan"))),
            "lr": float(metrics.get("lr", 0.0)),
            "grad_norm": float(metrics.get("grad_norm", 0.0)),
            "tokens_seen": int(self.tokens_seen),
            "tokens_per_sec": round(tok_per_sec, 1),
            "tokens_per_sec_per_chip": round(tok_per_sec / self._n_chips, 1),
            "elapsed_s": round(now - self._t0, 3),
        }
        if self.non_pad_frac is not None:
            record["non_pad_frac"] = round(float(self.non_pad_frac), 4)
            record["effective_tokens_per_sec"] = round(
                tok_per_sec * float(self.non_pad_frac), 1
            )
        if self.model_config is not None and self._peak is not None:
            record["mfu"] = round(
                mfu(tok_per_sec, self.model_config, self._n_chips, self._peak,
                    self.seq_len), 4
            )
        mem = memory_stats()
        if mem["peak_bytes_in_use"]:
            record["peak_mem_gb"] = round(mem["peak_bytes_in_use"] / 2**30, 3)
        if "telemetry" in metrics:
            record.update(telemetry_lib.flatten_scalars(metrics["telemetry"]))
        if extra:
            record.update(extra)

        self._window_t = now
        self._window_tokens = 0
        if self.stdout:
            parts = [f"step {record['step']:>6d}", f"loss {record['loss']:.4f}",
                     f"lr {record['lr']:.2e}",
                     f"{record['tokens_per_sec']:,.0f} tok/s"]
            if "effective_tokens_per_sec" in record:
                parts.append(
                    f"{record['effective_tokens_per_sec']:,.0f} eff tok/s"
                )
            if "mfu" in record:
                parts.append(f"mfu {record['mfu']:.1%}")
            if "peak_mem_gb" in record:
                parts.append(f"mem {record['peak_mem_gb']:.2f}GB")
            print(" | ".join(parts), flush=True)
        if self._jsonl:
            self._jsonl.write(json.dumps(record) + "\n")
        self._emit_scalars(record["step"], {
            k: v for k, v in record.items()
            if isinstance(v, (int, float)) and k != "step"
        }, prefix="train")
        if self._recorder is not None:
            self._recorder.observe(record)
        if self._observer is not None:
            self._observer.observe(record)
        return record

    def _emit_scalars(self, step: int, scalars: dict, prefix: str) -> None:
        if self._wandb is not None:
            self._wandb.log(
                {f"{prefix}/{k}": v for k, v in scalars.items()}, step=step
            )
        if self._tb is not None:
            for k, v in scalars.items():
                self._tb.add_scalar(f"{prefix}/{k}", v, step)

    def log_eval(self, step: int, eval_loss: float, n_batches: int,
                 extra: Optional[dict] = None) -> dict:
        """Held-out eval record: loss + perplexity (exp clamped against
        overflow on early-training losses), written to the same sinks.
        ``extra`` merges into the record, same contract as ``log``."""
        import math

        record = {
            "kind": "eval",
            "schema_version": SCHEMA_VERSION,
            "step": int(step),
            "eval_loss": float(eval_loss),
            "perplexity": round(math.exp(min(float(eval_loss), 30.0)), 4),
            "eval_batches": int(n_batches),
        }
        if extra:
            record.update(extra)
        if self.stdout:
            print(
                f"eval | step {record['step']:>6d} | "
                f"loss {record['eval_loss']:.4f} | "
                f"ppl {record['perplexity']:.2f} ({n_batches} batches)",
                flush=True,
            )
        if self._jsonl:
            self._jsonl.write(json.dumps(record) + "\n")
        self._emit_scalars(record["step"], {
            "loss": record["eval_loss"], "perplexity": record["perplexity"],
        }, prefix="eval")
        if self._recorder is not None:
            self._recorder.observe(record)
        if self._observer is not None:
            self._observer.observe(record)
        return record

    def log_record(self, record: dict, stdout_lines=None) -> dict:
        """Write an arbitrary pre-built record (``kind`` already set) to the
        sinks: goodput ledger records, cost-analysis summaries, nan-scan
        reports. ``stdout_lines``: optional human-readable lines for the
        console (the raw dict goes to JSONL/wandb/TB either way)."""
        record.setdefault("schema_version", SCHEMA_VERSION)
        if self.stdout and stdout_lines:
            for line in stdout_lines:
                print(line, flush=True)
        if self._jsonl:
            self._jsonl.write(json.dumps(record) + "\n")
        step = record.get("step")
        if step is not None:
            self._emit_scalars(int(step), {
                k: v for k, v in record.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)
                and k != "step"
            }, prefix=str(record.get("kind", "misc")))
        if self._recorder is not None:
            self._recorder.observe(record)
        if self._observer is not None:
            self._observer.observe(record)
        return record

    def close(self) -> None:
        if self._jsonl:
            self._jsonl.close()
            self._jsonl = None
        if self._tb is not None:
            self._tb.close()
            self._tb = None
        if self._wandb is not None:
            self._wandb.finish()
            self._wandb = None
