"""Benchmark harness: training throughput, single-config and method x chips.

Default invocation (the driver contract) measures tokens/sec of the jitted
train step on GPT-2 124M, batch_size=8, seq_len=1024 — the exact setup of
the reference's example benchmark table (/root/reference/README.md:188-198,
"12,500 tok/s" single-device row; see BASELINE.md) — and prints ONE JSON
line:

    {"metric": "train_tokens_per_sec", "value": N, "unit": "tok/s",
     "vs_baseline": N / 12500.0}

`--table` produces the reference README's method x chips table shape
(DDP/FSDP x 1..N chips -> tok/s, tok/s/chip, peak memory, scaling
efficiency), one JSON line per cell on stderr plus a markdown table;
`--update-results` rewrites the scaling section of benchmarks/results.md in
place. On this box the table runs at whatever jax.devices() offers: the one
real TPU chip (1-chip rows), or a virtual 8-device CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu) as a
correctness-mode dry run of the harness itself — the same command fills in
real numbers the moment a pod exists.

Env overrides (back-compat): BENCH_MODEL_SIZE, BENCH_BATCH_SIZE,
BENCH_SEQ_LEN, BENCH_STEPS, BENCH_ACCUM, BENCH_FLASH=0/1, BENCH_REMAT=0/1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_RESULTS_MD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "results.md")
_TABLE_START = "<!-- scaling-table:start -->"
_TABLE_END = "<!-- scaling-table:end -->"
_REF_BASELINE = 12500.0  # reference README.md:195 single-device example


def _build_parser():
    env = os.environ.get
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model-size", default=env("BENCH_MODEL_SIZE", "small"))
    p.add_argument("--batch-size", type=int,
                   default=int(env("BENCH_BATCH_SIZE", "8")),
                   help="rows per data shard per micro-step")
    p.add_argument("--seq-len", type=int, default=int(env("BENCH_SEQ_LEN", "1024")))
    # 60-step windows: every measured window pays one fixed tail (final-step
    # latency + the loss readback that syncs it); longer windows amortize
    # it. The quantity measured is unchanged (wall clock over enqueued
    # steps, reference methodology).
    p.add_argument("--steps", type=int, default=int(env("BENCH_STEPS", "60")))
    p.add_argument("--accum", type=int, default=int(env("BENCH_ACCUM", "1")))
    p.add_argument("--flash", type=int, default=int(env("BENCH_FLASH", "1")))
    p.add_argument("--flash-bwd", default=env("BENCH_FLASH_BWD", "auto"),
                   choices=("auto", "fused", "split"),
                   help="flash backward kernel dispatch override "
                        "(auto: fused <= 2048, split beyond)")
    p.add_argument("--remat", type=int, default=None,
                   help="default: on for medium/large/xl")
    p.add_argument("--mesh", default=None, choices=("auto",),
                   help="'auto' runs the mesh auto-planner "
                        "(tpu_trainer.parallel.planner) over every feasible "
                        "six-axis split, benches the winner, and logs the "
                        "kind:\"mesh_plan\" record with measured-vs-"
                        "predicted step time; mutually exclusive with "
                        "explicit --mesh-* flags")
    p.add_argument("--hbm-gb", "--hbm_gb", dest="hbm_gb", type=float,
                   default=None,
                   help="per-device HBM budget in GiB for --mesh auto "
                        "pruning (default: the device's reported limit; "
                        "no pruning on CPU)")
    p.add_argument("--mesh-data", type=int, default=None)
    p.add_argument("--mesh-fsdp", type=int, default=None)
    p.add_argument("--mesh-tensor", type=int, default=1)
    p.add_argument("--mesh-sequence", type=int, default=1)
    p.add_argument("--mesh-expert", type=int, default=1)
    p.add_argument("--mesh-stage", type=int, default=1)
    p.add_argument("--strategy", default=None,
                   help="replicated | zero2 | zero3 (reference spellings ok)")
    p.add_argument("--offload", action="store_true",
                   help="host-offload optimizer state (pinned_host stream)")
    p.add_argument("--offload-dtype", default="float32",
                   help="offloaded-state storage: float32 | bfloat16 | int8")
    p.add_argument("--offload-budget-gb", type=float, default=0.0,
                   help="partial offload: GB of the largest moment leaves "
                        "kept device-resident (exact f32)")
    p.add_argument("--opt-state-dtype", default="float32",
                   help="on-device Adam moment storage: float32 | bfloat16 "
                        "| int8 (TrainingConfig.optimizer_state_dtype)")
    p.add_argument("--num-experts", type=int, default=0,
                   help="MoE: routed experts per FFN (0 = dense); MFU is "
                        "reported against ACTIVE params")
    p.add_argument("--moe-top-k", type=int, default=1)
    p.add_argument("--carry-cast", type=int,
                   default=int(env("BENCH_CARRY_CAST", "1")),
                   help="TrainingConfig.carry_cast_params (0 to free the "
                        "compute-dtype param copy on HBM-edge configs)")
    p.add_argument("--model-flag", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="override a GPTConfig field (repeatable), e.g. "
                        "--model-flag fused_loss_pallas=0 for configs at "
                        "the HBM edge (the saved-logits buffer is the "
                        "marginal ~0.8 GB there)")
    p.add_argument("--checkpoint-every", "--checkpoint_every", type=int,
                   default=int(env("BENCH_CHECKPOINT_EVERY", "0")),
                   help="save a checkpoint (async, utils/checkpoint.py "
                        "AsyncSaver) every N measured steps into a temp dir "
                        "— measures tok/s with checkpointing on and the "
                        "checkpoint_save/commit_wait goodput split (0 = off)")
    p.add_argument("--stream", action="store_true",
                   help="synthesize batches on the fly on the host (through "
                        "the host Prefetcher + DevicePrefetcher stack) "
                        "instead of a pre-generated corpus — makes data_wait "
                        "real so the prefetch overlap is measurable")
    p.add_argument("--prefetch-depth", "--prefetch_depth", type=int,
                   default=int(env("BENCH_PREFETCH_DEPTH", "2")),
                   help="--stream: host-side prefetch depth (0 = synchronous)")
    p.add_argument("--device-prefetch-depth", "--device_prefetch_depth",
                   type=int,
                   default=int(env("BENCH_DEVICE_PREFETCH_DEPTH", "2")),
                   help="--stream: batches placed on device ahead of the "
                        "step (0 = place inside the step)")
    p.add_argument("--jsonl", default=env("BENCH_JSONL"),
                   help="write the run's records (train windows, goodput, "
                        "comms_model) as schema-stamped JSONL here and run "
                        "tpu_trainer.tools.analyze over it (report on "
                        "stderr); default: a temp file")
    p.add_argument("--packed", action="store_true",
                   help="packed-vs-padded A/B: first-fit sequence packing "
                        "vs pad-to-seq over the same synthetic ragged "
                        "corpus, through the identical segment-aware train "
                        "step; reports effective (non-pad) tok/s per lane")
    p.add_argument("--mean-doc-len", "--mean_doc_len", type=int,
                   dest="mean_doc_len", default=None,
                   help="--packed: mean synthetic document length "
                        "(default seq_len // 4)")
    p.add_argument("--moe", action="store_true",
                   help="MoE routing A/B: dense FFN vs capacity-einsum vs "
                        "dropless grouped-matmul experts at matched active "
                        "params over a skewed token stream; reports tok/s "
                        "plus router drop_frac/max_group_frac per lane "
                        "(uses --num-experts [default 8] and --moe-top-k "
                        "[default 2])")
    p.add_argument("--table", action="store_true",
                   help="run the method x chips scaling table")
    p.add_argument("--update-results", action="store_true",
                   help="rewrite the scaling table in benchmarks/results.md")
    p.add_argument("--update-md", action="store_true",
                   help="splice the current lane's table into "
                        "benchmarks/results.md (alias of --update-results "
                        "for the --moe lane)")
    p.add_argument("--validate", action="store_true",
                   help="run the on-hardware validation lane "
                        "(tpu_trainer.validate) instead of benchmarking")
    return p


def _parse_model_flags(pairs):
    """``KEY=VALUE`` strings -> GPTConfig override dict (int/float/bool/str
    coerced by the field's current type)."""
    import dataclasses as _dc

    from tpu_trainer.models.config import GPTConfig

    fields = {f.name: f for f in _dc.fields(GPTConfig)}
    out = {}
    for pair in pairs or []:
        key, _, val = pair.partition("=")
        if key not in fields:
            raise SystemExit(f"--model-flag: unknown GPTConfig field {key!r}")
        cur = getattr(GPTConfig(), key, None)
        if isinstance(cur, bool):
            low = val.strip().lower()
            if low in ("1", "true", "yes"):
                out[key] = True
            elif low in ("0", "false", "no"):
                out[key] = False
            else:
                raise SystemExit(
                    f"--model-flag {key}: boolean value {val!r} not "
                    f"recognized (use 1/0/true/false/yes/no)"
                )
        elif isinstance(cur, int):
            out[key] = int(val)
        elif isinstance(cur, float):
            out[key] = float(val)
        else:
            out[key] = val
    return out


def _bench_model_config(model_size, *, seq_len, use_flash, remat,
                        num_experts=0, moe_top_k=1, model_flags=None):
    """The bench's GPTConfig for a preset/size — shared by the measured run
    and the mesh auto-planner so both price the same geometry."""
    from tpu_trainer.models.config import GPTConfig

    # Full reference-default dropout: the flash kernel implements
    # attention-weight dropout in-kernel (counter-based mask), so the
    # flash memory profile holds with dropout active.
    common = dict(
        max_seq_len=seq_len,
        use_flash_attention=use_flash,
        gradient_checkpointing=remat,
        dropout=0.1,
        attention_dropout=0.1,
    )
    if num_experts:
        # MoE variant of the geometry: every FFN becomes `num_experts`
        # routed experts (models/moe.py); z-loss at the recommended 1e-3.
        common.update(num_experts=num_experts, moe_top_k=moe_top_k,
                      router_z_weight=1e-3)
    if model_size == "tiny":
        # Correctness-mode size for CPU dry runs of the harness itself.
        model_config = GPTConfig(vocab_size=256, hidden_size=64,
                                 num_layers=2, num_heads=4, **common)
    else:
        model_config = GPTConfig.preset(model_size, **common)
    if model_flags:
        # Applied AFTER the preset so flags may override preset-fixed
        # fields too (e.g. num_heads=6 for the d=128 geometry experiment);
        # the frozen-dataclass replace re-runs __post_init__ validation.
        import dataclasses as _dc

        model_config = _dc.replace(model_config, **model_flags)
    return model_config


_OPT_STATE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def _auto_plan(args, n_devices, default_strategy="replicated"):
    """--mesh auto: rank every feasible six-axis split for the bench's
    model/batch geometry and return the winning ``mesh_plan`` record."""
    import jax

    from tpu_trainer.parallel import planner as planner_lib

    model_config = _bench_model_config(
        args.model_size, seq_len=args.seq_len, use_flash=bool(args.flash),
        remat=_remat(args), num_experts=args.num_experts,
        moe_top_k=args.moe_top_k,
        model_flags=_parse_model_flags(args.model_flag))
    # The CPU SPMD partitioner cannot lower the GPipe stage shard_map
    # (PartitionId rejection), so correctness-mode planning must not hand
    # back a mesh the trainer then crashes on. Real TPUs plan all six axes.
    exclude = () if jax.devices()[0].platform == "tpu" else ("stage",)
    try:
        record = planner_lib.plan(
            model_config, n_devices,
            global_rows=args.batch_size * n_devices,
            max_seq_len=args.seq_len, grad_accum=args.accum,
            strategy=args.strategy or default_strategy,
            hbm_gb=args.hbm_gb,
            opt_state_bytes=_OPT_STATE_BYTES.get(args.opt_state_dtype, 4),
            carry_cast=bool(args.carry_cast), exclude_axes=exclude)
    except planner_lib.NoFeasiblePlanError as e:
        raise SystemExit(f"--mesh auto: {e}")
    record["auto"] = True
    return record


def run_bench(*, model_size, batch_size, seq_len, steps, accum, use_flash,
              remat, mesh_cfg, strategy, devices=None, offload=False,
              offload_dtype="float32", num_experts=0, moe_top_k=1,
              model_flags=None, carry_cast=True,
              opt_state_dtype="float32", offload_budget_gb=0.0,
              checkpoint_every=0, stream=False, prefetch_depth=2,
              device_prefetch_depth=2, plan_record=None, hbm_gb=None):
    """One measured config -> result dict. ``batch_size`` is per data shard
    (global batch scales with the mesh, the reference's DDP semantics)."""
    import jax
    import numpy as np

    from tpu_trainer.data.device_prefetch import DevicePrefetcher
    from tpu_trainer.data.dummy import create_dummy_dataloader
    from tpu_trainer.data.prefetch import Prefetcher
    from tpu_trainer.parallel.mesh import make_mesh
    from tpu_trainer.training.config import TrainingConfig
    from tpu_trainer.training.trainer import ParallelConfig, Trainer
    from tpu_trainer.utils import telemetry as telemetry_lib
    from tpu_trainer.utils.logging import flops_per_token, memory_stats, mfu

    mesh = make_mesh(mesh_cfg, devices=devices)
    device = next(iter(mesh.devices.flat))
    on_tpu = device.platform == "tpu"
    model_config = _bench_model_config(
        model_size, seq_len=seq_len, use_flash=use_flash, remat=remat,
        num_experts=num_experts, moe_top_k=moe_top_k,
        model_flags=model_flags)
    training_config = TrainingConfig(
        batch_size=batch_size,
        max_seq_len=seq_len,
        gradient_accumulation_steps=accum,
        mixed_precision="bf16",
        log_interval=10**9,
        carry_cast_params=carry_cast,
        optimizer_state_dtype=opt_state_dtype,
    )
    trainer = Trainer(model_config, training_config,
                      ParallelConfig(mesh_cfg, strategy or "replicated",
                                     cpu_offload=offload,
                                     offload_dtype=offload_dtype,
                                     offload_budget_gb=offload_budget_gb),
                      mesh=mesh)

    rows = batch_size * accum * trainer.dp_size // trainer.process_count
    if stream:
        # Streaming input mode: batches are synthesized per-pull on the host
        # and flow through the full overlap stack (host Prefetcher thread →
        # DevicePrefetcher placement), so data_wait measures whatever the
        # overlap fails to hide instead of a pre-generated corpus's ~0.
        def synth():
            rng = np.random.default_rng(0)
            while True:
                yield rng.integers(
                    0, model_config.vocab_size, size=(rows, seq_len),
                    dtype=np.int32)

        host_iter = iter(Prefetcher(synth, depth=prefetch_depth))
        feed = DevicePrefetcher(
            lambda: next(host_iter), place=trainer.place_batch,
            depth=device_prefetch_depth)
        next_batch = feed.next
    else:
        loader = create_dummy_dataloader(
            batch_size=rows,
            seq_len=seq_len,
            vocab_size=model_config.vocab_size,
            num_batches=5 * steps + 3,
        )
        it = iter(loader)
        next_batch = lambda: next(it)  # noqa: E731

    # Async checkpointing lane: save into a throwaway dir every
    # checkpoint_every measured steps; the windows then price the snapshot
    # (checkpoint_save) while the commit overlaps the following steps
    # (residual drains show up as checkpoint_commit_wait).
    saver = ckpt_dir = None
    if checkpoint_every:
        import tempfile

        from tpu_trainer.utils import checkpoint as ckpt_lib

        ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
        saver = ckpt_lib.AsyncSaver()

    ledger = telemetry_lib.GoodputLedger()
    state = trainer.init_state()
    # Warmup: compile + 2 steps (first step may still include autotuning).
    # Sync by fetching the loss: a host read of the last chained result
    # waits for every step before it.
    with ledger.track("compile"):
        for _ in range(2):
            state, metrics = trainer.train_step(state, next_batch())
        float(metrics["loss"])

    # Five measured windows, keep the fastest (timeit's-min rationale; the
    # benchmark PR judges whether the rule stays). Each window syncs once
    # at its end, on the loss readback.
    window_elapsed = []
    final_loss = None
    measured = 0
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(steps):
            with ledger.track("data_wait"):
                batch = next_batch()
            with ledger.track("step"):
                state, metrics = trainer.train_step(state, batch)
            measured += 1
            if saver is not None and measured % checkpoint_every == 0:
                if saver.in_flight:
                    with ledger.track("checkpoint_commit_wait"):
                        saver.wait()
                with ledger.track("checkpoint_save"):
                    saver.save(ckpt_dir, state,
                               model_config=model_config,
                               training_config=training_config,
                               keep_last_n=2)
        with ledger.track("step"):  # the device wait lands here
            final_loss = float(metrics["loss"])  # end-of-window sync
        window_elapsed.append(time.perf_counter() - t0)
    elapsed = min(window_elapsed)
    if saver is not None:
        import shutil

        with ledger.track("checkpoint_commit_wait"):
            saver.wait()
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    n_chips = mesh.size
    tokens = steps * trainer.tokens_per_step
    tok_per_sec = tokens / elapsed
    mem = memory_stats(device)
    peak_mem_gb = (round(mem["peak_bytes_in_use"] / 2**30, 2)
                   if mem.get("peak_bytes_in_use") else None)
    mem_source = "runtime"
    if peak_mem_gb is None:
        if on_tpu:
            raise RuntimeError(
                "device.memory_stats() reported no peak_bytes_in_use on a "
                "TPU: the runtime is the source of peak memory there")
        # The CPU backend keeps no memory stats: report what the compiler
        # planned for the step, labelled as such. Reuse the last measured
        # batch — same shapes as the running step, and no coupling to the
        # loader's num_batches headroom.
        ma = trainer.step_memory_analysis(state, batch)
        if ma is not None:
            peak_mem_gb = round(ma["peak_bytes"] / 2**30, 2)
            mem_source = "compiled"
    # Predicted-vs-achieved FLOPs: the XLA cost model's count for the
    # compiled step (executable-cache hit — no recompile) next to the
    # analytic 6N+attention count at the ACTUAL seq_len, and the model
    # FLOP/s the measured windows achieved.
    ca = trainer.step_cost_analysis(state, batch)
    # Static collective-traffic model + HLO cross-check of the measured
    # config (ISSUE 3) — failure-guarded so an exotic mesh never kills the
    # measurement it annotates.
    try:
        from tpu_trainer.parallel import comms_model as comms_lib

        comms = comms_lib.build(trainer)
        comms.update(comms_lib.crosscheck(
            comms, trainer.compiled_step_text(state, batch)))
    except Exception as e:  # pragma: no cover - defensive
        comms = None
        print(f"bench: comms_model failed: {e}", file=sys.stderr)
    analytic_flops_step = flops_per_token(model_config, seq_len) \
        * trainer.tokens_per_step
    goodput = ledger.record(final=True)
    # Mesh auto-planner cross-check (ISSUE 11): score THIS mesh with the
    # planner's analytic model — or reuse the full --mesh auto search
    # record — and price the prediction against the measured step time.
    # Failure-guarded like the comms model above.
    measured_step_ms = elapsed / steps * 1e3
    try:
        from tpu_trainer.parallel import planner as planner_lib

        calibrated_peak = None
        if not on_tpu:
            # CPU correctness mode: no roofline table entry exists for the
            # host platform, so calibrate the compute roofline from this
            # run's achieved model FLOP/s — plan_error_frac then prices
            # the comms + pipeline-bubble residual instead of a made-up
            # compute constant. On TPU the device tables stand and the
            # prediction error is honest end to end.
            calibrated_peak = (tok_per_sec
                               * flops_per_token(model_config, seq_len)
                               / n_chips)
        scored = planner_lib.plan_single(
            trainer.model_config, dict(mesh.shape), trainer.strategy,
            global_rows=batch_size * trainer.dp_size,
            max_seq_len=seq_len, grad_accum=accum,
            device_kind=getattr(next(iter(mesh.devices.flat)),
                                "device_kind", ""),
            peak_flops=calibrated_peak, hbm_gb=hbm_gb,
            opt_state_bytes=_OPT_STATE_BYTES.get(opt_state_dtype, 4),
            carry_cast=carry_cast)
        if plan_record is None:
            plan_record = scored
            plan_record["auto"] = False
        else:
            # --mesh auto handed us the full search record: keep its
            # ranked list (the ranking is relative, so a wrong absolute
            # roofline cancels) but gate on the re-scored prediction for
            # the mesh that actually ran.
            plan_record = dict(plan_record)
            plan_record["predicted_step_ms"] = scored["predicted_step_ms"]
        if calibrated_peak is not None:
            plan_record["calibrated_peak_flops"] = round(calibrated_peak, 1)
        plan_record["measured_step_ms"] = round(measured_step_ms, 3)
        plan_record["plan_error_frac"] = round(
            abs(plan_record["predicted_step_ms"] - measured_step_ms)
            / measured_step_ms, 4)
    except Exception as e:  # pragma: no cover - defensive
        plan_record = None
        print(f"bench: mesh_plan failed: {e}", file=sys.stderr)
    return {
        "model_size": model_size,
        "params": model_config.num_parameters(),
        # MoE: MFU below is computed against ACTIVE params (top-k experts
        # per token); == params for dense models.
        "active_params": model_config.num_active_parameters(),
        "batch_size": batch_size,
        "global_batch": trainer.global_batch_size,
        "seq_len": seq_len,
        "accum": accum,
        "steps": steps,
        "platform": next(iter(mesh.devices.flat)).platform,
        "n_chips": n_chips,
        "mesh": dict(mesh.shape),
        "strategy": strategy or "replicated",
        "offload": bool(trainer.cpu_offload),
        "opt_state_dtype": opt_state_dtype,
        "offload_dtype": offload_dtype if trainer.cpu_offload else None,
        "checkpoint_every": checkpoint_every,
        "stream": bool(stream),
        "prefetch_depth": prefetch_depth if stream else None,
        "device_prefetch_depth": device_prefetch_depth if stream else None,
        "platform": device.platform,
        "device_kind": device.device_kind,
        "elapsed_s": round(elapsed, 3),
        "window_elapsed_s": [round(w, 3) for w in window_elapsed],
        "tokens_per_window": tokens,
        "tok_per_sec": round(tok_per_sec, 1),
        "tok_per_sec_per_chip": round(tok_per_sec / n_chips, 1),
        # MFU against the attention term at the RUN's seq_len, not the
        # model's max_seq_len (they already match here because the bench
        # sets max_seq_len=seq_len, but keep the call honest).
        "mfu": (round(mfu(tok_per_sec, model_config, seq_len=seq_len), 4)
                if on_tpu else None),
        "peak_mem_gb": peak_mem_gb,
        "peak_mem_source": mem_source if peak_mem_gb is not None else None,
        "final_loss": final_loss,
        "analytic_flops_per_step": analytic_flops_step,
        "xla_flops_per_step": (ca or {}).get("flops_per_step"),
        "achieved_model_flops_per_sec": round(
            tok_per_sec * flops_per_token(model_config, seq_len), 1),
        "goodput": {k: round(v, 4) if isinstance(v, float) else v
                    for k, v in goodput.items() if k != "kind"},
        "comms_model": comms,
        "measured_step_ms": round(measured_step_ms, 3),
        "predicted_step_ms": (plan_record or {}).get("predicted_step_ms"),
        "plan_error_frac": (plan_record or {}).get("plan_error_frac"),
        "mesh_plan": plan_record,
    }


def write_run_jsonl(path: str, detail: dict) -> None:
    """Persist the bench run as the same schema-stamped JSONL a training
    run emits: one synthetic ``train`` record per measured window (so the
    analyzer's percentile/stability machinery applies), the goodput
    ledger, and the comms_model record."""
    from tpu_trainer.utils.logging import SCHEMA_VERSION

    records = []
    cum = 0.0
    steps = detail["steps"]
    tokens = detail["tokens_per_window"]
    predicted_ms = detail.get("predicted_step_ms")
    for w, el in enumerate(detail.get("window_elapsed_s") or []):
        cum += el
        rec = {
            "kind": "train",
            "schema_version": SCHEMA_VERSION,
            "step": (w + 1) * steps,
            "loss": detail["final_loss"],
            "tokens_per_sec": round(tokens / el, 1),
            "elapsed_s": round(cum, 3),
            "mfu": detail["mfu"],
            "peak_mem_gb": detail["peak_mem_gb"],
        }
        if predicted_ms is not None:
            # Planner prediction vs THIS window's measured step time, so the
            # analyzer's percentile machinery applies to the plan error too.
            window_ms = el / steps * 1e3
            rec["predicted_step_ms"] = predicted_ms
            rec["plan_error_frac"] = round(
                abs(predicted_ms - window_ms) / window_ms, 4)
        records.append(rec)
    goodput = dict(detail["goodput"])
    goodput.update(kind="goodput", final=True, schema_version=SCHEMA_VERSION)
    records.append(goodput)
    if detail.get("comms_model"):
        comms = dict(detail["comms_model"])
        comms.setdefault("schema_version", SCHEMA_VERSION)
        records.append(comms)
    if detail.get("mesh_plan"):
        records.append(dict(detail["mesh_plan"]))
    records.append({
        "kind": "cost_analysis",
        "schema_version": SCHEMA_VERSION,
        "xla_flops_per_step": detail["xla_flops_per_step"],
        "analytic_flops_per_step": detail["analytic_flops_per_step"],
    })
    with open(path, "w") as fh:
        for rec in records:
            fh.write(json.dumps(rec, default=str) + "\n")


def analyze_run_jsonl(path: str) -> None:
    """Self-analysis: run the offline analyzer over the JSONL this bench
    just wrote, report to stderr (stdout stays the driver's JSON line)."""
    from tpu_trainer.tools import analyze as analyze_lib

    report = analyze_lib.summarize(analyze_lib.load_records(path))
    for line in analyze_lib.render(report):
        print(f"bench: {line}", file=sys.stderr)


def run_packed(args, mesh_cfg):
    """Packed-vs-padded effective-throughput A/B (``--packed``).

    All lanes bin the SAME deterministic synthetic ragged corpus
    (``data/packing.synthetic_documents``) into ``[rows, seq, 2]`` batches —
    first-fit packing, best-fit-decreasing packing (``packed_bfd``), and
    one-padded-document-per-row — and run the identical segment-aware train
    step (one compile, shared shapes), so raw tok/s is ~equal and the
    effective (non-pad) tok/s ratio isolates padding waste:
    ~seq/mean_doc_len upper bound, the packing headroom.
    """
    import jax  # noqa: F401  (platform init side effect)

    from tpu_trainer.data.packing import (PackedDataLoader,
                                          synthetic_documents)
    from tpu_trainer.models.config import GPTConfig
    from tpu_trainer.parallel.mesh import make_mesh
    from tpu_trainer.training.config import TrainingConfig
    from tpu_trainer.training.trainer import ParallelConfig, Trainer

    seq_len = args.seq_len
    mesh = make_mesh(mesh_cfg)
    common = dict(
        max_seq_len=seq_len,
        use_flash_attention=bool(args.flash),
        gradient_checkpointing=_remat(args),
        dropout=0.1,
        attention_dropout=0.1,
    )
    if args.model_size == "tiny":
        model_config = GPTConfig(vocab_size=256, hidden_size=64,
                                 num_layers=2, num_heads=4, **common)
    else:
        model_config = GPTConfig.preset(args.model_size, **common)
    training_config = TrainingConfig(
        batch_size=args.batch_size,
        max_seq_len=seq_len,
        gradient_accumulation_steps=args.accum,
        mixed_precision="bf16",
        log_interval=10**9,
    )
    trainer = Trainer(model_config, training_config,
                      ParallelConfig(mesh_cfg, args.strategy or "replicated"),
                      mesh=mesh)
    rows = args.batch_size * args.accum * trainer.dp_size \
        // trainer.process_count
    mean_len = args.mean_doc_len or max(8, seq_len // 4)
    lanes = {}
    for lane, pack, strat in (("packed", True, "first_fit"),
                              ("packed_bfd", True, "best_fit"),
                              ("padded", False, "first_fit")):
        # Corpus sized so one pass covers warmup + all windows with slack;
        # the cycling iterator below makes exhaustion a non-event anyway.
        per_row = max(1, seq_len // mean_len) if pack else 1
        total = (3 * args.steps + 4) * rows * (per_row + 2)
        loader = PackedDataLoader(
            lambda n=total: synthetic_documents(
                n, mean_len, model_config.vocab_size, seed=17),
            rows, seq_len, pack=pack, strategy=strat, seed=17,
        )

        def cycle(ld=loader):
            while True:
                yield from ld

        it = cycle()
        state = trainer.init_state()
        for _ in range(2):  # warmup: compile (first lane) + stabilize
            state, metrics = trainer.train_step(state, next(it))
        float(metrics["loss"])
        window_elapsed = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.steps):
                state, metrics = trainer.train_step(state, next(it))
            float(metrics["loss"])  # end-of-window device sync
            window_elapsed.append(time.perf_counter() - t0)
        elapsed = min(window_elapsed)
        tok_per_sec = args.steps * trainer.tokens_per_step / elapsed
        frac = loader.non_pad_frac
        lanes[lane] = {
            "tok_per_sec": round(tok_per_sec, 1),
            "non_pad_frac": round(frac, 4),
            "effective_tok_per_sec": round(tok_per_sec * frac, 1),
            "window_elapsed_s": [round(w, 3) for w in window_elapsed],
        }
    speedup = (lanes["packed"]["effective_tok_per_sec"]
               / max(lanes["padded"]["effective_tok_per_sec"], 1e-9))
    return {
        "metric": "packed_effective_tok_per_sec",
        "value": lanes["packed"]["effective_tok_per_sec"],
        "unit": "tok/s",
        "packed": lanes["packed"],
        "packed_bfd": lanes["packed_bfd"],
        "padded": lanes["padded"],
        "effective_speedup": round(speedup, 2),
        "model_size": args.model_size,
        "batch_size": args.batch_size,
        "seq_len": seq_len,
        "mean_doc_len": mean_len,
        "steps": args.steps,
        "platform": next(iter(mesh.devices.flat)).platform,
        "n_chips": mesh.size,
    }


_PACKING_START = "<!-- packing-table:start -->"
_PACKING_END = "<!-- packing-table:end -->"


def update_packing_md(result) -> None:
    """Splice the --packed A/B into benchmarks/results.md (own marker block,
    same mechanism as the scaling table)."""
    header = (
        f"Measured by `python bench.py --packed` — {result['model_size']}, "
        f"batch {result['batch_size']}/shard, seq {result['seq_len']}, "
        f"mean doc len {result['mean_doc_len']}, platform "
        f"{result['platform']} ({time.strftime('%Y-%m-%d')}).\n\n"
    )
    lines = [
        "| Lane | tok/s | non-pad frac | effective tok/s |",
        "|---|---|---|---|",
    ]
    for lane in ("packed", "packed_bfd", "padded"):
        r = result.get(lane)
        if r is None:
            continue  # JSONL from before the best-fit lane existed
        lines.append(
            f"| {lane} | {r['tok_per_sec']:,.0f} | {r['non_pad_frac']:.3f} "
            f"| {r['effective_tok_per_sec']:,.0f} |"
        )
    table = "\n".join(lines) + (
        f"\n\nEffective-throughput speedup (packed / padded): "
        f"**{result['effective_speedup']:.2f}x**"
    )
    block = f"{_PACKING_START}\n{header}{table}\n{_PACKING_END}"
    with open(_RESULTS_MD) as f:
        text = f.read()
    if _PACKING_START in text:
        pre = text.split(_PACKING_START)[0]
        post = text.split(_PACKING_END)[1]
        text = pre + block + post
    else:
        text += "\n## Sequence packing\n\n" + block + "\n"
    with open(_RESULTS_MD, "w") as f:
        f.write(text)
    print(f"wrote packing table to {_RESULTS_MD}", file=sys.stderr)


def run_moe(args, mesh_cfg):
    """Dense-FFN vs capacity-einsum vs dropless MoE A/B (``--moe``).

    Three lanes at matched ACTIVE params per token over the same
    deterministic SKEWED token stream: tokens are drawn from a handful of
    vocab ids, so the hidden states — and with them the router logits —
    are near-identical across the batch and the top-k choices pile onto a
    few experts.  That is the worst case for capacity routing (every
    token beyond ``C = ceil(k*T/E * capacity_factor)`` per hot expert is
    dropped, while the cold experts' slots burn dense matmul time empty)
    and exactly the case the grouped matmul exists for: the dropless lane
    computes the same k*T routed rows with no slot padding and no drops.

    - ``dense``: no routing; FFN widened to ``top_k * intermediate`` so
      the per-token matmul FLOPs match the MoE lanes' active params.
    - ``capacity``: ``moe_impl="capacity"``, ``moe_dispatch="einsum"``
      (the dense one-hot dispatch/combine path).
    - ``dropless``: ``moe_impl="dropless"`` — argsort/bincount into
      grouped matmuls (ops/grouped_matmul.py).

    Each MoE lane also runs one (untimed) telemetry step and reports the
    router's ``drop_frac`` / ``max_group_frac`` so the table shows WHY
    the throughput differs, not just that it does.
    """
    import dataclasses as _dc

    import jax  # noqa: F401  (platform init side effect)
    import numpy as np

    from tpu_trainer.parallel.mesh import make_mesh
    from tpu_trainer.training.config import TrainingConfig
    from tpu_trainer.training.trainer import ParallelConfig, Trainer
    from tpu_trainer.utils import telemetry as telemetry_lib

    seq_len = args.seq_len
    mesh = make_mesh(mesh_cfg)
    num_experts = args.num_experts or 8
    top_k = args.moe_top_k if args.moe_top_k > 1 else 2
    model_flags = _parse_model_flags(args.model_flag)

    moe_cfg = _bench_model_config(
        args.model_size, seq_len=seq_len, use_flash=bool(args.flash),
        remat=_remat(args), num_experts=num_experts, moe_top_k=top_k,
        model_flags=model_flags)
    dense_cfg = _bench_model_config(
        args.model_size, seq_len=seq_len, use_flash=bool(args.flash),
        remat=_remat(args), model_flags=model_flags)
    dense_cfg = _dc.replace(
        dense_cfg, intermediate_size=top_k * moe_cfg.intermediate_size)
    lane_cfgs = {
        "dense": dense_cfg,
        "capacity": _dc.replace(moe_cfg, moe_impl="capacity",
                                moe_dispatch="einsum"),
        "dropless": _dc.replace(moe_cfg, moe_impl="dropless"),
    }

    training_config = TrainingConfig(
        batch_size=args.batch_size,
        max_seq_len=seq_len,
        gradient_accumulation_steps=args.accum,
        mixed_precision="bf16",
        log_interval=10**9,
    )

    lanes = {}
    for lane, model_config in lane_cfgs.items():
        trainer = Trainer(model_config, training_config,
                          ParallelConfig(mesh_cfg,
                                         args.strategy or "replicated"),
                          mesh=mesh)
        rows = args.batch_size * args.accum * trainer.dp_size \
            // trainer.process_count
        # Skewed stream: a 4-id vocab slice keeps the router's top-k
        # concentrated; deterministic so every lane sees the same tokens.
        rng = np.random.default_rng(23)

        def next_batch():
            return rng.integers(0, 4, size=(rows, seq_len), dtype=np.int32)

        state = trainer.init_state()
        for _ in range(2):  # warmup: compile + stabilize
            state, metrics = trainer.train_step(state, next_batch())
        float(metrics["loss"])

        router = {}
        if model_config.num_experts:
            # One untimed telemetry step (separate executable) for the
            # router health columns of the table.
            state, metrics = trainer.train_step(state, next_batch(),
                                                telemetry=True)
            flat = telemetry_lib.flatten_scalars(metrics["telemetry"])

            def _layer_vals(key, flat=flat):
                pfx = f"telemetry/router/{key}/"
                return [v for k, v in flat.items() if k.startswith(pfx)]

            router = {
                "drop_frac": round(max(_layer_vals("drop_frac")), 4),
                "max_group_frac": round(max(_layer_vals("max_group_frac")),
                                        4),
                "entropy": round(
                    sum(_layer_vals("entropy"))
                    / max(len(_layer_vals("entropy")), 1), 4),
            }

        window_elapsed = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.steps):
                state, metrics = trainer.train_step(state, next_batch())
            float(metrics["loss"])  # end-of-window device sync
            window_elapsed.append(time.perf_counter() - t0)
        elapsed = min(window_elapsed)
        lanes[lane] = {
            "tok_per_sec": round(
                args.steps * trainer.tokens_per_step / elapsed, 1),
            "window_elapsed_s": [round(w, 3) for w in window_elapsed],
            **router,
        }

    speedup = (lanes["dropless"]["tok_per_sec"]
               / max(lanes["capacity"]["tok_per_sec"], 1e-9))
    return {
        "metric": "moe_dropless_tok_per_sec",
        "value": lanes["dropless"]["tok_per_sec"],
        "unit": "tok/s",
        "dense": lanes["dense"],
        "capacity": lanes["capacity"],
        "dropless": lanes["dropless"],
        "dropless_vs_capacity": round(speedup, 2),
        "num_experts": num_experts,
        "moe_top_k": top_k,
        "model_size": args.model_size,
        "batch_size": args.batch_size,
        "seq_len": seq_len,
        "steps": args.steps,
        "platform": next(iter(mesh.devices.flat)).platform,
        "n_chips": mesh.size,
    }


_MOE_START = "<!-- moe-table:start -->"
_MOE_END = "<!-- moe-table:end -->"


def update_moe_md(result) -> None:
    """Splice the --moe A/B into benchmarks/results.md (own marker block,
    same mechanism as the scaling and packing tables)."""
    header = (
        f"Measured by `python bench.py --moe` — {result['model_size']}, "
        f"{result['num_experts']} experts top-{result['moe_top_k']}, batch "
        f"{result['batch_size']}/shard, seq {result['seq_len']}, skewed "
        f"4-id token stream, platform {result['platform']} "
        f"({time.strftime('%Y-%m-%d')}).\n\n"
    )
    lines = [
        "| Lane | tok/s | drop_frac | max_group_frac | router entropy |",
        "|---|---|---|---|---|",
    ]
    for lane in ("dense", "capacity", "dropless"):
        r = result.get(lane)
        if r is None:
            continue

        def _cell(key, r=r):
            return f"{r[key]:.3f}" if key in r else "-"

        lines.append(
            f"| {lane} | {r['tok_per_sec']:,.0f} | {_cell('drop_frac')} "
            f"| {_cell('max_group_frac')} | {_cell('entropy')} |"
        )
    table = "\n".join(lines) + (
        f"\n\nThroughput ratio (dropless / capacity-einsum): "
        f"**{result['dropless_vs_capacity']:.2f}x** — same params, same "
        f"tokens; the capacity lane additionally DROPS "
        f"{result['capacity'].get('drop_frac', 0):.1%} of its routed "
        f"tokens on this skewed stream while dropless drops none."
    )
    block = f"{_MOE_START}\n{header}{table}\n{_MOE_END}"
    with open(_RESULTS_MD) as f:
        text = f.read()
    if _MOE_START in text:
        pre = text.split(_MOE_START)[0]
        post = text.split(_MOE_END)[1]
        text = pre + block + post
    else:
        text += "\n## Dropless MoE\n\n" + block + "\n"
    with open(_RESULTS_MD, "w") as f:
        f.write(text)
    print(f"wrote MoE table to {_RESULTS_MD}", file=sys.stderr)


def _chip_counts(n: int):
    c, out = 1, []
    while c <= n:
        out.append(c)
        c *= 2
    if out[-1] != n:
        out.append(n)
    return out


def run_table(args):
    """Method x chips (reference README.md:188-198 table shape)."""
    import jax

    from tpu_trainer.parallel.mesh import MeshConfig

    n = jax.device_count()
    rows = []
    base_per_method = {}
    methods = ("DDP", "FSDP") + (("AUTO",) if args.mesh == "auto" else ())
    for method in methods:
        for chips in _chip_counts(n):
            if method == "FSDP" and chips == 1:
                continue  # 1-chip FSDP is DDP
            if method == "AUTO" and chips != n:
                continue  # the planner lane plans for the full pod
            plan_record = None
            batch_size = args.batch_size
            if method == "AUTO":
                # --table --mesh auto: one extra lane where the planner
                # picks the split; the row's mesh_plan record carries its
                # full ranking plus measured-vs-predicted step time.
                from tpu_trainer.parallel import planner as planner_lib

                plan_record = _auto_plan(args, n, default_strategy="zero3")
                chosen = plan_record["chosen"]
                mesh_cfg = planner_lib.mesh_config_for(chosen)
                strategy = plan_record["strategy"]
                batch_size = chosen["batch_per_shard"]
            elif method == "DDP":
                mesh_cfg = MeshConfig(data=chips, fsdp=1)
                strategy = "replicated"
            else:
                mesh_cfg = MeshConfig(data=1, fsdp=chips)
                strategy = "zero3"
            r = run_bench(
                model_size=args.model_size, batch_size=batch_size,
                seq_len=args.seq_len, steps=args.steps, accum=args.accum,
                use_flash=bool(args.flash), remat=_remat(args),
                mesh_cfg=mesh_cfg, strategy=strategy,
                devices=jax.devices()[:chips], plan_record=plan_record,
                hbm_gb=args.hbm_gb,
            )
            r["method"] = method
            base = base_per_method.setdefault(
                "1chip", r["tok_per_sec"] if chips == 1 else None
            )
            if base:
                r["scaling_efficiency"] = round(
                    r["tok_per_sec"] / (base * chips), 3
                )
            else:
                r["scaling_efficiency"] = None
            rows.append(r)
            print(json.dumps(r), file=sys.stderr)
    return rows


def format_table(rows) -> str:
    lines = [
        "| Method | Chips | tok/s | tok/s/chip | Peak mem/chip | MFU "
        "| Scaling eff. | Plan err |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        mem = f"{r['peak_mem_gb']:.2f} GB" if r["peak_mem_gb"] else "n/a"
        if r["peak_mem_gb"] and r.get("peak_mem_source") == "compiled":
            # XLA memory_analysis of the step executable (CPU lanes: the
            # backend keeps no runtime stats) — args+outputs+temps-aliased.
            mem += " (compiled)"
        mfu_s = f"{100 * r['mfu']:.1f}%" if r["mfu"] else "n/a"
        eff = (f"{100 * r['scaling_efficiency']:.0f}%"
               if r.get("scaling_efficiency") else "—")
        method = r["method"]
        if method == "AUTO" and r.get("mesh"):
            method += " (" + "x".join(
                str(v) for v in r["mesh"].values()) + ")"
        perr = r.get("plan_error_frac")
        perr_s = f"{100 * perr:.0f}%" if perr is not None else "—"
        lines.append(
            f"| {method} | {r['n_chips']} | {r['tok_per_sec']:,.0f} "
            f"| {r['tok_per_sec_per_chip']:,.0f} | {mem} | {mfu_s} | {eff} "
            f"| {perr_s} |"
        )
    return "\n".join(lines)


def update_results_md(rows, args) -> None:
    table = format_table(rows)
    header = (
        f"Measured by `python bench.py --table` — {args.model_size}, "
        f"batch {args.batch_size}/shard, seq {args.seq_len}, "
        f"platform {rows[0]['platform']} "
        f"({time.strftime('%Y-%m-%d')}).\n\n"
    )
    block = f"{_TABLE_START}\n{header}{table}\n{_TABLE_END}"
    with open(_RESULTS_MD) as f:
        text = f.read()
    if _TABLE_START in text:
        pre = text.split(_TABLE_START)[0]
        post = text.split(_TABLE_END)[1]
        text = pre + block + post
    else:
        text += "\n" + block + "\n"
    with open(_RESULTS_MD, "w") as f:
        f.write(text)
    print(f"wrote scaling table to {_RESULTS_MD}", file=sys.stderr)


def _remat(args):
    if args.remat is not None:
        return bool(args.remat)
    env = os.environ.get("BENCH_REMAT")
    if env is not None:
        return env == "1"
    return args.model_size not in ("small", "tiny")


def main() -> None:
    from tpu_trainer.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # Partitionable threefry, same as tests/conftest.py: without it the
    # pipeline stage shard_map lowers per-step RNG to a PartitionId
    # instruction the SPMD partitioner rejects — stage>1 meshes (--mesh
    # auto picks them freely) would crash at the first train step.
    import jax as _jax

    _jax.config.update("jax_threefry_partitionable", True)
    args = _build_parser().parse_args()
    # No LIBTPU_INIT_ARGS scoped-VMEM raise here anymore: the flash
    # backward now dispatches to the two-kernel split path past s=2048
    # (s-independent VMEM residency, see ops/flash.py), so every sequence
    # length runs at default compiler flags. --flash_bwd forces a path for
    # A/B sweeps.
    if args.flash_bwd != "auto":
        os.environ["TPU_TRAINER_FLASH_BWD"] = args.flash_bwd
    if args.validate:
        from tpu_trainer.validate import main as validate_main

        # --tpu: bench.py is the on-hardware driver — a silent CPU
        # fallback must FAIL, not skip the kernel checks and exit green.
        sys.exit(validate_main(["--tpu"]))
    if args.table:
        rows = run_table(args)
        print(format_table(rows))
        if args.update_results:
            update_results_md(rows, args)
        return

    from tpu_trainer.parallel.mesh import MeshConfig

    plan_record = None
    if args.mesh == "auto":
        if (args.mesh_data is not None or args.mesh_fsdp is not None
                or args.mesh_tensor != 1 or args.mesh_sequence != 1
                or args.mesh_expert != 1 or args.mesh_stage != 1):
            raise SystemExit(
                "--mesh auto and explicit --mesh-* splits are mutually "
                "exclusive — drop the --mesh-* flags to let the planner "
                "choose, or pin the mesh and drop --mesh auto")
        import jax

        from tpu_trainer.parallel import planner as planner_lib

        plan_record = _auto_plan(args, jax.device_count())
        chosen = plan_record["chosen"]
        mesh_cfg = planner_lib.mesh_config_for(chosen)
        # The planner holds the GLOBAL batch fixed; run on the chosen
        # split's per-shard slice of it.
        args.batch_size = chosen["batch_per_shard"]
        for line in planner_lib.render_table(plan_record):
            print(f"bench: {line}", file=sys.stderr)
    else:
        mesh_cfg = MeshConfig(
            data=args.mesh_data if args.mesh_data is not None
            else (-1 if args.mesh_fsdp is None else 1),
            fsdp=args.mesh_fsdp if args.mesh_fsdp is not None else 1,
            sequence=args.mesh_sequence,
            tensor=args.mesh_tensor,
            expert=args.mesh_expert,
            stage=args.mesh_stage,
        )
    if args.packed:
        result = run_packed(args, mesh_cfg)
        print(json.dumps(result))
        if args.update_results or args.update_md:
            update_packing_md(result)
        return
    if args.moe:
        result = run_moe(args, mesh_cfg)
        print(json.dumps(result))
        if args.update_results or args.update_md:
            update_moe_md(result)
        return
    detail = run_bench(
        model_size=args.model_size, batch_size=args.batch_size,
        seq_len=args.seq_len, steps=args.steps, accum=args.accum,
        use_flash=bool(args.flash), remat=_remat(args),
        mesh_cfg=mesh_cfg, strategy=args.strategy,
        offload=args.offload, offload_dtype=args.offload_dtype,
        num_experts=args.num_experts, moe_top_k=args.moe_top_k,
        model_flags=_parse_model_flags(args.model_flag),
        carry_cast=bool(args.carry_cast),
        opt_state_dtype=args.opt_state_dtype,
        offload_budget_gb=args.offload_budget_gb,
        checkpoint_every=args.checkpoint_every, stream=args.stream,
        prefetch_depth=args.prefetch_depth,
        device_prefetch_depth=args.device_prefetch_depth,
        plan_record=plan_record, hbm_gb=args.hbm_gb,
    )
    comms = detail.get("comms_model") or {}
    result = {
        "metric": "train_tokens_per_sec",
        "value": detail["tok_per_sec"],
        "unit": "tok/s",
        "vs_baseline": round(detail["tok_per_sec"] / _REF_BASELINE, 4),
        # Additive observability fields (ISSUE 2): measured-loop goodput
        # and XLA-predicted vs analytic FLOPs for the compiled step.
        "goodput_productive_frac": detail["goodput"].get("productive_frac"),
        # Overlap split (ISSUE 4): with --checkpoint_every the save frac is
        # the snapshot cost only (the commit overlaps compute; residual
        # drains land in commit_wait); with --stream + prefetch, data_wait
        # should sit at ~0.
        "goodput_data_wait_frac": detail["goodput"].get("data_wait_frac"),
        "goodput_checkpoint_save_frac": detail["goodput"].get(
            "checkpoint_save_frac"),
        "goodput_checkpoint_commit_wait_frac": detail["goodput"].get(
            "checkpoint_commit_wait_frac"),
        "xla_flops_per_step": detail["xla_flops_per_step"],
        "analytic_flops_per_step": detail["analytic_flops_per_step"],
        # Static comms/compute split of the measured config (ISSUE 3).
        "comms_bytes_per_step": comms.get(
            "total_bytes_per_device_per_step"),
        "comms_compute_ratio": comms.get("comms_compute_ratio"),
        "roofline_bound": comms.get("bound"),
        # Mesh auto-planner validation loop (ISSUE 11): analytic predicted
        # step time for THIS mesh vs the measured windows.
        "measured_step_ms": detail["measured_step_ms"],
        "predicted_step_ms": detail["predicted_step_ms"],
        "plan_error_frac": detail["plan_error_frac"],
    }
    # Side-channel detail (stderr keeps stdout to the single JSON line the
    # driver parses).
    print(json.dumps(result))
    print(json.dumps(detail, default=str), file=sys.stderr)
    jsonl_path = args.jsonl
    if not jsonl_path:
        import tempfile

        fd, jsonl_path = tempfile.mkstemp(prefix="bench_", suffix=".jsonl")
        os.close(fd)
    try:
        write_run_jsonl(jsonl_path, detail)
        print(f"bench: records -> {jsonl_path}", file=sys.stderr)
        analyze_run_jsonl(jsonl_path)
    except Exception as e:  # pragma: no cover - defensive
        print(f"bench: run analysis failed: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
