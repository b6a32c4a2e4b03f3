#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

One process, real entry points, the ``small`` preset at full width (12
layers, hidden 768, 12 heads x d=64, vocab 50257, seq 1024, bf16), weights
random from a seed::

    python chip_smoke.py             # one TPU chip: device, train, serve
    python chip_smoke.py --chips 4   # four chips: ONLY the sharded-training
                                     # phase and what it is compared with

Each phase prints one JSON line as it finishes; any failure exits non-zero
at once. Without a TPU (``JAX_PLATFORMS=cpu``, or no accelerator) it exits
non-zero before running a phase. The last stdout line is the contract's::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The earlier lines carry plain facts (compile seconds, step ms, peak bytes);
they are not benchmark numbers. One process holds the chip: this script
starts no child process.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# Scratch for the trainer CLI's checkpoints and metrics: inside the
# checkout, git-ignored (.gitignore: checkpoints/), removed on the way out.
WORK = os.path.join(ROOT, "checkpoints", "chip_smoke")

# flash_decode vs paged_attention_reference (f32 HIGHEST oracle), same chip,
# same inputs. The kernel multiplies in f32 at HIGHEST precision, so what is
# left is reduction order; outputs are O(1).
DECODE_ATOL = 2e-3
# Sharded vs single-chip per-step loss (bf16 compute, dropout off): only the
# gradient reduction order differs between the three runs.
LOSS_RTOL = 2e-3

_CACHE_EVENTS = {"hits": 0, "misses": 0}


class SmokeFailure(Exception):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _count_cache_events() -> None:
    import jax

    def listener(event, **_):
        if event.endswith("/compilation_cache/cache_hits"):
            _CACHE_EVENTS["hits"] += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            _CACHE_EVENTS["misses"] += 1

    jax.monitoring.register_event_listener(listener)


# --- device ----------------------------------------------------------------

def phase_device(chips: int) -> dict:
    from tpu_trainer.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    import jaxlib

    _count_cache_events()
    devices = jax.devices()
    first = devices[0]
    require(first.platform == "tpu",
            f"no TPU: jax found platform {first.platform!r} "
            f"({len(devices)} x {first.device_kind}); this smoke only runs "
            f"on the chip")
    require(len(devices) == chips,
            f"found {len(devices)} TPU device(s), this run needs {chips} "
            f"(--chips {len(devices)}?)")
    from importlib import metadata

    from tpu_trainer.utils.logging import device_peak_flops

    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(devices)}
    emit("device", **device,
         jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=metadata.version("libtpu"),
         peak_bf16_flops=device_peak_flops(first),  # unknown kind raises
         compile_cache_dir=cache_dir,
         compile_cache_entries_at_start=(
             len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0),
         tpu_worker_hostnames=os.environ.get("TPU_WORKER_HOSTNAMES"))
    return device


# --- trainer CLI, in process -----------------------------------------------

def _cli_argv(tag: str, *, batch_size: int, steps: int, extra=()) -> list:
    # --num_batches 1: the dummy corpus is uniform random tokens, which no
    # model can learn across fresh batches (10 steps moved the loss by 0.005
    # on the chip); one repeated batch can be memorised, so a working
    # optimizer shows as a loss that plainly falls.
    return [
        "--model_size", "small", "--dataset", "dummy", "--num_batches", "1",
        "--batch_size", str(batch_size), "--grad_accum", "1",
        "--seq_len", "1024", "--mixed_precision", "bf16",
        "--max_steps", str(steps), "--warmup_steps", "2",
        "--log_interval", "1", "--no_auto_resume",
        "--checkpoint_dir", os.path.join(WORK, tag, "ckpt"),
        "--metrics_jsonl", os.path.join(WORK, tag, "metrics.jsonl"),
        *extra,
    ]


def _run_cli(mode: str, tag: str, argv: list) -> dict:
    """The function behind ``python -m tpu_trainer.training.train_<mode>``,
    called in this process; returns its JSONL records grouped by kind."""
    from tpu_trainer.training.cli import run_training

    shutil.rmtree(os.path.join(WORK, tag), ignore_errors=True)
    rc = run_training(argv, mode=mode)
    require(rc == 0, f"train_{mode} {tag}: exit code {rc}")
    by_kind: dict = {}
    with open(os.path.join(WORK, tag, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            by_kind.setdefault(rec["kind"], []).append(rec)
    return by_kind


def _trainer_like_cli(mode: str, argv: list, devices=None):
    """A Trainer from the very configs the CLI resolves for ``argv``
    (optionally on a subset of the devices) plus its dummy loader."""
    from tpu_trainer.parallel.mesh import make_mesh
    from tpu_trainer.training import cli
    from tpu_trainer.training.trainer import Trainer

    args = cli.build_parser(mode).parse_args(argv)
    model_config, training_config, parallel_config, data_opts = (
        cli.resolve_configs(args, mode))
    mesh = (make_mesh(parallel_config.mesh, devices=devices)
            if devices is not None else None)
    trainer = Trainer(model_config, training_config, parallel_config,
                      mesh=mesh)
    loader, _ = cli.build_dataloaders(data_opts, trainer, model_config)
    return trainer, loader


def _losses(records: dict, steps: int) -> list:
    losses = [r["loss"] for r in records.get("train", [])]
    require(len(losses) == steps,
            f"expected {steps} train records, got {len(losses)}")
    require(all(x == x and abs(x) != float("inf") for x in losses),
            f"non-finite loss: {losses}")
    return losses


def _peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    require(stats.get("peak_bytes_in_use"),
            "device.memory_stats() reports no peak_bytes_in_use")
    return stats.get("peak_bytes_in_use")


def _pallas_calls(hlo: str) -> list:
    """Where each Pallas custom call of a compiled program came from."""
    import re

    return [m.group(1) for m in re.finditer(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', hlo)]


def phase_train() -> None:
    import numpy as np

    steps, batch = 10, 8
    argv = _cli_argv("train", batch_size=batch, steps=steps)
    t0 = time.perf_counter()
    records = _run_cli("ddp", "train", argv)
    wall = time.perf_counter() - t0
    losses = _losses(records, steps)
    require(losses[-1] < losses[0],
            f"loss did not fall over {steps} steps: {losses}")

    # The kernels ran, not their XLA twins: the compiled step of the same
    # configs (a cache hit of the program the CLI just ran) must hold the
    # Pallas custom calls — attention forward and backward in every layer
    # and the fused head+CE.
    trainer, _ = _trainer_like_cli("ddp", argv)
    state = trainer.init_state()
    hlo = trainer.compiled_step_text(
        state, np.zeros((batch, 1024), np.int32))
    calls = _pallas_calls(hlo)
    attention = [c for c in calls if "/attention/" in c]
    require(any("transpose(" in c for c in attention)
            and any("transpose(" not in c for c in attention),
            f"compiled step lacks the flash attention kernels (forward and "
            f"backward): {sorted(set(calls))}")
    require(len(calls) > len(attention),
            f"compiled step lacks the fused head+CE kernel: "
            f"{sorted(set(calls))}")
    del state

    goodput = records["goodput"][-1]
    tokens = batch * 1024
    # The logger's windowed rate: the first records hold the compile, the
    # last two are drained in a burst at the end of the run.
    steady = records["train"][2:-2]
    step_ms = [1e3 * tokens / r["tokens_per_sec"] for r in steady]
    emit("train", entry="tpu_trainer.training.train_ddp (in process)",
         model="small", batch=batch, seq=1024, steps=steps,
         losses=[round(x, 4) for x in losses],
         pallas_custom_calls=len(calls),
         pallas_attention_calls=len(attention),
         pallas_head_ce_calls=len(calls) - len(attention),
         compile_seconds=round(goodput["compile_seconds"], 2),
         steady_step_ms_median=statistics.median(step_ms),
         mfu_median=statistics.median(r["mfu"] for r in steady),
         wall_seconds=round(wall, 1),
         peak_bytes_in_use=_peak_bytes(),
         cache_hits=_CACHE_EVENTS["hits"],
         cache_misses=_CACHE_EVENTS["misses"])


# --- serving engine --------------------------------------------------------

def _decode_case(*, heads, kv_heads, d, int8, seed, batch=8, block=16,
                 max_blocks=64):
    """Random paged-attention operands at serving shapes, on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_trainer.utils.quant import quantize_kv_int8

    rs = np.random.RandomState(seed)
    nblk = batch * max_blocks + 1
    q = jnp.asarray(rs.standard_normal((batch, heads, d)), jnp.bfloat16)
    pools = [jnp.asarray(rs.standard_normal((nblk, block, kv_heads, d)),
                         jnp.bfloat16) for _ in range(2)]
    tables = jnp.asarray(
        rs.permutation(np.arange(1, nblk)).reshape(batch, max_blocks),
        jnp.int32)
    full = block * max_blocks
    lengths = jnp.asarray(
        [1, block + 1, 100, 256, 257, full // 2, full - 24, full][:batch],
        jnp.int32)
    kw = {}
    if int8:
        (pools[0], kw["k_scale"]), (pools[1], kw["v_scale"]) = (
            quantize_kv_int8(pools[0]), quantize_kv_int8(pools[1]))
    return (q, pools[0], pools[1], tables, lengths), kw


def _check_flash_decode() -> list:
    """flash_decode (compiled) vs paged_attention_reference: the engine's
    geometry in bf16 and int8, plus a d=128 GQA geometry."""
    import jax
    import jax.numpy as jnp

    from tpu_trainer.ops.flash import flash_decode, paged_attention_reference

    out = []
    for heads, kv_heads, d in [(12, 12, 64), (16, 4, 128)]:
        for int8 in (False, True):
            args, kw = _decode_case(heads=heads, kv_heads=kv_heads, d=d,
                                    int8=int8, seed=heads + int8)
            got = jax.jit(lambda *a, **k: flash_decode(
                *a, interpret=False, **k))(*args, **kw)
            with jax.default_matmul_precision("highest"):
                want = jax.jit(paged_attention_reference)(*args, **kw)
            err = float(jnp.max(jnp.abs(got - want)))
            require(bool(jnp.all(jnp.isfinite(got))),
                    f"flash_decode h={heads} kvh={kv_heads} d={d} "
                    f"int8={int8}: non-finite output")
            require(err <= DECODE_ATOL,
                    f"flash_decode h={heads} kvh={kv_heads} d={d} "
                    f"int8={int8}: max|kernel - reference| = {err:.3e} > "
                    f"{DECODE_ATOL}")
            out.append({"heads": heads, "kv_heads": kv_heads, "d": d,
                        "pool": "int8" if int8 else "bf16",
                        "max_abs_err": err})
    return out


def phase_serve() -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from tpu_trainer.models.config import GPTConfig
    from tpu_trainer.models.gpt import GPT
    from tpu_trainer.serving.engine import ServingEngine, poisson_trace

    # Built the way ``python -m tpu_trainer.eval.infer --serve`` and
    # ``python -m tpu_trainer.serving.engine`` build it, at `small` widths.
    config = dataclasses.replace(
        GPTConfig.preset("small"), dtype="bfloat16", dropout=0.0,
        attention_dropout=0.0)
    params = GPT(config).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    n_requests, max_new = 8, 32
    # Default pool: every one of the 8 slots at the full 1024-token context.
    engine = ServingEngine(params, config, max_batch=8, block_size=16,
                           attention="auto")
    trace = poisson_trace(
        n_requests, vocab_size=config.vocab_size, seed=0,
        prompt_len_range=(32, 256), max_new_range=(max_new, max_new),
        temperature=0.0)
    t0 = time.perf_counter()
    finished = engine.run(trace, time_mode="steps")
    wall = time.perf_counter() - t0
    require(len(finished) == n_requests,
            f"{len(finished)} of {n_requests} requests finished")
    for r in finished:
        require(len(r.generated) == max_new
                and all(0 <= t < config.vocab_size for t in r.generated),
                f"request {r.rid}: bad stream {r.generated}")

    calls = _pallas_calls(engine.compiled_decode_text())
    require(calls, "the jitted decode step holds no Pallas custom call: "
                   "attention='auto' did not pick the flash_decode kernel")
    summary = engine.summary()
    emit("serve", entry="tpu_trainer.serving.ServingEngine.run",
         model="small", dtype="bfloat16", max_batch=8, block_size=16,
         pool_blocks=engine.config.paged_num_blocks,
         requests_finished=len(finished),
         prompt_lens=[len(r.prompt) for r in finished],
         new_tokens_each=max_new,
         decode_attention="kernel (flash_decode, compiled)",
         decode_step_pallas_calls=len(calls),
         prefill_iters=summary.get("prefill_iters"),
         decode_iters=summary.get("decode_iters"),
         wall_seconds_incl_compile=round(wall, 1),
         flash_decode_vs_reference=_check_flash_decode(),
         decode_atol=DECODE_ATOL,
         peak_bytes_in_use=_peak_bytes(),
         cache_hits=_CACHE_EVENTS["hits"],
         cache_misses=_CACHE_EVENTS["misses"])


# --- four chips: DDP and FSDP against one chip -----------------------------

def _shard_report(tree) -> dict:
    """How a pytree's bytes spread over devices (``addressable_shards``)."""
    import jax

    per_device: dict = {}
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes)
    return {"total_bytes": total,
            "bytes_per_device": dict(sorted(per_device.items()))}


def phase_sharded() -> None:
    import jax

    from tpu_trainer.parallel.comms_model import hlo_collective_counts

    steps, global_batch = 4, 8
    os.makedirs(WORK, exist_ok=True)
    # Dropout off for the comparison: the three runs then compute the same
    # function of the same global batch.
    no_dropout = os.path.join(WORK, "no_dropout.yaml")
    with open(no_dropout, "w") as f:
        f.write("model:\n  dropout: 0.0\n  attention_dropout: 0.0\n")

    def argv(tag, batch_size, mesh):
        return _cli_argv(tag, batch_size=batch_size, steps=steps,
                         extra=["--config", no_dropout, *mesh])

    modes = {
        "NO_SHARD": ("ddp4", ["--sharding", "NO_SHARD",
                              "--mesh_data", "4", "--mesh_fsdp", "1"]),
        "FULL_SHARD": ("fsdp4", ["--sharding", "FULL_SHARD",
                                 "--mesh_data", "1", "--mesh_fsdp", "4"]),
    }
    modes = {name: (tag, argv(tag, global_batch // 4, mesh))
             for name, (tag, mesh) in modes.items()}
    losses = {name: _losses(_run_cli("fsdp", tag, a), steps)
              for name, (tag, a) in modes.items()}

    # The same global batch on ONE of the four chips (library boundary: the
    # CLI has no flag for "a subset of the devices").
    one_argv = argv("one", global_batch,
                    ["--sharding", "NO_SHARD",
                     "--mesh_data", "1", "--mesh_fsdp", "1"])
    trainer, loader = _trainer_like_cli("fsdp", one_argv,
                                        devices=jax.devices()[:1])
    state = trainer.init_state()
    single = []
    batch = next(iter(loader))  # --num_batches 1: every step sees this one
    for _ in range(steps):
        state, metrics = trainer.train_step(state, batch)
        single.append(float(metrics["loss"]))
    del state
    for name, got in losses.items():
        for step, (a, b) in enumerate(zip(got, single)):
            require(abs(a - b) <= LOSS_RTOL * abs(b),
                    f"{name} loss at step {step} = {a} vs one chip {b} "
                    f"(rtol {LOSS_RTOL}); all: {got} vs {single}")

    # Where the FULL_SHARD state lives, and what the compiler put in.
    report = {}
    for name, (_, a) in modes.items():
        trainer, loader = _trainer_like_cli("fsdp", a)
        state = trainer.init_state()
        hlo = trainer.compiled_step_text(state, next(iter(loader)))
        report[name] = {
            "mesh": {k: v for k, v in trainer.mesh.shape.items() if v > 1},
            "collectives": {k: v for k, v in
                            hlo_collective_counts(hlo).items() if v},
            "pallas_custom_calls": len(_pallas_calls(hlo)),
            "params": _shard_report(state.params),
            "opt_state": _shard_report(state.opt_state),
        }
        del state
    ddp, fsdp = report["NO_SHARD"], report["FULL_SHARD"]
    require(ddp["collectives"].get("all-reduce"),
            f"NO_SHARD step has no gradient all-reduce: {ddp['collectives']}")
    require(fsdp["collectives"].get("all-gather")
            and (fsdp["collectives"].get("reduce-scatter")
                 or fsdp["collectives"].get("all-reduce")),
            f"FULL_SHARD step lacks param all-gather / grad reduce-scatter: "
            f"{fsdp['collectives']}")
    require(ddp["pallas_custom_calls"] and fsdp["pallas_custom_calls"],
            "sharded step lost its Pallas kernels")
    for part in ("params", "opt_state"):
        spread = fsdp[part]["bytes_per_device"]
        quarter = fsdp[part]["total_bytes"] / 4
        require(len(spread) == 4
                and all(0.9 * quarter <= b <= 1.15 * quarter
                        for b in spread.values()),
                f"FULL_SHARD {part} not spread a quarter per device: "
                f"{fsdp[part]}")
    emit("sharded_train",
         entry="tpu_trainer.training.train_fsdp (in process)",
         model="small", global_batch=global_batch, seq=1024, steps=steps,
         losses={**losses, "one_chip": single}, loss_rtol=LOSS_RTOL,
         max_rel_diff={name: max(abs(a - b) / abs(b)
                                 for a, b in zip(got, single))
                       for name, got in losses.items()},
         report=report,
         cache_hits=_CACHE_EVENTS["hits"],
         cache_misses=_CACHE_EVENTS["misses"])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run ONLY the sharded-training phase (NO_SHARD "
                        "data=4 and FULL_SHARD fsdp=4 against one chip)")
    args = p.parse_args(argv)
    try:
        device = phase_device(args.chips)
        if args.chips == 4:
            phase_sharded()
        else:
            phase_train()
            phase_serve()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
