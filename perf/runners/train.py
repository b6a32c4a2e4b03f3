"""Runs a training cell: the program's Trainer, one optimizer step after
another on seeded batches, for ``--seconds``."""

from __future__ import annotations

import functools
import math
import time
from typing import Any, Dict

from perf import harness, program, program_trace, reference, registry, work

MAX_IN_FLIGHT = 2  # steps dispatched ahead of the one the host waits for


def compare_logits_fn(trainer, cfg):
    """``f(params, tokens)``: the program's forward (its kernels, its compute
    type) against the plain float32 reference; returns the error norms.

    The forward runs the program's ROLLED layer scan whatever the job trains
    with: same parameters, same layer code, same kernels, but one layer to
    trace instead of all of them (the unrolled forward of 32 layers took 50 s
    of every run's set-up, my chip run, PR 24). What it cannot see is a fault
    that lives only in the unrolled loop; the job's own path is held by the
    first step's loss, at the configuration's ``loss_rel`` tolerance."""
    import dataclasses

    import jax.numpy as jnp

    from tpu_trainer.models.gpt import GPT
    from tpu_trainer.parallel import context as ctx_lib

    model = GPT(dataclasses.replace(trainer.model_config, scan_unroll=False))

    def compare(params, toks):
        with ctx_lib.mesh_scope(trainer.mesh):
            got, _ = model.apply({"params": params}, toks, train=False)
        want = reference.forward(params, toks, cfg)
        diff = got.astype(jnp.float32) - want
        scale = jnp.sqrt(jnp.mean(want * want))
        return {"rel_rms": jnp.sqrt(jnp.mean(diff * diff)) / scale,
                "max_abs_over_rms": jnp.max(jnp.abs(diff)) / scale,
                "ref_rms": scale,
                "finite": jnp.all(jnp.isfinite(got))}

    return compare


def reference_loss_fn(cfg, job):
    return functools.partial(
        reference.loss, cfg=cfg,
        rows_per_pass=job["check"]["loss_rows_per_pass"])


def _place_rows(trainer, rows):
    """Host ``[rows, seq]`` tokens onto the mesh, rows over the data axes."""
    import jax
    from jax.sharding import NamedSharding

    from tpu_trainer.parallel import mesh as mesh_lib

    return jax.device_put(rows, NamedSharding(
        trainer.mesh, mesh_lib.batch_spec_2d()))


def _check_logits(trainer, state, cfg, job, seed, spans):
    import jax
    import numpy as np

    check = job["check"]
    rng = np.random.default_rng(seed + 1)
    tokens = rng.integers(0, cfg["vocab_size"],
                          size=(check["rows"], check["seq_len"]),
                          dtype=np.int32)
    with spans.span("first_call", what="logit_check"):
        out = jax.jit(compare_logits_fn(trainer, cfg))(
            state.params, _place_rows(trainer, tokens))
        return {k: float(v) for k, v in out.items()}


def _reference_loss(trainer, state, cfg, job, batch, spans):
    import jax

    with spans.span("first_call", what="reference_loss"):
        return float(jax.jit(reference_loss_fn(cfg, job))(
            state.params, _place_rows(trainer, batch)))


def run(cell: Dict[str, Any], *, devices, seed: int, seconds: float,
        trace: bool, process_start: float) -> harness.Result:
    import jax

    cfg, traffic, job = cell["config_file"], cell["traffic_file"], cell["job"]
    spans = harness.Spans()
    cache = harness.CacheCounter()
    generator = registry.code("generators", traffic["generator"])
    batches = generator.generate(traffic, seed=seed,
                                 vocab_size=cfg["vocab_size"])
    runner_start = time.perf_counter()
    trainer = program.build_trainer(cfg, traffic, job, devices)
    built = time.perf_counter()
    with spans.span("first_call", what="init_state"):
        state = trainer.init_state(seed % (2 ** 31 - 1))
        jax.block_until_ready(state.params)

    # --- correct: outside the window, every run --------------------------
    # The tolerances belong to the configuration and the compute type, not
    # to the cell: every cell of one configuration is held to the same.
    tol = cfg["reference_tolerance"][program.COMPUTE_TYPE]
    logit_err = _check_logits(trainer, state, cfg, job, seed, spans)
    first_batch = next(batches)
    want_loss = _reference_loss(trainer, state, cfg, job, first_batch, spans)
    with spans.span("first_call", what="train_step"):
        state, metrics = trainer.train_step(state, first_batch)
        got_loss = float(metrics["loss"])
    loss_err = abs(got_loss - want_loss) / abs(want_loss)
    compared = {
        "logit_rel_rms": [logit_err["rel_rms"], tol["logit_rel_rms"]],
        "logit_max_over_rms": [logit_err["max_abs_over_rms"],
                               tol["logit_max_over_rms"]],
        "loss_rel": [loss_err, tol["loss_rel"]]}
    correct = logit_err["finite"] == 1.0 and all(
        reading <= limit for reading, limit in compared.values())
    harness.note("correct_check", logits=logit_err, first_step_loss=got_loss,
                 reference_loss=want_loss, loss_rel_err=loss_err,
                 tolerances=tol, ok=bool(correct))
    # A second warm step: the first ran on fresh optimizer state.
    state, metrics = trainer.train_step(state, next(batches))
    jax.block_until_ready(state.params)

    # --- the window --------------------------------------------------------
    # Untraced: steps for `seconds`. Traced: a few steps, then the traced
    # stretch of `trace_steps` steps, synced at both ends, which is then the
    # window the per-layer metrics are read over; the run ends with it
    # (stopping the profiler takes seconds that belong to no step).
    tracer = harness.TraceWindow(cell["name"], spans) if trace else None
    tokens_per_step = trainer.tokens_per_step
    harness.note("setup", setup_s=time.perf_counter() - process_start,
                 imports_and_devices_s=runner_start - process_start,
                 build_trainer_s=built - runner_start,
                 first_calls=[[s.attrs["what"], s.dur]
                              for s in spans.named("first_call")],
                 cache_hits=cache.hits, cache_misses=cache.misses,
                 # What the first calls were made of, by the program's own
                 # compile log: a `setup_s` near its bound names its phase.
                 compile_log=program_trace.first_call_compiles(spans))
    losses = []
    window_start = time.perf_counter()
    setup_s = window_start - process_start
    while True:
        step = len(losses)
        if tracer is not None and step == job["trace_after_steps"]:
            jax.block_until_ready(state.params)
            tracer.start()
            window_start = tracer.started_at
        with spans.span("next_batch"):
            batch = trainer.place_batch(next(batches))
        with spans.span("train_step", step=step):
            state, metrics = trainer.train_step(state, batch)
        losses.append(metrics["loss"])
        if step >= MAX_IN_FLIGHT:
            losses[step - MAX_IN_FLIGHT].block_until_ready()
        if tracer is None:
            if time.perf_counter() - window_start >= seconds:
                break
        elif len(losses) == job["trace_after_steps"] + job["trace_steps"]:
            break
    jax.block_until_ready(state.params)
    window_end = time.perf_counter()
    if tracer is not None:
        tracer.stop()
    window_steps = (len(losses) if tracer is None else job["trace_steps"])

    window_s = window_end - window_start
    losses = [float(x) for x in losses]
    failed = sum(1 for x in losses if not math.isfinite(x))
    rate = window_steps * tokens_per_step / window_s
    seq_len = traffic["seq_len"]
    peak = cell["peaks"]["bf16_flops_per_s"]
    harness.note(
        "train_window", steps=window_steps, window_s=window_s,
        tokens_per_step=tokens_per_step, train_tokens_per_s=rate,
        step_ms=1e3 * window_s / window_steps,
        mfu=work.mfu(cfg, seq_len, rate, len(devices), peak),
        flops_per_token=work.train_flops_per_token(cfg, seq_len),
        loss_first=losses[0], loss_last=losses[-1],
        setup_s=setup_s)
    # After the window, so that it costs neither set-up nor measured time:
    # what the compiler planned beside what the runtime saw. The analysis
    # loads the step a second time; where the chip has no room for that the
    # run's result must not be lost with it.
    try:
        planned = trainer.step_memory_analysis(state, batch)
    except jax.errors.JaxRuntimeError as e:
        planned = {"error": str(e)[:300]}
    harness.note(
        "memory",
        memory_stats_peak_bytes=[
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices],
        compiled_memory_analysis=planned)

    obs = harness.Observations(
        cell=cell, spans=spans,
        window=(window_start, window_end),
        counters={"steps": window_steps,
                  "tokens_per_step": tokens_per_step,
                  "sequences_per_step": tokens_per_step // seq_len,
                  "seq_len": seq_len})
    if tracer is not None:
        harness.attach_trace(obs, tracer)
    return harness.Result(
        correct=bool(correct) and failed == 0, attempted=len(losses),
        failed=failed,
        end_to_end={"train_tokens_per_s": rate, "setup_s": setup_s},
        observations=obs, devices=devices,
        compared={**compared, "logits_finite": [logit_err["finite"], 1.0],
                  "losses_not_finite": [failed, 0]})
