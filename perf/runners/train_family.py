"""Runs a training cell of a model family that ``perf/program.py``,
``perf/reference.py`` and ``perf/work.py`` (bound by ``runners/train.py`` at
import, and written for the dense Llama-style family) do not know: the same
set-up, ``correct`` check, two-in-flight window and traced stretch as
``runners/train.py``, under the same harness span names, but the program's
``GPTConfig``, the plain reference and the FLOP counts come from
``perf/families/<family>.py``, named by the configuration's ``family`` key.

What differs from ``runners/train.py`` besides that:

- the logits are compared through the job's own (timed) layer loop, not the
  rolled one: these configurations are a few layers deep; on the first
  batch, with the reference routed by the program's choice of experts;
- the first step's gradient, read from the optimizer's state, is compared
  with the reference's (``check``);
- the step's metrics may carry device counters (``COUNTERS``); they are
  kept as device scalars and read after the window, never inside it. The
  readers get ``moe_rows_held``, ``moe_max_load``, ``ssm_tokens`` and
  ``ssd_kernel_tokens`` (``obs.counters``); ``moe_overflow_passes``,
  ``mtp_loss`` and ``ssd_min_log_decay`` go to the ``train_window`` note
  only.
"""

from __future__ import annotations

import functools
import math
import time
from typing import Any, Dict

from perf import harness, held_experts, program, program_trace, registry
from perf.runners.train import MAX_IN_FLIGHT, _place_rows

COUNTERS = ("moe_rows_held", "moe_max_load", "moe_overflow_passes",
            "mtp_loss", "ssm_tokens", "ssd_kernel_tokens",
            "ssd_min_log_decay")


def build_trainer(family, cfg, traffic, job, devices):
    """The program's Trainer for one training cell: ``program.build_trainer``
    with the family's ``gpt_config``. ``job`` names only options the program
    already has. The GPTConfig is built first: a program that lacks the
    family fails here, before anything is placed on the chip."""
    remat = job["remat"]  # "none" | "full" | "dots"
    model_config = family.gpt_config(
        cfg,
        use_flash_attention=True,
        fused_loss=True,
        fused_loss_pallas=True,
        gradient_checkpointing=remat != "none",
        remat_policy=remat if remat != "none" else "full",
        scan_unroll=job["scan_unroll"],
    )
    from tpu_trainer.parallel.mesh import MeshConfig, make_mesh
    from tpu_trainer.training.config import TrainingConfig
    from tpu_trainer.training.trainer import ParallelConfig, Trainer

    mesh_config = MeshConfig(**job["mesh"])
    dp = job["mesh"].get("data", 1) * job["mesh"].get("fsdp", 1)
    rows = traffic["tokens_per_step"] // traffic["seq_len"]
    if job["micro_batch"] * job["grad_accum"] * dp != rows:
        raise ValueError(
            f"micro_batch x grad_accum x data shards = "
            f"{job['micro_batch']} x {job['grad_accum']} x {dp} is not the "
            f"traffic's {rows} sequences a step")
    training_config = TrainingConfig(
        batch_size=job["micro_batch"],
        gradient_accumulation_steps=job["grad_accum"],
        max_seq_len=traffic["seq_len"],
        mixed_precision=program.COMPUTE_TYPE,
        optimizer_state_dtype="float32",
        learning_rate=job["learning_rate"],
        warmup_steps=job["warmup_steps"],
        max_steps=job["schedule_steps"],
    )
    parallel_config = ParallelConfig(
        mesh=mesh_config, sharding_strategy=job["sharding_strategy"])
    return Trainer(model_config, training_config, parallel_config,
                   mesh=make_mesh(mesh_config, devices=list(devices)))


def initial_state(trainer, family, cfg, job, seed, batch, spans):
    """The trainer's state from the seed, by the program's own initialiser;
    where the configuration picks the experts held here by their load
    (``experts_held_pick``: ``perf/held_experts.py``), relabelled so, with a
    note of what was done. Every seed then draws work of one difficulty."""
    import jax

    with spans.span("first_call", what="init_state"):
        state = trainer.init_state(seed % (2 ** 31 - 1))
        jax.block_until_ready(state.params)
    if cfg.get("experts_held_pick"):
        with spans.span("first_call", what="experts_held_pick"):
            state, done = held_experts.apply(
                trainer, state, family, cfg, job, batch,
                program_side(trainer), _place_rows)
        harness.note("experts_held_pick", **done)
    return state


def program_side(trainer):
    """``f(params, tokens) -> (logits, choices)``: the program's forward
    through the job's own layer loop, kernels and compute type, and which
    experts each token chose in each expert layer, in the order the layers
    run (``[batch, seq, k]`` ids; the capture's `deep` site). One jitted
    function a trainer, kept on it: the forward is traced in Python once a
    process, whoever calls it first (the pick of the held experts, the logit
    check), and a second caller's program takes the same jaxpr."""
    import jax

    kept = getattr(trainer, "_perf_program_side", None)
    if kept is not None:
        return kept

    from tpu_trainer.models.gpt import GPT
    from tpu_trainer.parallel import context as ctx_lib
    from tpu_trainer.utils import telemetry

    model = GPT(trainer.model_config)
    kinds = trainer.model_config.layer_kinds()

    def side(params, toks):
        with ctx_lib.mesh_scope(trainer.mesh), \
                telemetry.capture(deep=True) as cap:
            logits, _ = model.apply({"params": params}, toks, train=False)
        router = telemetry.assemble(cap.stats).get("router", {})
        seen, choices = {}, []
        for kind in kinds:
            if kind[1] == "moe":
                i = seen.get(kind, 0)
                seen[kind] = i + 1
                choices.append(router["_".join(kind)]["choice"][i].reshape(
                    *toks.shape, -1))
        return logits, choices

    trainer._perf_program_side = jax.jit(side)
    return trainer._perf_program_side


def compare_fn(side, family, cfg):
    """``f(params, tokens, reference's params)``: one side's logits against
    the family's plain float32 reference ROUTED BY THAT SIDE'S CHOICES, as
    sums to be added up over passes (``errors``), and the choices. Near a
    tie a side that rounds picks another expert than the reference would;
    routed alike, the logits measure the arithmetic, and the rows whose
    choice differs are counted beside (a row is one token in one expert
    layer)."""
    import jax.numpy as jnp

    def compare(params, toks, reference_params):
        got, choices = side(params, toks)
        want, own = family.forward_and_choices(
            reference_params, toks, cfg, choices)
        diff = got.astype(jnp.float32) - want
        experts = family.router_width(cfg)
        flipped = sum(
            jnp.sum(jnp.any(family.chose(c, experts)
                            != family.chose(o, experts), axis=-1))
            for c, o in zip(choices, own))
        return {"sq_err": jnp.sum(diff * diff), "sq_ref": jnp.sum(want * want),
                "max_abs": jnp.max(jnp.abs(diff)), "logits": diff.size,
                "finite": jnp.all(jnp.isfinite(got)),
                "flipped": flipped,
                "rows": sum(o.size // o.shape[-1] for o in own)}, choices

    return compare


def errors(passes):
    """The comparison's numbers from what ``compare_fn`` returned a pass."""
    total = {k: sum(float(p[k]) for p in passes)
             for k in ("sq_err", "sq_ref", "logits", "flipped", "rows")}
    rms = math.sqrt(total["sq_ref"] / total["logits"])
    return {"logit_rel_rms": math.sqrt(total["sq_err"] / total["sq_ref"]),
            "logit_max_over_rms": max(float(p["max_abs"])
                                      for p in passes) / rms,
            "ref_rms": rms,
            "finite": all(bool(p["finite"]) for p in passes),
            "routing_flipped_frac": (total["flipped"] / total["rows"]
                                     if total["rows"] else 0.0),
            "routing_rows": total["rows"]}


def gradient_errors_fn(trainer):
    """``f(first moment, reference gradient) -> numbers``: what the first
    optimizer step left in AdamW's first moment (zero before it, so
    ``(1 - b1) x`` the step's clipped gradient: the timed path's whole
    backward, accumulation and clipping) against the same made from the
    reference's gradient, leaf by leaf: ``|got - want| / |want|``, which
    reads 1 where the state was left unchanged. The first step's learning
    rate is 0 under a warm-up, so the parameters show nothing yet."""
    import jax
    import jax.numpy as jnp
    import optax

    training = trainer.training_config

    def compare(moment, gradient):
        clip = jnp.minimum(
            1.0, training.grad_clip / optax.global_norm(gradient))
        out = {}
        for (path, got), want in zip(
                jax.tree_util.tree_leaves_with_path(moment),
                jax.tree_util.tree_leaves(gradient)):
            want = (1.0 - training.beta1) * clip * want
            scale = jnp.sqrt(jnp.sum(want * want))
            out[jax.tree_util.keystr(path)] = jnp.where(
                scale > 0, jnp.sqrt(jnp.sum((got - want) ** 2)) / scale,
                jnp.sqrt(jnp.sum(got * got)))
        return out, optax.global_norm(gradient)

    return compare


def first_moment(opt_state):
    """AdamW's first moment in the trainer's optimizer state."""
    import jax

    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    return found[0].mu


LIMITS = ("logit_rel_rms", "logit_max_over_rms", "loss_rel",
          "routing_flipped_frac", "grad_leaf_rel")


def judge(numbers, tol):
    """Each number of the comparison that has a limit in the configuration's
    ``reference_tolerance``, beside it: ``{name: [reading, limit, ok]}``."""
    held = {name: [numbers[name], tol[name], numbers[name] <= tol[name]]
            for name in LIMITS if name in tol and name in numbers}
    held["finite"] = [numbers["finite"], True, bool(numbers["finite"])]
    return held


def check_logits(trainer, params, family, cfg, job, batch, spans, side,
                 reference_params=None):
    """The logits of ``batch`` through ``side`` against the reference
    routed alike, ``check.rows`` rows a pass: the numbers, and the side's
    choices for the whole batch."""
    import jax
    import jax.numpy as jnp

    rows = job["check"]["rows"]
    with spans.span("first_call", what="logit_check"):
        compare = jax.jit(compare_fn(side, family, cfg))
        passes, choices = [], []
        for lo in range(0, len(batch), rows):
            out, chosen = compare(
                params, _place_rows(trainer, batch[lo:lo + rows]),
                params if reference_params is None else reference_params)
            passes.append(jax.device_get(out))
            choices.append(chosen)
        return errors(passes), [jnp.concatenate(c) for c in zip(*choices)]


def check(trainer, state, family, cfg, job, batch, spans):
    """`correct`, outside the window: the numbers, and the state after the
    first optimizer step on ``batch``.

    The logits of ``batch`` through the program's timed path against the
    reference routed alike; the reference's loss and gradient on the same
    batch, routed alike, one row at a time; then the step itself: its loss
    against the reference's, and the gradient it left in the optimizer's
    state against the reference's."""
    import jax
    import numpy as np

    numbers, choices = check_logits(trainer, state.params, family, cfg, job,
                                    batch, spans, program_side(trainer))
    with spans.span("first_call", what="reference_gradient"):
        want_loss, gradient = jax.jit(jax.value_and_grad(functools.partial(
            family.loss, cfg=cfg,
            rows_per_pass=job["check"]["loss_rows_per_pass"])))(
                state.params, _place_rows(trainer, batch), choice=choices)
        # Off the chip while the step runs: the step needs the room.
        want_loss, gradient = float(want_loss), jax.device_get(gradient)
    with spans.span("first_call", what="train_step"):
        state, metrics = trainer.train_step(state, batch)
        got_loss = float(metrics["loss"])
    with spans.span("first_call", what="gradient_check"):
        leaves, want_norm = jax.jit(gradient_errors_fn(trainer))(
            first_moment(state.opt_state), gradient)
        leaves = {k: float(v) for k, v in leaves.items()}
    if hasattr(family, "held"):
        # Rows a token of the batch brought to the experts held here, by
        # expert layer in the order they run (an even load: the family's
        # `even_rows_per_token`; the row buffers hold twice that).
        first, count = family.held(cfg)
        numbers["held_rows_per_token"] = [
            float(((c >= first) & (c < first + count)).sum()) / batch.size
            for c in choices]
    numbers.update(
        first_step_loss=got_loss, reference_loss=want_loss,
        loss_rel=abs(got_loss - want_loss) / abs(want_loss),
        grad_leaf_rel=max(leaves.values()), grad_leaves=leaves,
        grad_norm=float(metrics["grad_norm"]),
        reference_grad_norm=float(want_norm))
    numbers["finite"] = numbers["finite"] and bool(np.isfinite(got_loss))
    return numbers, state


def run(cell: Dict[str, Any], *, devices, seed: int, seconds: float,
        trace: bool, process_start: float) -> harness.Result:
    import jax

    cfg, traffic, job = cell["config_file"], cell["traffic_file"], cell["job"]
    family = registry.family(cfg)
    spans = harness.Spans()
    cache = harness.CacheCounter()
    generator = registry.code("generators", traffic["generator"])
    batches = generator.generate(traffic, seed=seed,
                                 vocab_size=cfg["vocab_size"])
    runner_start = time.perf_counter()
    trainer = build_trainer(family, cfg, traffic, job, devices)
    built = time.perf_counter()
    first_batch = next(batches)
    state = initial_state(trainer, family, cfg, job, seed, first_batch,
                          spans)

    # --- correct: outside the window, every run --------------------------
    # The tolerances belong to the configuration and the compute type, not
    # to the cell: every cell of one configuration is held to the same.
    tol = cfg["reference_tolerance"][program.COMPUTE_TYPE]
    numbers, state = check(trainer, state, family, cfg, job, first_batch,
                           spans)
    held = judge(numbers, tol)
    correct = all(ok for _, _, ok in held.values())
    harness.note("correct_check", held=held, numbers=numbers,
                 ok=bool(correct))
    # A second warm step: the first ran on fresh optimizer state.
    state, metrics = trainer.train_step(state, next(batches))
    jax.block_until_ready(state.params)

    # --- the window --------------------------------------------------------
    # Untraced: steps for `seconds`. Traced: a few steps, then the traced
    # stretch of `trace_steps` steps, synced at both ends, which is then the
    # window the per-layer metrics are read over; the run ends with it.
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
    step_metrics = []
    window_start = time.perf_counter()
    setup_s = window_start - process_start
    while True:
        step = len(step_metrics)
        if tracer is not None and step == job["trace_after_steps"]:
            jax.block_until_ready(state.params)
            tracer.start()
            window_start = tracer.started_at
        with spans.span("next_batch"):
            batch = trainer.place_batch(next(batches))
        with spans.span("train_step", step=step):
            state, metrics = trainer.train_step(state, batch)
        step_metrics.append(metrics)
        if step >= MAX_IN_FLIGHT:
            step_metrics[step - MAX_IN_FLIGHT]["loss"].block_until_ready()
        if tracer is None:
            if time.perf_counter() - window_start >= seconds:
                break
        elif len(step_metrics) == (job["trace_after_steps"]
                                   + job["trace_steps"]):
            break
    jax.block_until_ready(state.params)
    window_end = time.perf_counter()
    if tracer is not None:
        tracer.stop()
    window_steps = (len(step_metrics) if tracer is None
                    else job["trace_steps"])

    window_s = window_end - window_start
    losses = [float(m["loss"]) for m in step_metrics]
    failed = sum(1 for x in losses if not math.isfinite(x))
    rate = window_steps * tokens_per_step / window_s
    seq_len = traffic["seq_len"]
    peak = cell["peaks"]["bf16_flops_per_s"]
    # The program's counters over the window's steps (after the window).
    in_window = step_metrics[-window_steps:]
    counted = {name: [float(m[name]) for m in in_window]
               for name in COUNTERS if name in in_window[0]}
    counters = {"steps": window_steps,
                "tokens_per_step": tokens_per_step,
                "sequences_per_step": tokens_per_step // seq_len,
                "seq_len": seq_len,
                "grad_accum": job["grad_accum"]}
    rows_per_token = None
    if "moe_rows_held" in counted:
        layers = family.moe_layers(cfg)
        counters["moe_rows_held"] = sum(counted["moe_rows_held"])
        counters["moe_max_load"] = max(counted["moe_max_load"])
        counters["moe_rows_routed"] = (window_steps * tokens_per_step * layers
                                       * cfg["num_experts_per_tok"])
        rows_per_token = counters["moe_rows_held"] / (
            window_steps * tokens_per_step * layers)
    # Tokens x state-space blocks, and those of them whose scan took the
    # Pallas kernels of `ops/ssd.py`: summed over the window.
    for name in ("ssm_tokens", "ssd_kernel_tokens"):
        if name in counted:
            counters[name] = sum(counted[name])
    harness.note(
        "train_window", steps=window_steps, window_s=window_s,
        tokens_per_step=tokens_per_step, train_tokens_per_s=rate,
        step_ms=1e3 * window_s / window_steps,
        mfu=family.mfu(cfg, seq_len, rate, len(devices), peak,
                       rows_per_token),
        flops_per_token=family.train_flops_per_token(
            cfg, seq_len, rows_per_token),
        expert_rows_per_token_and_layer=rows_per_token,
        moe_max_load=counters.get("moe_max_load"),
        # Not 0: the window measured the expert layer's overflow path.
        moe_overflow_passes=(sum(counted["moe_overflow_passes"])
                             if "moe_overflow_passes" in counted else None),
        mtp_loss=counted["mtp_loss"][-1] if "mtp_loss" in counted else None,
        ssm_tokens=counters.get("ssm_tokens"),
        ssd_kernel_tokens=counters.get("ssd_kernel_tokens"),
        # The window's most negative in-chunk cumulative `dt A`.
        ssd_min_log_decay=(min(counted["ssd_min_log_decay"])
                           if "ssd_min_log_decay" in counted else None),
        loss_first=losses[0], loss_last=losses[-1],
        setup_s=setup_s)
    # After the window, so that it costs neither set-up nor measured time:
    # what the compiler planned beside what the runtime saw.
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
        cell=cell, spans=spans, window=(window_start, window_end),
        counters=counters)
    if tracer is not None:
        harness.attach_trace(obs, tracer)
    return harness.Result(
        correct=bool(correct) and failed == 0, attempted=len(losses),
        failed=failed,
        end_to_end={"train_tokens_per_s": rate, "setup_s": setup_s},
        observations=obs, devices=devices,
        compared={**{name: pair[:2] for name, pair in held.items()},
                  "losses_not_finite": [failed, 0]})
