"""What the `correct` of a ``qwen3_next`` cell has to refuse:
``perf/controls.py`` for the family of ``perf/families/qwen3_next.py``, whose
mechanisms that file's faults do not reach.

    python3 -m perf.controls_qwen3_next --workload <cell> --seeds 11,12 \\
        [--controls bf16_reference,decay_lost_at_a_chunk_boundary,...|none]

For each seed (chip only at a cell's real size; one process, one line a
reading, the whole under ``perf/out/<cell>.controls.json``):

- ``program``: the runner's own check, whole (logits, flips, loss, gradient);
- ``bf16_reference``: the family's reference computed in bfloat16
  (``perf/lower_precision.py``) in the program's place, its delta rule as
  one triangular system (``delta_rule_closed``: the walk refuses a loop
  primitive);
- ``decay_lost_at_a_chunk_boundary``: a fault in the PROGRAM's delta rule
  (``tpu_trainer/ops/gated_delta.py``): the state carried past the second
  chunk is not decayed over it. It goes through the whole check (a trainer
  of its own, its step traced with the fault planted);
- faults planted on the REFERENCE's side: the delta correction dropped
  (``S <- e^g S + beta k v^T``), attention's output gate left out, RoPE over
  the whole head, the gated norm's gate applied before its norm, the shared
  expert's gate left out.

Every reading is judged by ``train_family.judge`` against the configuration's
``reference_tolerance``; a control has to read ``correct: false``.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys

from perf import controls, harness, program, registry
from perf.runners import train_family


# --- faults on the reference's side: a function of the family replaced --------

def delta_correction_dropped(family):
    """``S <- e^g S + beta k v^T``: what the state predicts for ``k`` is not
    taken off ``v``."""
    def delta_rule(q, k, v, g, beta):
        """Unrolled: ``o_i = sum_{j <= i} e^(gamma_i - gamma_j) (q_i . k_j)
        beta_j v_j``, ``CLOSED_HEADS`` value heads at a time."""
        import jax.numpy as jnp

        seq, hk, hv = q.shape[1], q.shape[2], v.shape[2]
        q, k = family._repeat_keys(q, k, hv // hk)
        cum = jnp.moveaxis(jnp.cumsum(g, axis=1), 1, -1)   # [batch, H_v, seq]
        lower = jnp.tril(jnp.ones((seq, seq), bool))
        out = []
        for lo in range(0, hv, family.CLOSED_HEADS):
            heads = slice(lo, min(lo + family.CLOSED_HEADS, hv))
            c = cum[:, heads]
            decay = jnp.exp(jnp.where(
                lower, c[..., :, None] - c[..., None, :], -jnp.inf))
            scores = jnp.einsum("bihd,bjhd->bhij", q[:, :, heads],
                                k[:, :, heads]) * decay
            values = jnp.moveaxis(
                beta[:, :, heads, None] * v[:, :, heads], 1, 2)
            out.append(jnp.moveaxis(scores @ values, 1, 2))
        return jnp.concatenate(out, axis=2)

    return "delta_rule", delta_rule


def output_gate_dropped(family):
    return "output_gate", lambda out, gate: out


def rope_over_the_whole_head(family):
    healthy = family.rope
    return "rope", lambda x, theta, rotated: healthy(x, theta, x.shape[-1])


def gate_before_norm(family):
    """``RMSNorm(o * silu(z)) w`` for ``RMSNorm(o) w * silu(z)``."""
    def gated_norm(o, z, weight, eps):
        import jax

        return family._rms_norm(o * jax.nn.silu(z), weight, eps)

    return "gated_norm", gated_norm


def shared_expert_gate_dropped(family):
    def shared_expert(h, p):
        s = lambda name: p["shared_expert"][name]["kernel"].astype(  # noqa
            family.F32)
        return family._swiglu(h, s("gate_proj"), s("up_proj"), s("down_proj"))

    return "shared_expert", shared_expert


REFERENCE_FAULTS = {
    "delta_correction_dropped": delta_correction_dropped,
    "output_gate_dropped": output_gate_dropped,
    "rope_over_the_whole_head": rope_over_the_whole_head,
    "gate_before_norm": gate_before_norm,
    "shared_expert_gate_dropped": shared_expert_gate_dropped}


@contextlib.contextmanager
def planted(family, fault):
    """The reference with ``fault`` in it, for the block."""
    name, replacement = REFERENCE_FAULTS[fault](family)
    healthy = getattr(family, name)
    setattr(family, name, replacement)
    try:
        yield
    finally:
        setattr(family, name, healthy)


# --- a fault in the program's delta rule ----------------------------------------

@contextlib.contextmanager
def decay_lost_at_a_chunk_boundary():
    """The program's chunked delta rule with the decay over its second chunk
    left out of the state carried across it (for what is traced in the
    block)."""
    from tpu_trainer.ops import gated_delta

    healthy = gated_delta._across_chunks

    def faulty(u, w, to_end, whole, dtype):
        return healthy(u, w, to_end, whole.at[:, 1].set(1.0), dtype)

    gated_delta._across_chunks = faulty
    try:
        yield
    finally:
        gated_delta._across_chunks = healthy


PROGRAM_FAULTS = {
    "decay_lost_at_a_chunk_boundary": decay_lost_at_a_chunk_boundary}
CONTROLS = ("bf16_reference", *PROGRAM_FAULTS, *REFERENCE_FAULTS)


# --- the readings -------------------------------------------------------------

@contextlib.contextmanager
def _closed_rule(family):
    """The reference's delta rule as one triangular system: nothing in the
    forward is a loop primitive."""
    healthy = family.delta_rule
    family.delta_rule = family.delta_rule_closed
    try:
        yield
    finally:
        family.delta_rule = healthy


def reading(control, trainer, params, family, cfg, job, batch, spans):
    """The numbers of a control that the logits show: the logit check's over
    the first pass of the batch (and, for the bf16 reference, its loss)."""
    if control == "bf16_reference":
        with _closed_rule(family):
            return controls.bf16_reading(trainer, params, family, cfg, job,
                                         batch, spans)
    with planted(family, control):
        return train_family.check_logits(
            trainer, params, family, cfg, job, batch[:job["check"]["rows"]],
            spans, train_family.program_side(trainer))[0]


def readings(cell, devices, seeds, controls_wanted=CONTROLS):
    """One line a reading: ``seed``, ``what``, ``correct``, every number
    beside its limit (``held``) and the numbers."""
    cfg, traffic, job = cell["config_file"], cell["traffic_file"], cell["job"]
    family = registry.family(cfg)
    tol = cfg["reference_tolerance"][program.COMPUTE_TYPE]
    generator = registry.code("generators", traffic["generator"])
    build = functools.partial(train_family.build_trainer, family, cfg,
                              traffic, job, devices)
    trainer = build()
    # A trainer of its own for each fault in the program: its step is
    # traced, once, with the fault planted.
    faulty = {c: build() for c in controls_wanted if c in PROGRAM_FAULTS}
    spans = harness.Spans()

    def line(seed, what, numbers):
        held = train_family.judge(numbers, tol)
        return {"seed": seed, "what": what,
                "correct": all(ok for _, _, ok in held.values()),
                "held": held, "numbers": numbers,
                "device": devices[0].device_kind}

    for seed in seeds:
        batch = next(generator.generate(traffic, seed=seed,
                                        vocab_size=cfg["vocab_size"]))
        state = train_family.initial_state(trainer, family, cfg, job, seed,
                                           batch, spans)

        def whole(which):
            """The whole check from a fresh state; one state on the chip."""
            return train_family.check(
                which, train_family.initial_state(
                    which, family, cfg, job, seed, batch, spans),
                family, cfg, job, batch, spans)[0]

        for control in controls_wanted:
            if control not in PROGRAM_FAULTS:
                yield line(seed, control, reading(
                    control, trainer, state.params, family, cfg, job, batch,
                    spans))
        del state
        for control, other in faulty.items():
            with PROGRAM_FAULTS[control]():
                yield line(seed, control, whole(other))
        yield line(seed, "program", whole(trainer))


def run_controls(cell, *, devices, seed, names=CONTROLS):
    """``{"program": line, "controls": {name: line}}`` for one seed."""
    lines = list(readings(cell, devices, [seed], names))
    return {"program": lines[-1],
            "controls": {line["what"]: line for line in lines[:-1]}}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--controls", default=",".join(CONTROLS))
    args = parser.parse_args(argv)
    wanted = [c for c in args.controls.split(",") if c and c != "none"]
    unknown = set(wanted) - set(CONTROLS)
    if unknown:
        parser.error(f"unknown controls {sorted(unknown)}; have {CONTROLS}")
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(harness.OUT_DIR, "tpu_logs"))
    cell = registry.workload(args.workload)
    harness.enable_compile_cache()
    try:
        devices = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        harness.fail(f"perf.controls_qwen3_next: {e}", 3)
    lines = []
    for line in readings(cell, devices,
                         [int(s) for s in args.seeds.split(",")], wanted):
        lines.append(line)
        print(json.dumps(line), flush=True)
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    with open(os.path.join(harness.OUT_DIR,
                           f"{args.workload}.controls.json"), "w") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
