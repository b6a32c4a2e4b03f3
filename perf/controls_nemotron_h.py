"""What the `correct` of a ``nemotron_h`` cell has to refuse:
``perf/controls.py`` for the family of ``perf/families/nemotron_h.py``, whose
mechanisms that file's faults do not reach.

    python3 -m perf.controls_nemotron_h --workload <cell> --seeds 11,12 \\
        [--controls bf16_reference,decay_dropped_at_a_chunk_boundary,...|none]

For each seed (chip only at a cell's real size; one process, one line a
reading, the whole under ``perf/out/<cell>.controls.json``):

- ``program``: the runner's own check, whole (logits, flips, loss, gradient);
- ``bf16_reference``: the family's reference computed in bfloat16
  (``perf/lower_precision.py``) in the program's place, its scan in the
  quadratic form at every length (the walk refuses a loop primitive);
- ``decay_dropped_at_a_chunk_boundary``: a fault in the PROGRAM's scan
  (``tpu_trainer/ops/ssd.py``): the states carried past the second chunk
  are not decayed over it, as a chunked scan that loses one boundary's
  factor would compute. One factor of one boundary moves the logits by less
  than bf16 rounding does; the gradients of ``A_log`` and ``dt_bias``, which
  flow through exactly those factors, show it, so it goes through the whole
  check (a trainer of its own, its step traced with the fault planted);
- faults planted on the REFERENCE's side that the logits show: the skip term
  ``D x`` left out, the norm before the gate, the shared expert left out,
  the relu not squared.

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

def skip_term_dropped(family):
    healthy = family.mamba

    def mamba(u, p, cfg):
        import jax.numpy as jnp

        return healthy(u, dict(p, D=jnp.zeros_like(p["D"])), cfg)

    return "mamba", mamba


def norm_before_gate(family):
    """``RMSNorm_groups(y) * silu(z)`` for ``RMSNorm_groups(y * silu(z))``."""
    def gated_norm(y, z, weight, groups, eps):
        import jax
        import jax.numpy as jnp

        shape = y.shape
        y = y.reshape(*shape[:-1], groups, shape[-1] // groups)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        return y.reshape(shape) * weight * jax.nn.silu(z)

    return "gated_norm", gated_norm


def shared_expert_dropped(family):
    import jax.numpy as jnp

    return "shared_expert", lambda h, p: jnp.zeros_like(h)


def relu_not_squared(family):
    import jax

    return "_relu2_ffn", lambda h, up, down: jax.nn.relu(h @ up) @ down


REFERENCE_FAULTS = {
    "skip_term_dropped": skip_term_dropped,
    "norm_before_gate": norm_before_gate,
    "shared_expert_dropped": shared_expert_dropped,
    "relu_not_squared": relu_not_squared}


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


# --- a fault in the program's scan -----------------------------------------------

@contextlib.contextmanager
def decay_dropped_at_a_chunk_boundary():
    """The program's chunked scan with the decay over its second chunk left
    out of what is carried across it (for what is traced in the block)."""
    from tpu_trainer.ops import ssd

    healthy = ssd._boundary_states

    def faulty(total, chunk_states):
        return healthy(total.at[..., 1].set(0.0), chunk_states)

    ssd._boundary_states = faulty
    try:
        yield
    finally:
        ssd._boundary_states = healthy


PROGRAM_FAULTS = {
    "decay_dropped_at_a_chunk_boundary": decay_dropped_at_a_chunk_boundary}
CONTROLS = ("bf16_reference", *PROGRAM_FAULTS, *REFERENCE_FAULTS)


# --- the readings -------------------------------------------------------------

@contextlib.contextmanager
def _quadratic_scan(family):
    """The reference's scan in its quadratic form at every length, its
    blocks a Python loop: nothing in the forward is a loop primitive."""
    healthy = family.RECURRENCE_MAX_SEQ, family.UNROLLED
    family.RECURRENCE_MAX_SEQ, family.UNROLLED = 0, True
    try:
        yield
    finally:
        family.RECURRENCE_MAX_SEQ, family.UNROLLED = healthy


def reading(control, trainer, params, family, cfg, job, batch, spans):
    """The numbers of a control that the logits show: the logit check's over
    the first pass of the batch (and, for the bf16 reference, its loss)."""
    if control == "bf16_reference":
        with _quadratic_scan(family):
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
        harness.fail(f"perf.controls_nemotron_h: {e}", 3)
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
