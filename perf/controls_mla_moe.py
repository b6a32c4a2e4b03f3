"""What the `correct` of an ``mla_moe`` cell has to refuse: ``perf/controls.py``
for the family of ``perf/families/mla_moe.py``, whose mechanisms that file's
faults do not reach.

    python3 -m perf.controls_mla_moe --workload <cell> --seeds 11,12 \
        [--controls bf16,rope_half_split,...|none]

For each seed (chip only at a cell's real size; one process, one line a
reading, the whole under ``perf/out/<cell>.controls.json``):

- ``program`` and ``bf16``: as in ``perf/controls.py`` (the runner's own
  check; the family's reference computed in bfloat16 in the program's place);
- a fault planted on the REFERENCE's side that the logits show: the rotation
  pairing lanes ``(i, i + 32)`` where the config says ``(2i, 2i + 1)``, the
  key-value latent's norm left out, the shared expert left out, the routed
  scale 1 for the published 2.5, softmax in place of sigmoid scores, one held
  expert of every layer left out;
- ``mtp_labels_shifted``: the reference's prediction module scored against
  token ``i + 3``. No logit shows the module, so it goes through the whole
  check: the loss and the first step's gradient;
- ``mtp_backward_dropped``: a fault in the PROGRAM's backward (no gradient
  flows back from the module's loss: its leaves stay at zero and the trunk
  loses the term), through the whole check.

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

def rope_half_split(family):
    healthy = family._rope
    return "_rope", lambda x, cfg: healthy(x, dict(cfg, rope_interleave=False))


def kv_norm_dropped(family):
    return "_kv_latent_norm", lambda c_kv, p, cfg: c_kv


def shared_expert_dropped(family):
    import jax.numpy as jnp

    return "shared_expert", lambda h, p: jnp.zeros_like(h)


def routed_scale_one(family):
    healthy = family.routing
    return "routing", lambda h, p, cfg, choice=None: healthy(
        h, p, dict(cfg, routed_scaling_factor=1.0), choice)


def softmax_gates(family):
    def routing(h, p, cfg, choice=None):
        import jax

        probs = jax.nn.softmax(h @ p["router"]["kernel"], axis=-1)
        _, own = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
        picked = family.chose(own if choice is None else choice,
                              probs.shape[-1]) * probs
        return (cfg["routed_scaling_factor"] * picked
                / picked.sum(axis=-1, keepdims=True)), own

    return "routing", routing


def mtp_labels_shifted(family):
    return "MTP_SHIFT", family.MTP_SHIFT + 1


def expert_dropped(params):
    """The second held expert of every expert layer gives nothing (a stack's
    ``experts_down`` is ``[layers, experts, I, H]``, the module's block's
    ``[experts, I, H]``)."""
    import jax

    def drop(path, p):
        if "experts_down" not in str(path):
            return p
        return p.at[:, 1].set(0) if p.ndim == 4 else p.at[1].set(0)

    return jax.tree_util.tree_map_with_path(drop, params)


@contextlib.contextmanager
def mtp_backward_dropped():
    """The program's prediction module gives its loss and no gradient, for
    the block (what is traced in it)."""
    import jax

    from tpu_trainer.models import gpt

    healthy = gpt.fused_shifted_cross_entropy

    def faulty(*args, shift=1, **kwargs):
        loss = healthy(*args, shift=shift, **kwargs)
        return jax.lax.stop_gradient(loss) if shift != 1 else loss

    gpt.fused_shifted_cross_entropy = faulty
    try:
        yield
    finally:
        gpt.fused_shifted_cross_entropy = healthy


REFERENCE_FAULTS = {
    "rope_half_split": rope_half_split, "kv_norm_dropped": kv_norm_dropped,
    "shared_expert_dropped": shared_expert_dropped,
    "routed_scale_one": routed_scale_one, "softmax_gates": softmax_gates}
PARAMS_FAULTS = {"expert_dropped": expert_dropped}
# Through the whole check (the loss and the gradient): on the reference's
# side, and in the program's backward.
WHOLE_REFERENCE_FAULTS = {"mtp_labels_shifted": mtp_labels_shifted}
BACKWARD_FAULTS = {"mtp_backward_dropped": mtp_backward_dropped}
CONTROLS = ("bf16", *REFERENCE_FAULTS, *PARAMS_FAULTS,
            *WHOLE_REFERENCE_FAULTS, *BACKWARD_FAULTS)


@contextlib.contextmanager
def planted(family, fault):
    """The reference with ``fault`` in it, for the block."""
    make = {**REFERENCE_FAULTS, **WHOLE_REFERENCE_FAULTS}.get(fault)
    if make is None:
        yield
        return
    name, replacement = make(family)
    healthy = getattr(family, name)
    setattr(family, name, replacement)
    try:
        yield
    finally:
        setattr(family, name, healthy)


# --- the readings -------------------------------------------------------------

def fault_reading(trainer, params, family, cfg, job, batch, spans, fault):
    """The program against the reference with ``fault`` planted: the logit
    check's numbers over the first pass of the batch."""
    rows = job["check"]["rows"]
    reference_params = PARAMS_FAULTS.get(fault, lambda p: p)(params)
    with planted(family, fault):
        return train_family.check_logits(
            trainer, params, family, cfg, job, batch[:rows], spans,
            train_family.program_side(trainer), reference_params)[0]


def readings(cell, devices, seeds, controls_wanted=CONTROLS):
    """One line a reading: ``seed``, ``what``, ``correct``, every number
    beside its limit (``held``) and the numbers."""
    cfg, traffic, job = cell["config_file"], cell["traffic_file"], cell["job"]
    family = registry.code("families", cfg["family"])
    tol = cfg["reference_tolerance"][program.COMPUTE_TYPE]
    generator = registry.code("generators", traffic["generator"])
    build = functools.partial(train_family.build_trainer, family, cfg,
                              traffic, job, devices)
    trainer = build()
    # A trainer of its own for each fault in the backward: its step is
    # traced, once, with the fault planted.
    faulty = {c: build() for c in controls_wanted if c in BACKWARD_FAULTS}
    spans = harness.Spans()

    def line(seed, what, numbers):
        held = train_family.judge(numbers, tol)
        return {"seed": seed, "what": what,
                "correct": all(ok for _, _, ok in held.values()),
                "held": held, "numbers": numbers,
                "device": devices[0].device_kind}

    def whole(which, seed, batch):
        """The whole check from a fresh state; one state on the chip."""
        numbers, state = train_family.check(
            which, which.init_state(seed % (2 ** 31 - 1)), family, cfg, job,
            batch, spans)
        del state
        return numbers

    for seed in seeds:
        batch = next(generator.generate(traffic, seed=seed,
                                        vocab_size=cfg["vocab_size"]))
        state = trainer.init_state(seed % (2 ** 31 - 1))
        for control in controls_wanted:
            if control == "bf16":
                yield line(seed, control, controls.bf16_reading(
                    trainer, state.params, family, cfg, job, batch, spans))
            elif control in REFERENCE_FAULTS or control in PARAMS_FAULTS:
                yield line(seed, control, fault_reading(
                    trainer, state.params, family, cfg, job, batch, spans,
                    control))
        del state
        yield line(seed, "program", whole(trainer, seed, batch))
        for control in controls_wanted:
            if control in WHOLE_REFERENCE_FAULTS:
                with planted(family, control):
                    yield line(seed, control, whole(trainer, seed, batch))
        for control, other in faulty.items():
            with BACKWARD_FAULTS[control]():
                yield line(seed, control, whole(other, seed, batch))


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
        harness.fail(f"perf.controls_mla_moe: {e}", 3)
    lines = []
    for reading in readings(cell, devices,
                            [int(s) for s in args.seeds.split(",")],
                            wanted):
        lines.append(reading)
        print(json.dumps(reading), flush=True)
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    with open(os.path.join(harness.OUT_DIR,
                           f"{args.workload}.controls.json"), "w") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
