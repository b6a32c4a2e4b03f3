"""What a cell's `correct` has to refuse, run through the cell's own comparison.

    python3 -m perf.controls --workload <cell> --seeds 11,12 \
        [--controls bf16,taps_reversed,...|none]

For each seed (chip only at a cell's real size; one process, one line a
reading, the whole under ``perf/out/<cell>.controls.json``):

- ``program``: the runner's own check (``runners/train_family.check``), so
  that the healthy readings stand beside the controls' from the same seeds;
- ``bf16``: the family's plain reference computed in bfloat16
  (``perf/lower_precision.py``) put in the program's place, against the same
  float32 reference: its logits, its rows whose choice of expert flipped,
  its loss. The nearest precision below what the configuration states;
- a planted fault, each on the REFERENCE's side (the comparison is
  symmetric, and the program stays what is measured): the conv's taps in the
  wrong order, one held expert left out, softmax in place of sigmoid scores,
  the chosen scores not normalised;
- ``wgrad_expert_dropped``: a fault in the PROGRAM's backward (the grouped
  matmuls' weight gradient of one held expert left at zero), through the
  whole check: only the first step's gradient can see it.

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

from perf import harness, lower_precision, program, registry
from perf.runners import train_family


# --- planted faults, each a change to the reference's side --------------------

def taps_reversed(params):
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, p: p[..., ::-1] if "conv_weight" in str(path) else p,
        params)


def expert_dropped(params):
    """The second held expert of every layer gives nothing."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, p: p.at[:, 1].set(0) if "experts_down" in str(path)
        else p, params)


def softmax_gates(family):
    def routing(h, p, cfg, choice=None):
        import jax

        probs = jax.nn.softmax(h @ p["router"]["kernel"], axis=-1)
        _, own = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
        chosen = family.chose(own if choice is None else choice,
                              probs.shape[-1])
        picked = chosen * probs
        return picked / picked.sum(axis=-1, keepdims=True), own

    return routing


def unnormalised_gates(family):
    def routing(h, p, cfg, choice=None):
        import jax

        scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
        _, own = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
        return family.chose(own if choice is None else choice,
                            scores.shape[-1]) * scores, own

    return routing


@contextlib.contextmanager
def wgrad_expert_dropped():
    """The program's grouped matmuls leave the weight gradient of the second
    expert of their group at zero, for the block (what is traced in it)."""
    from tpu_trainer.ops import grouped_matmul

    healthy = grouped_matmul._tgmm_dispatch
    grouped_matmul._tgmm_dispatch = (
        lambda *args: healthy(*args).at[1].set(0))
    try:
        yield
    finally:
        grouped_matmul._tgmm_dispatch = healthy


PARAMS_FAULTS = {"taps_reversed": taps_reversed,
                 "expert_dropped": expert_dropped}
ROUTING_FAULTS = {"softmax_gates": softmax_gates,
                  "unnormalised_gates": unnormalised_gates}
BACKWARD_FAULTS = {"wgrad_expert_dropped": wgrad_expert_dropped}
CONTROLS = ("bf16", *PARAMS_FAULTS, *ROUTING_FAULTS, *BACKWARD_FAULTS)


@contextlib.contextmanager
def planted(family, fault):
    """The reference with ``fault`` in its router, for the block."""
    if fault not in ROUTING_FAULTS:
        yield
        return
    healthy = family.routing
    family.routing = ROUTING_FAULTS[fault](family)
    try:
        yield
    finally:
        family.routing = healthy


# --- the readings -------------------------------------------------------------

def bf16_reading(trainer, params, family, cfg, job, batch, spans):
    """The reference in bfloat16 in the program's place: the logit check's
    numbers, and its loss against the float32 reference's routed alike."""
    import jax

    side = lower_precision.in_bf16(
        lambda p, toks: family.forward_and_choices(p, toks, cfg))
    # A row a pass: this side's float32 scores come on top of the reference's.
    numbers, choices = train_family.check_logits(
        trainer, params, family, cfg,
        dict(job, check=dict(job["check"], rows=1)), batch, spans, side)
    row_loss = jax.jit(lower_precision.in_bf16(
        lambda p, row: family.rows_loss(p, row, cfg)))
    got = sum(float(row_loss(params, batch[i:i + 1]))
              for i in range(len(batch))) / len(batch)
    want = float(jax.jit(
        lambda p, toks, choice: family.loss(
            p, toks, cfg, job["check"]["loss_rows_per_pass"], choice))(
                params, batch, choices))
    numbers.update(first_step_loss=got, reference_loss=want,
                   loss_rel=abs(got - want) / abs(want))
    return numbers


def fault_reading(trainer, params, family, cfg, job, batch, spans, fault):
    """The program against the reference with ``fault`` planted: the logit
    check's numbers over the first pass of the batch."""
    rows = job["check"]["rows"]
    reference_params = PARAMS_FAULTS.get(fault, lambda p: p)(params)
    with planted(family, fault):
        return train_family.check_logits(
            trainer, params, family, cfg, job, batch[:rows], spans,
            train_family.program_side(trainer), reference_params)[0]


def readings(cell, devices, seeds, controls=CONTROLS):
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
    faulty = {c: build() for c in controls if c in BACKWARD_FAULTS}
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
        state = trainer.init_state(seed % (2 ** 31 - 1))
        for control in controls:
            if control in BACKWARD_FAULTS:
                continue
            reading = bf16_reading if control == "bf16" else (
                lambda *a: fault_reading(*a, control))
            yield line(seed, control, reading(
                trainer, state.params, family, cfg, job, batch, spans))
        numbers, state = train_family.check(
            trainer, state, family, cfg, job, batch, spans)
        yield line(seed, "program", numbers)
        del state
        for control, other in faulty.items():   # one state on the chip
            with BACKWARD_FAULTS[control]():
                numbers, state = train_family.check(
                    other, other.init_state(seed % (2 ** 31 - 1)), family,
                    cfg, job, batch, spans)
            del state
            yield line(seed, control, numbers)


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
        harness.fail(f"perf.controls: {e}", 3)
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
