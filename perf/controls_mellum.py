"""What the `correct` of a ``mellum`` cell has to refuse: ``perf/controls.py``
for the family of ``perf/families/mellum.py``, whose mechanisms (a window,
YaRN on the full layers) that file's faults do not reach.

    python3 -m perf.controls_mellum --workload <cell> --seeds 11,12 \\
        [--controls bf16_reference,window_off_by_one,...|none]

For each seed (chip only at a cell's real size; one process, one line a
reading, the whole under ``perf/out/<cell>.controls.json``):

- ``program``: the runner's own check, whole (logits, flips, loss, gradient);
- ``bf16_reference``: the family's reference computed in bfloat16
  (``perf/lower_precision.py``) in the program's place;
- faults planted in the PROGRAM, each a setting of its ``GPTConfig`` that
  the compiled forward takes, so that on the chip they run where the cell
  runs, through the banded kernels of ``ops/flash.py``: the window one key
  wider (1,025 keys), window layers attending causally over the whole
  prefix, the full layer at plain RoPE in place of YaRN, and YaRN's
  ``attention_factor`` left out. Each is read through the logit check of the
  cell's comparison: the program's forward with the fault against the
  reference routed by the same forward's choice of experts.

Every reading is judged by ``train_family.judge`` against the configuration's
``reference_tolerance``; a control has to read ``correct: false``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import types

from perf import controls, harness, program, registry
from perf.runners import train_family

# A fault in the program: the fields of its GPTConfig that change, given the
# configuration file and the sequence length.
PROGRAM_FAULTS = {
    "window_off_by_one": lambda cfg, seq: {
        "sliding_window": cfg["sliding_window"] + 1},
    "window_layers_causal_in_full": lambda cfg, seq: {
        "sliding_window": seq},
    "full_layer_default_rope": lambda cfg, seq: {"yarn_factor": 0.0},
    "attention_factor_left_out": lambda cfg, seq: {
        "yarn_attention_factor": 1.0},
}
CONTROLS = ("bf16_reference", *PROGRAM_FAULTS)


def faulty_side(trainer, fault, cfg, seq):
    """``program_side`` of a program that differs from ``trainer``'s by
    ``fault`` alone: the same parameters, mesh and layer loop."""
    model_config = dataclasses.replace(
        trainer.model_config, **PROGRAM_FAULTS[fault](cfg, seq))
    return train_family.program_side(types.SimpleNamespace(
        model_config=model_config, mesh=trainer.mesh))


def reading(control, trainer, params, family, cfg, job, batch, spans, seq):
    """The numbers of a control that the logits show: the logit check's over
    the first pass of the batch (and, for the bf16 reference, its loss)."""
    if control == "bf16_reference":
        return controls.bf16_reading(trainer, params, family, cfg, job, batch,
                                     spans)
    return train_family.check_logits(
        trainer, params, family, cfg, job, batch[:job["check"]["rows"]],
        spans, faulty_side(trainer, control, cfg, seq))[0]


def readings(cell, devices, seeds, controls_wanted=CONTROLS):
    """One line a reading: ``seed``, ``what``, ``correct``, every number
    beside its limit (``held``) and the numbers."""
    cfg, traffic, job = cell["config_file"], cell["traffic_file"], cell["job"]
    family = registry.family(cfg)
    tol = cfg["reference_tolerance"][program.COMPUTE_TYPE]
    generator = registry.code("generators", traffic["generator"])
    trainer = train_family.build_trainer(family, cfg, traffic, job, devices)
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
        for control in controls_wanted:
            yield line(seed, control, reading(
                control, trainer, state.params, family, cfg, job, batch,
                spans, traffic["seq_len"]))
        numbers, state = train_family.check(trainer, state, family, cfg, job,
                                            batch, spans)
        del state
        yield line(seed, "program", numbers)


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
        harness.fail(f"perf.controls_mellum: {e}", 3)
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
