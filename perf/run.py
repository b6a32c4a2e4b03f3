"""Run one cell of the benchmark once, as a new process.

    python3 -m perf.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's chips or exits non-zero (never the CPU), builds the
configuration, makes the weights on the device from the seed, warms the
cell's shapes, measures for ``--seconds`` and prints as its last line one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, in a traced run, ``breakdown``. With ``--trace 0`` the metrics are the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics, read
from a profiler trace of part of the window. Earlier lines carry plain
facts; files go to ``perf/out/``.
"""

import time

PROCESS_START = time.perf_counter()  # setup_s counts from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--set", action="append", default=[], metavar="KEY=JSON",
        help="override a key of the cell's job or traffic parameters for "
             "this run only (the builder's trials; the driver never passes "
             "it), e.g. --set job.micro_batch=8 --set job.grad_accum=2")
    args = parser.parse_args(argv)

    # The checkout's root on the path, so `python3 perf/run.py` works as
    # `python3 -m perf.run` does.
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from perf import harness, registry

    # libtpu writes its logs to the fixed /tmp/tpu_logs unless told
    # otherwise; a run writes nothing outside its checkout.
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(harness.OUT_DIR, "tpu_logs"))

    try:
        cell = registry.workload(args.workload)
        _apply_overrides(cell, args.set)
        runner = registry.code("runners", cell["runner"])
    except registry.RegistryError as e:
        harness.fail(f"perf.run: {e}", 2)
    try:
        import tpu_trainer  # noqa: F401  the system under test
    except ImportError as e:
        harness.fail(f"perf.run: the program is not in this checkout: {e}", 2)

    harness.enable_compile_cache()
    try:
        devices = harness.require_chips(cell["chips"])
        cell["peaks"] = registry.peaks(devices[0].device_kind)
    except (harness.NoChip, registry.RegistryError) as e:
        harness.fail(f"perf.run: {e}", 3)

    result = runner.run(cell, devices=devices, seed=args.seed,
                        seconds=args.seconds, trace=bool(args.trace),
                        process_start=PROCESS_START)
    print(harness.result_line(cell, result, bool(args.trace)), flush=True)
    return 0


def _apply_overrides(cell, overrides) -> None:
    import json

    for item in overrides:
        key, _, raw = item.partition("=")
        section, _, field = key.partition(".")
        target = {"job": cell.get("job"),
                  "traffic": cell["traffic_file"]}.get(section)
        if target is None or not field:
            raise SystemExit(f"--set {item!r}: use job.<key> or traffic.<key>")
        target[field] = json.loads(raw)


if __name__ == "__main__":
    sys.exit(main())
