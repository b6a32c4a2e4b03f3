"""`python -m perf.run` refuses to measure without the chip."""

import json
import os
import subprocess
import sys

from perf import registry


def _run(*args, cwd=registry.CHECKOUT):
    return subprocess.run(
        [sys.executable, "-m", "perf.run", *args], cwd=cwd, text=True,
        capture_output=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7"))


def test_no_chip_no_result():
    done = _run("--workload", "train-360m-1chip", "--seed", "2147483659",
                "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert "no TPU" in done.stderr
    # No result line: nothing on stdout parses as the contract's object.
    for line in done.stdout.splitlines():
        try:
            assert "metrics" not in json.loads(line)
        except ValueError:
            pass


def test_unknown_workload_is_an_error():
    done = _run("--workload", "no-such-cell", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert done.returncode != 0 and "no workloads file" in done.stderr


def test_without_the_program_it_does_not_run(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` there is no system under test: non-zero, no result."""
    import shutil

    shutil.copy(os.path.join(registry.CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(registry.ROOT, tmp_path / "perf",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    done = subprocess.run(
        [sys.executable, "-m", "perf.run", "--workload", "train-360m-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, text=True, capture_output=True, timeout=300,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert done.returncode != 0
    assert "not in this checkout" in done.stderr
    assert "metrics" not in done.stdout
