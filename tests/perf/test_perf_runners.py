"""The training runner end to end on the CPU at a tiny width: the control
flow, the correctness check and the span readers. No time measured here is a result."""

import time

import jax
import pytest

from perf import harness, registry
from tests.perf.test_perf_reference import TINY


def _cell(name):
    cell = registry.workload(name)
    cell["config_file"] = dict(
        TINY, max_position_embeddings=512,
        reference_tolerance=cell["config_file"]["reference_tolerance"])
    cell["peaks"] = registry.peaks("TPU v5 lite")
    return cell


def _read(cell, result):
    return harness.read_per_layer(cell, result.observations)


def test_train_runner():
    cell = _cell("train-360m-1chip")
    cell["traffic_file"] = dict(cell["traffic_file"], seq_len=64,
                                tokens_per_step=256)
    cell["job"].update(micro_batch=2, grad_accum=2)
    cell["job"]["check"].update(seq_len=64)
    runner = registry.code("runners", "train")
    result = runner.run(cell, devices=jax.devices()[:1], seed=2 ** 31 + 7,
                        seconds=1.0, trace=False,
                        process_start=time.perf_counter())
    assert result.correct and result.failed == 0 and result.attempted >= 2
    assert result.end_to_end["train_tokens_per_s"] > 0
    assert result.end_to_end["setup_s"] > 0
    got = _read(cell, result)
    # Span metrics are read; trace metrics find nothing and are left out.
    assert {"compile_s", "step_ms.train", "data_wait_frac.train"} == set(got)
    steps = result.attempted
    lo, hi = result.observations.window
    assert got["step_ms.train"]["value"] == pytest.approx(
        1e3 * (hi - lo) / steps)
    assert 0 <= got["data_wait_frac.train"]["value"] < 100


def test_train_runner_sees_a_wrong_tolerance():
    """`correct` is decided by the comparison, not assumed: with a
    tolerance no bf16 forward can meet, it turns false."""
    cell = _cell("train-360m-1chip")
    cell["traffic_file"] = dict(cell["traffic_file"], seq_len=64,
                                tokens_per_step=128)
    cell["job"].update(micro_batch=2, grad_accum=1)
    cell["job"]["check"].update(seq_len=64)
    cell["config_file"]["reference_tolerance"] = {
        "bf16": dict(cell["config_file"]["reference_tolerance"]["bf16"],
                     logit_rel_rms=1e-9)}
    result = registry.code("runners", "train").run(
        cell, devices=jax.devices()[:1], seed=3, seconds=0.2, trace=False,
        process_start=time.perf_counter())
    assert not result.correct
