"""The training runner end to end on the CPU at a tiny width: the control
flow, the correctness check and the span readers. No time measured here is a result."""

import json
import time

import jax
import pytest

from perf import harness, registry
from tests.perf.test_perf_reference import TINY


def _cell(name):
    cell = registry.workload(name)
    cell["config_file"] = dict(
        TINY, family="llama", max_position_embeddings=512,
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
    tol = cell["config_file"]["reference_tolerance"]["bf16"]
    assert {k: limit for k, (_, limit) in result.compared.items()} == {
        "logit_rel_rms": tol["logit_rel_rms"],
        "logit_max_over_rms": tol["logit_max_over_rms"],
        "loss_rel": tol["loss_rel"], "logits_finite": 1.0,
        "losses_not_finite": 0}
    got = _read(cell, result)
    # Span metrics are read; trace metrics find nothing and are left out.
    # `mfu.train` is the window's rate through the family's count, as the
    # `train_window` note has it.
    assert {"compile_s", "step_ms.train", "data_wait_frac.train",
            "mfu.train"} == set(got)
    from perf import work
    assert got["mfu.train"]["value"] == pytest.approx(100.0 * work.mfu(
        cell["config_file"], 64, result.end_to_end["train_tokens_per_s"], 1,
        cell["peaks"]["bf16_flops_per_s"]), rel=1e-9)
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


def test_the_result_line_ends_with_what_was_compared(monkeypatch, capsys):
    """Each number that decided `correct` beside its limit: the result
    line's last key, and the last line on standard error."""
    cell = registry.workload("train-360m-1chip")
    monkeypatch.setattr(harness, "device_line", lambda devices: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
        "memory_peak_bytes": 1})
    compared = {"loss_rel": [4e-5, 3e-5], "losses_not_finite": [0, 0]}
    result = harness.Result(
        correct=False, attempted=3, failed=0,
        end_to_end={"train_tokens_per_s": 1.0, "setup_s": 2.0},
        observations=None, devices=None, compared=compared)
    harness.emit(cell, result, False)
    printed = capsys.readouterr()
    line = json.loads(printed.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared" and line["compared"] == compared
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
    last = printed.err.strip().splitlines()[-1]
    assert last.startswith("compared") and json.dumps(compared) in last


@pytest.mark.parametrize("name", ["train-360m-1chip",
                                  "train-lfm2-24b-ep8-1chip"])
def test_step_jaxpr_traces_a_cell_of_each_runner(name):
    """`python3 -m perf.step_jaxpr`: the cell's step at two layers, built
    as its runner builds it, with the kernels' TPU branch in the text."""
    from perf import step_jaxpr

    text = step_jaxpr.step_jaxpr(name)
    assert text.count("pallas_call") > 0 and "frozenset" in text
    assert text == step_jaxpr.step_jaxpr(name)
