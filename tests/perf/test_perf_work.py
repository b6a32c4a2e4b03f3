"""perf/work.py and perf/peaks.json against hand numbers."""

import json
import os

import pytest

from perf import registry, work


def _cfg(name):
    return registry.config(name)


@pytest.mark.parametrize("name,params,matmul_params", [
    # embed 49152*960; layer = 2*960^2 + 2*960*320 + 3*960*2560 + 2*960
    ("smollm2-360m", 361_821_120, 361_758_720),
    # embed 49152*2048; layer = 4*2048^2 + 3*2048*8192 + 2*2048
    ("smollm2-1.7b", 1_711_376_384, 1_711_276_032),
])
def test_parameter_counts(name, params, matmul_params):
    cfg = _cfg(name)
    assert work.param_count(cfg) == params
    assert work.matmul_param_count(cfg) == matmul_params


def test_parameter_count_matches_the_program():
    from perf import program

    for name in ("smollm2-360m", "smollm2-1.7b"):
        cfg = _cfg(name)
        assert program.gpt_config(cfg).num_parameters() == \
            work.param_count(cfg)


def test_train_flops_per_token_by_hand():
    cfg = _cfg("smollm2-360m")
    # causal attention forward of one 2048-token sequence, 32 layers:
    # 2 matmuls * 2 * 15 heads * 64 * 2048 * 2049 / 2
    attn = 32 * 4 * 15 * 64 * 2048 * 2049 // 2
    assert work.attention_flops_fwd(cfg, 2048) == attn == 257_823_866_880
    assert work.train_flops_per_token(cfg, 2048) == \
        6 * 361_758_720 + 3 * attn / 2048 == 2_548_224_000
    cfg = _cfg("smollm2-1.7b")
    assert work.train_flops_per_token(cfg, 2048) == pytest.approx(
        6 * 1_711_276_032 + 6 * 24 * 2049 * 32 * 64)
    # Causal counts half the square (plus the diagonal).
    full_square = 24 * 4 * 32 * 64 * 2048 * 2048
    assert work.attention_flops_fwd(cfg, 2048) == pytest.approx(
        full_square / 2, rel=1e-3)


def test_mfu_is_rate_times_a_constant():
    cfg = _cfg("smollm2-360m")
    one = work.mfu(cfg, 2048, 30_000.0, 1, 197e12)
    assert one == pytest.approx(30_000 * 2_548_224_000 / 197e12)
    assert work.mfu(cfg, 2048, 60_000.0, 2, 197e12) == pytest.approx(one)


def test_flash_work():
    cfg = _cfg("smollm2-360m")
    assert work.flash_train_flops(cfg, 2048, 16) == \
        3 * 257_823_866_880 * 16


def test_peaks_table_has_a_source_and_refuses_unknown_kinds():
    with open(os.path.join(registry.ROOT, "peaks.json")) as f:
        table = json.load(f)
    assert "v5e" in table["source"]
    peaks = registry.peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(registry.RegistryError, match="not in perf/peaks"):
        registry.peaks("TPU v9 imaginary")


# --- the readers that turn the run's counts into shares ----------------------

def _observations(cell, counters, window=(10.0, 14.0)):
    from perf import harness

    cell = dict(registry.workload(cell),
                peaks=registry.peaks("TPU v5 lite"))
    return harness.Observations(cell=cell, spans=harness.Spans(),
                                window=window, counters=counters)


def _read(obs, metric):
    spec = obs.cell["per_layer_specs"][metric]
    return registry.code("readers", spec["reader"]).read(
        obs, **spec.get("args", {}))


def test_mfu_reader_is_the_notes_arithmetic_on_a_dense_cell():
    """`mfu.train`: the window's tokens over its wall time through the
    family's count, over chips x the table's peak, x 100: what
    `runners/train.py` prints as `mfu` on the `train_window` note."""
    obs = _observations("train-1.7b-fsdp4", {
        "steps": 5, "tokens_per_step": 32768, "seq_len": 2048,
        "sequences_per_step": 16})
    rate = 5 * 32768 / 4.0
    note = work.mfu(obs.cell["config_file"], 2048, rate, 4, 197e12)
    assert _read(obs, "mfu.train") == pytest.approx(100.0 * note, rel=1e-9)
    # By hand: 10,569,962,496 FLOPs a token (the test above) at 40,960
    # tokens/s over four chips of 197 TFLOP/s.
    assert _read(obs, "mfu.train") == pytest.approx(
        100.0 * (6 * 1_711_276_032 + 6 * 24 * 2049 * 32 * 64) * rate
        / (4 * 197e12))
    assert 0 < _read(obs, "mfu.train") < 100
    obs.counters["steps"] = 0
    assert _read(obs, "mfu.train") is None


def test_mfu_reader_counts_the_experts_at_the_rows_the_counter_gives():
    """A family with expert layers: the rows a token and expert layer come
    from the program's counter, as `runners/train_family.py` passes them to
    the family's `mfu`; more rows held, more work, a larger share."""
    from perf.families import lfm2_moe

    cell = "train-lfm2-24b-ep8-1chip"
    counters = {"steps": 4, "tokens_per_step": 32768, "seq_len": 4096,
                "sequences_per_step": 8, "grad_accum": 2,
                "moe_rows_held": 4 * 32768 * 4 * 0.5}   # 4 expert layers
    obs = _observations(cell, counters, window=(0.0, 2.0))
    cfg = obs.cell["config_file"]
    assert lfm2_moe.moe_layers(cfg) == 4
    rate = 4 * 32768 / 2.0
    assert _read(obs, "mfu.train") == pytest.approx(
        100.0 * lfm2_moe.mfu(cfg, 4096, rate, 1, 197e12, 0.5), rel=1e-9)
    even = _read(obs, "mfu.train")
    obs.counters["moe_rows_held"] *= 2
    assert _read(obs, "mfu.train") > even


def test_ssd_kernel_frac_reads_the_scans_that_took_the_kernels():
    """`ssd_kernel_frac.train`: 100 when every token of every scan took the
    Pallas kernels, the share when a shape fell back, nothing where the
    model counts neither (the other cells' runs)."""
    cell = "train-nemotron-twotower-ep16-1chip"
    base = {"steps": 4, "tokens_per_step": 32768, "seq_len": 4096}
    every = _observations(cell, dict(base, ssm_tokens=4 * 131072.0,
                                     ssd_kernel_tokens=4 * 131072.0))
    assert _read(every, "ssd_kernel_frac.train") == 100.0
    half = _observations(cell, dict(base, ssm_tokens=4 * 131072.0,
                                    ssd_kernel_tokens=2 * 131072.0))
    assert _read(half, "ssd_kernel_frac.train") == 50.0
    assert _read(_observations(cell, base), "ssd_kernel_frac.train") is None
    assert "ssd_kernel_frac.train" not in registry.workload(
        "train-lfm2-24b-ep8-1chip")["per_layer"]
