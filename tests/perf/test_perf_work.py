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
