"""Observability subsystems: profiling (§5.1), guards (§5.2), metrics (§5.5).

The reference has none of these (SURVEY.md §5.1-§5.2: no profiler usage, no
sanitizers; §5.5: rank-0 prints with a cumulative-average rate). These tests
pin the real implementations.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from tpu_trainer.models.config import GPTConfig
from tpu_trainer.utils.guards import (
    DivergenceError, check_finite, check_hosts_in_sync,
)
from tpu_trainer.utils.logging import MetricLogger, flops_per_token, mfu
from tpu_trainer.utils.profiling import WindowedTrace, trace


class TestGuards:
    def test_finite_ok(self):
        check_finite(5, 2.37)

    def test_nan_and_inf_raise(self):
        with pytest.raises(FloatingPointError, match="step 7"):
            check_finite(7, float("nan"))
        with pytest.raises(FloatingPointError):
            check_finite(8, float("inf"))

    def test_single_host_sync_is_noop(self):
        check_hosts_in_sync(3, 1.23)  # process_count == 1 -> no allgather


class TestProfiling:
    def test_windowed_trace_disabled_without_dir(self):
        wt = WindowedTrace(None, start=0, num_steps=2)
        for i in range(5):
            wt.step(i)
        wt.close()  # no-op, nothing was started

    def test_windowed_trace_writes_capture(self, tmp_path):
        wt = WindowedTrace(str(tmp_path), start=1, num_steps=2)
        x = jnp.ones((8, 8))
        for i in range(4):
            wt.step(i)
            jax.block_until_ready(x @ x)
        wt.close()
        host_dir = tmp_path / "host_0"
        assert host_dir.is_dir()
        # A plugins/profile capture tree appears under the host dir.
        assert any(host_dir.rglob("*.pb")) or any(host_dir.rglob("*.trace*"))

    def test_trace_context_manager(self, tmp_path):
        with trace(str(tmp_path)):
            jax.block_until_ready(jnp.ones((4, 4)) @ jnp.ones((4, 4)))
        assert (tmp_path / "host_0").is_dir()

    def _fake_profiler(self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: calls.append(("start", d)))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: calls.append(("stop",)))

        class FakeAnnotation:
            def __init__(self, name, step_num=None):
                self.step_num = step_num

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "StepTraceAnnotation",
                            FakeAnnotation)
        return calls, FakeAnnotation

    def test_windowed_trace_opens_on_resume_past_start(
            self, tmp_path, monkeypatch):
        # A resume landing beyond `start` must still open the window
        # (`i == start` never fires there — the original bug), trace
        # exactly num_steps steps, and hand back a StepTraceAnnotation
        # for each traced step.
        calls, FakeAnnotation = self._fake_profiler(monkeypatch)
        wt = WindowedTrace(str(tmp_path), start=5, num_steps=3)
        cms = [wt.step(i) for i in range(10, 16)]   # resume at step 10
        assert [c[0] for c in calls] == ["start", "stop"]
        assert [isinstance(c, FakeAnnotation) for c in cms] == [
            True, True, True, False, False, False]
        assert [c.step_num for c in cms[:3]] == [10, 11, 12]

    def test_windowed_trace_single_window_per_run(
            self, tmp_path, monkeypatch):
        calls, _ = self._fake_profiler(monkeypatch)
        wt = WindowedTrace(str(tmp_path), start=0, num_steps=2)
        for i in range(10):
            wt.step(i)
        wt.close()
        # One open at step 0, one close at step 2 — never re-opens.
        assert calls == [("start", str(tmp_path / "host_0")), ("stop",)]

    def test_windowed_trace_close_stops_open_window(
            self, tmp_path, monkeypatch):
        calls, _ = self._fake_profiler(monkeypatch)
        wt = WindowedTrace(str(tmp_path), start=0, num_steps=100)
        wt.step(0)
        wt.close()
        assert [c[0] for c in calls] == ["start", "stop"]


class TestMetricLogger:
    def test_windowed_rate_and_jsonl(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        logger = MetricLogger(
            GPTConfig.gpt2_small(), tokens_per_step=100,
            log_interval=2, jsonl_path=path, stdout=False,
        )
        records = []
        for step in range(4):
            r = logger.log(step, {"loss": 1.0, "lr": 1e-4, "grad_norm": 0.5})
            if r:
                records.append(r)
        logger.close()
        assert len(records) == 2               # every log_interval=2 steps
        assert records[-1]["tokens_seen"] == 400
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 2
        assert lines[0]["step"] == 1 and lines[1]["step"] == 3

    def test_eval_record_with_perplexity(self, tmp_path):
        import math

        path = str(tmp_path / "m.jsonl")
        logger = MetricLogger(jsonl_path=path, stdout=False)
        r = logger.log_eval(7, 2.0, 4)
        logger.close()
        assert r["kind"] == "eval" and r["step"] == 7
        assert r["perplexity"] == pytest.approx(math.exp(2.0), rel=1e-4)
        line = json.loads(open(path).read().strip())
        assert line["eval_loss"] == 2.0

    def test_wandb_sink_via_stub(self, monkeypatch):
        """W&B sink (reference requirements.txt:12 — declared, never wired):
        exercised against a stub module, as the package isn't installed."""
        import sys
        import types

        calls = {"init": None, "log": [], "finish": 0}

        class Run:
            def log(self, scalars, step=None):
                calls["log"].append((step, scalars))

            def finish(self):
                calls["finish"] += 1

        stub = types.ModuleType("wandb")
        stub.init = lambda project, config: (
            calls.__setitem__("init", (project, config)) or Run()
        )
        monkeypatch.setitem(sys.modules, "wandb", stub)

        logger = MetricLogger(
            GPTConfig.gpt2_small(), tokens_per_step=10, stdout=False,
            wandb_project="proj", run_config={"x": 1},
        )
        logger.log(0, {"loss": 1.5, "lr": 1e-4, "grad_norm": 0.5})
        logger.log_eval(0, 2.0, 1)
        logger.close()
        assert calls["init"][0] == "proj"
        train_logs = [s for _, s in calls["log"] if "train/loss" in s]
        eval_logs = [s for _, s in calls["log"] if "eval/loss" in s]
        assert train_logs and train_logs[0]["train/loss"] == 1.5
        assert eval_logs and eval_logs[0]["eval/perplexity"] > 0
        assert calls["finish"] == 1

    def test_wandb_missing_degrades_to_warning(self, monkeypatch):
        import sys

        # Force the import to fail regardless of the environment (None in
        # sys.modules makes `import wandb` raise ImportError).
        monkeypatch.setitem(sys.modules, "wandb", None)
        with pytest.warns(UserWarning, match="wandb sink disabled"):
            logger = MetricLogger(stdout=False, wandb_project="p")
        assert logger._wandb is None
        logger.log(0, {"loss": 1.0, "lr": 0.0, "grad_norm": 0.0})
        logger.close()

    def test_tensorboard_sink_writes_events(self, tmp_path):
        pytest.importorskip("tensorboardX")
        tb_dir = str(tmp_path / "tb")
        logger = MetricLogger(
            GPTConfig.gpt2_small(), tokens_per_step=10, stdout=False,
            tensorboard_dir=tb_dir,
        )
        logger.log(0, {"loss": 1.5, "lr": 1e-4, "grad_norm": 0.5})
        logger.close()
        import os

        files = os.listdir(tb_dir)
        assert any("tfevents" in f for f in files), files

    def test_schema_version_stamped_on_every_record(self, tmp_path):
        from tpu_trainer.utils.logging import SCHEMA_VERSION

        path = str(tmp_path / "m.jsonl")
        logger = MetricLogger(
            GPTConfig.gpt2_small(), tokens_per_step=100,
            log_interval=1, jsonl_path=path, stdout=False,
        )
        logger.log(0, {"loss": 1.0, "lr": 1e-4, "grad_norm": 0.5})
        logger.log_eval(0, 2.0, 1)
        logger.log_record({"kind": "custom", "step": 0})
        logger.close()
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 3
        assert all(l["schema_version"] == SCHEMA_VERSION for l in lines)

    def test_recorder_sees_every_record(self):
        seen = []

        class Recorder:
            def observe(self, record):
                seen.append(record)

        logger = MetricLogger(
            GPTConfig.gpt2_small(), tokens_per_step=100,
            log_interval=1, stdout=False, recorder=Recorder(),
        )
        logger.log(0, {"loss": 1.0, "lr": 1e-4, "grad_norm": 0.5})
        logger.log_eval(0, 2.0, 1)
        logger.log_record({"kind": "custom", "step": 0})
        logger.close()
        assert [r["kind"] for r in seen] == ["train", "eval", "custom"]

    def test_deferred_entries_log_the_rate_of_their_dispatch(
            self, monkeypatch):
        # The CLI reads step metrics two steps late and drains the last
        # ones in one burst: every line must still read the steady rate.
        import time

        from tpu_trainer.utils.telemetry import DeferredFetcher

        clock = [100.0]
        monkeypatch.setattr(time, "perf_counter", lambda: clock[0])
        logger = MetricLogger(tokens_per_step=1000, stdout=False)
        fetcher = DeferredFetcher(window=2)
        records = []

        def consume(entries):
            for step, metrics, pushed_at in entries:
                records.append(logger.log(step, metrics, at=pushed_at))

        for step in range(5):
            clock[0] += 0.5                    # one step every 0.5 s
            consume(fetcher.push(step, {"loss": 1.0}))
        assert len(records) == 3               # steps 3 and 4 in flight
        clock[0] += 0.001                      # the run ends: one burst
        consume(fetcher.drain())
        logger.close()
        assert [r["step"] for r in records] == [0, 1, 2, 3, 4]
        assert [r["tokens_per_sec"] for r in records] == [2000.0] * 5

    def test_mfu_math(self):
        cfg = GPTConfig.gpt2_small()
        fpt = flops_per_token(cfg)
        # 6N dominates; attention term is positive.
        assert fpt > 6 * cfg.num_parameters()
        # At peak-flops throughput, MFU == 1 by construction.
        peak = 100e12
        tok_s = peak / fpt
        assert mfu(tok_s, cfg, n_chips=1, peak_flops=peak) == pytest.approx(1.0)


class TestDevicePeaks:
    """No made-up hardware: an unknown TPU kind is an error, and off-TPU
    there is no peak (so no MFU) rather than an assumed one."""

    def test_known_kinds_match_reported_strings(self):
        from tpu_trainer.utils.logging import peak_flops_for_kind

        # What jax reports for a v5e, and the short spellings users pass.
        assert peak_flops_for_kind("TPU v5 lite") == 197e12
        assert peak_flops_for_kind("v5e") == 197e12
        assert peak_flops_for_kind("TPU v5p") == 459e12
        assert peak_flops_for_kind("TPU v4") == 275e12

    @pytest.mark.parametrize("kind", ["TPU v9", "", "cpu", None])
    def test_unknown_kind_raises(self, kind):
        from tpu_trainer.utils.logging import peak_flops_for_kind

        with pytest.raises(ValueError, match="no peak FLOP/s on record"):
            peak_flops_for_kind(kind)

    def test_unknown_tpu_device_raises_and_cpu_has_no_peak(self):
        from types import SimpleNamespace

        from tpu_trainer.utils.logging import device_peak_flops

        with pytest.raises(ValueError, match="TPU v9"):
            device_peak_flops(
                SimpleNamespace(platform="tpu", device_kind="TPU v9"))
        assert device_peak_flops(jax.devices()[0]) is None
        # ... so MFU is absent on the CPU, not computed against a guess.
        assert mfu(1000.0, GPTConfig.gpt2_small()) is None

    def test_unknown_kind_has_no_ici_default(self):
        from tpu_trainer.parallel.comms_model import _ici_bytes_per_sec

        assert _ici_bytes_per_sec("TPU v5 lite") == _ici_bytes_per_sec("v5e")
        with pytest.raises(ValueError, match="no ICI bandwidth on record"):
            _ici_bytes_per_sec("TPU v9")


class TestCompileCachePlacement:
    def test_env_var_is_left_to_jax(self, monkeypatch, tmp_path):
        from tpu_trainer.utils import compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # untouched

    def test_default_is_one_fixed_dir_in_the_checkout(self, monkeypatch):
        from tpu_trainer.utils import compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        # This process is pinned to the CPU: it is told the path, and the
        # cache stays off (XLA:CPU entries are not worth keeping).
        assert compile_cache.enable_compile_cache() == os.path.join(
            repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.setattr(compile_cache, "_pinned_to_cpu", lambda: False)
        try:
            got = compile_cache.enable_compile_cache()
            assert got == os.path.join(repo, ".jax_cache")
            assert got == compile_cache.enable_compile_cache()  # no pid/time
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


class TestChipSmokeRefusesTheCpu:
    def test_exits_nonzero_without_a_phase(self):
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "chip_smoke.py")],
            env=env, capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0
        assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
        assert proc.stdout.strip() == ""  # no phase line, no result line
