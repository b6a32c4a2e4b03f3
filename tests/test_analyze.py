"""Run-anatomy tests (ISSUE 3): collective-traffic model, offline analyzer
+ regression gate, recompile watchdog, crash flight recorder.

The comms-model lanes pin per-device byte counts against the ring-collective
formulas computed by hand in the test (the acceptance criterion: pure-DP
grad traffic == 2*(n-1)/n * params * 4 within 1%). The analyzer lanes run on
synthetic JSONL so the gate semantics (PASS/FAIL/SKIP, exit codes) are
pinned without a training run; one subprocess each drives the documented
``python -m tpu_trainer.tools.analyze`` entrypoint and the CLI's crash
flight-recorder path end to end.
"""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_trainer.models.config import GPTConfig
from tpu_trainer.parallel import comms_model
from tpu_trainer.parallel.mesh import MeshConfig, make_mesh
from tpu_trainer.tools import analyze
from tpu_trainer.training.config import TrainingConfig
from tpu_trainer.training.trainer import (
    ParallelConfig, RecompileWatchdog, Trainer,
)
from tpu_trainer.utils.flight_recorder import FlightRecorder, env_snapshot
from tpu_trainer.utils.logging import SCHEMA_VERSION

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_model(**kw):
    d = dict(vocab_size=64, hidden_size=16, num_layers=2, num_heads=2,
             intermediate_size=32, max_seq_len=16, dropout=0.0,
             attention_dropout=0.0, use_flash_attention=False)
    d.update(kw)
    return GPTConfig(**d)


def tiny_train(**kw):
    d = dict(batch_size=2, max_seq_len=16, gradient_accumulation_steps=1,
             mixed_precision="bf16", seed=0)
    d.update(kw)
    return TrainingConfig(**d)


def make_trainer(mesh_cfg, strategy="replicated", model_kw=None,
                 train_kw=None, devices=None):
    mesh = make_mesh(mesh_cfg, devices=devices)
    return Trainer(tiny_model(**(model_kw or {})),
                   tiny_train(**(train_kw or {})),
                   ParallelConfig(mesh_cfg, strategy), mesh=mesh)


def _param_shapes(trainer):
    return jax.eval_shape(
        lambda rng: trainer.model.init(
            rng, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))


class TestCommsModel:
    def test_pure_dp_matches_ring_formula(self):
        # The acceptance criterion: pure-DP per-device grad traffic is one
        # f32 ring all-reduce of the full gradient, 2*(n-1)/n * P * 4.
        n = 8
        trainer = make_trainer(MeshConfig(data=n, fsdp=1), "replicated")
        rec = comms_model.build(trainer)
        params = trainer.model_config.num_parameters()
        assert rec["params"] == params
        expected = 2.0 * (n - 1) / n * params * 4
        got = rec["per_axis"]["data"]["bytes"]
        assert got == pytest.approx(expected, rel=0.01)
        # No other axis carries traffic on a pure-DP mesh.
        for axis in ("fsdp", "tensor", "sequence", "expert", "stage"):
            assert rec["per_axis"][axis]["bytes"] == 0.0
        assert rec["total_bytes_per_device_per_step"] == got
        assert rec["bound"] in ("comms", "compute")
        json.dumps(rec, default=str)  # JSONL-able

    def test_zero3_bytes_hand_computed(self):
        # fsdp=8 zero3: grad reduce-scatter on the full f32 tree + 2 param
        # all-gathers per step in compute dtype for >=2-D leaves (the 1-D
        # final-norm scale stays f32). Every leaf of this tiny config is
        # divisible by 8, so all of them shard (verified by the totals
        # matching exactly).
        f = 8
        trainer = make_trainer(MeshConfig(data=1, fsdp=f), "zero3")
        shapes = _param_shapes(trainer)
        leaves = jax.tree_util.tree_leaves(shapes)
        p_total = sum(int(np.prod(l.shape)) for l in leaves)
        scatter = (f - 1) / f * p_total * 4
        gather = 2.0 * (f - 1) / f * sum(
            int(np.prod(l.shape)) * (2 if len(l.shape) >= 2 else 4)
            for l in leaves)
        rec = comms_model.build(trainer)
        ax = rec["per_axis"]["fsdp"]
        assert ax["scatter_bytes"] == pytest.approx(scatter, rel=1e-6)
        assert ax["gather_bytes"] == pytest.approx(gather, rel=1e-6)
        assert ax["bytes"] == pytest.approx(scatter + gather, rel=1e-6)
        assert rec["per_axis"]["data"]["bytes"] == 0.0  # data axis size 1

    def test_tensor_axis_bytes(self):
        # 2-way TP: 4 activation all-reduces per layer per micro-step, each
        # a ring all-reduce (2*(tp-1)/tp) of the [rows, seq, hidden] bf16
        # activation block.
        trainer = make_trainer(MeshConfig(data=4, tensor=2), "replicated")
        tc, mc = trainer.training_config, trainer.model_config
        payload = tc.batch_size * tc.max_seq_len * mc.hidden_size * 2
        expected = (tc.gradient_accumulation_steps * mc.num_layers * 4
                    * 2.0 * (2 - 1) / 2 * payload)
        rec = comms_model.build(trainer)
        assert rec["per_axis"]["tensor"]["bytes"] == pytest.approx(
            expected, rel=1e-6)

    def test_ring_helpers_degenerate_axis(self):
        assert comms_model.ring_all_reduce_bytes(1000.0, 1) == 0.0
        assert comms_model.ring_all_gather_bytes(1000.0, 1) == 0.0
        assert comms_model.ring_sendrecv_bytes(1000.0, 1) == 0.0
        assert comms_model.all_to_all_bytes(1000.0, 1) == 0.0
        assert comms_model.ring_all_reduce_bytes(8.0, 4) == 12.0
        assert comms_model.ring_sendrecv_bytes(8.0, 4) == 24.0

    def test_hlo_counts_opcode_positions_only(self):
        hlo = """
        %ar = f32[8]{0} all-reduce(f32[8]{0} %p), replica_groups={}
        %ag.1 = f32[8]{0} all-gather-start(f32[1]{0} %x)
        ROOT %r = f32[8]{0} add(f32[8]{0} %ar, f32[8]{0} %all-reduce.7)
        """
        counts = comms_model.hlo_collective_counts(hlo)
        assert counts["all-reduce"] == 1      # operand ref not counted
        assert counts["all-gather"] == 1      # async -start form counted
        assert counts["reduce-scatter"] == 0

    def test_crosscheck_against_compiled_hlo_dp(self):
        # GSPMD must insert a grad all-reduce on an 8-way DP mesh; the
        # model charges the data axis, so the cross-check has no mismatch.
        trainer = make_trainer(MeshConfig(data=8, fsdp=1), "replicated")
        state = trainer.init_state()
        rng = np.random.default_rng(0)
        batch = trainer.put_batch(rng.integers(
            0, 64, (trainer.global_batch_size, 16), dtype=np.int32))
        hlo = trainer.compiled_step_text(state, batch)
        assert hlo is not None
        counts = comms_model.hlo_collective_counts(hlo)
        assert counts["all-reduce"] > 0
        rec = comms_model.build(trainer)
        cc = comms_model.crosscheck(rec, hlo)
        assert cc["hlo_mismatches"] == []

    def test_summary_lines(self):
        trainer = make_trainer(MeshConfig(data=8, fsdp=1), "replicated")
        rec = comms_model.build(trainer)
        lines = comms_model.summary_lines(rec)
        assert any("data[8]" in l for l in lines)
        assert any("-bound" in l for l in lines)


# --- analyzer --------------------------------------------------------------

def _run_records(tok=1000.0, n=6, mfu=0.4, mem=10.0, loss=3.0,
                 version=SCHEMA_VERSION):
    recs = []
    for i in range(n):
        recs.append({
            "kind": "train", "schema_version": version, "step": i * 10,
            "loss": loss - 0.01 * i, "tokens_per_sec": tok,
            "elapsed_s": 5.0 + 2.0 * i, "mfu": mfu, "peak_mem_gb": mem,
        })
    recs.append({
        "kind": "goodput", "schema_version": version, "final": True,
        "total_seconds": 100.0, "productive_frac": 0.9, "step_frac": 0.9,
        "data_wait_frac": 0.05, "untracked_frac": 0.05,
    })
    return recs


def _write(path, records):
    with open(path, "w") as fh:
        for r in records:
            fh.write(json.dumps(r) + "\n")
    return str(path)


class TestAnalyzer:
    def test_summarize_and_render(self, tmp_path):
        recs = _run_records()
        recs.append({"kind": "recompile", "schema_version": SCHEMA_VERSION,
                     "step": 30, "executables": 2, "new_executables": 1,
                     "batch_abstract": "int32[2,16]", "storm": False})
        recs.append({"kind": "rollback", "schema_version": SCHEMA_VERSION,
                     "step": 40, "cause": "FloatingPointError",
                     "restored_step": 35})
        path = _write(tmp_path / "run.jsonl", recs)
        report = analyze.summarize(analyze.load_records(path))
        assert report["train"]["tok_per_sec"]["p50"] == 1000.0
        assert report["train"]["peak_mem_gb"] == 10.0
        # elapsed_s advances 2 s per 10 steps -> 0.2 s/step.
        assert report["train"]["step_time_s"]["p50"] == pytest.approx(0.2)
        assert report["goodput"]["productive_frac"] == 0.9
        assert report["recompiles"]["count"] == 1
        assert report["rollbacks"][0]["cause"] == "FloatingPointError"
        text = "\n".join(analyze.render(report))
        assert "tok/s" in text and "recompiles 1" in text
        assert "rollback at step 40" in text

    def test_storm_flag_renders_loudly(self, tmp_path):
        recs = _run_records()
        recs.append({"kind": "recompile", "schema_version": SCHEMA_VERSION,
                     "step": 30, "batch_abstract": "int32[2,8]",
                     "storm": True})
        report = analyze.summarize(analyze.load_records(
            _write(tmp_path / "run.jsonl", recs)))
        assert report["recompiles"]["storm"] is True
        assert any("RECOMPILE STORM" in l for l in analyze.render(report))

    def test_unversioned_record_exits_2(self, tmp_path, capsys):
        recs = _run_records()
        del recs[2]["schema_version"]
        path = _write(tmp_path / "run.jsonl", recs)
        assert analyze.main([path]) == 2
        assert "schema_version" in capsys.readouterr().err

    def test_wrong_version_exits_2(self, tmp_path):
        path = _write(tmp_path / "run.jsonl", _run_records(version=999))
        assert analyze.main([path]) == 2

    def test_bad_json_and_empty_file_exit_2(self, tmp_path):
        bad = tmp_path / "bad.jsonl"
        bad.write_text("{not json\n")
        assert analyze.main([str(bad)]) == 2
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        assert analyze.main([str(empty)]) == 2

    def test_identical_runs_pass(self, tmp_path):
        base = _write(tmp_path / "base.jsonl", _run_records())
        new = _write(tmp_path / "new.jsonl", _run_records())
        assert analyze.main([new, "--compare", base]) == 0

    def test_tok_regression_fails(self, tmp_path):
        base = _write(tmp_path / "base.jsonl", _run_records(tok=1000.0))
        new = _write(tmp_path / "new.jsonl", _run_records(tok=850.0))
        assert analyze.main([new, "--compare", base]) == 1

    def test_exactly_ten_percent_fails(self, tmp_path):
        # The documented gate is ">= 10% regression fails".
        base = _write(tmp_path / "base.jsonl", _run_records(tok=1000.0))
        new = _write(tmp_path / "new.jsonl", _run_records(tok=900.0))
        assert analyze.main([new, "--compare", base]) == 1

    def test_memory_regression_fails(self, tmp_path):
        base = _write(tmp_path / "base.jsonl", _run_records(mem=10.0))
        new = _write(tmp_path / "new.jsonl", _run_records(mem=12.0))
        assert analyze.main([new, "--compare", base]) == 1

    def test_absent_metric_skips_not_fails(self, tmp_path):
        # CPU runs have no MFU — the gate SKIPs it rather than failing.
        base = _write(tmp_path / "base.jsonl", _run_records(mfu=0.4))
        new = _write(tmp_path / "new.jsonl", _run_records(mfu=None))
        assert analyze.main([new, "--compare", base]) == 0

    def test_compare_verdict_shape(self, tmp_path):
        base = analyze.summarize(analyze.load_records(
            _write(tmp_path / "b.jsonl", _run_records(tok=1000.0))))
        new = analyze.summarize(analyze.load_records(
            _write(tmp_path / "n.jsonl", _run_records(tok=1080.0))))
        verdicts = {v["metric"]: v for v in analyze.compare(base, new)}
        assert verdicts["tok_per_sec_p50"]["verdict"] == "PASS"  # improved
        assert verdicts["tok_per_sec_p50"]["delta_pct"] == pytest.approx(8.0)
        assert verdicts["final_loss"]["verdict"] == "PASS"
        lines = analyze.render_verdicts(list(verdicts.values()))
        assert any(l.startswith("PASS tok_per_sec_p50") for l in lines)

    def test_module_entrypoint_subprocess(self, tmp_path):
        # The documented invocation, end to end: identical runs exit 0,
        # an injected 15% tok/s regression exits nonzero.
        base = _write(tmp_path / "base.jsonl", _run_records(tok=1000.0))
        same = _write(tmp_path / "same.jsonl", _run_records(tok=1000.0))
        slow = _write(tmp_path / "slow.jsonl", _run_records(tok=850.0))
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        env.pop("XLA_FLAGS", None)
        cmd = [sys.executable, "-m", "tpu_trainer.tools.analyze"]
        r_ok = subprocess.run(cmd + [same, "--compare", base],
                              capture_output=True, text=True, env=env,
                              timeout=120)
        assert r_ok.returncode == 0, r_ok.stderr
        assert "PASS tok_per_sec_p50" in r_ok.stdout
        r_bad = subprocess.run(cmd + [slow, "--compare", base],
                               capture_output=True, text=True, env=env,
                               timeout=120)
        assert r_bad.returncode != 0
        assert "FAIL tok_per_sec_p50" in r_bad.stdout


# --- recompile watchdog ----------------------------------------------------

class TestRecompileWatchdog:
    def test_fires_on_forced_shape_change(self):
        trainer = make_trainer(
            MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1],
            model_kw={"max_seq_len": 32}, train_kw={"max_seq_len": 32})
        state = trainer.init_state()
        rng = np.random.default_rng(0)

        def batch(seq):
            return trainer.put_batch(
                rng.integers(0, 64, (trainer.global_batch_size, seq),
                             dtype=np.int32))

        wd = RecompileWatchdog(warn_after=2)
        b1 = batch(32)
        state, _ = trainer.train_step(state, b1)
        assert wd.observe(0, b1, expected=True) is None  # warmup compile
        state, _ = trainer.train_step(state, b1)
        assert wd.observe(1, b1) is None                 # cache hit
        b2 = batch(16)
        state, _ = trainer.train_step(state, b2)         # silent recompile
        rec = wd.observe(2, b2)
        assert rec is not None and rec["kind"] == "recompile"
        assert rec["new_executables"] == 1
        assert "16" in rec["batch_abstract"]
        assert rec["storm"] is False
        b3 = batch(8)
        state, _ = trainer.train_step(state, b3)
        rec2 = wd.observe(3, b3)
        assert rec2 is not None and rec2["storm"] is True
        assert rec2["recompiles_total"] == 2

    def test_an_eval_compile_is_not_a_train_step_recompile(self):
        """The watchdog reads the program's compile log by span: an eval
        step's first compile between two train steps is not a recompile of
        the train step; a new batch shape after it is."""
        trainer = make_trainer(
            MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1],
            model_kw={"max_seq_len": 32}, train_kw={"max_seq_len": 32})
        state = trainer.init_state()
        rng = np.random.default_rng(1)

        def rows(seq):
            return rng.integers(0, 64, (trainer.global_batch_size, seq),
                                dtype=np.int32)

        wd = RecompileWatchdog()
        b1 = trainer.put_batch(rows(32))
        state, _ = trainer.train_step(state, b1)
        assert wd.observe(0, b1, expected=True) is None
        trainer.eval_step(state, rows(24))              # compiles, not a step
        state, _ = trainer.train_step(state, b1)
        assert wd.observe(1, b1) is None
        assert wd.events == []
        b2 = trainer.put_batch(rows(24))
        state, _ = trainer.train_step(state, b2)
        rec = wd.observe(2, b2)
        assert rec is not None and rec["new_executables"] == 1
        assert rec["executables"] == 2 and "24" in rec["batch_abstract"]


# --- crash flight recorder -------------------------------------------------

class TestFlightRecorder:
    def test_ring_keeps_last_n_and_dumps(self, tmp_path):
        fr = FlightRecorder(capacity=3, snapshot={"mesh": {"data": 1}})
        for i in range(10):
            fr.observe({"kind": "train", "step": i})
        assert len(fr) == 3
        path = fr.dump(str(tmp_path), reason="test",
                       exc=ValueError("boom"), step=9)
        assert os.path.basename(path) == "crash_report.json"
        report = json.load(open(path))
        assert report["kind"] == "crash_report"
        assert report["schema_version"] == SCHEMA_VERSION
        assert report["reason"] == "test" and report["step"] == 9
        assert [r["step"] for r in report["records"]] == [7, 8, 9]
        assert report["exception"]["type"] == "ValueError"
        assert "boom" in report["exception"]["message"]
        assert report["snapshot"]["mesh"] == {"data": 1}

    def test_dump_overwrites_previous(self, tmp_path):
        fr = FlightRecorder(capacity=2)
        fr.observe({"step": 1})
        fr.dump(str(tmp_path), reason="first")
        fr.observe({"step": 2})
        path = fr.dump(str(tmp_path), reason="second")
        report = json.load(open(path))
        assert report["reason"] == "second"
        assert len(report["records"]) == 2
        assert not os.path.exists(path + ".tmp")  # atomic write cleaned up

    def test_env_snapshot_contents(self):
        snap = env_snapshot(model_config=tiny_model(),
                            training_config=tiny_train(), argv=["--x", "1"])
        assert snap["argv"] == ["--x", "1"]
        assert snap["model_config"]["hidden_size"] == 16
        assert snap["training_config"]["batch_size"] == 2
        assert "jax_version" in snap
        assert all(any(k.startswith(p) for p in
                       ("JAX", "XLA", "TPU", "LIBTPU", "TF_CPP"))
                   for k in snap["env"])

    def test_cli_dumps_crash_report_on_divergence(self, tmp_path):
        # End to end: an injected NaN with no rollback budget kills the run
        # through the divergence path, which must leave crash_report.json.
        yaml = tmp_path / "tiny.yaml"
        yaml.write_text("""
model:
  name: "gpt2-small"
  vocab_size: 128
  hidden_size: 32
  num_layers: 2
  num_heads: 2
  intermediate_size: 64
  max_seq_len: 32
  dropout: 0.0
  attention_dropout: 0.0
  use_flash_attention: false
training:
  batch_size: 2
  learning_rate: 1e-3
  max_steps: 8
  warmup_steps: 1
  log_interval: 1
  eval_interval: 0
  save_interval: 0
data:
  dataset: "dummy"
""")
        ck = tmp_path / "ck"
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        env.pop("XLA_FLAGS", None)   # 1 CPU device: speed, not mesh shape
        r = subprocess.run(
            [sys.executable, "-m", "tpu_trainer.training.train_ddp",
             "--config", str(yaml),
             "--checkpoint_dir", str(ck),
             "--metrics_jsonl", str(tmp_path / "m.jsonl"),
             "--inject_fault", "nan_loss@3",
             "--guard_interval", "1",
             "--max_rollbacks", "0",
             "--flight_recorder_steps", "32"],
            capture_output=True, text=True, env=env, timeout=240)
        assert r.returncode != 0
        report_path = ck / "crash_report.json"
        assert report_path.exists(), r.stdout + r.stderr
        report = json.load(open(report_path))
        assert report["reason"].startswith("divergence")
        assert report["exception"] is not None
        assert report["records"], "ring should hold the emitted records"
        assert all("schema_version" in rec for rec in report["records"])
        assert report["snapshot"]["model_config"]["hidden_size"] == 32


def _plan_record(*, auto=True, predicted=10.0, measured=None, err=None):
    rec = {
        "kind": "mesh_plan", "schema_version": SCHEMA_VERSION,
        "devices": 8, "strategy": "zero3", "global_rows": 16,
        "seq_len": 16, "grad_accum": 1, "device_kind": "cpu",
        "hbm_budget_gb": None, "n_enumerated": 56, "n_feasible": 29,
        "pruned": {"divisibility": 27, "hbm": 0}, "auto": auto,
        "chosen": {"mesh": {"data": 1, "fsdp": 8, "sequence": 1,
                            "tensor": 1, "expert": 1, "stage": 1},
                   "batch_per_shard": 2, "predicted_step_ms": predicted,
                   "compute_ms": 9.0, "comms_ms": 1.0, "bubble_factor": 1.0,
                   "bytes_per_device": 1e6, "peak_hbm_gb": 0.5,
                   "bound": "compute"},
        "ranked": [], "predicted_step_ms": predicted,
        "assumptions": {},
    }
    if measured is not None:
        rec["measured_step_ms"] = measured
        rec["plan_error_frac"] = (err if err is not None else
                                  abs(predicted - measured) / measured)
    return rec


class TestPlanSection:
    def test_summarize_and_render_plan(self, tmp_path):
        recs = _run_records()
        for r in recs:
            if r["kind"] == "train":
                r["plan_error_frac"] = 0.05
        recs.append(_plan_record(measured=10.5))
        report = analyze.summarize(analyze.load_records(
            _write(tmp_path / "run.jsonl", recs)))
        pl = report["plan"]
        assert pl["auto"] is True
        assert pl["mesh"] == {"data": 1, "fsdp": 8, "sequence": 1,
                              "tensor": 1, "expert": 1, "stage": 1}
        # Median of the per-window train errors wins over the record's own.
        assert pl["plan_error_frac"] == pytest.approx(0.05)
        assert pl["measured_step_ms"] == 10.5
        text = "\n".join(analyze.render(report))
        assert "auto mesh 1x8x1x1x1x1" in text
        assert "median err 5.0%" in text

    def test_plan_without_measurement_still_reports(self, tmp_path):
        # Training-CLI --mesh auto runs log the plan but no measured step.
        recs = _run_records() + [_plan_record()]
        report = analyze.summarize(analyze.load_records(
            _write(tmp_path / "run.jsonl", recs)))
        assert report["plan"]["measured_step_ms"] is None
        assert any("plan" in l for l in analyze.render(report))

    def test_gate_passes_under_tol_and_fails_over(self, tmp_path):
        base = _write(tmp_path / "b.jsonl",
                      _run_records() + [_plan_record(measured=10.5)])
        good = _write(tmp_path / "g.jsonl",
                      _run_records() + [_plan_record(measured=11.0)])
        assert analyze.main([good, "--compare", base]) == 0
        bad = _write(tmp_path / "f.jsonl",
                     _run_records() + [_plan_record(measured=20.0)])
        assert analyze.main([bad, "--compare", base]) == 1

    def test_gate_is_absolute_not_relative(self, tmp_path):
        # Base run 45% off, new run 35% off: an IMPROVEMENT, but still over
        # the fixed 30% budget — the absolute gate fails it anyway.
        base = analyze.summarize(analyze.load_records(_write(
            tmp_path / "b.jsonl",
            _run_records() + [_plan_record(predicted=14.5, measured=10.0)])))
        new = analyze.summarize(analyze.load_records(_write(
            tmp_path / "n.jsonl",
            _run_records() + [_plan_record(predicted=13.5, measured=10.0)])))
        verdicts = {v["metric"]: v for v in analyze.compare(base, new)}
        v = verdicts["plan_error_frac"]
        assert v["verdict"] == "FAIL" and v["absolute"] is True
        lines = analyze.render_verdicts([v])
        assert any("tol 30% abs" in l for l in lines)

    def test_gate_skips_without_measured_step(self, tmp_path):
        # CLI-only runs (plan logged, nothing measured) and plan-less runs
        # both SKIP rather than fail.
        base = _write(tmp_path / "b.jsonl",
                      _run_records() + [_plan_record(measured=10.5)])
        unmeasured = _write(tmp_path / "u.jsonl",
                            _run_records() + [_plan_record()])
        assert analyze.main([unmeasured, "--compare", base]) == 0
        planless = _write(tmp_path / "p.jsonl", _run_records())
        assert analyze.main([planless, "--compare", base]) == 0

    def test_plan_tol_flag_reaches_gate(self, tmp_path):
        base = _write(tmp_path / "b.jsonl",
                      _run_records() + [_plan_record(measured=10.5)])
        new = _write(tmp_path / "n.jsonl",
                     _run_records() + [_plan_record(measured=11.0)])
        # ~9% error: passes the default 30% budget, fails a 5% one.
        assert analyze.main([new, "--compare", base]) == 0
        assert analyze.main([new, "--compare", base,
                             "--plan-tol", "0.05"]) == 1


class TestRouterSection:
    """MoE router report + the dropless drop_frac gate (ISSUE 12)."""

    @staticmethod
    def _router_records(drop=0.0, dropless=1.0):
        recs = _run_records()
        for r in recs:
            if r["kind"] == "train":
                for layer in ("L00", "L01"):
                    r[f"telemetry/router/entropy/{layer}"] = 1.3
                    r[f"telemetry/router/drop_frac/{layer}"] = drop
                    r[f"telemetry/router/max_group_frac/{layer}"] = 0.4
                    r[f"telemetry/router/dropless/{layer}"] = dropless
                    r[f"telemetry/router/load/{layer}/max"] = 0.4
                    r[f"telemetry/router/load/{layer}/min"] = 0.1
        return recs

    def test_summarize_and_render_router(self, tmp_path):
        report = analyze.summarize(analyze.load_records(_write(
            tmp_path / "run.jsonl", self._router_records(drop=0.1,
                                                         dropless=0.0))))
        ro = report["router"]
        assert ro["dropless"] is False
        assert ro["drop_frac_max"] == pytest.approx(0.1)
        assert ro["entropy"]["p50"] == pytest.approx(1.3)
        assert ro["max_group_frac"]["p90"] == pytest.approx(0.4)
        text = "\n".join(analyze.render(report))
        assert "router  capacity" in text
        assert "TOKENS DROPPED" not in text

    def test_dropless_run_with_drops_renders_flag(self, tmp_path):
        report = analyze.summarize(analyze.load_records(_write(
            tmp_path / "run.jsonl", self._router_records(drop=0.05))))
        assert report["router"]["dropless"] is True
        text = "\n".join(analyze.render(report))
        assert "TOKENS DROPPED ON DROPLESS RUN" in text

    def test_gate_fails_dropless_run_with_drops(self, tmp_path):
        base = _write(tmp_path / "b.jsonl", self._router_records())
        good = _write(tmp_path / "g.jsonl", self._router_records())
        assert analyze.main([good, "--compare", base]) == 0
        bad = _write(tmp_path / "f.jsonl", self._router_records(drop=0.02))
        assert analyze.main([bad, "--compare", base]) == 1
        # A loosened absolute budget lets the same run through.
        assert analyze.main([bad, "--compare", base,
                             "--moe-drop-tol", "0.05"]) == 0

    def test_gate_skips_capacity_runs(self, tmp_path):
        # Capacity-mode drops are a tuning choice, not a bug: SKIP even at
        # large drop_frac. Runs without router telemetry SKIP too.
        base = _write(tmp_path / "b.jsonl", _run_records())
        capacity = _write(tmp_path / "c.jsonl",
                          self._router_records(drop=0.5, dropless=0.0))
        assert analyze.main([capacity, "--compare", base]) == 0
        plain = _write(tmp_path / "p.jsonl", _run_records())
        assert analyze.main([plain, "--compare", base]) == 0


class TestSpecGate:
    """Speculative-decoding serve report + the acceptance-floor gate
    (ISSUE 13)."""

    @staticmethod
    def _serve_record(spec="ngram", accept_mean=3.5):
        rec = {"kind": "serve", "schema_version": SCHEMA_VERSION,
               "lane": "spec_on", "tok_per_sec": 600.0, "spec": spec}
        if spec != "off":
            rec.update({"spec_k": 4, "spec_steps": 100, "spec_drafted": 400,
                        "spec_accepted": int(accept_mean * 100),
                        "spec_accept_mean": accept_mean,
                        "spec_accept_rate": accept_mean / 4.0,
                        "spec_accept_hist": [10, 20, 30, 40]})
        return rec

    def test_summarize_and_render_spec(self, tmp_path):
        report = analyze.summarize(analyze.load_records(_write(
            tmp_path / "run.jsonl",
            _run_records() + [self._serve_record()])))
        sv = report["serve"]
        assert sv["spec"] == "ngram"
        assert sv["spec_accept_mean"] == pytest.approx(3.5)
        text = "\n".join(analyze.render(report))
        assert "accepted drafts/step" in text

    def test_gate_passes_over_floor_and_fails_under(self, tmp_path):
        base = _write(tmp_path / "b.jsonl",
                      _run_records() + [self._serve_record()])
        good = _write(tmp_path / "g.jsonl",
                      _run_records() + [self._serve_record(accept_mean=2.0)])
        assert analyze.main([good, "--compare", base,
                             "--spec-accept-tol", "1.0"]) == 0
        bad = _write(tmp_path / "f.jsonl",
                     _run_records() + [self._serve_record(accept_mean=0.4)])
        assert analyze.main([bad, "--compare", base,
                             "--spec-accept-tol", "1.0"]) == 1

    def test_gate_is_absolute_with_plain_tolerance(self, tmp_path):
        # Even an acceptance IMPROVEMENT over base fails a floor it does
        # not clear — the gate reads only the new run.
        base = analyze.summarize(analyze.load_records(_write(
            tmp_path / "b.jsonl",
            _run_records() + [self._serve_record(accept_mean=0.2)])))
        new = analyze.summarize(analyze.load_records(_write(
            tmp_path / "n.jsonl",
            _run_records() + [self._serve_record(accept_mean=0.5)])))
        verdicts = {v["metric"]: v for v in analyze.compare(
            base, new, spec_accept_tol=1.0)}
        v = verdicts["spec_accept_mean"]
        assert v["verdict"] == "FAIL" and v["absolute"] is True
        assert v["tolerance"] == 1.0
        lines = analyze.render_verdicts([v])
        assert any("floor 1.00 abs" in l for l in lines)

    def test_gate_skips_non_spec_runs(self, tmp_path):
        # spec-off serve runs and serve-less runs both SKIP, even under
        # a floor that would fail any spec run.
        base = _write(tmp_path / "b.jsonl",
                      _run_records() + [self._serve_record()])
        off = _write(tmp_path / "o.jsonl",
                     _run_records() + [self._serve_record(spec="off")])
        assert analyze.main([off, "--compare", base,
                             "--spec-accept-tol", "99.0"]) == 0
        plain = _write(tmp_path / "p.jsonl", _run_records())
        assert analyze.main([plain, "--compare", base,
                             "--spec-accept-tol", "99.0"]) == 0

    def test_default_floor_always_passes(self, tmp_path):
        base = _write(tmp_path / "b.jsonl",
                      _run_records() + [self._serve_record()])
        weak = _write(tmp_path / "w.jsonl",
                      _run_records() + [self._serve_record(accept_mean=0.0)])
        assert analyze.main([weak, "--compare", base]) == 0


class TestJsonOutput:
    """``--json``: the machine-readable gate envelope. The contract a CI
    caller parses: top-level ``report`` / ``verdicts`` / ``gate`` /
    ``exit_code`` keys, one verdict row per gate with PASS/FAIL/SKIP and
    the evaluated values + tolerance, and ``exit_code`` agreeing with
    the process exit code byte-for-byte."""

    def _json(self, capsys):
        out = capsys.readouterr().out
        return json.loads(out)

    def test_report_only_envelope(self, tmp_path, capsys):
        path = _write(tmp_path / "run.jsonl", _run_records())
        assert analyze.main([path, "--json"]) == 0
        env = self._json(capsys)
        assert set(env) == {"report", "verdicts", "gate", "exit_code"}
        assert env["verdicts"] is None and env["gate"] is None
        assert env["exit_code"] == 0
        assert env["report"]["train"]["tok_per_sec"]["p50"] == 1000.0

    def test_compare_pass_verdict_rows(self, tmp_path, capsys):
        base = _write(tmp_path / "base.jsonl", _run_records())
        new = _write(tmp_path / "new.jsonl", _run_records())
        assert analyze.main([new, "--compare", base, "--json"]) == 0
        env = self._json(capsys)
        assert env["exit_code"] == 0
        verdicts = env["verdicts"]
        assert isinstance(verdicts, list) and verdicts
        for v in verdicts:
            assert v["verdict"] in ("PASS", "FAIL", "SKIP")
            assert "metric" in v
        tok = next(v for v in verdicts if v["metric"] == "tok_per_sec_p50")
        assert tok["verdict"] == "PASS"
        assert tok["base"] == 1000.0 and tok["new"] == 1000.0
        assert tok["tolerance_pct"] == 10.0
        gate = env["gate"]
        assert set(gate) == {"PASS", "FAIL", "SKIP"}
        assert sum(gate.values()) == len(verdicts)
        assert gate["FAIL"] == 0

    def test_compare_fail_sets_exit_code(self, tmp_path, capsys):
        base = _write(tmp_path / "base.jsonl", _run_records(tok=1000.0))
        new = _write(tmp_path / "new.jsonl", _run_records(tok=850.0))
        assert analyze.main([new, "--compare", base, "--json"]) == 1
        env = self._json(capsys)
        assert env["exit_code"] == 1
        assert env["gate"]["FAIL"] >= 1
        tok = next(v for v in env["verdicts"]
                   if v["metric"] == "tok_per_sec_p50")
        assert tok["verdict"] == "FAIL"
        assert tok["delta_pct"] == -15.0

    def test_json_cli_subprocess_round_trip(self, tmp_path):
        # The documented entrypoint, parsed the way CI would: stdout is
        # ONE JSON document, nothing else mixed in.
        path = _write(tmp_path / "run.jsonl", _run_records())
        proc = subprocess.run(
            [sys.executable, "-m", "tpu_trainer.tools.analyze",
             path, "--compare", path, "--json"],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr
        env = json.loads(proc.stdout)
        assert env["exit_code"] == 0
        assert env["gate"]["FAIL"] == 0
