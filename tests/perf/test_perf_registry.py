"""Every data file loads and names only things that exist; BENCHMARK.json
says what the files say; a new cell needs new files only."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from perf import registry

with open(os.path.join(registry.CHECKOUT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
WIDTH_KEYS = ("hidden_size", "intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads")


@pytest.mark.parametrize("name", registry.names("workloads"))
def test_workload_resolves(name):
    cell = registry.workload(name)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    for metric, spec in cell["per_layer_specs"].items():
        assert spec["moves"] in cell["end_to_end"], metric
        assert hasattr(registry.code("readers", spec["reader"]), "read")
    assert hasattr(registry.code("runners", cell["runner"]), "run")
    generator = registry.code("generators", cell["traffic_file"]["generator"])
    assert hasattr(generator, "generate")


@pytest.mark.parametrize("name", registry.names("metrics"))
def test_metric_file(name):
    spec = registry.metric(name)
    assert registry.NAME_RE.match(name)
    assert 1 <= len(spec["unit"]) <= 16 and " " not in spec["unit"]
    if "layer" in spec:  # a per-layer metric moves an end-to-end one
        moved = registry.metric(spec["moves"])
        assert "layer" not in moved
        assert moved["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("name", registry.names("configs"))
def test_config_file(name):
    cfg = registry.config(name)
    assert cfg["source"].startswith("https://huggingface.co/")
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    assert not set(cfg["reduced"]) & set(WIDTH_KEYS)
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    assert "assumed" in cfg and "deployment" in cfg
    from perf import program
    tol = cfg["reference_tolerance"][program.COMPUTE_TYPE]
    assert all(0 < tol[k] < 1 for k in (
        "logit_rel_rms", "logit_max_over_rms", "loss_rel")) and tol["why"]


@pytest.mark.parametrize("name", registry.names("traffic"))
def test_traffic_file(name):
    assert hasattr(
        registry.code("generators", registry.traffic(name)["generator"]),
        "generate")


def test_no_cell_runs_a_preset():
    from perf import program
    from tpu_trainer.models.config import GPTConfig

    presets = [GPTConfig.preset(n) for n in ("small", "medium", "large", "xl")]
    for name in registry.names("configs"):
        got = program.gpt_config(registry.config(name))
        assert all((got.hidden_size, got.num_layers, got.vocab_size)
                   != (p.hidden_size, p.num_layers, p.vocab_size)
                   for p in presets)


def test_benchmark_json_says_what_the_files_say():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["perf", "tests/perf"]
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 4)
    for name, entry in cells.items():
        cell = registry.workload(name)
        assert (entry["config"], entry["traffic"], entry["chips"],
                entry["why"]) == (cell["config"], cell["traffic"],
                                  cell["chips"], cell["why"])
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert set(configs) == {w["config"] for w in cells.values()}
    for name, entry in configs.items():
        cfg = registry.config(name)
        assert entry["file"] == f"perf/configs/{name}.json"
        assert (entry["source"], entry["reduced"]) == (
            cfg["source"], cfg["reduced"])
    listed = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name, entry in listed.items():
        spec = registry.metric(name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entry.get(key) == spec.get(key), (name, key)
        reporting = {c for c in cells if name in (
            registry.workload(c)["end_to_end"]
            + registry.workload(c)["per_layer"])}
        assert set(entry.get("workloads", cells)) == reporting, name
    for name in cells:
        cell = registry.workload(name)
        assert set(cell["end_to_end"] + cell["per_layer"]) <= set(listed)
    bounds = {m["name"]: m["bound"] for m in BENCH["end_to_end"]}
    assert all(0.01 <= b <= 0.1 for b in bounds.values())
    assert "setup_s" in bounds


def test_a_layer_metric_without_its_end_to_end_metric_is_refused(
        monkeypatch):
    real = registry._load

    def fake(kind, name):
        if (kind, name) == ("metrics", "made_up_s"):
            return {"name": name, "unit": "s", "better": "lower",
                    "source": "host_clock"}
        data = real(kind, name)
        if kind == "workloads":
            data["end_to_end"] = ["setup_s", "made_up_s"]
        return data

    monkeypatch.setattr(registry, "_load", fake)
    with pytest.raises(registry.RegistryError, match="does not report"):
        registry.workload("train-360m-1chip")


def _digest(root):
    out = {}
    for base, _, files in os.walk(root):
        if "__pycache__" in base or os.sep + "out" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_a_new_cell_needs_new_files_only(tmp_path):
    """A dummy configuration, traffic mix, cell, metric and reader are added
    to a scratch copy as files; the harness resolves the cell and reads the
    metric, and no file that was there has changed."""
    copy = tmp_path / "perf"
    shutil.copytree(registry.ROOT, copy,
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    before = _digest(copy)
    cfg = dict(registry.config("smollm2-360m"), name="dummy-config")
    (copy / "configs" / "dummy-config.json").write_text(json.dumps(cfg))
    (copy / "traffic" / "dummy-mix.json").write_text(json.dumps(
        dict(registry.traffic("pretrain-2k"), name="dummy-mix", seq_len=1024)))
    (copy / "metrics" / "dummy_ms.train.json").write_text(json.dumps({
        "unit": "ms", "better": "lower", "source": "program_span",
        "layer": "trainer", "moves": "train_tokens_per_s",
        "reader": "dummy_reader", "args": {"scale": 2.0}}))
    (copy / "readers" / "dummy_reader.py").write_text(
        "def read(obs, *, scale):\n    return scale * obs.counters['x']\n")
    cell = dict(registry._load("workloads", "train-360m-1chip"),
                name="dummy-cell", config="dummy-config",
                traffic="dummy-mix")
    cell["per_layer"] = cell["per_layer"] + ["dummy_ms.train"]
    (copy / "workloads" / "dummy-cell.json").write_text(json.dumps(cell))
    script = (
        "from perf import registry, harness\n"
        "cell = registry.workload('dummy-cell')\n"
        "assert cell['traffic_file']['seq_len'] == 1024\n"
        "obs = harness.Observations(cell=cell, "
        "spans=harness.Spans(), window=(0.0, 1.0), counters={'x': 21.0})\n"
        "got = harness.read_per_layer(cell, obs)\n"
        "assert got['dummy_ms.train'] == {'value': 42.0, 'unit': 'ms'}, got\n"
        "assert 'flash_roofline.train' not in got  # nothing to read\n"
        "print(registry.ROOT)\n")
    done = subprocess.run(
        [sys.executable, "-c", script], cwd=tmp_path, text=True,
        capture_output=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == str(copy)
    after = _digest(copy)
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 5
