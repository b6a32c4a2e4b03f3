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


@pytest.mark.parametrize("name", registry.names("workloads"))
def test_a_cell_lists_its_own_metrics_then_those_that_name_it(name):
    """A cell's resolved per-layer metrics: its file's list in the file's
    order, then exactly the metric files whose `workloads` names the cell,
    in sorted order, each resolved once and each moving an end-to-end
    metric the cell reports."""
    own = registry._load("workloads", name)["per_layer"]
    naming = [m for m in registry.names("metrics")
              if name in registry._load("metrics", m).get("workloads", ())]
    cell = registry.workload(name)
    assert cell["per_layer"] == own + [m for m in naming if m not in own]
    assert list(cell["per_layer_specs"]) == cell["per_layer"]
    assert len(set(cell["per_layer"])) == len(cell["per_layer"])
    for m in naming:
        assert cell["per_layer_specs"][m]["moves"] in cell["end_to_end"], m
    assert "mfu.train" in naming      # the whole step's share, every cell


def _with_metric_files(monkeypatch, files):
    """The registry with some metric files added or replaced in memory."""
    real_load, real_names = registry._load, registry.names

    def load(kind, name):
        if kind == "metrics" and name in files:
            return dict(files[name], name=name)
        return real_load(kind, name)

    monkeypatch.setattr(registry, "_load", load)
    monkeypatch.setattr(registry, "names", lambda kind: sorted(
        set(real_names(kind)) | set(files)) if kind == "metrics"
        else real_names(kind))


def test_a_metric_that_names_a_missing_cell_is_refused(monkeypatch):
    spec = dict(registry.metric("step_ms.train"),
                workloads=["train-360m-1chip", "no-such-cell"])
    _with_metric_files(monkeypatch, {"made_up_ms.train": spec})
    with pytest.raises(registry.RegistryError, match="no-such-cell"):
        registry.metric("made_up_ms.train")
    # A cell is not resolved past such a file, whichever cell it is.
    with pytest.raises(registry.RegistryError, match="do not exist"):
        registry.workload("train-lfm2-24b-ep8-1chip")


def test_a_metric_named_by_the_cell_and_by_its_own_file_is_resolved_once(
        monkeypatch):
    """`step_ms.train` is in every cell's own list; naming a cell in its file
    too changes nothing, and the cell's order stands."""
    before = registry.workload("train-360m-1chip")["per_layer"]
    spec = dict(registry.metric("step_ms.train"),
                workloads=["train-360m-1chip"])
    _with_metric_files(monkeypatch, {"step_ms.train": spec})
    cell = registry.workload("train-360m-1chip")
    assert cell["per_layer"] == before
    assert cell["per_layer"].count("step_ms.train") == 1


def test_a_metric_that_names_a_cell_must_move_what_the_cell_reports(
        monkeypatch):
    spec = dict(registry.metric("step_ms.train"), moves="ttft_p95_ms",
                workloads=["train-360m-1chip"])
    _with_metric_files(monkeypatch, {"made_up_ms.serve": spec})
    with pytest.raises(registry.RegistryError, match="does not report"):
        registry.workload("train-360m-1chip")
    registry.workload("train-1.7b-fsdp4")     # not named: not held to it


@pytest.mark.parametrize("name", registry.names("metrics"))
def test_metric_file(name):
    spec = registry.metric(name)      # refuses a cell that does not exist
    assert set(spec.get("workloads", ())) <= set(registry.names("workloads"))
    assert registry.NAME_RE.match(name)
    assert 1 <= len(spec["unit"]) <= 16 and " " not in spec["unit"]
    if "layer" in spec:  # a per-layer metric moves an end-to-end one
        moved = registry.metric(spec["moves"])
        assert "layer" not in moved
        assert moved["source"] in ("host_clock", "device_trace")


def check_config_file(name):
    """What every file of perf/configs/ is held to, through its own family
    (shared with the scratch copy of ``test_a_new_cell_needs_new_files_only``).
    The family's ``gpt_config`` refuses what its program cannot state: its
    width identities and fixed keys are its own."""
    cfg = registry.config(name)
    family = registry.family(cfg)
    assert cfg["source"].startswith("https://huggingface.co/")
    built = family.gpt_config(cfg)
    assert built.num_parameters() == family.param_count(cfg)
    widths = set(family.WIDTH_KEYS) | set(WIDTH_KEYS) & set(cfg)
    assert not set(cfg["reduced"]) & widths, "reduced names a width"
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    assert "assumed" in cfg and "deployment" in cfg
    from perf import program
    tol = cfg["reference_tolerance"][program.COMPUTE_TYPE]
    assert all(0 < tol[k] < 1 for k in (
        "logit_rel_rms", "logit_max_over_rms", "loss_rel")) and tol["why"]


@pytest.mark.parametrize("name", registry.names("configs"))
def test_config_file(name):
    check_config_file(name)


@pytest.mark.parametrize("name", registry.names("configs"))
def test_a_width_under_reduced_is_refused(name, monkeypatch):
    """Each of the family's own widths, and each of the old five the file
    has: listed under `reduced`, the file fails its check."""
    cfg = registry.config(name)
    widths = [k for k in dict.fromkeys(
        registry.family(cfg).WIDTH_KEYS + WIDTH_KEYS) if k in cfg]
    for key in widths:
        bad = dict(cfg, reduced=cfg["reduced"] + [key],
                   reduced_why=dict(cfg["reduced_why"], **{key: "narrower"}))
        monkeypatch.setattr(registry, "config", lambda _, bad=bad: bad)
        with pytest.raises(AssertionError, match="reduced names a width"):
            check_config_file(name)


def test_a_configuration_without_family_is_refused():
    cfg = registry.config("smollm2-360m")
    del cfg["family"]
    with pytest.raises(registry.RegistryError, match="names no family") as e:
        registry.family(cfg)
    assert all(name in str(e.value) for name in registry.names("families"))
    with pytest.raises(registry.RegistryError, match="no perf/families"):
        registry.family(dict(cfg, family="no_such_family"))


# What `runners/train_family.py` calls of a family, and what each reader
# calls, by the metric a cell of the family lists.
FAMILY_EXPORTS = ("gpt_config", "WIDTH_KEYS", "param_count",
                  "train_flops_per_token", "mfu")
TRAIN_FAMILY_EXPORTS = ("forward_and_choices", "loss", "chose",
                        "router_width", "moe_layers")
READER_EXPORTS = {"gmm_roofline.train": "gmm_work",
                  "mla_flash_roofline.train": "flash_work"}


@pytest.mark.parametrize("name", registry.names("families"))
def test_family_file(name):
    family = registry.code("families", name)
    wanted = set(FAMILY_EXPORTS)
    cells = [registry.workload(c) for c in registry.names("workloads")]
    cells = [c for c in cells if c["config_file"].get("family") == name]
    for cell in cells:
        if cell["runner"] == "train_family":
            wanted |= set(TRAIN_FAMILY_EXPORTS)
        wanted |= {export for metric, export in READER_EXPORTS.items()
                   if metric in cell["per_layer"]}
    assert not [export for export in wanted if not hasattr(family, export)]
    assert all(isinstance(k, str) for k in family.WIDTH_KEYS)
    if name == "llama":   # the dense family lists neither roofline
        assert not hasattr(family, "gmm_work")
        assert not hasattr(family, "flash_work")


@pytest.mark.parametrize("name", registry.names("traffic"))
def test_traffic_file(name):
    assert hasattr(
        registry.code("generators", registry.traffic(name)["generator"]),
        "generate")


def check_no_cell_runs_a_preset():
    """Every file of perf/configs/, built through its own family."""
    from tpu_trainer.models.config import GPTConfig

    presets = [GPTConfig.preset(n) for n in ("small", "medium", "large", "xl")]
    for name in registry.names("configs"):
        cfg = registry.config(name)
        got = registry.family(cfg).gpt_config(cfg)
        assert all((got.hidden_size, got.num_layers, got.vocab_size)
                   != (p.hidden_size, p.num_layers, p.vocab_size)
                   for p in presets)


def test_no_cell_runs_a_preset():
    check_no_cell_runs_a_preset()


# The program's GPTConfig each committed file built at the parent of PR 34
# (the fields that differ from GPTConfig's defaults), written down from that
# tree: the `family` keys and JoyAI's published `tie_word_embeddings` change
# nothing that is built.
BUILT = {
    "smollm2-360m": dict(
        attention_dropout=0.0, dropout=0.0, hidden_size=960,
        intermediate_size=2560, max_seq_len=8192, num_heads=15,
        num_kv_heads=5, num_layers=32, rope_theta=100000.0, vocab_size=49152),
    "smollm2-1.7b": dict(
        attention_dropout=0.0, dropout=0.0, hidden_size=2048,
        intermediate_size=8192, max_seq_len=8192, num_heads=32,
        num_kv_heads=32, num_layers=24, rope_theta=130000.0,
        vocab_size=49152),
    "lfm2-24b-a2b-ep8": dict(
        attention_dropout=0.0, dropout=0.0, hidden_size=2048,
        intermediate_size=11776,
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        max_seq_len=128000, moe_aux_weight=0.0, moe_experts_held=(0, 8),
        moe_impl="dropless", moe_intermediate_size=1536,
        moe_router="sigmoid", moe_top_k=4, norm_eps=1e-05,
        num_dense_layers=1, num_experts=64, num_heads=32, num_kv_heads=8,
        num_layers=5, qk_norm=True, rope_theta=1000000.0, vocab_size=8192),
    "joyai-llm-flash-ep32": dict(
        attention_dropout=0.0, dropout=0.0, hidden_size=2048,
        intermediate_size=7168, kv_lora_rank=512, max_seq_len=131072,
        moe_aux_weight=0.0, moe_experts_held=(0, 8), moe_gate_eps=1e-20,
        moe_impl="dropless", moe_intermediate_size=768, moe_routed_scale=2.5,
        moe_router="sigmoid", moe_shared_experts=1, moe_top_k=8, mtp_layers=1,
        num_dense_layers=1, num_experts=256, num_heads=32, num_kv_heads=32,
        num_layers=5, q_lora_rank=1536, qk_nope_head_dim=128,
        qk_rope_head_dim=64, rope_interleave=True, rope_theta=32000000.0,
        tie_word_embeddings=False, v_head_dim=128, vocab_size=16160),
}


@pytest.mark.parametrize("name", sorted(BUILT))
def test_the_committed_files_build_what_they_built(name):
    import dataclasses

    from tpu_trainer.models.config import GPTConfig

    cfg = registry.config(name)
    got = dataclasses.asdict(registry.family(cfg).gpt_config(cfg))
    default = dataclasses.asdict(GPTConfig())
    assert {k: v for k, v in got.items() if v != default[k]} == BUILT[name]


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


# A family file and a configuration no family of today could state: an
# attention width (4 heads x 32) that is not the hidden size (96), an untied
# head under its published key, none of the dense family's keys
# (`intermediate_size`, `rope_theta`, `num_key_value_heads`), a layer
# pattern under a key of its own. Its `gpt_config` returns any small GPTConfig.
SCRATCH_FAMILY = '''
from tpu_trainer.models.config import GPTConfig

WIDTH_KEYS = ("hidden_size", "head_dim", "num_attention_heads", "ffn_width")


def gpt_config(cfg, **options):
    if cfg["tie_word_embeddings"]:
        raise ValueError("this family's head is its own matrix")
    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=len(cfg["block_pattern"]),
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["ffn_width"],
        max_seq_len=cfg["max_position_embeddings"],
        tie_word_embeddings=False, **options)


def param_count(cfg):
    return gpt_config(cfg).num_parameters()
'''
SCRATCH_CONFIG = {
    "name": "scratch-config", "family": "scratch_family",
    "source": "https://huggingface.co/scratch/scratch/blob/main/config.json",
    "deployment": "none: a file of the registry's tests",
    "hidden_size": 96, "num_attention_heads": 4, "head_dim": 32,
    "tie_word_embeddings": False, "block_pattern": "MEM*", "ffn_width": 128,
    "vocab_size": 512, "max_position_embeddings": 256,
    "reduced": [], "reduced_why": {}, "assumed": {},
    "reference_tolerance": {"bf16": {
        "logit_rel_rms": 0.1, "logit_max_over_rms": 0.5, "loss_rel": 0.01,
        "why": "none measured: a file of the registry's tests"}}}


def test_a_new_cell_needs_new_files_only(tmp_path):
    """A dummy configuration, traffic mix, cell, metric and reader, a metric
    and its reader for a cell that STANDS, and a family with a configuration
    no family of today could state, are added to a scratch copy of the
    benchmark as files; the harness resolves both cells and reads both
    metrics, every configuration there passes the registry's checks through
    its own family, and no file that was there has changed."""
    copy = tmp_path / "perf"
    ignore = shutil.ignore_patterns("__pycache__", "out")
    shutil.copytree(registry.ROOT, copy, ignore=ignore)
    shutil.copytree(os.path.dirname(__file__), tmp_path / "tests" / "perf",
                    ignore=ignore)
    for name in ("BENCHMARK.json", os.path.join("tests", "__init__.py")):
        shutil.copy(os.path.join(registry.CHECKOUT, name), tmp_path / name)
    before = _digest(tmp_path)
    for name in registry.names("families"):
        with pytest.raises((KeyError, ValueError)):
            registry.code("families", name).gpt_config(SCRATCH_CONFIG)
    (copy / "families" / "scratch_family.py").write_text(SCRATCH_FAMILY)
    (copy / "configs" / "scratch-config.json").write_text(
        json.dumps(SCRATCH_CONFIG))
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
    # A metric on a STANDING cell: the metric's file names the cell, its
    # reader beside it, and no file of the cell is touched.
    (copy / "metrics" / "standing_ms.train.json").write_text(json.dumps({
        "unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "trainer", "moves": "train_tokens_per_s",
        "reader": "standing_reader",
        "workloads": ["train-lfm2-24b-ep8-1chip"]}))
    (copy / "readers" / "standing_reader.py").write_text(
        "def read(obs):\n    return obs.counters['x'] / 3\n")
    cell = dict(registry._load("workloads", "train-360m-1chip"),
                name="dummy-cell", config="dummy-config",
                traffic="dummy-mix")
    cell["per_layer"] = cell["per_layer"] + ["dummy_ms.train"]
    (copy / "workloads" / "dummy-cell.json").write_text(json.dumps(cell))
    script = (
        "import sys\n"
        f"sys.path.append({registry.CHECKOUT!r})  # for tpu_trainer alone\n"
        "from perf import registry, harness\n"
        "from tests.perf import test_perf_registry as checks\n"
        "assert checks.registry is registry\n"
        "cell = registry.workload('dummy-cell')\n"
        "assert cell['traffic_file']['seq_len'] == 1024\n"
        "obs = harness.Observations(cell=cell, "
        "spans=harness.Spans(), window=(0.0, 1.0), counters={'x': 21.0})\n"
        "got = harness.read_per_layer(cell, obs)\n"
        "assert got['dummy_ms.train'] == {'value': 42.0, 'unit': 'ms'}, got\n"
        "assert 'flash_roofline.train' not in got  # nothing to read\n"
        "assert 'standing_ms.train' not in cell['per_layer']\n"
        "standing = registry.workload('train-lfm2-24b-ep8-1chip')\n"
        "own = registry._load('workloads', standing['name'])['per_layer']\n"
        "assert standing['per_layer'][:len(own)] == own\n"
        "assert 'standing_ms.train' in standing['per_layer'][len(own):]\n"
        "obs.cell = standing\n"
        "got = harness.read_per_layer(standing, obs)\n"
        "assert got['standing_ms.train'] == {'value': 7.0, 'unit': 'ms'}\n"
        "names = registry.names('configs')\n"
        "assert {'scratch-config', 'dummy-config'} < set(names)\n"
        "for name in names:\n"
        "    checks.check_config_file(name)\n"
        "checks.check_no_cell_runs_a_preset()\n"
        "print(registry.ROOT, checks.__file__)\n")
    done = subprocess.run(
        [sys.executable, "-c", script], cwd=tmp_path, text=True,
        capture_output=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert done.returncode == 0, done.stderr
    assert done.stdout.split() == [
        str(copy), str(tmp_path / "tests" / "perf" / "test_perf_registry.py")]
    after = _digest(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 9
