"""CLI + YAML config tests (SURVEY.md C28/C29, §5.6).

The reference documents ``--config configs/*.yaml`` but never loads YAML
(SURVEY.md §0.1); these tests pin down that our CLI actually does, with the
documented precedence (CLI flags > YAML > dataclass defaults), and that the
training driver runs end to end — including the auto-resume path that the
reference left dead.
"""

import dataclasses
import os

import pytest

from tpu_trainer.training.cli import build_parser, resolve_configs, run_training

TINY_YAML = """
model:
  name: "gpt2-small"
  vocab_size: 128
  hidden_size: 32
  num_layers: 1
  num_heads: 2
  intermediate_size: 64
  max_seq_len: 32
  dropout: 0.0
  attention_dropout: 0.0
  use_flash_attention: false
training:
  batch_size: 2
  gradient_accumulation_steps: 2
  learning_rate: 1e-3
  max_steps: 3
  warmup_steps: 1
  log_interval: 10
  eval_interval: 100
  save_interval: 100
distributed:
  mixed_precision: "fp32"
data:
  dataset: "dummy"
"""


@pytest.fixture
def tiny_yaml(tmp_path):
    p = tmp_path / "tiny.yaml"
    p.write_text(TINY_YAML)
    return str(p)


class TestConfigResolution:
    def test_yaml_is_actually_loaded(self, tiny_yaml):
        args = build_parser("ddp").parse_args(["--config", tiny_yaml])
        model, train, parallel, data = resolve_configs(args, "ddp")
        assert model.hidden_size == 32
        assert model.num_layers == 1
        assert train.learning_rate == pytest.approx(1e-3)  # str-float coerced
        assert train.gradient_accumulation_steps == 2
        assert data["dataset"] == "dummy"

    def test_cli_overrides_yaml(self, tiny_yaml):
        args = build_parser("ddp").parse_args(
            ["--config", tiny_yaml, "--batch_size", "4", "--max_steps", "7",
             "--learning_rate", "5e-4"]
        )
        _, train, _, _ = resolve_configs(args, "ddp")
        assert train.batch_size == 4
        assert train.max_steps == 7
        assert train.learning_rate == pytest.approx(5e-4)

    def test_defaults_without_yaml(self):
        args = build_parser("ddp").parse_args([])
        model, train, parallel, _ = resolve_configs(args, "ddp")
        assert model.hidden_size == 768          # small preset
        assert train.learning_rate == pytest.approx(6e-4)
        assert parallel.sharding_strategy == "replicated"
        assert parallel.mesh.data == -1 and parallel.mesh.fsdp == 1

    def test_fsdp_mode_reference_spellings(self, tiny_yaml):
        for spelling, mesh_fsdp in [("FULL_SHARD", -1), ("SHARD_GRAD_OP", -1)]:
            args = build_parser("fsdp").parse_args(
                ["--config", tiny_yaml, "--sharding", spelling]
            )
            _, _, parallel, _ = resolve_configs(args, "fsdp")
            assert parallel.sharding_strategy == spelling
            assert parallel.mesh.fsdp == mesh_fsdp

    def test_fsdp_activation_checkpointing_default_on(self, tiny_yaml):
        # reference fsdp_trainer.py:312-328: ON unless --no_activation_checkpointing
        args = build_parser("fsdp").parse_args(["--config", tiny_yaml])
        model, _, _, _ = resolve_configs(args, "fsdp")
        assert model.gradient_checkpointing
        args = build_parser("fsdp").parse_args(
            ["--config", tiny_yaml, "--no_activation_checkpointing"]
        )
        model, _, _, _ = resolve_configs(args, "fsdp")
        assert not model.gradient_checkpointing

    def test_offload_dtype_choices_reach_parallel_config(self, tiny_yaml):
        # VERDICT r4 weak #4: int8 (the 8-bit offloaded optimizer state)
        # must be reachable from the production CLI.
        for dt in ("float32", "bfloat16", "int8"):
            args = build_parser("fsdp").parse_args(
                ["--config", tiny_yaml, "--cpu_offload",
                 "--offload_dtype", dt]
            )
            _, _, parallel, _ = resolve_configs(args, "fsdp")
            assert parallel.cpu_offload
            assert parallel.offload_dtype == dt

    def test_offload_dtype_from_yaml(self, tmp_path):
        p = tmp_path / "off.yaml"
        p.write_text(TINY_YAML + "fsdp:\n  cpu_offload: true\n"
                     "  offload_dtype: \"int8\"\n")
        args = build_parser("fsdp").parse_args(["--config", str(p)])
        _, _, parallel, _ = resolve_configs(args, "fsdp")
        assert parallel.cpu_offload and parallel.offload_dtype == "int8"

    def test_all_shipped_configs_parse(self):
        # Every YAML under configs/ must resolve through the CLI layering
        # (schema drift between shipped examples and the loader is a user-
        # facing break the suite should catch).
        import glob

        cfgs = sorted(glob.glob(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "configs", "*.yaml")))
        assert cfgs, "no shipped configs found"
        for path in cfgs:
            for mode in ("ddp", "fsdp"):
                args = build_parser(mode).parse_args(["--config", path])
                model, train, parallel, data = resolve_configs(args, mode)
                assert model.num_parameters() > 0, path

    def test_fault_tolerance_flags_parse_for_all_shipped_configs(self):
        # The rollback/GC/injection flags must layer over every shipped
        # YAML — an example config that rejects --keep_last_n would make
        # the fault-tolerance docs a lie.
        import glob

        cfgs = sorted(glob.glob(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "configs", "*.yaml")))
        assert cfgs, "no shipped configs found"
        for path in cfgs:
            for mode in ("ddp", "fsdp"):
                args = build_parser(mode).parse_args(
                    ["--config", path, "--keep_last_n", "2",
                     "--max_rollbacks", "3", "--skip_batches_on_rollback",
                     "2", "--rollback_lr_backoff", "0.25",
                     "--inject_fault", "nan_loss@5"])
                _, _, _, data = resolve_configs(args, mode)
                assert data["keep_last_n"] == 2, path
                assert data["max_rollbacks"] == 3, path
                assert data["skip_batches_on_rollback"] == 2, path
                assert data["rollback_lr_backoff"] == 0.25, path
                assert data["inject_fault"] == "nan_loss@5", path

    def test_fault_tolerance_yaml_section(self, tmp_path):
        p = tmp_path / "ft.yaml"
        p.write_text(TINY_YAML + "checkpoint:\n  keep_last_n: 3\n"
                     "fault_tolerance:\n  max_rollbacks: 5\n"
                     "  skip_batches_on_rollback: 0\n"
                     "  rollback_lr_backoff: 1.0\n")
        args = build_parser("ddp").parse_args(["--config", str(p)])
        _, _, _, data = resolve_configs(args, "ddp")
        assert data["keep_last_n"] == 3
        assert data["max_rollbacks"] == 5
        assert data["skip_batches_on_rollback"] == 0
        assert data["rollback_lr_backoff"] == 1.0
        # ...and the documented defaults with no section at all.
        args = build_parser("ddp").parse_args([])
        _, _, _, data = resolve_configs(args, "ddp")
        assert data["keep_last_n"] == 0
        assert data["max_rollbacks"] == 2
        assert data["skip_batches_on_rollback"] == 1
        assert data["rollback_lr_backoff"] == 0.5

    def test_optimizer_state_dtype_reaches_training_config(self, tiny_yaml):
        for dt in ("float32", "bfloat16", "int8"):
            args = build_parser("ddp").parse_args(
                ["--config", tiny_yaml, "--optimizer_state_dtype", dt]
            )
            _, train, _, _ = resolve_configs(args, "ddp")
            assert train.optimizer_state_dtype == dt
        # YAML spelling (training: section)
        args = build_parser("ddp").parse_args(["--config", tiny_yaml])
        _, train, _, _ = resolve_configs(args, "ddp")
        assert train.optimizer_state_dtype == "float32"  # default

    def test_offload_dtype_yaml_rejects_unknown(self, tmp_path):
        # The YAML path must enforce the same choice list as argparse:
        # an unknown dtype (int16) would flow into jnp.dtype() as a
        # storage cast that silently truncates Adam moments to zero.
        p = tmp_path / "bad.yaml"
        p.write_text(TINY_YAML + "fsdp:\n  cpu_offload: true\n"
                     "  offload_dtype: \"int16\"\n")
        args = build_parser("fsdp").parse_args(["--config", str(p)])
        with pytest.raises(SystemExit):
            resolve_configs(args, "fsdp")

    def test_hybrid_shard_requires_mesh_split(self, tiny_yaml):
        args = build_parser("fsdp").parse_args(
            ["--config", tiny_yaml, "--sharding", "HYBRID_SHARD"]
        )
        with pytest.raises(SystemExit):
            resolve_configs(args, "fsdp")


class TestEndToEnd:
    def test_ddp_train_and_auto_resume(self, tiny_yaml, tmp_path, capsys):
        ckpt = str(tmp_path / "ck")
        rc = run_training(
            ["--config", tiny_yaml, "--checkpoint_dir", ckpt,
             "--num_batches", "8", "--eval_batches", "1"],
            mode="ddp",
        )
        assert rc == 0
        assert os.path.isdir(os.path.join(ckpt, "step_00000003"))
        capsys.readouterr()
        # Second invocation auto-resumes from step 3 and trains 2 more.
        rc = run_training(
            ["--config", tiny_yaml, "--checkpoint_dir", ckpt,
             "--num_batches", "8", "--max_steps", "5", "--eval_batches", "1"],
            mode="ddp",
        )
        assert rc == 0
        out = capsys.readouterr().out
        assert "resumed" in out and "step 3" in out
        assert os.path.isdir(os.path.join(ckpt, "step_00000005"))

    def test_text_dataset_map_and_streaming(self, tiny_yaml, tmp_path):
        """Real-data path through the CLI: tinystories loader, map-style and
        streaming. The model vocab covers every id either tokenizer (HF gpt2
        if cached, byte fallback with eos=50256 otherwise) can produce, so
        training runs on faithful, un-clamped labels."""
        yaml_path = tmp_path / "tiny_fullvocab.yaml"
        yaml_path.write_text(TINY_YAML.replace(
            "vocab_size: 128", "vocab_size: 50304"
        ))
        corpus = tmp_path / "stories.txt"
        corpus.write_text(
            "\n".join(f"story {i} " + "once upon a time " * 8 for i in range(60))
        )
        for extra in ([], ["--streaming", "--cache_max_tokens", "10000"]):
            ckpt = str(tmp_path / ("ck_txt" + ("_s" if extra else "")))
            rc = run_training(
                ["--config", str(yaml_path), "--checkpoint_dir", ckpt,
                 "--dataset", "tinystories", "--data_path", str(corpus),
                 "--tokenizer", "byte",
                 "--max_steps", "3", "--eval_batches", "1"] + extra,
                mode="ddp",
            )
            assert rc == 0
            assert os.path.isdir(os.path.join(ckpt, "step_00000003"))

    def test_tokenizer_fallback_is_opt_in_for_training(
        self, tiny_yaml, tmp_path, monkeypatch
    ):
        """VERDICT r1 weak #6: with no local HF cache, training on a text
        dataset must fail loudly unless the byte tokenizer is chosen
        explicitly — a silent byte-level run produces a checkpoint no GPT-2
        tokenizer can consume."""
        import transformers

        def no_cache(*a, **k):
            raise OSError("no local cache (test)")

        monkeypatch.setattr(
            transformers.GPT2TokenizerFast, "from_pretrained", no_cache
        )
        corpus = tmp_path / "stories.txt"
        corpus.write_text("\n".join("once upon a time " * 8 for _ in range(40)))
        # Full vocab: byte-tokenizer ids (<= eos 50256) must fit the model.
        yaml_path = tmp_path / "tiny_tok.yaml"
        yaml_path.write_text(
            TINY_YAML.replace("vocab_size: 128", "vocab_size: 50304")
        )
        args = ["--config", str(yaml_path), "--dataset", "tinystories",
                "--data_path", str(corpus),
                "--checkpoint_dir", str(tmp_path / "ck_tok")]
        with pytest.raises(RuntimeError, match="--tokenizer byte"):
            run_training(args, mode="ddp")
        # Explicit opt-in: same command + --tokenizer byte trains fine.
        rc = run_training(args + ["--tokenizer", "byte", "--max_steps", "2",
                                  "--eval_batches", "1"], mode="ddp")
        assert rc == 0

    def test_too_small_dataset_fails_loudly(self, tiny_yaml, tmp_path):
        corpus = tmp_path / "tiny.txt"
        corpus.write_text("just one short line\n")
        with pytest.raises((SystemExit, ValueError), match="tokens|batches"):
            run_training(
                ["--config", tiny_yaml, "--dataset", "tinystories",
                 "--data_path", str(corpus), "--tokenizer", "byte",
                 "--checkpoint_dir", str(tmp_path / "ck_small")],
                mode="ddp",
            )

    def test_eval_split_is_heldout_and_logged(self, tmp_path):
        """VERDICT r1 weak #5: eval must measure held-out data. Asserts
        (a) train/eval chunk indices are disjoint and cover the corpus,
        (b) the eval loss lands in the metrics JSONL with perplexity."""
        import json

        from tpu_trainer.data.text import ChunkSubset, create_text_dataloader

        corpus = tmp_path / "stories.txt"
        corpus.write_text(
            "\n".join(f"story {i} " + "once upon a time " * 8
                      for i in range(200))
        )
        loader = create_text_dataloader(
            str(corpus), batch_size=2, seq_len=32, tokenizer_name="byte",
            eval_split=0.1,
        )
        train_ds, eval_ds = loader.dataset, loader.eval_loader.dataset
        assert isinstance(train_ds, ChunkSubset)
        assert isinstance(eval_ds, ChunkSubset)
        assert train_ds.dataset is eval_ds.dataset
        train_idx = set(range(train_ds.start, train_ds.stop))
        eval_idx = set(range(eval_ds.start, eval_ds.stop))
        assert train_idx.isdisjoint(eval_idx)
        assert train_idx | eval_idx == set(range(len(train_ds.dataset)))
        assert len(eval_idx) >= 1

        # End to end: eval records (with perplexity) in the metrics JSONL.
        yaml_path = tmp_path / "tiny_eval.yaml"
        yaml_path.write_text(
            TINY_YAML.replace("vocab_size: 128", "vocab_size: 50304")
        )
        jsonl = tmp_path / "metrics.jsonl"
        rc = run_training(
            ["--config", str(yaml_path), "--dataset", "tinystories",
             "--data_path", str(corpus), "--tokenizer", "byte",
             "--eval_split", "0.2", "--eval_interval", "2",
             "--max_steps", "2", "--eval_batches", "2",
             "--checkpoint_dir", str(tmp_path / "ck_ev"),
             "--metrics_jsonl", str(jsonl)],
            mode="ddp",
        )
        assert rc == 0
        records = [json.loads(l) for l in jsonl.read_text().splitlines()]
        evals = [r for r in records if r.get("kind") == "eval"]
        assert evals, records
        assert evals[-1]["perplexity"] > 0
        assert evals[-1]["eval_loss"] > 0

    def test_streaming_holdout_partitions_lines(self, tmp_path):
        from tpu_trainer.data.text import StreamingTextDataset

        corpus = tmp_path / "s.txt"
        corpus.write_text("\n".join(f"line {i} aaaa" for i in range(60)))

        def lines_of(holdout):
            ds = StreamingTextDataset(str(corpus), seq_len=4,
                                      tokenizer_name="byte", holdout=holdout)
            with open(str(corpus)) as f:
                return {i for i, _ in ds._sharded_lines(f)}

        train = lines_of(("train", 5))
        ev = lines_of(("eval", 5))
        assert train.isdisjoint(ev)
        assert train | ev == set(range(60))
        assert ev == {i for i in range(60) if i % 5 == 4}

    def test_fsdp_zero3_end_to_end(self, tiny_yaml, tmp_path):
        ckpt = str(tmp_path / "ck_fsdp")
        rc = run_training(
            ["--config", tiny_yaml, "--sharding", "FULL_SHARD",
             "--checkpoint_dir", ckpt, "--num_batches", "8",
             "--eval_batches", "1"],
            mode="fsdp",
        )
        assert rc == 0
        assert os.path.isdir(os.path.join(ckpt, "step_00000003"))


class TestMeshAuto:
    """--mesh auto + shared early mesh validation (ISSUE 11)."""

    def test_auto_conflicts_with_explicit_mesh(self, tiny_yaml):
        args = build_parser("fsdp").parse_args(
            ["--config", tiny_yaml, "--mesh", "auto", "--mesh_tensor", "2"])
        with pytest.raises(SystemExit, match="mutually exclusive"):
            resolve_configs(args, "fsdp")

    def test_infeasible_explicit_mesh_fails_at_startup(self, tiny_yaml):
        # TINY_YAML has 2 heads: tensor=8 can't split them. The shared
        # feasibility predicate rejects this at startup (before the Trainer
        # builds anything) with a pointer at --mesh auto.
        with pytest.raises(SystemExit, match="infeasible"):
            run_training(
                ["--config", tiny_yaml, "--mesh_tensor", "8",
                 "--num_batches", "8"],
                mode="fsdp",
            )

    def test_mesh_auto_end_to_end(self, tiny_yaml, tmp_path, capsys):
        import json

        import jax

        jsonl = str(tmp_path / "metrics.jsonl")
        rc = run_training(
            ["--config", tiny_yaml, "--mesh", "auto",
             "--checkpoint_dir", str(tmp_path / "ck"),
             "--metrics_jsonl", jsonl, "--num_batches", "8",
             "--eval_batches", "1"],
            mode="fsdp",
        )
        assert rc == 0
        out = capsys.readouterr().out
        assert "mesh_plan |" in out  # ranked table printed at startup
        recs = [json.loads(l) for l in open(jsonl)]
        plans = [r for r in recs if r.get("kind") == "mesh_plan"]
        assert len(plans) == 1
        rec = plans[0]
        assert rec["auto"] is True
        assert rec["schema_version"] == recs[0]["schema_version"]
        assert rec["chosen"] == rec["ranked"][0]
        prod = 1
        for v in rec["chosen"]["mesh"].values():
            prod *= v
        assert prod == jax.device_count()
        # CPU correctness mode never gets a stage mesh (SPMD PartitionId).
        assert rec["chosen"]["mesh"]["stage"] == 1
        # The run actually trained on the chosen split (goodput ledger is
        # the final record; 3 steps is below log_interval so no train rows).
        assert any(r.get("kind") == "goodput" for r in recs)
