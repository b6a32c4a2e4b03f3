"""The one place the benchmark touches the system under test: it turns a
configuration file into the program's own ``GPTConfig`` and builds the
program's own ``Trainer`` through its normal constructor. Nothing here
changes what the program computes."""

from __future__ import annotations

from typing import Mapping

# What tpu_trainer/models/gpt.py fixes in code and a configuration must
# therefore state: a file that says otherwise cannot be run as written.
PROGRAM_FIXES = {
    "rms_norm_eps": 1e-06,
    "hidden_act": "silu",
    "attention_bias": False,
    "mlp_bias": False,
    "tie_word_embeddings": True,
    "rope_scaling": None,
}


# The compute type every training cell runs in; a configuration's
# ``reference_tolerance`` is keyed by it.
COMPUTE_TYPE = "bf16"


def gpt_config(cfg: Mapping, **options):
    """The program's GPTConfig at the configuration file's sizes."""
    from tpu_trainer.models.config import GPTConfig

    for key, fixed in PROGRAM_FIXES.items():
        if cfg.get(key, fixed) != fixed:
            raise ValueError(
                f"configuration {cfg.get('name')!r} has {key}={cfg[key]!r}; "
                f"the program computes {fixed!r} and has no option for it")
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise ValueError("the program's head_dim is hidden_size / heads")
    return GPTConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        initializer_range=cfg["initializer_range"],
        dropout=0.0,
        attention_dropout=cfg["attention_dropout"],
        **options,
    )


def build_trainer(cfg: Mapping, traffic: Mapping, job: Mapping, devices):
    """The program's Trainer for one training cell. ``job`` names only
    options the program already has."""
    from tpu_trainer.parallel.mesh import MeshConfig, make_mesh
    from tpu_trainer.training.config import TrainingConfig
    from tpu_trainer.training.trainer import ParallelConfig, Trainer

    remat = job["remat"]  # "none" | "full" | "dots"
    model_config = gpt_config(
        cfg,
        use_flash_attention=True,
        fused_loss=True,
        fused_loss_pallas=True,
        gradient_checkpointing=remat != "none",
        remat_policy=remat if remat != "none" else "full",
        scan_unroll=job["scan_unroll"],
    )
    mesh_config = MeshConfig(**job["mesh"])
    dp = job["mesh"].get("data", 1) * job["mesh"].get("fsdp", 1)
    rows = traffic["tokens_per_step"] // traffic["seq_len"]
    if job["micro_batch"] * job["grad_accum"] * dp != rows:
        raise ValueError(
            f"micro_batch x grad_accum x data shards = "
            f"{job['micro_batch']} x {job['grad_accum']} x {dp} is not the "
            f"traffic's {rows} sequences a step")
    training_config = TrainingConfig(
        batch_size=job["micro_batch"],
        gradient_accumulation_steps=job["grad_accum"],
        max_seq_len=traffic["seq_len"],
        mixed_precision=COMPUTE_TYPE,
        optimizer_state_dtype="float32",
        learning_rate=job["learning_rate"],
        warmup_steps=job["warmup_steps"],
        max_steps=job["schedule_steps"],
    )
    parallel_config = ParallelConfig(
        mesh=mesh_config, sharding_strategy=job["sharding_strategy"])
    return Trainer(model_config, training_config, parallel_config,
                   mesh=make_mesh(mesh_config, devices=list(devices)))
