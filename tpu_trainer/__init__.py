"""tpu_trainer — a TPU-native distributed LLM training framework.

Brand-new JAX/XLA/Pallas/GSPMD re-design with the capabilities of the
reference PyTorch/NCCL trainer (``zhc180/distributed-llm-trainer``) and
beyond: LLaMA-style GPT (plus a routed-MoE variant), one GSPMD train step
covering DDP / ZeRO-2/3 / hybrid / tensor / sequence (ring attention) /
expert parallelism, a GPipe pipeline schedule, Pallas flash attention with
in-kernel dropout and RoPE, KV-cached generation, Orbax sharded
checkpointing with auto-resume and preemption handling, host-offloaded
optimizer state, and dummy/TinyStories/OpenWebText data with a native C
tokenize fast path. See SURVEY.md at the repo root for the
component-by-component parity map and PERF.md / PERF_LEDGER.jsonl for
measured numbers.
"""

__version__ = "0.2.0"  # keep in sync with pyproject.toml

from tpu_trainer.models.config import GPTConfig
from tpu_trainer.models.gpt import (
    GPT, count_parameters, generate, generate_bucketed, generate_kv,
)

__all__ = [
    "GPTConfig", "GPT", "count_parameters", "generate",
    "generate_bucketed", "generate_kv",
    "__version__",
]
