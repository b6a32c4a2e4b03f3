"""Operations the algorithm needs, from shapes alone.

The benchmark's own count, kept here so that no PR that claims a gain can
change it. Conventions, all stated once:

- A matmul of ``[m, k] x [k, n]`` is ``2 m k n`` FLOPs.
- Training counts forward + backward = 3 x forward. Recomputation is not
  counted: ``mfu`` is model FLOPs, not hardware FLOPs.
- Attention is counted CAUSAL: query ``i`` sees ``i + 1`` keys, so one
  sequence of ``S`` tokens costs ``2 * 2 * heads * d * S (S + 1) / 2``
  forward FLOPs (QK^T and PV). ``tpu_trainer.utils.logging.flops_per_token``
  counts the full ``S^2`` square; this one does not.
- The embedding lookup is a gather (no FLOPs); the tied head is one matmul
  and counted once.
"""

from __future__ import annotations

from typing import Mapping


def _sizes(cfg: Mapping) -> tuple:
    h = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    kvh = cfg.get("num_key_value_heads", heads)
    d = cfg.get("head_dim", h // heads)
    return (h, cfg["num_hidden_layers"], heads, kvh, d,
            cfg["intermediate_size"], cfg["vocab_size"])


def param_count(cfg: Mapping) -> int:
    """Every parameter of the model, the tied embedding counted once."""
    h, layers, heads, kvh, d, inter, vocab = _sizes(cfg)
    attn = h * heads * d + 2 * h * kvh * d + heads * d * h
    mlp = 3 * h * inter
    norms = 2 * h
    return vocab * h + layers * (attn + mlp + norms) + h


def matmul_param_count(cfg: Mapping) -> int:
    """Parameters that sit in a matmul a token flows through: the layers'
    projections plus the tied head once (the lookup is not a matmul)."""
    h, layers, heads, kvh, d, inter, vocab = _sizes(cfg)
    attn = h * heads * d + 2 * h * kvh * d + heads * d * h
    return layers * (attn + 3 * h * inter) + vocab * h


def attention_flops_fwd(cfg: Mapping, seq_len: int) -> float:
    """Causal attention forward FLOPs of ONE sequence, all layers."""
    _, layers, heads, _, d, _, _ = _sizes(cfg)
    return layers * 2 * 2 * heads * d * seq_len * (seq_len + 1) / 2


def train_flops_per_token(cfg: Mapping, seq_len: int) -> float:
    """Model FLOPs per trained token: 6 x matmul parameters plus causal
    attention forward and backward (3 x forward) spread over the sequence
    (which is 6 * layers * (S + 1) * heads * d per token)."""
    return (6.0 * matmul_param_count(cfg)
            + 3.0 * attention_flops_fwd(cfg, seq_len) / seq_len)


def mfu(cfg: Mapping, seq_len: int, tokens_per_s: float, chips: int,
        peak_flops_per_s: float) -> float:
    return (train_flops_per_token(cfg, seq_len) * tokens_per_s
            / (chips * peak_flops_per_s))


def flash_train_flops(cfg: Mapping, seq_len: int, sequences: int) -> float:
    """What the attention kernels must compute for ``sequences`` sequences
    in one training step, forward and backward, causal (3 x forward; the
    forward recomputation inside the backward kernel is not counted)."""
    return 3.0 * attention_flops_fwd(cfg, seq_len) * sequences
