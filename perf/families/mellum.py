"""The ``mellum`` family (Mellum 2: window and full attention mixed, three
window layers to one full, every layer a softmax-routed MoE with no shared
expert, an untied head): what the benchmark knows of it.

Three things, kept with the benchmark so that no PR that claims a gain can
change them: the plain reference (forward pass and loss; gradients by
``jax.grad``), the mapping from a configuration file to the program's
``GPTConfig``, and the operation and byte counts (``gmm_work``,
``flash_work``, ``window_flash_work``).

**The reference** is straightforward ``jax.numpy``, float32, every matmul at
``jax.default_matmul_precision("highest")``; no kernels, no blocks of keys
skipped, no sorting of tokens. Written from the published ``config.json``
keys:

- layer ``i`` (every layer has experts: ``mlp_layer_types`` all
  ``sparse``): ``h = x + attn_i(norm(x))``, ``out = h + moe(norm(h))``; a
  final norm and an untied head. Every norm is RMSNorm ``x rsqrt(mean(x^2)
  + eps) w``, eps ``rms_norm_eps``.
- attention (both kinds): ``q = x W_q`` in ``num_attention_heads`` heads,
  ``k = x W_k``, ``v = x W_v`` in ``num_key_value_heads`` heads of
  ``head_dim``, query head ``i`` reading K/V head ``i // (heads / kv
  heads)``; no bias, no QK-norm; RoPE in halves ``(j, j + d / 2)`` on q and
  k; ``softmax(q k^T / sqrt(head_dim) + mask) v``; ``o = attn W_o``.
- a ``sliding_attention`` layer: the mask keeps ``i - sliding_window < j
  <= i``; RoPE at ``rope_theta`` of its ``rope_parameters``, frequency ``j``
  of ``d / 2`` ``theta^(-2j / d)``.
- a ``full_attention`` layer: the mask keeps ``j <= i``; RoPE by YaRN
  (arXiv:2309.00071): frequency ``j`` is ``ramp_j theta_j / factor + (1 -
  ramp_j) theta_j`` with ``ramp_j = clip((j - low) / (high - low), 0, 1)``,
  ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``, ``c(beta)
  = d ln(original_max_position_embeddings / (2 pi beta)) / (2 ln theta)``;
  cos and sin both times ``attention_factor``.
- both are computed ``ROW_BLOCK`` query rows at a time, against the keys
  from the first any of them may see to the last of them (a way to fit
  memory: every masked score is still computed and masked).
- MoE: ``p = softmax(x W_r)`` over ALL published experts in f32; the
  ``num_experts_per_tok`` largest, renormalised to sum 1
  (``norm_topk_prob``); experts ``W2 (silu(W1 x) * W3 x)``; ``y = sum_j g_j
  E_j(x)``. Given ``choice`` (the experts another computation picked), the
  gates are ``p`` at that choice over its sum; the reference hands out its
  OWN choice beside, for whoever counts the flips.

Departures, each shared with the program and listed in the configuration
file: (1) only the experts ``experts_held_first .. + num_experts`` have
weights here; what the others would add is left out, and with all of
``num_experts_published`` held this is the uncut layer; (2) the vocabulary
may be a slice; (3) no router auxiliary loss (its weight is not in the
row); (4) no multi-token-prediction head (``described_as`` names one; the
row states no key for it). It depends on ``tpu_trainer/models/gpt.py`` only
for the NAMES of the parameter tree it reads: kernels are stored ``[in,
out]``, the layers of one kind are stacked under
``layers_sliding_attention_moe`` / ``layers_attention_moe``.
"""

from __future__ import annotations

import math
from typing import Mapping, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROW_BLOCK = 512

# A layer's kind -> the stack its layers lie under.
STACKS = {"sliding_attention": "layers_sliding_attention_moe",
          "full_attention": "layers_attention_moe"}


# --- the configuration file -> the program ------------------------------------

# Keys of a configuration file that `reduced` may never name.
WIDTH_KEYS = ("hidden_size", "intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads",
              "moe_intermediate_size", "num_experts_per_tok",
              "sliding_window")
# `perf/step_jaxpr.py`'s two layers: a window layer, then a full one.
STEP_CUT = {"num_hidden_layers": 2,
            "layer_types": ["sliding_attention", "full_attention"],
            "mlp_layer_types": ["sparse", "sparse"]}


def held(cfg: Mapping) -> tuple:
    """(first id, count) of the experts that have weights here."""
    return cfg.get("experts_held_first", 0), cfg["num_experts"]


def router_width(cfg: Mapping) -> int:
    return cfg.get("num_experts_published", cfg["num_experts"])


def layer_types(cfg: Mapping) -> list:
    types = list(cfg["layer_types"])
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"configuration {cfg.get('name')!r}: {len(types)} layer_types "
            f"for {cfg['num_hidden_layers']} layers")
    return types


def rope_parameters(cfg: Mapping, kind: str) -> dict:
    return cfg["rope_parameters"][kind]


def gpt_config(cfg: Mapping, **options):
    """The program's GPTConfig at the configuration file's sizes."""
    from tpu_trainer.models.config import GPTConfig

    stated = {"hidden_act": "silu", "norm_topk_prob": True,
              "tie_word_embeddings": False, "attention_bias": False,
              "use_sliding_window": True}
    for key, value in stated.items():
        if cfg.get(key, value) != value:
            raise ValueError(
                f"configuration {cfg.get('name')!r} has {key}={cfg[key]!r}; "
                f"the program computes {value!r} and has no option for it")
    if set(cfg["mlp_layer_types"]) != {"sparse"} or len(
            cfg["mlp_layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("every layer of the program has experts")
    types = layer_types(cfg)
    window = rope_parameters(cfg, "sliding_attention")
    full = rope_parameters(cfg, "full_attention")
    if window["rope_type"] != "default" or full["rope_type"] != "yarn" or (
            window["rope_theta"] != full["rope_theta"]):
        raise ValueError(
            "the program takes default RoPE on window layers and YaRN on "
            "full ones, at one theta")
    fields = dict(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        layer_types=tuple(types),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        attention_head_dim=cfg["head_dim"],
        rope_theta=float(window["rope_theta"]),
        sliding_window=cfg["sliding_window"],
        yarn_factor=float(full["factor"]),
        yarn_original_max_position=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        # Read and unused: no layer is dense.
        intermediate_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        initializer_range=cfg["initializer_range"],
        dropout=0.0,
        attention_dropout=cfg["attention_dropout"],
        norm_eps=cfg["rms_norm_eps"],
        num_experts=router_width(cfg),
        moe_experts_held=held(cfg),
        moe_top_k=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_router="softmax",
        moe_impl="dropless",
        moe_aux_weight=0.0,
        router_z_weight=0.0,
        tie_word_embeddings=False,
        **options,
    )
    try:
        return GPTConfig(**fields)
    except TypeError as e:
        # A program from before the family: fail at once, before the chip.
        raise SystemExit(f"perf.families.mellum: the program's GPTConfig "
                         f"cannot state this configuration: {e}")


# --- the plain reference ------------------------------------------------------

def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(F32)


def inv_freq(cfg: Mapping, kind: str):
    """A layer kind's RoPE frequencies, ``[head_dim / 2]``, and the factor
    on its cos and sin."""
    d = cfg["head_dim"]
    rope = rope_parameters(cfg, kind)
    theta = float(rope["rope_theta"])
    plain = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    if rope["rope_type"] == "default":
        return plain, 1.0

    def c(beta):
        return (d * math.log(rope["original_max_position_embeddings"]
                             / (2 * math.pi * beta)) / (2 * math.log(theta)))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    return (ramp * plain / rope["factor"] + (1.0 - ramp) * plain,
            float(rope["attention_factor"]))


def rope(x, cfg: Mapping, kind: str):
    """RoPE of ``x [batch, seq, heads, d]`` in halves, the layer kind's
    frequencies and factor."""
    freq, factor = inv_freq(cfg, kind)
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * jnp.cos(angles) + turned * jnp.sin(angles)) * factor


def window_of(cfg: Mapping, kind: str, seq: int) -> int:
    """How many keys back a position sees: ``sliding_window`` in a window
    layer, every earlier one in a full layer."""
    return cfg["sliding_window"] if kind == "sliding_attention" else seq


def attention(h, p, cfg: Mapping, kind: str):
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    batch, s, _ = h.shape
    f = lambda name: p[name]["kernel"].astype(F32)  # noqa: E731
    q = rope((h @ f("q_proj")).reshape(batch, s, heads, d), cfg, kind)
    k = rope((h @ f("k_proj")).reshape(batch, s, kv, d), cfg, kind)
    # Query head i reads key/value head i // (heads / kv).
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat((h @ f("v_proj")).reshape(batch, s, kv, d), heads // kv,
                   axis=2)
    scale = 1.0 / jnp.sqrt(F32(d))
    window = window_of(cfg, kind, s)
    pos = jnp.arange(s)

    def rows(lo):
        """Query rows ``lo .. lo + ROW_BLOCK`` against the keys from the
        first any of them sees to the last of them."""
        hi = min(lo + ROW_BLOCK, s)
        first = max(0, lo - window + 1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi],
                            k[:, first:hi]) * scale
        diff = pos[lo:hi, None] - pos[None, first:hi]
        seen = (diff >= 0) & (diff < window)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                          v[:, first:hi])

    # Under `jax.grad` a row block keeps its inputs only.
    out = jnp.concatenate(
        [jax.checkpoint(rows, static_argnums=0)(lo)
         for lo in range(0, s, ROW_BLOCK)], axis=1)
    return out.reshape(batch, s, heads * d) @ f("o_proj")


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def chose(ids, experts: int):
    """``[..., k]`` expert ids as a ``[..., experts]`` mask."""
    return jnp.any(ids[..., None] == jnp.arange(experts), axis=-2)


def routing(h, p, cfg, choice=None):
    """Dense routing weights ``[..., experts published]`` (zero where an
    expert was not chosen) in float32, and this router's own choice (ids
    ``[..., k]``). ``choice``: ids to route by in place of its own."""
    probs = jax.nn.softmax(h @ p["router"]["kernel"].astype(F32), axis=-1)
    _, own = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    picked = jnp.where(chose(own if choice is None else choice,
                             probs.shape[-1]), probs, 0.0)
    return picked / jnp.sum(picked, axis=-1, keepdims=True), own


def routed_experts(h, p, cfg, choice=None):
    """The routed sum over the experts held here, and the router's own
    choice."""
    first, count = held(cfg)
    weights, own = routing(h, p, cfg, choice)
    # Every held expert, every token: [held, ..., H], then masked by the
    # routing as it is summed.
    every = jax.vmap(_swiglu, in_axes=(None, 0, 0, 0))(
        h, p["experts_gate"].astype(F32), p["experts_up"].astype(F32),
        p["experts_down"].astype(F32))
    return jnp.einsum("e...h,...e->...h", every,
                      weights[..., first:first + count]), own


def _layer(x, p, kind, cfg, choice=None):
    """The layer's output and its router's own choice."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(_rms_norm(x, p["operator_norm"]["weight"], eps),
                      p["attention"], cfg, kind)
    routed, own = routed_experts(
        _rms_norm(x, p["ffn_norm"]["weight"], eps), p["moe_mlp"], cfg,
        choice)
    return x + routed, own


def forward_and_choices(params, tokens, cfg: Mapping, choice=None):
    """Logits ``[batch, seq, vocab]`` in float32 for ``tokens [batch, seq]``
    and, for each layer in order, which experts its router chose (ids
    ``[batch, seq, k]``). ``choice``: for each layer, the ids to route by
    instead."""
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"][tokens].astype(F32)
        given = iter(choice) if choice is not None else None
        seen: dict = {}
        chosen = []
        for kind in layer_types(cfg):
            i = seen.get(kind, 0)
            seen[kind] = i + 1
            # Under `jax.grad` a layer keeps its input only.
            x, own = jax.checkpoint(
                lambda x, p, ids, kind=kind: _layer(x, p, kind, cfg, ids))(
                x, jax.tree_util.tree_map(lambda a: a[i],
                                          params[STACKS[kind]]),
                next(given) if given is not None else None)
            chosen.append(own)
        x = _rms_norm(x, params["norm"]["weight"], cfg["rms_norm_eps"])
        return x @ params["lm_head"].astype(F32).T, chosen


def forward(params, tokens, cfg: Mapping, choice=None):
    return forward_and_choices(params, tokens, cfg, choice)[0]


def rows_loss(params, rows, cfg: Mapping, choice=None):
    """Next-token cross entropy over ``rows [n, seq]``: position ``i``
    predicts token ``i + 1``, a mean over the positions that have one."""
    logp = jax.nn.log_softmax(
        forward(params, rows, cfg, choice)[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, rows[:, 1:, None], axis=-1))


def loss(params, tokens, cfg: Mapping, rows_per_pass: int = 1, choice=None):
    """``rows_loss`` of ``tokens [batch, seq]``, every row weighing the
    same, ``rows_per_pass`` rows at a time."""
    batch, seq = tokens.shape
    split = lambda a: a.reshape(  # noqa: E731
        batch // rows_per_pass, rows_per_pass, *a.shape[1:])
    return jnp.mean(jax.lax.map(
        jax.checkpoint(lambda xs: rows_loss(params, xs[0], cfg, xs[1])),
        (split(tokens), None if choice is None
         else [split(c) for c in choice])))


# --- operations and bytes, from shapes alone ----------------------------------
#
# Conventions as perf/work.py: a matmul [m, k] x [k, n] is 2 m k n FLOPs;
# training is 3 x forward; attention counts the (query, key) pairs each
# layer's mask keeps (a window layer's band, a full layer's causal
# triangle); the untied head is a matmul a pass (the lookup is not);
# recomputation is not counted.

def layers_of(cfg: Mapping, kind: str) -> int:
    return layer_types(cfg).count(kind)


def attention_params(cfg: Mapping) -> int:
    """q, k, v, o."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return (2 * h * cfg["num_attention_heads"] * d
            + 2 * h * cfg["num_key_value_heads"] * d)


def expert_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_params(cfg: Mapping) -> int:
    """Every parameter of one layer (both kinds alike): attention, the held
    experts, the router, the two norms."""
    h = cfg["hidden_size"]
    return (attention_params(cfg) + cfg["num_experts"] * expert_params(cfg)
            + h * router_width(cfg) + 2 * h)


def param_count(cfg: Mapping) -> int:
    """Every parameter that lives here: the held experts, the sliced
    embedding and head, the final norm."""
    h = cfg["hidden_size"]
    return (2 * cfg["vocab_size"] * h + h
            + cfg["num_hidden_layers"] * _layer_params(cfg))


def moe_layers(cfg: Mapping) -> int:
    """Expert layers a token passes: every layer."""
    return cfg["num_hidden_layers"]


def even_rows_per_token(cfg: Mapping) -> float:
    """Rows a token brings to the experts held here, each expert layer, when
    routing is even."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / router_width(cfg))


def pairs(seq_len: int, window: int) -> int:
    """(query, key) pairs of one sequence that a mask keeping ``i - window <
    j <= i`` keeps."""
    window = min(window, seq_len)
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_flops_fwd(cfg: Mapping, seq_len: int,
                        kinds=("sliding_attention", "full_attention")
                        ) -> float:
    """Attention forward FLOPs of ONE sequence, every layer of ``kinds``:
    QK^T and PV a head over the pairs the layer's mask keeps."""
    per_pair = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    return sum(layers_of(cfg, kind) * per_pair
               * pairs(seq_len, window_of(cfg, kind, seq_len))
               for kind in kinds)


def train_flops_per_token(cfg: Mapping, seq_len: int,
                          rows_per_token: Optional[float] = None) -> float:
    """Model FLOPs per trained token, forward + backward. The routed experts
    count by the rows they are given: ``rows_per_token`` a token and expert
    layer (what the program's counter read; the even share if not given)."""
    if rows_per_token is None:
        rows_per_token = even_rows_per_token(cfg)
    h = cfg["hidden_size"]
    matmul = (cfg["num_hidden_layers"] * (
        attention_params(cfg) + h * router_width(cfg)
        + rows_per_token * expert_params(cfg)) + cfg["vocab_size"] * h)
    return 6.0 * matmul + 3.0 * attention_flops_fwd(cfg, seq_len) / seq_len


def mfu(cfg: Mapping, seq_len: int, tokens_per_s: float, chips: int,
        peak_flops_per_s: float,
        rows_per_token: Optional[float] = None) -> float:
    return (train_flops_per_token(cfg, seq_len, rows_per_token) * tokens_per_s
            / (chips * peak_flops_per_s))


def _attention_work(cfg: Mapping, seq_len: int, sequences: float, kinds,
                    bytes_per_value: int) -> dict:
    """FLOPs (3 x forward: the score recomputation inside the backward is
    not counted) and least HBM bytes of the attention kernels of the layers
    of ``kinds``, for ``sequences`` sequences. Bytes: the forward reads q,
    k and v (a K/V head once) and writes the result; the backward reads
    those, the result and its cotangent and writes the three gradients, each
    once in the compute type."""
    d = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    operands = q + 2 * kv
    values = seq_len * (operands + q                 # forward
                        + 2 * operands + 2 * q)      # backward
    layers = sum(layers_of(cfg, kind) for kind in kinds)
    return {"flops": (3.0 * attention_flops_fwd(cfg, seq_len, kinds)
                      * sequences),
            "bytes": layers * sequences * values * bytes_per_value}


def flash_work(cfg: Mapping, seq_len: int, sequences: float,
               bytes_per_value: int = 2) -> dict:
    """The attention kernels of every layer, forward and backward: a window
    layer's band of pairs, a full layer's causal triangle, the model's query
    heads over its K/V heads."""
    return _attention_work(cfg, seq_len, sequences, tuple(STACKS),
                           bytes_per_value)


def window_flash_work(cfg: Mapping, seq_len: int, sequences: float,
                      bytes_per_value: int = 2) -> dict:
    """``flash_work`` of the window layers alone."""
    return _attention_work(cfg, seq_len, sequences, ("sliding_attention",),
                           bytes_per_value)


def gmm_work(cfg: Mapping, rows: float, layer_passes: int,
             bytes_per_value: int = 2) -> dict:
    """FLOPs and least HBM bytes of the grouped matmuls of ``layer_passes``
    passes through an expert layer (forward AND backward each) that gave the
    held experts ``rows`` rows in all: a pass is 3 ``gmm`` forward (gate,
    up, down), 3 ``gmm`` (the inputs' gradients) and 3 ``tgmm`` (the
    weights') backward, each ``2 * rows * H * I`` FLOPs. Bytes: every call
    reads its rows' operands once and writes its result once in the compute
    type; a ``gmm`` reads the held experts' weights once a call, a ``tgmm``
    writes their gradient once a call in float32."""
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["num_experts"] * h * inter
    return {"flops": 9 * 2.0 * rows * h * inter,
            "bytes": (rows * 9 * (h + inter) * bytes_per_value
                      + layer_passes * weights * (6 * bytes_per_value + 3 * 4))}
