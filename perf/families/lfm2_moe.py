"""The ``lfm2_moe`` family (LiquidAI LFM2-MoE): what the benchmark knows of it.

Three things, kept with the benchmark so that no PR that claims a gain can
change them: the plain reference (forward pass and next-token loss; gradients
by ``jax.grad``), the mapping from a configuration file to the program's
``GPTConfig``, and the operation and byte counts.

**The reference** is straightforward ``jax.numpy``, float32, every matmul at
``jax.default_matmul_precision("highest")``; no kernels, no sorting of
tokens: every held expert is applied to every token and masked by the
routing. Written from the published ``config.json`` and the public ``lfm2`` /
``lfm2_moe`` modelling code:

- layer: ``h = x + op(norm_op(x))``, ``y = h + ffn(norm_ffn(h))``, RMSNorm
  ``n(x) = x / sqrt(mean(x^2) + norm_eps) * w``; one RMSNorm after the last
  layer, logits through the tied embedding.
- ``full_attention``: GQA, no biases; q and k each get an RMSNorm over their
  head's ``head_dim`` (a ``[head_dim]`` weight each) BEFORE RoPE (half-split
  layout, base ``rope_theta``); causal softmax scaled by ``1/sqrt(head_dim)``.
- ``conv`` (gated short convolution): ``[B, C, u] = split3(x W_in)``,
  ``z = B * u``, ``v_t = sum_j w[:, j] z_{t-(L-1-j)}`` per channel
  (``L = conv_L_cache``, depthwise, causal, zeros before the sequence, no
  bias), ``op = (C * v) W_out``.
- dense ffn (the first ``num_dense_layers`` layers): ``W2 (silu(W1 x) * W3 x)``.
- expert ffn: ``s = sigmoid(x W_r)`` in f32 over ALL published experts; the
  ``num_experts_per_tok`` experts are the largest of ``s + b`` (``b`` selects
  only); weights ``g = s[sel] / (sum(s[sel]) + 1e-6)`` (the published
  ``routed_scaling_factor`` is 1; a file with another is refused); the result
  is ``sum_j g_j expert_j(x)``. Given ``choice`` (the experts another
  computation picked, ``[batch, seq, k]`` ids a layer), ``sel`` is that
  choice and everything else is as above: near a tie a program that rounds
  its activations picks another expert than this float32 reference, and the
  comparison then measures the flips, not the arithmetic; the reference
  hands out its OWN choice beside, for whoever counts them.

Departures, each shared with the program and listed in the configuration
file: (1) only the experts ``experts_held_first .. + num_experts`` have
weights here; what the others would add is left out, and with all of
``num_experts_published`` held this is the uncut layer; (2) the vocabulary
may be a slice (a smaller vocabulary); (3) ``b`` is the constant zero it is
initialised to (no update rule is published); (4) the embedding is tied
(``assumed``). It depends on ``tpu_trainer/models/gpt.py`` only for the
NAMES of the parameter tree it reads: kernels are stored ``[in, out]``, and
the layers of one (operator, ffn) kind are stacked under
``layers_<operator>_<ffn>`` in published order.
"""

from __future__ import annotations

from typing import Mapping, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


# --- the configuration file -> the program ------------------------------------

def held(cfg: Mapping) -> tuple:
    """(first id, count) of the experts that have weights here."""
    return cfg.get("experts_held_first", 0), cfg["num_experts"]


def router_width(cfg: Mapping) -> int:
    return cfg.get("num_experts_published", cfg["num_experts"])


def gpt_config(cfg: Mapping, **options):
    """The program's GPTConfig at the configuration file's sizes."""
    from tpu_trainer.models.config import GPTConfig

    stated = {"norm_topk_prob": True, "use_expert_bias": True,
              "conv_bias": False, "conv_L_cache": 3,
              "routed_scaling_factor": 1}
    for key, value in stated.items():
        if cfg.get(key, value) != value:
            raise ValueError(
                f"configuration {cfg.get('name')!r} has {key}={cfg[key]!r}; "
                f"the program's sigmoid router / short convolution computes "
                f"{value!r} and has no option for it")
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
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        initializer_range=cfg["initializer_range"],
        dropout=0.0,
        attention_dropout=cfg["attention_dropout"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_experts=router_width(cfg),
        moe_experts_held=held(cfg),
        moe_top_k=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_router="sigmoid",
        moe_impl="dropless",
        moe_aux_weight=0.0,
        router_z_weight=0.0,
        qk_norm=True,
        norm_eps=cfg["norm_eps"],
        **options,
    )


def layer_kinds(cfg: Mapping) -> list:
    """(operator, ffn) of each layer, in the order they run."""
    return [("conv" if kind == "conv" else "attention",
             "dense" if i < cfg["num_dense_layers"] else "moe")
            for i, kind in enumerate(cfg["layer_types"])]


# --- the plain reference ------------------------------------------------------

def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(F32)


def _rope(x, theta):
    """x: [batch, seq, heads, d]; positions 0..seq-1; half-split layout."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)       # [seq, d]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention(h, p, cfg):
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    b, s, _ = h.shape
    f = lambda name: p[name]["kernel"].astype(F32)  # noqa: E731
    q = (h @ f("q_proj")).reshape(b, s, heads, d)
    k = (h @ f("k_proj")).reshape(b, s, kvh, d)
    v = (h @ f("v_proj")).reshape(b, s, kvh, d)
    q = _rope(_rms_norm(q, p["q_layernorm"]["weight"], eps), theta)
    k = _rope(_rms_norm(k, p["k_layernorm"]["weight"], eps), theta)
    k = jnp.repeat(k, heads // kvh, axis=2)
    v = jnp.repeat(v, heads // kvh, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(b, s, heads * d) @ f("o_proj")


def _short_conv(h, p, cfg):
    taps = cfg["conv_L_cache"]
    gate_in, gate_out, u = jnp.split(
        h @ p["in_proj"]["kernel"].astype(F32), 3, axis=-1)   # B, C, u
    z = gate_in * u
    w = p["conv_weight"].astype(F32)                          # [channels, L]
    seq = z.shape[1]
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))      # zeros before
    # v_t = sum_j w[:, j] * z_{t - (L - 1 - j)}: tap L-1 is the current one.
    v = sum(padded[:, j:j + seq] * w[:, j] for j in range(taps))
    return (gate_out * v) @ p["out_proj"]["kernel"].astype(F32)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def chose(ids, experts: int):
    """``[..., k]`` expert ids as a ``[..., experts]`` mask."""
    return jnp.any(ids[..., None] == jnp.arange(experts), axis=-2)


def routing(h, p, cfg, choice=None):
    """Dense routing weights ``[..., experts published]`` (zero where an
    expert was not chosen) in float32, and this router's own choice (ids
    ``[..., k]``). ``choice``: ids to route by in place of its own."""
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"].astype(F32))
    biased = scores + p["expert_bias"].astype(F32)
    _, own = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    chosen = chose(own if choice is None else choice, scores.shape[-1])
    picked = jnp.where(chosen, scores, 0.0)
    return picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6), own


def _experts(h, p, cfg, choice=None):
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
    """The layer's output and, of an expert layer, its router's own choice."""
    operator, ffn = kind
    eps = cfg["norm_eps"]
    h = _rms_norm(x, p["operator_norm"]["weight"], eps)
    x = x + (_short_conv(h, p["conv"], cfg) if operator == "conv"
             else _attention(h, p["attention"], cfg))
    h = _rms_norm(x, p["ffn_norm"]["weight"], eps)
    if ffn == "moe":
        out, own = _experts(h, p["moe_mlp"], cfg, choice)
        return x + out, own
    m = lambda name: p["mlp"][name]["kernel"].astype(F32)  # noqa: E731
    return x + _swiglu(h, m("gate_proj"), m("up_proj"), m("down_proj")), None


def forward_and_choices(params, tokens, cfg: Mapping, choice=None):
    """Logits ``[batch, seq, vocab]`` in float32 for ``tokens [batch, seq]``
    and, for each expert layer in the order they run, which experts its
    router chose (ids ``[batch, seq, k]``). ``cfg`` is the
    configuration file (the published key names). ``choice``: for each
    expert layer, the ids ``[batch, seq, k]`` to route by instead."""
    with jax.default_matmul_precision("highest"):
        embedding = params["embed_tokens"]["embedding"]
        x = embedding[tokens].astype(F32)
        given = iter(choice) if choice is not None else None
        seen: dict = {}
        chosen = []
        for kind in layer_kinds(cfg):
            i = seen.get(kind, 0)
            seen[kind] = i + 1
            stack = params["layers_" + "_".join(kind)]
            # Under `jax.grad` a layer keeps its input only: the float32
            # scores of one 4,096-token row are 2 GB a tensor.
            x, own = jax.checkpoint(
                lambda x, p, ids, kind=kind: _layer(x, p, kind, cfg, ids))(
                x, jax.tree_util.tree_map(lambda a: a[i], stack),
                next(given) if given is not None and kind[1] == "moe"
                else None)
            if own is not None:
                chosen.append(own)
        x = _rms_norm(x, params["norm"]["weight"], cfg["norm_eps"])
        return x @ embedding.astype(F32).T, chosen


def forward(params, tokens, cfg: Mapping, choice=None):
    return forward_and_choices(params, tokens, cfg, choice)[0]


def rows_loss(params, rows, cfg: Mapping, choice=None):
    """Mean next-token cross entropy over ``rows [n, seq]``: position ``i``
    predicts token ``i + 1``."""
    logp = jax.nn.log_softmax(
        forward(params, rows, cfg, choice)[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, rows[:, 1:, None], axis=-1))


def loss(params, tokens, cfg: Mapping, rows_per_pass: int = 1, choice=None):
    """``rows_loss`` of ``tokens [batch, seq]``, every row weighing the
    same, ``rows_per_pass`` rows at a time so that the ``[rows, heads, seq,
    seq]`` scores fit the chip."""
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
# training is 3 x forward; attention is counted causal and on attention
# layers only; the tied head is counted once; recomputation is not counted.

def _operator_params(cfg: Mapping, operator: str) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    if operator == "conv":
        return 3 * h * h + h * cfg["conv_L_cache"] + h * h
    kv = cfg["num_key_value_heads"] * d
    return 2 * h * h + 2 * h * kv + 2 * d       # q/o, k/v, the two QK-norms


def expert_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def param_count(cfg: Mapping) -> int:
    """Every parameter that lives here: the held experts, the sliced
    vocabulary (tied, counted once), the selection bias with the router."""
    h = cfg["hidden_size"]
    total = cfg["vocab_size"] * h + h
    for operator, ffn in layer_kinds(cfg):
        total += _operator_params(cfg, operator) + 2 * h
        if ffn == "moe":
            total += (cfg["num_experts"] * expert_params(cfg)
                      + h * router_width(cfg) + router_width(cfg))
        else:
            total += 3 * h * cfg["intermediate_size"]
    return total


def moe_layers(cfg: Mapping) -> int:
    return sum(ffn == "moe" for _, ffn in layer_kinds(cfg))


def even_rows_per_token(cfg: Mapping) -> float:
    """Rows a token brings to the experts held here, each expert layer, when
    routing is even."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / router_width(cfg)


def attention_flops_fwd(cfg: Mapping, seq_len: int) -> float:
    """Causal attention forward FLOPs of ONE sequence, attention layers."""
    layers = sum(op == "attention" for op, _ in layer_kinds(cfg))
    return (layers * 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
            * seq_len * (seq_len + 1) / 2)


def train_flops_per_token(cfg: Mapping, seq_len: int,
                          rows_per_token: Optional[float] = None) -> float:
    """Model FLOPs per trained token, forward + backward. The experts count
    by the rows they are given: ``rows_per_token`` a token and expert layer
    (what the program's counter read; the even share if not given)."""
    if rows_per_token is None:
        rows_per_token = even_rows_per_token(cfg)
    h = cfg["hidden_size"]
    matmul = cfg["vocab_size"] * h              # the tied head, once
    taps = 0.0
    for operator, ffn in layer_kinds(cfg):
        if operator == "conv":
            matmul += 4 * h * h
            taps += h * cfg["conv_L_cache"]     # a multiply-add a tap
        else:
            matmul += 2 * h * h + 2 * h * (
                cfg["num_key_value_heads"] * cfg["head_dim"])
        if ffn == "moe":
            matmul += h * router_width(cfg) + rows_per_token * expert_params(cfg)
        else:
            matmul += 3 * h * cfg["intermediate_size"]
    return (6.0 * (matmul + taps)
            + 3.0 * attention_flops_fwd(cfg, seq_len) / seq_len)


def mfu(cfg: Mapping, seq_len: int, tokens_per_s: float, chips: int,
        peak_flops_per_s: float,
        rows_per_token: Optional[float] = None) -> float:
    return (train_flops_per_token(cfg, seq_len, rows_per_token) * tokens_per_s
            / (chips * peak_flops_per_s))


def flash_train_flops(cfg: Mapping, seq_len: int, sequences: int) -> float:
    """What the attention kernels must compute for ``sequences`` sequences in
    one training step, forward and backward, causal (3 x forward)."""
    return 3.0 * attention_flops_fwd(cfg, seq_len) * sequences


def gmm_work(cfg: Mapping, rows: float, layer_passes: int,
             bytes_per_value: int = 2) -> dict:
    """FLOPs and least HBM bytes of the grouped matmuls of ``layer_passes``
    passes through an expert layer (forward AND backward each) that gave the
    held experts ``rows`` rows in all: a pass is 3 ``gmm`` forward (gate, up,
    down), 3 ``gmm`` (the inputs' gradients) and 3 ``tgmm`` (the weights')
    backward, each ``2 * rows * H * I`` FLOPs. Bytes: every call reads its
    rows' operands once and writes its result once in the compute type
    (``bytes_per_value``); a ``gmm`` reads the held experts' weights once a
    call, a ``tgmm`` writes their gradient once a call in float32."""
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["num_experts"] * h * inter
    flops = 9 * 2.0 * rows * h * inter
    row_values = (
        3 * (h + inter)             # forward: lhs in, result out, x 3
        + 3 * (h + inter)           # dgrad: cotangent in, gradient out
        + 3 * (h + inter))          # wgrad: lhs and cotangent in
    bytes_ = (rows * row_values * bytes_per_value
              + layer_passes * weights * (6 * bytes_per_value + 3 * 4))
    return {"flops": flops, "bytes": bytes_}
