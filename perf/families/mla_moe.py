"""The ``mla_moe`` family (DeepSeek-V3-style: latent attention, a sigmoid-
routed MoE with a shared expert, an untied head, a multi-token-prediction
module): what the benchmark knows of it.

Three things, kept with the benchmark so that no PR that claims a gain can
change them: the plain reference (forward pass and loss with the MTP term;
gradients by ``jax.grad``), the mapping from a configuration file to the
program's ``GPTConfig``, and the operation and byte counts.

**The reference** is straightforward ``jax.numpy``, float32, every matmul at
``jax.default_matmul_precision("highest")``; no kernels, no sorting of
tokens: every held expert is applied to every token and masked by the
routing; attention scores are a ``[heads, rows, seq]`` matrix, ``ROW_BLOCK``
query rows at a time. Written from the published ``config.json`` keys and the
DeepSeek-V3 technical report, sections 2.1-2.2:

- block: ``h = x + attn(norm1(x))``, ``y = h + ffn(norm2(h))``, RMSNorm
  ``n(x) = x / sqrt(mean(x^2) + rms_norm_eps) * w``; one RMSNorm after the
  last layer; logits ``= norm(y) W_head^T``, ``W_head`` its own parameter.
- latent attention (training order, nothing absorbed): ``c_q = rms(x W_qa)``,
  ``q = c_q W_qb`` -> ``[s, heads, nope + rope]``; ``[c_kv | k_r] = x W_kva``,
  ``c_kv = rms(c_kv)``, ``[k_nope | v] = c_kv W_kvb`` -> ``[s, heads, nope +
  v]``; RoPE (base ``rope_theta``; with ``rope_interleave`` the pairs are
  lanes ``(2i, 2i+1)``) on ``q``'s last ``rope`` lanes and on ``k_r``, which
  every head shares; scores ``(q_nope . k_nope + q_r . k_r) / sqrt(nope +
  rope)``, causal softmax, ``o = (P v) W_o``. No biases.
- sparse ffn (layers >= ``first_k_dense_replace``): ``s = sigmoid(x W_r)``
  over ALL published experts; the ``num_experts_per_tok`` largest of ``s + b``
  (``b`` selects only; ``n_group`` = ``topk_group`` = 1: no group step); ``g =
  routed_scaling_factor x s[sel] / (sum s[sel] + 1e-20)``; ``y = sum_j g_j
  E_j(x) + E_shared(x)``, every expert ``W2 (silu(W1 x) * W3 x)``. Earlier
  layers: the same SwiGLU at ``intermediate_size``. Given ``choice`` (the
  experts another computation picked), ``sel`` is that choice; the
  reference hands out its OWN beside, for whoever counts the flips.
- MTP, depth 1: ``h'_i = [rms_e(Emb(t_{i+1})) ; rms_h(h_i)] W_eh``, ``h_i``
  the main stack's output BEFORE its final norm; one more block (its own
  weights) over ``h'``; ``logits'_i = norm'(block(h')_i) W_head^T`` against
  ``t_{i+2}``. ``loss = CE_main + mtp_loss_weight x CE_mtp``, each a mean
  over its valid positions.

Departures, each shared with the program and listed in the configuration
file: (1) only the experts ``experts_held_first .. + n_routed_experts`` have
weights here; what the others would add is left out, and with all of
``n_routed_experts_published`` held this is the uncut layer; (2) the
vocabulary may be a slice; (3) ``b`` is the constant zero it is initialised
to. It depends on ``tpu_trainer/models/gpt.py`` only for the NAMES of the
parameter tree it reads: kernels are stored ``[in, out]``, the layers of one
(operator, ffn) kind are stacked under ``layers_attention_<ffn>``, the
prediction module lies under ``mtp``.
"""

from __future__ import annotations

from typing import Mapping, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROW_BLOCK = 512
# Position i of the prediction module is scored against token i + MTP_SHIFT.
MTP_SHIFT = 2


# --- the configuration file -> the program ------------------------------------

def held(cfg: Mapping) -> tuple:
    """(first id, count) of the experts that have weights here."""
    return cfg.get("experts_held_first", 0), cfg["n_routed_experts"]


def router_width(cfg: Mapping) -> int:
    return cfg.get("n_routed_experts_published", cfg["n_routed_experts"])


def gpt_config(cfg: Mapping, **options):
    """The program's GPTConfig at the configuration file's sizes."""
    from tpu_trainer.models.config import GPTConfig

    stated = {"norm_topk_prob": True, "scoring_func": "sigmoid",
              "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
              "moe_layer_freq": 1, "attention_bias": False,
              "hidden_act": "silu", "rope_scaling": None, "untied_head": True}
    for key, value in stated.items():
        if cfg.get(key, value) != value:
            raise ValueError(
                f"configuration {cfg.get('name')!r} has {key}={cfg[key]!r}; "
                f"the program computes {value!r} and has no option for it")
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + qk_rope_head_dim")
    fields = dict(
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
        norm_eps=cfg["rms_norm_eps"],
        q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rope_interleave=cfg["rope_interleave"],
        num_dense_layers=cfg["first_k_dense_replace"],
        num_experts=router_width(cfg),
        moe_experts_held=held(cfg),
        moe_top_k=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_experts=cfg["n_shared_experts"],
        moe_routed_scale=cfg["routed_scaling_factor"],
        moe_gate_eps=1e-20,
        moe_router="sigmoid",
        moe_impl="dropless",
        moe_aux_weight=0.0,
        router_z_weight=0.0,
        tie_word_embeddings=False,
        mtp_layers=cfg["num_nextn_predict_layers"],
        mtp_loss_weight=cfg["mtp_loss_weight"],
        **options,
    )
    try:
        return GPTConfig(**fields)
    except TypeError as e:
        # A program from before the family: fail at once, before the chip.
        raise SystemExit(f"perf.families.mla_moe: the program's GPTConfig "
                         f"cannot state this configuration: {e}")


def layer_kinds(cfg: Mapping) -> list:
    """The ffn of each main layer, in the order they run."""
    return ["dense" if i < cfg["first_k_dense_replace"] else "moe"
            for i in range(cfg["num_hidden_layers"])]


# --- the plain reference ------------------------------------------------------

def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(F32)


def _rope(x, cfg):
    """x: ``[batch, seq, heads, rope]``; positions 0..seq-1. With
    ``rope_interleave`` lane ``2i`` pairs with ``2i + 1``, else ``i`` with
    ``i + rope/2``; pair ``i`` turns by ``pos / theta^(2i / rope)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (float(cfg["rope_theta"])
                      ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]                   # [1, s, 1, d/2]
    sin = jnp.sin(angles)[None, :, None, :]
    if cfg["rope_interleave"]:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _kv_latent_norm(c_kv, p, cfg):
    return _rms_norm(c_kv, p["kv_a_layernorm"]["weight"], cfg["rms_norm_eps"])


def _attention(h, p, cfg):
    heads = cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    b, s, _ = h.shape
    f = lambda name: p[name]["kernel"].astype(F32)  # noqa: E731
    c_q = _rms_norm(h @ f("q_a_proj"), p["q_a_layernorm"]["weight"],
                    cfg["rms_norm_eps"])
    q = (c_q @ f("q_b_proj")).reshape(b, s, heads, nope + rope)
    kv_a = h @ f("kv_a_proj_with_mqa")
    c_kv = _kv_latent_norm(kv_a[..., :rank], p, cfg)
    kv = (c_kv @ f("kv_b_proj")).reshape(b, s, heads, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_r = q[..., :nope], _rope(q[..., nope:], cfg)
    k_r = _rope(kv_a[..., rank:][:, :, None, :], cfg)[:, :, 0]   # one head
    scale = 1.0 / jnp.sqrt(F32(nope + rope))
    pos = jnp.arange(s)

    def rows(lo):
        """Query rows ``lo .. lo + ROW_BLOCK`` against the keys up to the
        last of them (no later key is seen by any)."""
        hi = min(lo + ROW_BLOCK, s)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope[:, lo:hi],
                             k_nope[:, :hi])
                  + jnp.einsum("bqhd,bkd->bhqk", q_r[:, lo:hi],
                               k_r[:, :hi])) * scale
        causal = pos[lo:hi, None] >= pos[None, :hi]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                          v[:, :hi])

    # Under `jax.grad` a row block keeps its inputs only.
    out = jnp.concatenate(
        [jax.checkpoint(rows, static_argnums=0)(lo)
         for lo in range(0, s, ROW_BLOCK)], axis=1)
    return out.reshape(b, s, heads * dv) @ f("o_proj")


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
    return cfg["routed_scaling_factor"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20), own


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


def shared_expert(h, p):
    s = lambda name: p["shared_expert"][name]["kernel"].astype(F32)  # noqa: E731
    return _swiglu(h, s("gate_proj"), s("up_proj"), s("down_proj"))


def _layer(x, p, ffn, cfg, choice=None):
    """The layer's output and, of a sparse layer, its router's own choice."""
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["operator_norm"]["weight"], eps),
                       p["attention"], cfg)
    h = _rms_norm(x, p["ffn_norm"]["weight"], eps)
    if ffn == "moe":
        routed, own = routed_experts(h, p["moe_mlp"], cfg, choice)
        return x + routed + shared_expert(h, p["moe_mlp"]), own
    m = lambda name: p["mlp"][name]["kernel"].astype(F32)  # noqa: E731
    return x + _swiglu(h, m("gate_proj"), m("up_proj"), m("down_proj")), None


def head(params):
    return params["lm_head"].astype(F32)


def trunk(params, tokens, cfg: Mapping, choice=None):
    """The main stack's output BEFORE its final norm, and each sparse
    layer's own choice of experts, in the order the layers run."""
    x = params["embed_tokens"]["embedding"][tokens].astype(F32)
    given = iter(choice) if choice is not None else None
    seen: dict = {}
    chosen = []
    for ffn in layer_kinds(cfg):
        i = seen.get(ffn, 0)
        seen[ffn] = i + 1
        stack = params["layers_attention_" + ffn]
        # Under `jax.grad` a layer keeps its input only.
        x, own = jax.checkpoint(
            lambda x, p, ids, ffn=ffn: _layer(x, p, ffn, cfg, ids))(
            x, jax.tree_util.tree_map(lambda a: a[i], stack),
            next(given) if given is not None and ffn == "moe" else None)
        if own is not None:
            chosen.append(own)
    return x, chosen


def forward_and_choices(params, tokens, cfg: Mapping, choice=None):
    """Logits ``[batch, seq, vocab]`` in float32 for ``tokens [batch, seq]``
    and, for each sparse layer of the main stack in the order they run,
    which experts its router chose (ids ``[batch, seq, k]``). ``choice``:
    for each of them, the ids to route by instead (further entries, the
    prediction module's, are not read here)."""
    with jax.default_matmul_precision("highest"):
        x, chosen = trunk(params, tokens, cfg, choice)
        x = _rms_norm(x, params["norm"]["weight"], cfg["rms_norm_eps"])
        return x @ head(params).T, chosen


def forward(params, tokens, cfg: Mapping, choice=None):
    return forward_and_choices(params, tokens, cfg, choice)[0]


def _cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def mtp_logits(params, hidden, tokens, cfg: Mapping, choice=None):
    """The prediction module's logits for positions ``0 .. seq - 2`` (those
    that have a next token to embed): ``hidden`` is the main stack's output
    before its final norm."""
    p, eps = params["mtp"], cfg["rms_norm_eps"]
    embedding = params["embed_tokens"]["embedding"]
    both = jnp.concatenate(
        [_rms_norm(embedding[tokens[:, 1:]].astype(F32),
                   p["enorm"]["weight"], eps),
         _rms_norm(hidden[:, :-1], p["hnorm"]["weight"], eps)], axis=-1)
    x, _ = jax.checkpoint(
        lambda x, block, ids: _layer(x, block, "moe", cfg, ids))(
        both @ p["eh_proj"]["kernel"].astype(F32), p["block"], choice)
    return _rms_norm(x, p["norm"]["weight"], eps) @ head(params).T


def loss_terms(params, rows, cfg: Mapping, choice=None):
    """(next-token cross entropy, the prediction module's) over ``rows [n,
    seq]``: position ``i`` predicts token ``i + 1``, the module's position
    ``i`` token ``i + 2``; each a mean over the positions that have a
    target. ``choice``: ids for the main stack's sparse layers and,
    optionally, one more entry for the module's (else it routes by its
    own)."""
    with jax.default_matmul_precision("highest"):
        main = moe_layers(cfg) - cfg["num_nextn_predict_layers"]
        hidden, _ = trunk(params, rows, cfg,
                          None if choice is None else choice[:main])
        x = _rms_norm(hidden, params["norm"]["weight"], cfg["rms_norm_eps"])
        ce = _cross_entropy((x @ head(params).T)[:, :-1], rows[:, 1:])
        if not cfg["num_nextn_predict_layers"]:
            return ce, jnp.zeros((), F32)
        extra = (choice[main][:, :-1]
                 if choice is not None and len(choice) > main else None)
        logits = mtp_logits(params, hidden, rows, cfg, extra)
        valid = rows.shape[1] - MTP_SHIFT
        return ce, _cross_entropy(logits[:, :valid], rows[:, MTP_SHIFT:])


def rows_loss(params, rows, cfg: Mapping, choice=None):
    ce, mtp = loss_terms(params, rows, cfg, choice)
    return ce + cfg["mtp_loss_weight"] * mtp


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
# training is 3 x forward; attention is counted causal, at the model's 192
# lanes a score and 128 a value whatever the kernels pad to; the untied head
# is a matmul a pass (the lookup is not); recomputation is not counted.

def attention_params(cfg: Mapping) -> int:
    """One latent-attention operator's matmul parameters."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (h * cfg["q_lora_rank"]
            + cfg["q_lora_rank"] * heads * (nope + rope)
            + h * (cfg["kv_lora_rank"] + rope)
            + cfg["kv_lora_rank"] * heads * (nope + dv)
            + heads * dv * h)


def expert_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _block_params(cfg: Mapping, ffn: str) -> int:
    """Every parameter of one block: operator, its two latent norms, ffn,
    the block's two norms."""
    h = cfg["hidden_size"]
    total = (attention_params(cfg) + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
             + 2 * h)
    if ffn == "dense":
        return total + 3 * h * cfg["intermediate_size"]
    return total + (
        (cfg["n_routed_experts"] + cfg["n_shared_experts"])
        * expert_params(cfg) + h * router_width(cfg) + router_width(cfg))


def param_count(cfg: Mapping) -> int:
    """Every parameter that lives here: the held experts, the sliced
    embedding and head, the selection bias with the router, the prediction
    module (W_eh, its block, its three norms)."""
    h = cfg["hidden_size"]
    total = 2 * cfg["vocab_size"] * h + h
    total += sum(_block_params(cfg, ffn) for ffn in layer_kinds(cfg))
    total += cfg["num_nextn_predict_layers"] * (
        2 * h * h + _block_params(cfg, "moe") + 3 * h)
    return total


def moe_layers(cfg: Mapping) -> int:
    """Expert layers a token passes: the main stack's and the prediction
    module's."""
    return (sum(ffn == "moe" for ffn in layer_kinds(cfg))
            + cfg["num_nextn_predict_layers"])


def attention_layers(cfg: Mapping) -> int:
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def even_rows_per_token(cfg: Mapping) -> float:
    """Rows a token brings to the experts held here, each expert layer, when
    routing is even."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / router_width(cfg))


def attention_flops_fwd(cfg: Mapping, seq_len: int) -> float:
    """Causal attention forward FLOPs of ONE sequence, every latent-attention
    operator: a query sees ``i + 1`` keys, ``2 x (nope + rope)`` FLOPs a
    score and ``2 x v`` a value, a head."""
    lanes = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
             + cfg["v_head_dim"])
    return (attention_layers(cfg) * 2 * cfg["num_attention_heads"] * lanes
            * seq_len * (seq_len + 1) / 2)


def train_flops_per_token(cfg: Mapping, seq_len: int,
                          rows_per_token: Optional[float] = None) -> float:
    """Model FLOPs per trained token, forward + backward. The routed experts
    count by the rows they are given: ``rows_per_token`` a token and expert
    layer (what the program's counter read; the even share if not given)."""
    if rows_per_token is None:
        rows_per_token = even_rows_per_token(cfg)
    h = cfg["hidden_size"]
    sparse = (h * router_width(cfg)
              + (cfg["n_shared_experts"] + rows_per_token)
              * expert_params(cfg))
    dense = 3 * h * cfg["intermediate_size"]
    matmul = attention_layers(cfg) * attention_params(cfg)
    matmul += sum(dense if ffn == "dense" else sparse
                  for ffn in layer_kinds(cfg))
    # The head once a pass; the prediction module's projection, expert
    # layer and second pass of the head.
    matmul += cfg["vocab_size"] * h
    matmul += cfg["num_nextn_predict_layers"] * (
        2 * h * h + sparse + cfg["vocab_size"] * h)
    return 6.0 * matmul + 3.0 * attention_flops_fwd(cfg, seq_len) / seq_len


def mfu(cfg: Mapping, seq_len: int, tokens_per_s: float, chips: int,
        peak_flops_per_s: float,
        rows_per_token: Optional[float] = None) -> float:
    return (train_flops_per_token(cfg, seq_len, rows_per_token) * tokens_per_s
            / (chips * peak_flops_per_s))


def flash_work(cfg: Mapping, seq_len: int, sequences: float,
               bytes_per_value: int = 2) -> dict:
    """FLOPs and least HBM bytes of the attention kernels for ``sequences``
    sequences of one training step, every latent-attention operator, forward
    and backward, causal (3 x forward; the score recomputation inside the
    backward kernels is not counted). Bytes: the forward reads q, k (the
    rope key once, not a head) and v and writes the result; the backward
    reads those, the result and its cotangent and writes the three
    gradients, each once in the compute type."""
    heads = cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    operands = heads * (nope + rope) + heads * nope + rope + heads * dv
    values = seq_len * (operands + heads * dv            # forward
                        + 2 * operands + 2 * heads * dv)  # backward
    return {"flops": 3.0 * attention_flops_fwd(cfg, seq_len) * sequences,
            "bytes": (attention_layers(cfg) * sequences * values
                      * bytes_per_value)}


def gmm_work(cfg: Mapping, rows: float, layer_passes: int,
             bytes_per_value: int = 2) -> dict:
    """FLOPs and least HBM bytes of the grouped matmuls of ``layer_passes``
    passes through an expert layer (forward AND backward each) that gave the
    held experts ``rows`` rows in all: a pass is 3 ``gmm`` forward (gate, up,
    down), 3 ``gmm`` (the inputs' gradients) and 3 ``tgmm`` (the weights')
    backward, each ``2 * rows * H * I`` FLOPs. Bytes: every call reads its
    rows' operands once and writes its result once in the compute type; a
    ``gmm`` reads the held experts' weights once a call, a ``tgmm`` writes
    their gradient once a call in float32. The shared expert is a plain
    matmul and is not counted here."""
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["n_routed_experts"] * h * inter
    return {"flops": 9 * 2.0 * rows * h * inter,
            "bytes": (rows * 9 * (h + inter) * bytes_per_value
                      + layer_passes * weights * (6 * bytes_per_value + 3 * 4))}
