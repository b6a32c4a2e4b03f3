"""The ``qwen3_next`` family (Qwen3-Next: Gated DeltaNet linear-attention
layers, arXiv:2412.06464, three to one beside gated softmax attention, every
layer a softmax-routed MoE with a gated shared expert, an untied head): what
the benchmark knows of it.

Three things, kept with the benchmark so that no PR that claims a gain can
change them: the plain reference (forward pass and loss; gradients by
``jax.grad``), the mapping from a configuration file to the program's
``GPTConfig``, and the operation and byte counts (``gmm_work``,
``flash_work``, ``gated_delta_work``).

**The reference** is straightforward ``jax.numpy``, float32, every matmul at
``jax.default_matmul_precision("highest")``; no kernels, no chunks, no
sorting of tokens. Written from the published ``config.json`` keys:

- layer ``i`` (every layer has experts: ``decoder_sparse_step`` 1,
  ``mlp_only_layers`` empty): ``h = x + mixer(norm(x))``, ``out = h +
  moe(norm(h))``; the mixer is full attention iff ``(i + 1) %
  full_attention_interval == 0``, else linear attention; a final norm and an
  untied head. Every norm is Qwen3-Next's zero-centred RMSNorm ``x^ (1 +
  w)``, eps ``rms_norm_eps``: the parameter tree stores ``1 + w`` (the
  program's ``RMSNorm`` weight, ones at the start), and that is what is read
  here.
- linear attention (Gated DeltaNet; ``H_k`` key heads of ``d_k``, ``H_v``
  value heads of ``d_v``, ``r = H_v / H_k``): ``[q, k, v, z] = x W_qkvz``
  with the columns grouped by key head ``[q_h, k_h, v_{rh..}, z_{rh..}]``,
  ``[b, a] = x W_ba`` grouped alike; ``[q, k, v] <- silu(causal depthwise
  conv of linear_conv_kernel_dim taps, no bias)``; ``beta = sigmoid(b)``,
  ``g = -exp(A_log) softplus(a + dt_bias)``; ``q <- l2norm(q) / sqrt(d_k)``,
  ``k <- l2norm(k)`` (``l2norm(x) = x rsqrt(sum x^2 + 1e-6)``), value head
  ``j`` reading key head ``j // r``; per value head from ``S = 0``: ``S' =
  e^g S``, ``S <- S' + k (beta (v - S'^T k))^T``, ``o = S^T q``; ``o <-
  RMSNorm(o) w ⊙ silu(z)`` over each value head's ``d_v`` lanes (the norm
  first, then the gate; ``w`` plain, ones at the start); ``out = o W_out``.
- **the delta rule is NOT the program's decomposition** (``ops/gated_delta.
  py`` cuts the sequence into chunks and inverts a triangle a chunk): here
  it is the recurrence itself, token by token (``lax.scan``), under
  ``jax.checkpoint`` in segments of ``SEGMENT`` tokens so that its gradient
  keeps a state a segment, not one a token (a way to fit memory, not an
  approximation). ``delta_rule_closed`` is the same rule over a whole
  sequence as ONE triangular system (no loop primitive, so that
  ``perf/lower_precision.py`` can walk it): what the bf16 control runs.
- attention: ``[q_h, gate_h] = split(x W_q)`` a head, ``W_q [hidden, 2
  heads head_dim]``; ``q <- norm(q)``, ``k <- norm(x W_k)`` a head; ``v = x
  W_v``; RoPE over a head's first ``partial_rotary_factor x head_dim`` lanes
  in halves, base ``rope_theta``, the other lanes untouched; causal softmax
  of ``q . k / sqrt(head_dim)``; ``o = (attn ⊙ sigmoid(gate)) W_o``.
- MoE: ``p = softmax(x W_r)`` over ALL published experts in f32; the
  ``num_experts_per_tok`` largest, renormalised to sum 1
  (``norm_topk_prob``); experts ``W2 (silu(W1 x) ⊙ W3 x)``; the shared
  expert the same at ``shared_expert_intermediate_size``, times
  ``sigmoid(x w_sg)``; ``y = sum_j g_j E_j(x) + sigmoid(x w_sg)
  E_shared(x)``. Given ``choice`` (the experts another computation picked),
  the gates are ``p`` at that choice over its sum; the reference hands out
  its OWN choice beside, for whoever counts the flips.

Departures, each shared with the program and listed in the configuration
file: (1) only the experts ``experts_held_first .. + num_experts`` have
weights here; what the others would add is left out, and with all of
``num_experts_published`` held this is the uncut layer; (2) the vocabulary
may be a slice; (3) no router auxiliary loss (its weight is not in the
row); (4) no multi-token-prediction module (the row states no key for it).
It depends on ``tpu_trainer/models/gpt.py`` only for the NAMES of the
parameter tree it reads: kernels are stored ``[in, out]``, the layers of one
kind are stacked under ``layers_linear_attention_moe`` /
``layers_attention_moe``.
"""

from __future__ import annotations

from typing import Mapping, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROW_BLOCK = 512
# Tokens a checkpointed segment of the reference's delta rule.
SEGMENT = 64
# The chunk the program's delta rule runs at (the family's own default),
# at which `gated_delta_work` counts.
DELTA_CHUNK = 64
# Value heads of one block of `delta_rule_closed` (bounds its [s, s]
# matrices).
CLOSED_HEADS = 2

# A layer's (operator, ffn) -> the stack its layers lie under, the module's
# name.
MIXERS = {"linear_attention": ("layers_linear_attention_moe", "linear_attn"),
          "attention": ("layers_attention_moe", "attention")}


# --- the configuration file -> the program ------------------------------------

# Keys of a configuration file that `reduced` may never name.
WIDTH_KEYS = ("hidden_size", "intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads",
              "linear_num_key_heads", "linear_num_value_heads",
              "linear_key_head_dim", "linear_value_head_dim",
              "linear_conv_kernel_dim", "partial_rotary_factor",
              "moe_intermediate_size", "shared_expert_intermediate_size",
              "num_experts_per_tok")
# `perf/step_jaxpr.py`'s two layers: a linear-attention layer, then a full
# one.
STEP_CUT = {"num_hidden_layers": 2, "full_attention_interval": 2}


def held(cfg: Mapping) -> tuple:
    """(first id, count) of the experts that have weights here."""
    return cfg.get("experts_held_first", 0), cfg["num_experts"]


def router_width(cfg: Mapping) -> int:
    return cfg.get("num_experts_published", cfg["num_experts"])


def layer_types(cfg: Mapping) -> list:
    """Each layer's mixer as the program names it, in order."""
    every = cfg["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(cfg["num_hidden_layers"])]


def mixers(cfg: Mapping) -> list:
    return ["attention" if t == "full_attention" else t
            for t in layer_types(cfg)]


def gpt_config(cfg: Mapping, **options):
    """The program's GPTConfig at the configuration file's sizes."""
    from tpu_trainer.models.config import GPTConfig

    stated = {"hidden_act": "silu", "decoder_sparse_step": 1,
              "mlp_only_layers": [], "norm_topk_prob": True,
              "tie_word_embeddings": False, "use_sliding_window": False,
              "rope_scaling": None}
    for key, value in stated.items():
        if cfg.get(key, value) != value:
            raise ValueError(
                f"configuration {cfg.get('name')!r} has {key}={cfg[key]!r}; "
                f"the program computes {value!r} and has no option for it")
    fields = dict(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        layer_types=tuple(layer_types(cfg)),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        attention_head_dim=cfg["head_dim"],
        qk_norm=True,
        attention_output_gate=True,
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]),
        # Read and unused: no layer is dense.
        intermediate_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        initializer_range=cfg["initializer_range"],
        dropout=0.0,
        attention_dropout=cfg["attention_dropout"],
        norm_eps=cfg["rms_norm_eps"],
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        num_experts=router_width(cfg),
        moe_experts_held=held(cfg),
        moe_top_k=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_experts=1,
        moe_shared_expert_width=cfg["shared_expert_intermediate_size"],
        moe_shared_expert_gate=True,
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
        raise SystemExit(f"perf.families.qwen3_next: the program's GPTConfig "
                         f"cannot state this configuration: {e}")


# --- the plain reference ------------------------------------------------------

def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(F32)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _causal_conv(x, weight):
    """Depthwise causal convolution, no bias: ``y_t = sum_j w[:, j] x_{t -
    (K - 1 - j)}``, ``weight [channels, K]`` (the last tap on the current
    position), zeros before the sequence."""
    taps, seq = weight.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + seq] * weight[:, j] for j in range(taps))


def _repeat_keys(q, k, per):
    return jnp.repeat(q, per, axis=2), jnp.repeat(k, per, axis=2)


def delta_rule(q, k, v, g, beta):
    """``S' = e^g S``, ``S <- S' + k (beta (v - S'^T k))^T``, ``o = S^T q``,
    token by token from a zero state. ``q``, ``k`` ``[batch, seq, H_k,
    d_k]``, ``v [batch, seq, H_v, d_v]``, ``g``, ``beta`` ``[batch, seq,
    H_v]``; ``[batch, seq, H_v, d_v]`` out. Segments of ``SEGMENT`` tokens
    under ``jax.checkpoint``; a short last segment is padded with steps that
    change nothing (``g = 0``, ``beta = 0``)."""
    batch, seq, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    q, k = _repeat_keys(q, k, hv // hk)

    def step(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs              # [batch, H_v, .]
        state = jnp.exp(g_t)[..., None, None] * state
        predicted = jnp.sum(state * k_t[..., None], axis=-2)
        state = state + k_t[..., None] * (
            beta_t[..., None] * (v_t - predicted))[..., None, :]
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    segment = min(SEGMENT, seq)
    pad = -seq % segment
    xs = [jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
          for x in (q, k, v, g, beta)]
    # [segments, segment, batch, ...]: time-major within a segment.
    xs = [jnp.moveaxis(x.reshape(batch, -1, segment, *x.shape[2:]), (1, 2),
                       (0, 1)) for x in xs]

    @jax.checkpoint
    def run(state, inputs):
        return jax.lax.scan(step, state, inputs)

    _, o = jax.lax.scan(run, jnp.zeros((batch, hv, dk, dv), F32), xs)
    o = jnp.moveaxis(o, (0, 1), (1, 2)).reshape(batch, seq + pad, hv, dv)
    return o[:, :seq]


def delta_rule_closed(q, k, v, g, beta):
    """The same rule over the whole sequence as one triangular system (the
    state starts at zero): with ``gamma`` the cumulative sum of ``g`` and
    ``D_ij = exp(gamma_i - gamma_j)`` for ``j <= i``, the corrected values
    ``U`` solve ``(I + strict_lower(diag(beta) (K K^T o D))) U = diag(beta)
    V`` and ``o = lower(Q K^T o D) U``. ``CLOSED_HEADS`` value heads at a
    time, a Python loop: no loop primitive, for ``in_bf16``."""
    batch, seq, hk, dk = q.shape
    hv = v.shape[2]
    q, k = _repeat_keys(q, k, hv // hk)
    cum = jnp.cumsum(g, axis=1)                              # [batch, seq, H_v]
    lower = jnp.tril(jnp.ones((seq, seq), bool))
    strict = jnp.tril(jnp.ones((seq, seq), bool), k=-1)
    eye = jnp.eye(seq, dtype=F32)
    out = []
    for lo in range(0, hv, CLOSED_HEADS):
        heads = slice(lo, min(lo + CLOSED_HEADS, hv))
        cum_h = jnp.moveaxis(cum[:, :, heads], 1, -1)         # [batch, h, seq]
        decay = jnp.exp(jnp.where(
            lower, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
        k_h, q_h = k[:, :, heads], q[:, :, heads]
        beta_h = jnp.moveaxis(beta[:, :, heads], 1, -1)[..., None]
        a = jnp.where(strict, jnp.einsum("bihd,bjhd->bhij", k_h, k_h)
                      * decay, 0.0) * beta_h
        u = jax.scipy.linalg.solve_triangular(
            eye + a, beta_h * jnp.moveaxis(v[:, :, heads], 1, 2), lower=True,
            unit_diagonal=True)                               # [batch, h, seq, dv]
        scores = jnp.einsum("bihd,bjhd->bhij", q_h, k_h) * decay
        out.append(jnp.moveaxis(scores @ u, 1, 2))
    return jnp.concatenate(out, axis=2)


def linear_attention(u, p, cfg):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    per = hv // hk
    batch, seq, _ = u.shape
    f = lambda name: p[name]["kernel"].astype(F32)  # noqa: E731
    qkvz = (u @ f("in_proj_qkvz")).reshape(batch, seq, hk,
                                           2 * dk + 2 * per * dv)
    q, k, v, z = (qkvz[..., :dk], qkvz[..., dk:2 * dk],
                  qkvz[..., 2 * dk:2 * dk + per * dv],
                  qkvz[..., 2 * dk + per * dv:])
    ba = (u @ f("in_proj_ba")).reshape(batch, seq, hk, 2 * per)
    b, a = ba[..., :per].reshape(batch, seq, hv), ba[..., per:].reshape(
        batch, seq, hv)
    mixed = jax.nn.silu(_causal_conv(
        jnp.concatenate([q.reshape(batch, seq, -1), k.reshape(batch, seq, -1),
                         v.reshape(batch, seq, -1)], axis=-1),
        p["conv_weight"].astype(F32)))
    q = _l2norm(mixed[..., :hk * dk].reshape(batch, seq, hk, dk)) / jnp.sqrt(
        F32(dk))
    k = _l2norm(mixed[..., hk * dk:2 * hk * dk].reshape(batch, seq, hk, dk))
    v = mixed[..., 2 * hk * dk:].reshape(batch, seq, hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(
        a + p["dt_bias"].astype(F32))
    o = delta_rule(q, k, v, g, beta)
    o = gated_norm(o, z.reshape(batch, seq, hv, dv), p["norm"]["weight"],
                   cfg["rms_norm_eps"])
    return o.reshape(batch, seq, hv * dv) @ f("out_proj")


def gated_norm(o, z, weight, eps):
    """``RMSNorm(o) w ⊙ silu(z)`` over each value head's lanes: the norm
    FIRST, then the gate."""
    return _rms_norm(o, weight, eps) * jax.nn.silu(z)


def rope(x, theta, rotated):
    """RoPE over the first ``rotated`` lanes of ``x [batch, seq, heads, d]``
    (halves ``(i, i + rotated / 2)``, frequencies ``theta^(-2i /
    rotated)``); the other lanes pass through."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotated, 2, dtype=F32)
                                / rotated))
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    part, rest = x[..., :rotated], x[..., rotated:]
    half = rotated // 2
    turned = jnp.concatenate([-part[..., half:], part[..., :half]], axis=-1)
    return jnp.concatenate(
        [part * jnp.cos(angles) + turned * jnp.sin(angles), rest], axis=-1)


def attention(h, p, cfg):
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    rotated = int(round(d * cfg["partial_rotary_factor"]))
    batch, s, _ = h.shape
    f = lambda name: p[name]["kernel"].astype(F32)  # noqa: E731
    q_and_gate = (h @ f("q_proj")).reshape(batch, s, heads, 2 * d)
    q, gate = q_and_gate[..., :d], q_and_gate[..., d:]
    q = rope(_rms_norm(q, p["q_layernorm"]["weight"], eps), theta, rotated)
    k = rope(_rms_norm((h @ f("k_proj")).reshape(batch, s, kv, d),
                       p["k_layernorm"]["weight"], eps), theta, rotated)
    # Query head i reads key/value head i // (heads / kv).
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat((h @ f("v_proj")).reshape(batch, s, kv, d), heads // kv,
                   axis=2)
    scale = 1.0 / jnp.sqrt(F32(d))
    pos = jnp.arange(s)

    def rows(lo):
        """Query rows ``lo .. lo + ROW_BLOCK`` against the keys up to the
        last of them."""
        hi = min(lo + ROW_BLOCK, s)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi]) * scale
        causal = pos[lo:hi, None] >= pos[None, :hi]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                          v[:, :hi])

    # Under `jax.grad` a row block keeps its inputs only.
    out = jnp.concatenate(
        [jax.checkpoint(rows, static_argnums=0)(lo)
         for lo in range(0, s, ROW_BLOCK)], axis=1)
    out = output_gate(out, gate)
    return out.reshape(batch, s, heads * d) @ f("o_proj")


def output_gate(out, gate):
    """Attention's output gate: ``attn ⊙ sigmoid(gate)``, a head and lane."""
    return out * jax.nn.sigmoid(gate)


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


def shared_expert(h, p):
    """The shared expert times its gate ``sigmoid(x w_sg)``."""
    s = lambda name: p["shared_expert"][name]["kernel"].astype(F32)  # noqa: E731
    return jax.nn.sigmoid(h @ s("gate")) * _swiglu(
        h, s("gate_proj"), s("up_proj"), s("down_proj"))


def _layer(x, p, mixer, cfg, choice=None):
    """The layer's output and its router's own choice."""
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, p["operator_norm"]["weight"], eps)
    name = MIXERS[mixer][1]
    x = x + (linear_attention(h, p[name], cfg) if mixer == "linear_attention"
             else attention(h, p[name], cfg))
    h = _rms_norm(x, p["ffn_norm"]["weight"], eps)
    routed, own = routed_experts(h, p["moe_mlp"], cfg, choice)
    return x + routed + shared_expert(h, p["moe_mlp"]), own


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
        for mixer in mixers(cfg):
            i = seen.get(mixer, 0)
            seen[mixer] = i + 1
            # Under `jax.grad` a layer keeps its input only.
            x, own = jax.checkpoint(
                lambda x, p, ids, mixer=mixer: _layer(x, p, mixer, cfg, ids))(
                x, jax.tree_util.tree_map(lambda a: a[i],
                                          params[MIXERS[mixer][0]]),
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
# training is 3 x forward; attention is counted causal; the untied head is a
# matmul a pass (the lookup is not); recomputation is not counted. Of a
# linear-attention layer the projections and the delta rule's own matrix
# products in the chunked form (``delta_flops_fwd_per_token``) are counted;
# the taps, the norms and the gates are vector work and are not.

def layers_of(cfg: Mapping, mixer: str) -> int:
    return mixers(cfg).count(mixer)


def linear_attention_matmul_params(cfg: Mapping) -> int:
    h = cfg["hidden_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return h * (2 * hk * dk + 2 * hv * dv) + h * 2 * hv + hv * dv * h


def attention_params(cfg: Mapping) -> int:
    """q (and its gate), k, v, o."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return (3 * h * cfg["num_attention_heads"] * d
            + 2 * h * cfg["num_key_value_heads"] * d)


def expert_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: Mapping) -> int:
    """The shared expert and its gate's [hidden, 1]."""
    h = cfg["hidden_size"]
    return 3 * h * cfg["shared_expert_intermediate_size"] + h


def _layer_params(cfg: Mapping, mixer: str) -> int:
    """Every parameter of one layer: its mixer, its held experts, the
    router, the shared expert and its gate, the two norms."""
    h = cfg["hidden_size"]
    if mixer == "linear_attention":
        hv, k = cfg["linear_num_value_heads"], cfg["linear_conv_kernel_dim"]
        conv = (2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
                + hv * cfg["linear_value_head_dim"])
        # taps; dt_bias, A_log; the gated norm's weight
        operator = (linear_attention_matmul_params(cfg) + conv * k + 2 * hv
                    + cfg["linear_value_head_dim"])
    else:
        operator = attention_params(cfg) + 2 * cfg["head_dim"]  # QK-norms
    return (operator + cfg["num_experts"] * expert_params(cfg)
            + shared_params(cfg) + h * router_width(cfg) + 2 * h)


def param_count(cfg: Mapping) -> int:
    """Every parameter that lives here: the held experts, the sliced
    embedding and head."""
    h = cfg["hidden_size"]
    return (2 * cfg["vocab_size"] * h + h
            + sum(_layer_params(cfg, mixer) for mixer in mixers(cfg)))


def moe_layers(cfg: Mapping) -> int:
    """Expert layers a token passes: every layer."""
    return cfg["num_hidden_layers"]


def even_rows_per_token(cfg: Mapping) -> float:
    """Rows a token brings to the experts held here, each expert layer, when
    routing is even."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / router_width(cfg))


def attention_flops_fwd(cfg: Mapping, seq_len: int) -> float:
    """Causal attention forward FLOPs of ONE sequence, every attention
    layer: a query sees ``i + 1`` keys, QK^T and PV a head."""
    return (layers_of(cfg, "attention") * 2 * 2 * cfg["num_attention_heads"]
            * cfg["head_dim"] * seq_len * (seq_len + 1) / 2)


def delta_flops_fwd_per_token(cfg: Mapping, chunk: int = DELTA_CHUNK) -> float:
    """Forward FLOPs a token of ONE linear-attention layer's delta rule in
    the chunked form at chunk ``C``, a value head: ``K K^T``, ``Q K^T`` and
    ``W = T (beta e^gamma K)`` (``2 C d_k`` each), ``U = T (beta V)`` and
    the in-chunk part of the output (``2 C d_v`` each), the triangular
    inverse (``C^3 / 3`` multiply-adds a chunk: ``2 C^2 / 3``), and ``W S``,
    ``(Q e^gamma) S`` and the state's update (``2 d_k d_v`` each); times the
    value heads."""
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    per_head = (2 * chunk * (3 * dk + 2 * dv) + 2 * chunk ** 2 / 3
                + 6 * dk * dv)
    return per_head * cfg["linear_num_value_heads"]


def train_flops_per_token(cfg: Mapping, seq_len: int,
                          rows_per_token: Optional[float] = None) -> float:
    """Model FLOPs per trained token, forward + backward. The routed experts
    count by the rows they are given: ``rows_per_token`` a token and expert
    layer (what the program's counter read; the even share if not given)."""
    if rows_per_token is None:
        rows_per_token = even_rows_per_token(cfg)
    h = cfg["hidden_size"]
    matmul = (layers_of(cfg, "linear_attention")
              * linear_attention_matmul_params(cfg)
              + layers_of(cfg, "attention") * attention_params(cfg)
              + moe_layers(cfg) * (h * router_width(cfg) + shared_params(cfg)
                                   + rows_per_token * expert_params(cfg))
              + cfg["vocab_size"] * h)
    return (6.0 * matmul
            + 3.0 * layers_of(cfg, "linear_attention")
            * delta_flops_fwd_per_token(cfg)
            + 3.0 * attention_flops_fwd(cfg, seq_len) / seq_len)


def mfu(cfg: Mapping, seq_len: int, tokens_per_s: float, chips: int,
        peak_flops_per_s: float,
        rows_per_token: Optional[float] = None) -> float:
    return (train_flops_per_token(cfg, seq_len, rows_per_token) * tokens_per_s
            / (chips * peak_flops_per_s))


def flash_work(cfg: Mapping, seq_len: int, sequences: float,
               bytes_per_value: int = 2) -> dict:
    """FLOPs and least HBM bytes of the attention kernels for ``sequences``
    sequences of one training step, every attention layer, forward and
    backward, causal (3 x forward; the score recomputation inside the
    backward kernels is not counted), at the model's heads: ``heads x
    head_dim`` lanes of queries over ``num_key_value_heads`` heads of keys
    and values. Bytes: the forward reads q, k and v (a K/V head once) and
    writes the result; the backward reads those, the result and its
    cotangent and writes the three gradients, each once in the compute
    type."""
    d = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    operands = q + 2 * kv
    values = seq_len * (operands + q                 # forward
                        + 2 * operands + 2 * q)      # backward
    return {"flops": 3.0 * attention_flops_fwd(cfg, seq_len) * sequences,
            "bytes": (layers_of(cfg, "attention") * sequences * values
                      * bytes_per_value)}


def gmm_work(cfg: Mapping, rows: float, layer_passes: int,
             bytes_per_value: int = 2) -> dict:
    """FLOPs and least HBM bytes of the grouped matmuls of ``layer_passes``
    passes through an expert layer (forward AND backward each) that gave the
    held experts ``rows`` rows in all: a pass is 3 ``gmm`` forward (gate,
    up, down), 3 ``gmm`` (the inputs' gradients) and 3 ``tgmm`` (the
    weights') backward, each ``2 * rows * H * I`` FLOPs. Bytes: every call
    reads its rows' operands once and writes its result once in the compute
    type; a ``gmm`` reads the held experts' weights once a call, a ``tgmm``
    writes their gradient once a call in float32. The shared expert is a
    plain matmul and is not counted here."""
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["num_experts"] * h * inter
    return {"flops": 9 * 2.0 * rows * h * inter,
            "bytes": (rows * 9 * (h + inter) * bytes_per_value
                      + layer_passes * weights * (6 * bytes_per_value + 3 * 4))}


def gated_delta_work(cfg: Mapping, tokens: float,
                     bytes_per_value: int = 2) -> dict:
    """FLOPs and least HBM bytes of the delta rule that ``tokens`` tokens of
    training pass, EVERY linear-attention layer, forward and backward:
    FLOPs the chunked form's at ``DELTA_CHUNK`` (``delta_flops_fwd_per_
    token``), forward + twice that for the backward, whatever computes the
    rule; bytes ``q``, ``k`` (``H_k d_k`` lanes each), ``v`` and ``o``
    (``H_v d_v``) in the compute type, ``g`` and ``beta`` (``H_v``) in
    float32, each moved once, and their six gradients once. A recomputation
    inside the backward is not counted."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    once = (2 * hk * dk + 2 * hv * dv) * bytes_per_value + 2 * hv * 4
    layers = layers_of(cfg, "linear_attention")
    return {"flops": (3.0 * delta_flops_fwd_per_token(cfg) * tokens
                      * layers),
            "bytes": 2.0 * once * tokens * layers}
