"""The ``nemotron_h`` family (Nemotron-H, arXiv:2504.03624: Mamba-2 state-
space layers, arXiv:2405.21060, among a few attention layers, every block ONE
sublayer, a sigmoid-routed MoE of two-matrix relu^2 experts beside a shared
one, an untied head): what the benchmark knows of it.

Three things, kept with the benchmark so that no PR that claims a gain can
change them: the plain reference (forward pass and loss; gradients by
``jax.grad``), the mapping from a configuration file to the program's
``GPTConfig``, and the operation and byte counts (``gmm_work``, ``ssd_work``).

**The reference** is straightforward ``jax.numpy``, float32, every matmul at
``jax.default_matmul_precision("highest")``; no kernels, no chunks, no
sorting of tokens. Written from the published ``config.json`` keys and the
two papers:

- block ``i``, by character ``i`` of ``hybrid_override_pattern``: ``h <- h +
  mixer_i(RMSNorm(h))``, eps ``layer_norm_epsilon``; ``M`` Mamba-2, ``*``
  attention, ``E`` MoE, ``-`` the dense FFN; then a final RMSNorm and an
  untied head. No biases but the conv's.
- Mamba-2 (``H`` heads of ``P`` lanes, ``d_in = H P``, ``G`` groups, ``N``
  lanes of state): ``[z, xBC, dt] = u W_in`` (``d_in``, ``d_in + 2 G N``,
  ``H`` wide, in that order); ``xBC <- silu(causal depthwise conv,
  conv_kernel taps, with bias)``; ``[x, B, C] = xBC``; ``dt <- softplus(dt +
  dt_bias)``; ``A = -exp(A_log)``; per head ``h`` of group ``h // (H / G)``:
  ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D_h
  x_t``; ``y <- RMSNorm_groups(y * silu(z))`` (the gate first, then a norm
  over each group's ``d_in / G`` lanes, times a ``[d_in]`` weight); ``out = y
  W_out``.
- **the scan is NOT the program's decomposition** (``ops/ssd.py`` cuts the
  sequence into chunks of 128 and carries ``[P, N]`` states across them).
  Up to ``RECURRENCE_MAX_SEQ`` tokens it is the recurrence itself, token by
  token (``lax.scan`` over the sequence): what the tests compare with. At
  the cell's 4,096 tokens the recurrence's backward would keep a ``[H, P,
  N]`` state a token (8.6 GB a row), so there it is the quadratic dual the
  paper derives, ``y = (L o C B^T)(dt x)`` with ``L[i, j] = exp(sum_{j < r
  <= i} dt_r A)`` the whole ``[seq, seq]`` lower triangle a head, 1,024 query
  rows of one group of heads at a time under ``jax.checkpoint``: no chunk, no
  carried state, nothing of the program's schedule. ``tests/perf`` holds the two forms to
  each other.
- attention: ``q`` ``[hidden, heads x head_dim]``, ``k``, ``v`` ``[hidden,
  kv_heads x head_dim]``, causal softmax of ``q . k / sqrt(head_dim)``, ``o``
  back; no bias, no QK-norm, NO rotary embedding.
- MoE: ``s = sigmoid(x W_r)`` over ALL published experts; the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` selects only; ``n_group``
  = ``topk_group`` = 1: no group step); ``g = routed_scaling_factor x s[sel]
  / (sum s[sel] + 1e-20)``; every expert ``relu(x W_up)^2 W_down``; ``y =
  sum_j g_j E_j(x) + E_shared(x)``. Given ``choice`` (the experts another
  computation picked), ``sel`` is that choice; the reference hands out its
  OWN beside, for whoever counts the flips.

Departures, each shared with the program and listed in the configuration
file: (1) only the experts ``experts_held_first .. + n_routed_experts`` have
weights here; what the others would add is left out, and with all of
``n_routed_experts_published`` held this is the uncut layer; (2) the
vocabulary may be a slice; (3) ``b`` is the constant zero it is initialised
to; (4) the second (denoiser) tower, its conditioning and the denoising
objective are not built: the configuration states no key for them. It
depends on ``tpu_trainer/models/gpt.py`` only for the NAMES of the parameter
tree it reads: kernels are stored ``[in, out]``, the blocks of one kind are
stacked under ``layers_mamba_none`` / ``layers_attention_none`` /
``layers_none_moe`` / ``layers_none_dense``.
"""

from __future__ import annotations

from typing import Mapping, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROW_BLOCK = 512
# The longest sequence the scan is run token by token at.
RECURRENCE_MAX_SEQ = 512
# Query rows of one block of the scan's quadratic form.
QUADRATIC_ROWS = 1024
# True: the quadratic form's blocks as a Python loop, not `lax.map`
# (`perf/lower_precision.in_bf16` walks the forward's jaxpr and refuses a
# loop primitive; the bf16 control sets it).
UNROLLED = False

# A character of `hybrid_override_pattern` -> (the program's layer type, the
# stack its blocks lie under, the module's name in a block, its norm's).
BLOCKS = {"M": ("mamba", "layers_mamba_none", "mamba", "operator_norm"),
          "*": ("full_attention", "layers_attention_none", "attention",
                "operator_norm"),
          "E": ("moe", "layers_none_moe", "moe_mlp", "ffn_norm"),
          "-": ("mlp", "layers_none_dense", "mlp", "ffn_norm")}


# --- the configuration file -> the program ------------------------------------

# Keys of a configuration file that `reduced` may never name.
WIDTH_KEYS = ("hidden_size", "intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads",
              "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
              "n_groups", "conv_kernel", "chunk_size", "expand",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "num_experts_per_tok", "n_shared_experts")
# `perf/step_jaxpr.py`'s layers: one block of each kind the cell runs.
STEP_CUT = {"num_hidden_layers": 3, "hybrid_override_pattern": "ME*"}


def held(cfg: Mapping) -> tuple:
    """(first id, count) of the experts that have weights here."""
    return cfg.get("experts_held_first", 0), cfg["n_routed_experts"]


def router_width(cfg: Mapping) -> int:
    return cfg.get("n_routed_experts_published", cfg["n_routed_experts"])


def pattern(cfg: Mapping) -> str:
    blocks = cfg["hybrid_override_pattern"]
    if len(blocks) != cfg["num_hidden_layers"] or set(blocks) - set(BLOCKS):
        raise ValueError(
            f"hybrid_override_pattern {blocks!r} does not name one of "
            f"{sorted(BLOCKS)} for each of the {cfg['num_hidden_layers']} "
            f"blocks")
    return blocks


def gpt_config(cfg: Mapping, **options):
    """The program's GPTConfig at the configuration file's sizes."""
    from tpu_trainer.models.config import GPTConfig

    stated = {"attention_bias": False, "mamba_proj_bias": False,
              "mlp_bias": False, "use_bias": False, "use_conv_bias": True,
              "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
              "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
              "tie_word_embeddings": False, "time_step_limit": [0, None],
              "residual_in_fp32": False, "sliding_window": None}
    for key, value in stated.items():
        if cfg.get(key, value) != value:
            raise ValueError(
                f"configuration {cfg.get('name')!r} has {key}={cfg[key]!r}; "
                f"the program computes {value!r} and has no option for it")
    if cfg["norm_eps"] != cfg["layer_norm_epsilon"]:
        raise ValueError("the program has one norm epsilon")
    fields = dict(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        layer_types=tuple(BLOCKS[c][0] for c in pattern(cfg)),
        one_sublayer_blocks=True,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        attention_head_dim=cfg["head_dim"],
        rotary_embedding=False,
        intermediate_size=cfg["intermediate_size"],
        ffn_kind="relu2",
        max_seq_len=cfg["max_position_embeddings"],
        initializer_range=cfg["initializer_range"],
        dropout=0.0,
        attention_dropout=cfg["attention_dropout"],
        norm_eps=cfg["layer_norm_epsilon"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"],
        mamba_n_groups=cfg["n_groups"],
        mamba_conv_kernel=cfg["conv_kernel"],
        mamba_chunk_size=cfg["chunk_size"],
        mamba_dt_min=cfg["time_step_min"],
        mamba_dt_max=cfg["time_step_max"],
        mamba_dt_floor=cfg["time_step_floor"],
        num_experts=router_width(cfg),
        moe_experts_held=held(cfg),
        moe_top_k=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_experts=cfg["n_shared_experts"],
        moe_shared_expert_width=cfg["moe_shared_expert_intermediate_size"],
        moe_routed_scale=cfg["routed_scaling_factor"],
        moe_gate_eps=1e-20,
        moe_router="sigmoid",
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
        raise SystemExit(f"perf.families.nemotron_h: the program's GPTConfig "
                         f"cannot state this configuration: {e}")


# --- the plain reference ------------------------------------------------------

def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(F32)


def _causal_conv(x, weight, bias):
    """Depthwise causal convolution: ``y_t = bias + sum_j w[:, j] x_{t - (K -
    1 - j)}``, ``x [batch, seq, channels]``, ``weight [channels, K]`` (the
    last tap on the current position), zeros before the sequence."""
    taps, seq = weight.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(padded[:, j:j + seq] * weight[:, j]
                      for j in range(taps))


def scan_recurrence(x, dt, a, b, c):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t``,
    token by token from a zero state. ``x [batch, seq, H, P]``, ``dt [batch,
    seq, H]``, ``a [H]``, ``b``, ``c`` ``[batch, seq, G, N]``."""
    batch, _, heads, p = x.shape
    per = heads // b.shape[2]
    expand = lambda v: jnp.repeat(v, per, axis=2)  # noqa: E731

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs                        # [batch, H, .]
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    to_time = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731
    _, y = jax.lax.scan(
        step, jnp.zeros((batch, heads, p, b.shape[3]), F32),
        (to_time(x), to_time(dt), to_time(expand(b)), to_time(expand(c))))
    return jnp.moveaxis(y, 0, 1)


def scan_quadratic(x, dt, a, b, c):
    """The same scan as its quadratic dual: ``y_i = sum_{j <= i} (C_i . B_j)
    exp(cum_i - cum_j) dt_j x_j`` with ``cum`` the cumulative sum of ``dt a``
    over the WHOLE sequence (every difference taken is <= 0): a ``[rows,
    seq]`` block of the lower triangle for one group's heads at a time
    (``QUADRATIC_ROWS`` query rows against every key, masked), one block
    after another; under ``jax.grad`` a block keeps its indices only."""
    batch, seq, heads, p = x.shape
    groups = b.shape[2]
    per = heads // groups
    rows = min(QUADRATIC_ROWS, seq)
    if seq % rows:
        raise ValueError(f"{seq} tokens are not whole blocks of {rows} rows")
    cum = jnp.cumsum(dt * a, axis=1)                        # [batch, seq, H]
    pos = jnp.arange(seq)

    @jax.checkpoint
    def block(g, lo):
        of_group = lambda v: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            v, g * per, per, axis=2)
        of_rows = lambda v: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            v, lo, rows, axis=1)
        x_g, dt_g, cum_g = of_group(x), of_group(dt), of_group(cum)
        b_g = jax.lax.dynamic_index_in_dim(b, g, axis=2, keepdims=False)
        c_g = of_rows(jax.lax.dynamic_index_in_dim(c, g, axis=2,
                                                   keepdims=False))
        seen = (lo + jnp.arange(rows))[:, None] >= pos[None, :]
        decay = jnp.exp(jnp.where(
            seen[None, :, :, None],
            of_rows(cum_g)[:, :, None] - cum_g[:, None], -jnp.inf))
        scores = jnp.einsum("bin,bjn->bij", c_g, b_g)       # [batch, rows, seq]
        weights = scores[..., None] * decay * dt_g[:, None]
        return jnp.einsum("bijh,bjhp->bihp", weights, x_g)

    blocks = [(g, lo) for g in range(groups) for lo in range(0, seq, rows)]
    if UNROLLED:
        out = jnp.stack([block(g, lo) for g, lo in blocks])
    else:
        out = jax.lax.map(lambda at: block(at[0], at[1]),
                          (jnp.array([g for g, _ in blocks]),
                           jnp.array([lo for _, lo in blocks])))
    # [groups, blocks a group, batch, rows, per, p] -> [batch, seq, H, p]
    out = out.reshape(groups, seq // rows, batch, rows, per, p)
    return out.transpose(2, 1, 3, 0, 4, 5).reshape(batch, seq, heads, p)


def scan(x, dt, a, b, c):
    """The state-space scan of the reference (see the module's note)."""
    form = scan_recurrence if x.shape[1] <= RECURRENCE_MAX_SEQ else \
        scan_quadratic
    return form(x, dt, a, b, c)


def gated_norm(y, z, weight, groups, eps):
    """``RMSNorm_groups(y * silu(z))``: the gate FIRST, then the norm over
    each of the ``groups`` groups of lanes, times the ``[d_in]`` weight."""
    shape = y.shape
    gated = (y * jax.nn.silu(z)).reshape(
        *shape[:-1], groups, shape[-1] // groups)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
    return normed.reshape(shape) * weight


def mamba(u, p, cfg):
    heads, lanes = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner = heads * lanes
    batch, seq, _ = u.shape
    zxbcdt = u @ p["in_proj"]["kernel"].astype(F32)
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:-heads],
                  zxbcdt[..., -heads:])
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_weight"].astype(F32),
                                   p["conv_bias"].astype(F32)))
    x = xbc[..., :inner].reshape(batch, seq, heads, lanes)
    b = xbc[..., inner:inner + groups * n].reshape(batch, seq, groups, n)
    c = xbc[..., inner + groups * n:].reshape(batch, seq, groups, n)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    y = scan(x, dt, -jnp.exp(p["A_log"].astype(F32)), b, c)
    y = y + p["D"].astype(F32)[:, None] * x
    y = gated_norm(y.reshape(batch, seq, inner), z,
                   p["norm"]["weight"].astype(F32), groups,
                   cfg["layer_norm_epsilon"])
    return y @ p["out_proj"]["kernel"].astype(F32)


def attention(h, p, cfg):
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    batch, s, _ = h.shape
    f = lambda name: p[name]["kernel"].astype(F32)  # noqa: E731
    q = (h @ f("q_proj")).reshape(batch, s, heads, d)
    # Query head i reads key/value head i // (heads / kv).
    k = jnp.repeat((h @ f("k_proj")).reshape(batch, s, kv, d),
                   heads // kv, axis=2)
    v = jnp.repeat((h @ f("v_proj")).reshape(batch, s, kv, d),
                   heads // kv, axis=2)
    scale = 1.0 / jnp.sqrt(F32(d))
    pos = jnp.arange(s)

    def rows(lo):
        """Query rows ``lo .. lo + ROW_BLOCK`` against the keys up to the
        last of them (no later key is seen by any)."""
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
    return out.reshape(batch, s, heads * d) @ f("o_proj")


def _relu2_ffn(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down


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
    every = jax.vmap(_relu2_ffn, in_axes=(None, 0, 0))(
        h, p["experts_up"].astype(F32), p["experts_down"].astype(F32))
    return jnp.einsum("e...h,...e->...h", every,
                      weights[..., first:first + count]), own


def shared_expert(h, p):
    s = lambda name: p["shared_expert"][name]["kernel"].astype(F32)  # noqa: E731
    return _relu2_ffn(h, s("up_proj"), s("down_proj"))


def _block(x, p, kind, cfg, choice=None):
    """The block's output and, of an expert block, its router's own
    choice."""
    _, _, module, norm = BLOCKS[kind]
    h = _rms_norm(x, p[norm]["weight"], cfg["layer_norm_epsilon"])
    p = p[module]
    if kind == "M":
        return x + mamba(h, p, cfg), None
    if kind == "*":
        return x + attention(h, p, cfg), None
    if kind == "E":
        routed, own = routed_experts(h, p, cfg, choice)
        shared = shared_expert(h, p) if cfg["n_shared_experts"] else 0.0
        return x + routed + shared, own
    m = lambda name: p[name]["kernel"].astype(F32)  # noqa: E731
    return x + _relu2_ffn(h, m("up_proj"), m("down_proj")), None


def head(params):
    return params["lm_head"].astype(F32)


def trunk(params, tokens, cfg: Mapping, choice=None):
    """The stack's output before the final norm, and each expert block's own
    choice of experts, in the order the blocks run."""
    x = params["embed_tokens"]["embedding"][tokens].astype(F32)
    given = iter(choice) if choice is not None else None
    seen: dict = {}
    chosen = []
    for kind in pattern(cfg):
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        # Under `jax.grad` a block keeps its input only.
        x, own = jax.checkpoint(
            lambda x, p, ids, kind=kind: _block(x, p, kind, cfg, ids))(
            x, jax.tree_util.tree_map(lambda a: a[i],
                                      params[BLOCKS[kind][1]]),
            next(given) if given is not None and kind == "E" else None)
        if own is not None:
            chosen.append(own)
    return x, chosen


def forward_and_choices(params, tokens, cfg: Mapping, choice=None):
    """Logits ``[batch, seq, vocab]`` in float32 for ``tokens [batch, seq]``
    and, for each expert block in the order they run, which experts its
    router chose (ids ``[batch, seq, k]``). ``choice``: for each of them,
    the ids to route by instead."""
    with jax.default_matmul_precision("highest"):
        x, chosen = trunk(params, tokens, cfg, choice)
        x = _rms_norm(x, params["norm"]["weight"], cfg["layer_norm_epsilon"])
        return x @ head(params).T, chosen


def forward(params, tokens, cfg: Mapping, choice=None):
    return forward_and_choices(params, tokens, cfg, choice)[0]


def rows_loss(params, rows, cfg: Mapping, choice=None):
    """Next-token cross entropy over ``rows [n, seq]``: position ``i``
    predicts token ``i + 1``, a mean over the positions that have one."""
    logits = forward(params, rows, cfg, choice)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
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
# state-space block the two projections and the scan's own matrix products
# (``ssd_flops_fwd_per_token``) are counted; the taps, the gate and the norms
# are vector work and are not.

def blocks_of(cfg: Mapping, kind: str) -> int:
    return pattern(cfg).count(kind)


def mamba_matmul_params(cfg: Mapping) -> int:
    h, inner = cfg["hidden_size"], cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv_dim = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return h * (inner + conv_dim + cfg["mamba_num_heads"]) + inner * h


def attention_params(cfg: Mapping) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return (2 * h * cfg["num_attention_heads"] * d
            + 2 * h * cfg["num_key_value_heads"] * d)


def expert_params(cfg: Mapping) -> int:
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: Mapping) -> int:
    return (2 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]
            if cfg["n_shared_experts"] else 0)


def _block_params(cfg: Mapping, kind: str) -> int:
    """Every parameter of one block: its sublayer and its norm."""
    h = cfg["hidden_size"]
    if kind == "M":
        heads = cfg["mamba_num_heads"]
        inner = heads * cfg["mamba_head_dim"]
        conv_dim = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
        # taps and their bias; dt_bias, A_log, D; the gated norm's weight
        return (mamba_matmul_params(cfg) + conv_dim * (cfg["conv_kernel"] + 1)
                + 3 * heads + inner + h)
    if kind == "*":
        return attention_params(cfg) + h
    if kind == "E":
        return (cfg["n_routed_experts"] * expert_params(cfg)
                + shared_params(cfg)
                + h * router_width(cfg) + router_width(cfg) + h)
    return 2 * h * cfg["intermediate_size"] + h


def param_count(cfg: Mapping) -> int:
    """Every parameter that lives here: the held experts, the sliced
    embedding and head, the selection bias with the router."""
    h = cfg["hidden_size"]
    return (2 * cfg["vocab_size"] * h + h
            + sum(_block_params(cfg, kind) for kind in pattern(cfg)))


def moe_layers(cfg: Mapping) -> int:
    """Expert blocks a token passes."""
    return blocks_of(cfg, "E")


def even_rows_per_token(cfg: Mapping) -> float:
    """Rows a token brings to the experts held here, each expert block, when
    routing is even."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / router_width(cfg))


def attention_flops_fwd(cfg: Mapping, seq_len: int) -> float:
    """Causal attention forward FLOPs of ONE sequence, every attention
    block: a query sees ``i + 1`` keys, QK^T and PV a head."""
    return (blocks_of(cfg, "*") * 2 * 2 * cfg["num_attention_heads"]
            * cfg["head_dim"] * seq_len * (seq_len + 1) / 2)


def ssd_flops_fwd_per_token(cfg: Mapping) -> float:
    """Forward FLOPs a token of ONE state-space block's scan in the chunked
    form at the published chunk ``Q``: the in-chunk scores ``C B^T`` a group
    (``2 Q N G``), the masked scores times ``x`` a head (``2 Q P H``), a
    chunk's contribution to its end state (``2 P N H``) and the carried
    state's part of the outputs (``2 N P H``). The pass over the chunk
    boundaries is not counted: a sequential carry does not have it."""
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n, q = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    return 2.0 * (q * n * groups + q * p * heads + 2 * p * n * heads)


def train_flops_per_token(cfg: Mapping, seq_len: int,
                          rows_per_token: Optional[float] = None) -> float:
    """Model FLOPs per trained token, forward + backward. The routed experts
    count by the rows they are given: ``rows_per_token`` a token and expert
    block (what the program's counter read; the even share if not given)."""
    if rows_per_token is None:
        rows_per_token = even_rows_per_token(cfg)
    h = cfg["hidden_size"]
    matmul = (blocks_of(cfg, "M") * mamba_matmul_params(cfg)
              + blocks_of(cfg, "*") * attention_params(cfg)
              + blocks_of(cfg, "E") * (
                  h * router_width(cfg) + shared_params(cfg)
                  + rows_per_token * expert_params(cfg))
              + blocks_of(cfg, "-") * 2 * h * cfg["intermediate_size"]
              + cfg["vocab_size"] * h)
    return (6.0 * matmul
            + 3.0 * blocks_of(cfg, "M") * ssd_flops_fwd_per_token(cfg)
            + 3.0 * attention_flops_fwd(cfg, seq_len) / seq_len)


def mfu(cfg: Mapping, seq_len: int, tokens_per_s: float, chips: int,
        peak_flops_per_s: float,
        rows_per_token: Optional[float] = None) -> float:
    return (train_flops_per_token(cfg, seq_len, rows_per_token) * tokens_per_s
            / (chips * peak_flops_per_s))


def flash_work(cfg: Mapping, seq_len: int, sequences: float,
               bytes_per_value: int = 2) -> dict:
    """FLOPs and least HBM bytes of the attention kernels for ``sequences``
    sequences of one training step, every attention block, forward and
    backward, causal (3 x forward; the score recomputation inside the
    backward kernels is not counted), at the model's heads: ``heads x
    head_dim`` lanes of queries over ``num_key_value_heads`` heads of keys
    and values, nothing rotated. Bytes: the forward reads q, k and v (a K/V
    head once, not once a query head) and writes the result; the backward
    reads those, the result and its cotangent and writes the three
    gradients, each once in the compute type."""
    d = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    operands = q + 2 * kv
    values = seq_len * (operands + q                 # forward
                        + 2 * operands + 2 * q)      # backward
    return {"flops": 3.0 * attention_flops_fwd(cfg, seq_len) * sequences,
            "bytes": (blocks_of(cfg, "*") * sequences * values
                      * bytes_per_value)}


def gmm_work(cfg: Mapping, rows: float, layer_passes: int,
             bytes_per_value: int = 2) -> dict:
    """FLOPs and least HBM bytes of the grouped matmuls of ``layer_passes``
    passes through an expert block (forward AND backward each) that gave the
    held experts ``rows`` rows in all. An expert is TWO matrices: a pass is 2
    ``gmm`` forward (up, down), 2 ``gmm`` (the inputs' gradients) and 2
    ``tgmm`` (the weights') backward, each ``2 * rows * H * I`` FLOPs. Bytes:
    every call reads its rows' operands once and writes its result once in
    the compute type; a ``gmm`` reads the held experts' weights once a call,
    a ``tgmm`` writes their gradient once a call in float32. The shared
    expert is a plain matmul and is not counted here."""
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["n_routed_experts"] * h * inter
    return {"flops": 6 * 2.0 * rows * h * inter,
            "bytes": (rows * 6 * (h + inter) * bytes_per_value
                      + layer_passes * weights * (4 * bytes_per_value + 2 * 4))}


def ssd_work(cfg: Mapping, tokens: float, bytes_per_value: int = 2) -> dict:
    """FLOPs and least HBM bytes of the state-space scans that ``tokens``
    tokens of training pass, EVERY state-space block, forward and backward:
    FLOPs the chunked form's at the published chunk (``ssd_flops_fwd_per_
    token``), forward + twice that for the backward, whatever computes the
    scan; bytes ``x``, ``B``, ``C`` (compute type) and ``dt`` (float32) read
    once a pass, ``y`` written once forward, its cotangent read and the four
    gradients written once backward. A recomputation inside the backward is
    not counted."""
    heads = cfg["mamba_num_heads"]
    inner = heads * cfg["mamba_head_dim"]
    xbc = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    operands = xbc * bytes_per_value + heads * 4
    per_token = 3 * operands + 2 * inner * bytes_per_value
    blocks = blocks_of(cfg, "M")
    return {"flops": 3.0 * ssd_flops_fwd_per_token(cfg) * tokens * blocks,
            "bytes": float(per_token) * tokens * blocks}
