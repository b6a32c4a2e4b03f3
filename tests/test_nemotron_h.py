"""Mamba-2 state-space layers, blocks of one sublayer, two-matrix relu^2
experts beside a shared one of its own width, attention whose width is not
the hidden size and that rotates nothing: the chunked scan against the
token-by-token recurrence, the program against the plain reference of
``perf/families/nemotron_h.py`` at a small size, the shares tied to the uncut
model, what the trainer keeps in f32 and leaves undecayed, ZeRO-3 and a
checkpoint round trip, and what the new layers cannot do yet refused in
words."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.families import nemotron_h as family
from tpu_trainer.models import moe
from tpu_trainer.models.config import GPTConfig
from tpu_trainer.models.gpt import (
    GPT, Mamba2Mixer, RMSNorm, computed_in_f32, stack_name, undecayed)
from tpu_trainer.ops.ssd import ssd

# Published pattern's first five blocks at small widths: 8 experts top-3 of
# which 2 are held (ids 2-3), a shared expert of its own width, 4 query
# heads of 16 lanes over 2 K/V heads (64 lanes of attention, 48 hidden).
TINY = {
    "name": "tiny-nemotron-h", "family": "nemotron_h", "hidden_size": 48,
    "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 40, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 56, "n_shared_experts": 1,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 16, "expand": 2,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
    "time_step_limit": [0, None], "n_routed_experts": 2,
    "n_routed_experts_published": 8, "experts_held_first": 2,
    "num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "use_conv_bias": True, "layer_norm_epsilon": 1e-05, "norm_eps": 1e-05,
    "tie_word_embeddings": False, "vocab_size": 128,
    "max_position_embeddings": 128, "initializer_range": 0.02,
    "attention_dropout": 0.0,
}
UNCUT = dict(TINY, n_routed_experts=8, experts_held_first=0)


def scaled(params, by=4.0):
    """Larger matrices than the initialiser's (logits of order one, scores
    that spread), and vectors off their initial ones and zeros, so that
    every norm weight, ``D`` and the conv's bias show in the result."""
    def scale(path, p):
        name = str(path)
        if "router" in name:
            return p * by * 4
        if any(k in name for k in ("kernel", "embedding", "lm_head",
                                   "experts_")):
            return p * by
        if "expert_bias" in name:
            return p
        return p + 0.1 * jax.random.normal(
            jax.random.PRNGKey(p.size), p.shape, p.dtype)

    return jax.tree_util.tree_map_with_path(scale, params)


def init(cfg, dtype="float32", **options):
    model = GPT(family.gpt_config(cfg, dtype=dtype, **options))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, scaled(params)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 128)


def _rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2))
                 / (jnp.sqrt(jnp.mean(want ** 2)) + 1e-12))


# --- the chunked scan against the recurrence -----------------------------------

def _scan_inputs(seq, dt_scale=1.0, seed=0, heads=4, p=8, groups=2, n=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (2, seq, heads, p))
    dt = dt_scale * jax.nn.softplus(jax.random.normal(ks[1], (2, seq, heads)))
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.7))
    b = jax.random.normal(ks[3], (2, seq, groups, n))
    c = jax.random.normal(ks[4], (2, seq, groups, n))
    return x, dt, a, b, c


# A whole number of chunks of 16, one chunk and a bit, fewer tokens than a
# chunk; and time steps so large that the cumulative product of decays over
# a chunk is exp(-6500) = 0 in f32 (a ratio of such products is 0 / 0).
@pytest.mark.parametrize("seq,dt_scale,dtype,tol", [
    (64, 1.0, "float32", 2e-5), (37, 1.0, "float32", 2e-5),
    (9, 1.0, "float32", 2e-5), (48, 40.0, "float32", 2e-5),
    (64, 1.0, "bfloat16", 2e-2), (37, 40.0, "bfloat16", 2e-2)])
def test_the_chunked_scan_is_the_recurrence(seq, dt_scale, dtype, tol):
    args = _scan_inputs(seq, dt_scale)
    cast = tuple(v.astype(dtype) if i in (0, 3, 4) else v
                 for i, v in enumerate(args))
    wide = tuple(v.astype(jnp.float32) for v in cast)

    def chunked(*a):
        return ssd(*a, chunk=16)[0]

    with jax.default_matmul_precision("highest"):
        got, low = jax.jit(lambda *a: ssd(*a, chunk=16))(*cast)
        want = jax.jit(family.scan_recurrence)(*wide)
        assert got.dtype == jnp.float32 and bool(jnp.all(jnp.isfinite(got)))
        assert _rel(got, want) < tol
        if dt_scale > 1:
            assert float(low) < -200       # exp(low) underflows in f32
        weight = jax.random.normal(jax.random.PRNGKey(9), want.shape)
        grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(chunked(*a) * weight), argnums=range(5)))(*cast)
        wants = jax.jit(jax.grad(
            lambda *a: jnp.sum(family.scan_recurrence(*a) * weight),
            argnums=range(5)))(*wide)
    for got_g, want_g in zip(grads, wants):
        assert bool(jnp.all(jnp.isfinite(got_g.astype(jnp.float32))))
        assert _rel(got_g, want_g) < (40 * tol if dt_scale > 1 else 10 * tol)


def test_the_references_two_forms_of_the_scan_agree(monkeypatch):
    """The reference's recurrence (what the tests compare with) and its
    quadratic dual (what the cell's 4,096 tokens run), in one block, in
    blocks of rows under ``lax.map``, and unrolled as the bf16 control
    walks it."""
    args = _scan_inputs(64, seed=3)
    with jax.default_matmul_precision("highest"):
        want = family.scan_recurrence(*args)
        assert _rel(family.scan_quadratic(*args), want) < 1e-5
        monkeypatch.setattr(family, "QUADRATIC_ROWS", 16)
        assert _rel(family.scan_quadratic(*args), want) < 1e-5
        monkeypatch.setattr(family, "UNROLLED", True)
        assert _rel(family.scan_quadratic(*args), want) < 1e-5
        with pytest.raises(ValueError, match="whole blocks"):
            family.scan_quadratic(*_scan_inputs(50))
    monkeypatch.setattr(family, "RECURRENCE_MAX_SEQ", 0)
    assert family.scan(*args).shape == want.shape


# --- the model against the reference -------------------------------------------

# f32: only the order of additions differs. bf16: the program rounds every
# activation to 8 bits of mantissa where the reference keeps 24, and a
# choice of expert flips near a tie (the comparison here is not routed
# alike).
TOL = {"float32": {"logits": 2e-5, "loss": 1e-5, "grad": 3e-4},
       "bfloat16": {"logits": 0.10, "loss": 1e-2, "grad": 0.6}}


@jax.jit
def _reference(params, tokens):
    return family.forward(params, tokens, TINY), jax.value_and_grad(
        family.loss)(params, tokens, TINY, 2)


@pytest.mark.parametrize("dtype,unroll", [
    ("float32", True), ("float32", False), ("bfloat16", True)])
def test_program_matches_the_reference(tokens, dtype, unroll):
    model, params = init(TINY, dtype, scan_unroll=unroll)

    def loss(p):
        return model.apply({"params": p}, tokens, labels=tokens,
                           train=True)[1]

    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(model.apply)({"params": params}, tokens)
        got_loss, got_grad = jax.jit(jax.value_and_grad(loss))(params)
        want, (want_loss, want_grad) = _reference(params, tokens)
    tol = TOL[dtype]
    assert logits.shape == (2, 40, 128) and logits.dtype == jnp.float32
    assert _rel(logits, want) < tol["logits"]
    assert abs(float(got_loss) - float(want_loss)) < tol["loss"] * float(
        want_loss)
    leaves = jax.tree_util.tree_leaves_with_path(want_grad)
    assert len(leaves) == 24
    for (path, want_leaf), got_leaf in zip(
            leaves, jax.tree_util.tree_leaves(got_grad)):
        if "expert_bias" in str(path):     # a buffer: no gradient reaches it
            assert not bool(jnp.any(got_leaf)) and not bool(
                jnp.any(want_leaf))
            continue
        assert float(jnp.linalg.norm(want_leaf)) > 0, path
        assert _rel(got_leaf, want_leaf) < tol["grad"], path


def test_the_parameter_tree_and_its_count():
    model, params = init(TINY)
    cfg = model.config
    assert cfg.layer_kinds() == (
        ("mamba", "none"), ("none", "moe"), ("mamba", "none"),
        ("attention", "none"), ("none", "moe"))
    assert set(params) == {"embed_tokens", "lm_head", "norm",
                           "layers_mamba_none", "layers_attention_none",
                           "layers_none_moe"}
    mixer = params["layers_mamba_none"]["mamba"]
    assert {k: v.shape for k, v in mixer.items() if k not in (
        "in_proj", "out_proj", "norm")} == {
            "A_log": (2, 8), "D": (2, 8), "dt_bias": (2, 8),
            "conv_weight": (2, 128, 4), "conv_bias": (2, 128)}
    # [z, xBC, dt]: 64 + (64 + 2 x 2 x 16) + 8 columns.
    assert mixer["in_proj"]["kernel"].shape == (2, 48, 64 + 128 + 8)
    attention = params["layers_attention_none"]["attention"]
    assert attention["q_proj"]["kernel"].shape == (1, 48, 64)
    assert attention["k_proj"]["kernel"].shape == (1, 48, 32)
    assert attention["o_proj"]["kernel"].shape == (1, 64, 48)
    experts = params["layers_none_moe"]["moe_mlp"]
    assert set(experts) == {"router", "expert_bias", "experts_up",
                            "experts_down", "shared_expert"}
    assert experts["experts_up"].shape == (2, 2, 48, 24)
    assert experts["shared_expert"]["up_proj"]["kernel"].shape == (2, 48, 56)
    assert set(experts["shared_expert"]) == {"up_proj", "down_proj"}
    # One norm a block.
    assert set(params["layers_none_moe"]) == {"ffn_norm", "moe_mlp"}
    assert set(params["layers_mamba_none"]) == {"operator_norm", "mamba"}
    count = sum(p.size for p in jax.tree_util.tree_leaves(params))
    assert count == cfg.num_parameters() == family.param_count(TINY)
    # The initialiser: A in [1, 16], D = 1, dt = softplus(dt_bias) in range.
    fresh = model.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))["params"]
    fresh = fresh["layers_mamba_none"]["mamba"]
    a, dt = jnp.exp(fresh["A_log"]), jax.nn.softplus(fresh["dt_bias"])
    assert float(a.min()) >= 1 and float(a.max()) <= 16
    assert float(dt.min()) >= 0.00099 and float(dt.max()) <= 0.101
    assert bool(jnp.all(fresh["D"] == 1))


def test_a_mamba_layer_with_a_dense_ffn_in_a_two_sublayer_block():
    """``mamba`` is an operator like the others: without
    ``one_sublayer_blocks`` a block is (operator, FFN), and the dense FFN
    takes ``ffn_kind`` too."""
    cfg = GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=48, max_seq_len=32, dropout=0.0,
        attention_dropout=0.0, layer_types=("mamba", "full_attention"),
        ffn_kind="relu2", mamba_num_heads=4, mamba_head_dim=8,
        ssm_state_size=8, mamba_n_groups=1, mamba_chunk_size=8,
        dtype="float32")
    assert cfg.layer_kinds() == (("mamba", "dense"), ("attention", "dense"))
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 20), 0, 64)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(1), toks)["params"]
    assert set(params["layers_mamba_dense"]["mlp"]) == {"up_proj",
                                                        "down_proj"}
    assert sum(p.size for p in jax.tree_util.tree_leaves(params)) == (
        cfg.num_parameters())
    loss = model.apply({"params": params}, toks, labels=toks)[1]
    assert 3.5 < float(loss) < 5.0


# --- the shares and the uncut model ---------------------------------------------

def _routed_share(layer, first, count=2):
    return {"router": layer["router"], "expert_bias": layer["expert_bias"],
            **{k: layer[k][first:first + count]
               for k in ("experts_up", "experts_down")}}


def _summed_over_shares(cfg, layer, h):
    """What an expert-parallel deployment of four chips computes: each its
    routed part, summed by the exchange, and the shared expert ONCE."""
    routed_only = dataclasses.replace(cfg, moe_shared_experts=0,
                                      moe_shared_expert_width=None)
    out = moe.SharedExpert(cfg).apply({"params": layer["shared_expert"]}, h)
    for first in (0, 2, 4, 6):
        share = dataclasses.replace(routed_only, moe_experts_held=(first, 2))
        out = out + moe.MoEMLP(share).apply(
            {"params": _routed_share(layer, first)}, h)[0]
    return out


def test_the_shares_of_one_layer_add_up_to_the_uncut_layer():
    uncut_cfg = family.gpt_config(UNCUT, dtype="float32")
    _, params = init(UNCUT)
    layer = jax.tree_util.tree_map(
        lambda a: a[1], params["layers_none_moe"]["moe_mlp"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 48))

    @jax.jit
    def run(layer, h):
        routed, _ = family.routed_experts(h, layer, UNCUT)
        shared = family.shared_expert(h, layer)
        whole, _ = moe.MoEMLP(uncut_cfg).apply({"params": layer}, h)
        shares, references = [], []
        for first in (0, 2, 4, 6):
            cfg = dataclasses.replace(uncut_cfg, moe_experts_held=(first, 2))
            share = dict(_routed_share(layer, first),
                         shared_expert=layer["shared_expert"])
            shares.append(moe.MoEMLP(cfg).apply({"params": share}, h)[0])
            cut = dict(UNCUT, n_routed_experts=2, experts_held_first=first)
            references.append(family.routed_experts(h, share, cut)[0]
                              + family.shared_expert(h, share))
        return (routed, shared, whole, shares, references,
                _summed_over_shares(uncut_cfg, layer, h))

    with jax.default_matmul_precision("highest"):
        routed, shared, whole, shares, references, summed = run(layer, h)
    close = lambda a, b: float(jnp.max(jnp.abs(a - b))) < 1e-5  # noqa: E731
    want = routed + shared
    assert float(jnp.std(shared)) > 0.01 and float(jnp.std(routed)) > 0.01
    assert close(whole, want)
    assert all(close(a, b) for a, b in zip(shares, references))
    # Summed as they are, the shared expert counts four times ...
    assert close(sum(shares), want + 3 * shared)
    # ... the routed parts and the shared expert ONCE are the layer.
    assert close(summed, want)


def test_the_shares_summed_at_each_layer_give_the_uncut_loss(tokens):
    """Four chips, each with 2 of the 8 experts: every chip computes what it
    holds of each expert block, the partial results are summed with the
    shared expert once, the other blocks run whole, and the loss is the
    uncut reference's."""
    cfg = family.gpt_config(UNCUT, dtype="float32")
    _, params = init(UNCUT)
    norm = RMSNorm(eps=cfg.norm_eps)
    from tpu_trainer.models.gpt import CausalSelfAttention

    def block(p, x, kind):
        if kind[1] == "moe":
            h = norm.apply({"params": p["ffn_norm"]}, x)
            return x + _summed_over_shares(cfg, p["moe_mlp"], h)
        h = norm.apply({"params": p["operator_norm"]}, x)
        if kind[0] == "mamba":
            return x + Mamba2Mixer(cfg).apply({"params": p["mamba"]}, h)
        return x + CausalSelfAttention(cfg).apply(
            {"params": p["attention"]}, h)

    @jax.jit
    def summed_loss(params):
        seen = {}
        x = params["embed_tokens"]["embedding"][tokens]
        for kind in cfg.layer_kinds():
            i = seen.get(kind, 0)
            seen[kind] = i + 1
            x = block(jax.tree_util.tree_map(
                lambda a: a[i], params[stack_name(kind)]), x, kind)
        logits = norm.apply({"params": params["norm"]}, x) @ params[
            "lm_head"].T
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1))

    share = jax.tree_util.tree_map_with_path(
        lambda path, p: p[:, 2:4] if "experts_" in str(path) else p, params)
    with jax.default_matmul_precision("highest"):
        got = float(summed_loss(params))
        want = float(jax.jit(
            lambda p: family.loss(p, tokens, UNCUT, 2))(params))
        alone = float(jax.jit(
            lambda p: family.loss(p, tokens, TINY, 2))(share))
    assert abs(got - want) < 1e-5 * want
    assert abs(alone - want) > 1e-3


# --- two-matrix experts through the bounded path --------------------------------

def _expert_layer(held, experts=16, top_k=2, width=24, hidden=32, **more):
    return GPTConfig(
        vocab_size=64, hidden_size=hidden, num_layers=1, num_heads=2,
        num_experts=experts, moe_top_k=top_k, moe_intermediate_size=width,
        moe_impl="dropless", moe_router="sigmoid", moe_aux_weight=0.0,
        moe_experts_held=held, ffn_kind="relu2", dropout=0.0,
        dtype="float32", **more)


def _plain_loop(cfg, params, h):
    """Every held expert over every token, masked by the routing."""
    flat = h.reshape(-1, h.shape[-1])
    scores = jax.nn.sigmoid(flat @ params["router"]["kernel"])
    _, idx = jax.lax.top_k(scores + params["expert_bias"], cfg.moe_top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    gates = picked / (jnp.sum(picked, -1, keepdims=True) + cfg.moe_gate_eps)
    first, count = cfg.experts_held
    out = jnp.zeros_like(flat)
    for e in range(count):
        y = jnp.square(jax.nn.relu(flat @ params["experts_up"][e])
                       ) @ params["experts_down"][e]
        weight = jnp.sum(jnp.where(idx == first + e, gates, 0.0), axis=-1)
        out = out + weight[:, None] * y
    return out.reshape(h.shape)


# 2 of 16 held: R = 2 x (2 x 96) x 2 / 16 = 48 -> 128 > ... the bound is
# under k T only with many tokens; `overflow` biases the router so that
# every token chooses the held experts and the pass outgrows the buffers.
@pytest.mark.parametrize("overflow", [False, True])
def test_relu2_experts_through_the_bounded_path(overflow):
    cfg = _expert_layer((4, 2), experts=32, top_k=2)
    h = jax.random.normal(jax.random.PRNGKey(0), (4, 512, 32))
    layer = moe.MoEMLP(cfg)
    params = layer.init(jax.random.PRNGKey(1), h)["params"]
    assert set(params) == {"router", "expert_bias", "experts_up",
                           "experts_down"}
    params = dict(params, router={"kernel": params["router"]["kernel"] * 8})
    if overflow:
        params["expert_bias"] = jnp.zeros((32,)).at[4:6].set(10.0)
    rows = moe._receive_rows(2 * 4 * 512, 2, 32)
    assert rows == 512 < 2 * 4 * 512            # the bounded formulation

    def loss(params, h, fn):
        return jnp.sum(jnp.sin(fn(params, h)))

    def program(params, h):
        return layer.apply({"params": params}, h)[0]

    with jax.default_matmul_precision("highest"):
        got = jax.jit(program)(params, h)
        want = jax.jit(lambda p, x: _plain_loop(cfg, p, x))(params, h)
        held = jnp.sum(jnp.abs(want) > 0) / want.size
        assert (float(held) > 0.9) == overflow
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * (
            1 + float(jnp.max(jnp.abs(want))))
        g_got = jax.jit(jax.grad(lambda p, x: loss(p, x, program),
                                 argnums=(0, 1)))(params, h)
        g_want = jax.jit(jax.grad(
            lambda p, x: loss(p, x, lambda a, b: _plain_loop(cfg, a, b)),
            argnums=(0, 1)))(params, h)
    for name in ("experts_up", "experts_down"):
        assert _rel(g_got[0][name], g_want[0][name]) < 1e-4, name
    assert _rel(g_got[0]["router"]["kernel"],
                g_want[0]["router"]["kernel"]) < 1e-4
    assert _rel(g_got[1], g_want[1]) < 1e-4


def test_relu2_experts_all_held_run_the_worst_case_formulation():
    cfg = _expert_layer(None, experts=4, top_k=2)
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    layer = moe.MoEMLP(cfg)
    params = layer.init(jax.random.PRNGKey(1), h)["params"]
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, h)[0]
        want = _plain_loop(cfg, params, h)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


# --- what the trainer keeps in f32 and leaves undecayed --------------------------

def _trainer(mesh_axes, devices, strategy="replicated", cfg=TINY, **training):
    from tpu_trainer.parallel.mesh import MeshConfig, make_mesh
    from tpu_trainer.training.config import TrainingConfig
    from tpu_trainer.training.trainer import ParallelConfig, Trainer

    mesh_config = MeshConfig(**{"data": 1, "fsdp": 1, **mesh_axes})
    return Trainer(
        family.gpt_config(cfg),
        TrainingConfig(**{"batch_size": 2, "max_seq_len": 32, **training}),
        ParallelConfig(mesh=mesh_config, sharding_strategy=strategy),
        mesh=make_mesh(mesh_config, devices=jax.devices()[:devices]))


def test_what_a_module_computes_in_f32_and_what_takes_no_decay():
    from tpu_trainer.training.optimizer import decay_mask

    one = _trainer({}, 1, gradient_accumulation_steps=2,
                   mixed_precision="bf16")
    state = one.init_state(0)
    copy = state.params_c
    mixer = copy["layers_mamba_none"]["mamba"]
    for name in Mamba2Mixer.F32_LEAVES:          # [count, heads]: 2-D
        assert mixer[name].dtype == jnp.float32 and mixer[name].ndim == 2
    assert mixer["in_proj"]["kernel"].dtype == jnp.bfloat16
    assert mixer["conv_weight"].dtype == jnp.bfloat16
    experts = copy["layers_none_moe"]["moe_mlp"]
    assert experts["router"]["kernel"].dtype == jnp.float32
    assert experts["experts_up"].dtype == jnp.bfloat16
    assert computed_in_f32(("layers_mamba_none", "mamba", "A_log"))
    assert computed_in_f32(("layers_none_moe", "moe_mlp", "router", "kernel"))
    assert not computed_in_f32(("layers_mamba_none", "mamba", "conv_bias"))
    # A leaf called D elsewhere is nobody's declaration.
    assert not computed_in_f32(("elsewhere", "D"))
    assert not undecayed(("elsewhere", "D"))

    mask = decay_mask(state.params)
    mixer = mask["layers_mamba_none"]["mamba"]
    assert {k for k, v in mixer.items() if v is False} == {
        "A_log", "D", "dt_bias", "conv_bias"}
    assert mixer["norm"]["weight"] is False
    assert mixer["in_proj"]["kernel"] and mixer["conv_weight"]
    assert mask["layers_mamba_none"]["operator_norm"]["weight"] is False
    experts = mask["layers_none_moe"]["moe_mlp"]
    assert experts["expert_bias"] is False and experts["router"]["kernel"]
    assert mask["lm_head"] and mask["embed_tokens"]["embedding"]
    assert mask["norm"]["weight"] is False


def test_a_zero3_step_is_the_replicated_step_and_counts_the_scan():
    batch = np.random.default_rng(0).integers(0, 128, size=(4, 32),
                                              dtype=np.int32)
    one = _trainer({}, 1, gradient_accumulation_steps=1,
                   mixed_precision="fp32", batch_size=4)
    state, want = one.train_step(one.init_state(0), batch)
    # 4 x 32 tokens through 2 state-space blocks.
    assert float(want["ssm_tokens"]) == 4 * 32 * 2
    assert float(want["ssd_kernel_tokens"]) == 0    # chunk 16, off the chip
    assert -26.0 < float(want["ssd_min_log_decay"]) < 0.0  # 16 x 0.1 x 16
    assert float(want["moe_rows_held"]) > 0
    many = _trainer({"fsdp": 4}, 4, "zero3", gradient_accumulation_steps=1,
                    mixed_precision="fp32", batch_size=1)
    sharded = many.init_state(0)
    mixer = sharded.params["layers_mamba_none"]["mamba"]
    for name in ("conv_weight", "conv_bias", "A_log"):
        assert "fsdp" in str(mixer[name].sharding.spec), name
    assert "fsdp" in str(mixer["in_proj"]["kernel"].sharding.spec)
    sharded, got = many.train_step(sharded, batch)
    assert abs(float(got["loss"]) - float(want["loss"])) < 1e-5 * float(
        want["loss"])
    assert abs(float(got["grad_norm"]) - float(want["grad_norm"])) < (
        1e-4 * float(want["grad_norm"]))
    assert float(got["ssm_tokens"]) == float(want["ssm_tokens"])
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(sharded.params)):
        assert float(jnp.max(jnp.abs(a - jax.device_get(b)))) < 1e-5


def test_a_step_counts_the_tokens_whose_scan_took_the_kernels(monkeypatch):
    """At sizes the scan's kernels take (heads of 64 lanes, state 128, chunk
    128) and under the interpret hook, the step's ``ssd_kernel_tokens`` is
    its ``ssm_tokens``; the same step on the plain CPU path counts none and
    reads the same loss."""
    fitting = dict(TINY, hybrid_override_pattern="MEM", num_hidden_layers=3,
                   mamba_num_heads=2, mamba_head_dim=64, ssm_state_size=128,
                   n_groups=1, chunk_size=128, max_position_embeddings=128)
    batch = np.random.default_rng(2).integers(0, 128, size=(2, 128),
                                              dtype=np.int32)
    options = dict(cfg=fitting, gradient_accumulation_steps=1,
                   mixed_precision="fp32", batch_size=2, max_seq_len=128)
    plain = _trainer({}, 1, **options)
    _, want = plain.train_step(plain.init_state(0), batch)
    assert float(want["ssm_tokens"]) == 2 * 128 * 2
    assert float(want["ssd_kernel_tokens"]) == 0
    monkeypatch.setenv("TPU_TRAINER_FLASH_INTERPRET", "1")
    hooked = _trainer({}, 1, **options)
    _, got = hooked.train_step(hooked.init_state(0), batch)
    assert float(got["ssd_kernel_tokens"]) == float(got["ssm_tokens"]) == 512
    assert abs(float(got["loss"]) - float(want["loss"])) < 1e-5 * float(
        want["loss"])
    assert abs(float(got["grad_norm"]) - float(want["grad_norm"])) < (
        1e-3 * float(want["grad_norm"]))


def test_a_checkpoint_round_trip(tmp_path):
    from tpu_trainer.utils import checkpoint as ckpt

    batch = np.random.default_rng(1).integers(0, 128, size=(2, 32),
                                              dtype=np.int32)
    one = _trainer({}, 1, gradient_accumulation_steps=1,
                   mixed_precision="bf16")
    state, _ = one.train_step(one.init_state(0), batch)
    path = ckpt.save_checkpoint(
        str(tmp_path), state, model_config=one.model_config,
        training_config=one.training_config)
    again = _trainer({}, 1, gradient_accumulation_steps=1,
                     mixed_precision="bf16")
    restored, meta = ckpt.restore_checkpoint(path, again)
    # The layer pattern comes back a tuple of names, as the config holds it.
    assert tuple(meta["model_config"]["layer_types"]) == (
        one.model_config.layer_types)
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(restored.params)):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))
    _, first = one.train_step(state, batch)
    _, second = again.train_step(restored, batch)
    assert float(first["loss"]) == float(second["loss"])


# --- what the new layers cannot do yet -------------------------------------------

def test_generation_and_the_caches_are_refused_in_words():
    from tpu_trainer.models.gpt import (
        generate, generate_bucketed, generate_kv, init_cache,
        init_paged_cache)
    from tpu_trainer.serving.engine import ServingEngine

    model, params = init(TINY)
    cfg = model.config
    prompt = jnp.zeros((1, 4), jnp.int32)
    key = jax.random.PRNGKey(0)
    for fn in (generate, generate_bucketed, generate_kv):
        with pytest.raises(NotImplementedError, match="M8"):
            fn(params, key, prompt, config=cfg, max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="state-space"):
        init_cache(cfg, 1)
    with pytest.raises(NotImplementedError, match="state-space"):
        init_paged_cache(dataclasses.replace(
            cfg, decode_paged=True, paged_num_blocks=4, paged_max_blocks=2), 1)
    with pytest.raises(NotImplementedError, match="state-space"):
        model.apply({"params": params}, prompt, decode=True,
                    mutable=["cache"])
    with pytest.raises(NotImplementedError):
        ServingEngine(params, cfg)
    with pytest.raises(NotImplementedError, match="segment_ids"):
        model.apply({"params": params}, jnp.zeros((1, 8), jnp.int32),
                    segment_ids=jnp.ones((1, 8), jnp.int32))


@pytest.mark.parametrize("axis", ["stage", "sequence", "tensor", "expert"])
def test_mesh_axes_the_model_does_not_run_under_are_refused(axis):
    with pytest.raises(ValueError, match=(
            "moe_experts_held" if axis == "expert" else
            "layers differ" if axis != "tensor" else "mamba layers")):
        _trainer({axis: 2}, 2)


@pytest.mark.parametrize("axis,words", [
    ("stage", "layers differ"), ("sequence", "layers differ"),
    ("tensor", "mamba layers")])
def test_the_model_refuses_those_axes_at_trace_time(axis, words):
    from tpu_trainer.parallel import context as ctx_lib
    from tpu_trainer.parallel.mesh import MeshConfig, make_mesh

    model, params = init(TINY)
    mesh = make_mesh(MeshConfig(**{"data": 1, "fsdp": 1, axis: 2}),
                     devices=jax.devices()[:2])
    with ctx_lib.mesh_scope(mesh), pytest.raises(
            NotImplementedError, match=words):
        model.apply({"params": params}, jnp.zeros((2, 8), jnp.int32))


def test_bad_fields_are_refused():
    good = family.gpt_config(TINY)
    replace = dataclasses.replace
    with pytest.raises(ValueError, match="one_sublayer_blocks"):
        replace(good, one_sublayer_blocks=False)     # 'moe' blocks
    with pytest.raises(ValueError, match="needs layer_types"):
        GPTConfig(one_sublayer_blocks=True)
    with pytest.raises(ValueError, match="num_experts"):
        replace(good, num_experts=0, moe_experts_held=None,
                moe_shared_experts=0, moe_shared_expert_width=None,
                moe_routed_scale=1.0)
    with pytest.raises(ValueError, match="mamba_num_heads"):
        replace(good, mamba_num_heads=0)
    with pytest.raises(ValueError, match="multiple of"):
        replace(good, mamba_n_groups=3)
    with pytest.raises(ValueError, match="ffn_kind"):
        replace(good, ffn_kind="geglu")
    with pytest.raises(ValueError, match="dropless"):
        GPTConfig(num_experts=4, ffn_kind="relu2")
    with pytest.raises(ValueError, match="moe_shared_expert_width"):
        replace(good, moe_shared_experts=0)
    with pytest.raises(ValueError, match="rotary_embedding"):
        GPTConfig(rotary_embedding=False)
    with pytest.raises(ValueError, match="prediction module"):
        replace(good, mtp_layers=1)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        family.gpt_config(dict(TINY, hybrid_override_pattern="MEM"))
    with pytest.raises(ValueError, match="has no option"):
        family.gpt_config(dict(TINY, use_conv_bias=False))


def test_the_example_yaml_trains_through_the_clis_own_parser(tmp_path):
    """`configs/nemotron_h_tiny.yaml` through ``build_parser`` /
    ``resolve_configs``, as ``python -m tpu_trainer.training.train_ddp
    --config`` reads it, then two steps of the Trainer it describes."""
    from tpu_trainer.training.cli import build_parser, resolve_configs
    from tpu_trainer.training.trainer import Trainer

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", "nemotron_h_tiny.yaml")
    args = build_parser("ddp").parse_args(["--config", path])
    model, train, parallel, data = resolve_configs(args, "ddp")
    assert model.one_sublayer_blocks and model.has_mamba
    assert model.layer_types == ("mamba", "moe", "mamba", "moe", "mamba",
                                 "full_attention", "moe", "mamba", "moe")
    assert model.head_dim == 16 and model.attention_width == 64
    assert model.ffn_kind == "relu2" and not model.rotary_embedding
    assert model.experts_held == (0, 2) and data["dataset"] == "dummy"
    trainer = Trainer(model, train, parallel)
    state = trainer.init_state(0)
    batch = np.random.default_rng(0).integers(
        0, model.vocab_size, dtype=np.int32,
        size=(train.batch_size * train.gradient_accumulation_steps
              * jax.device_count(), train.max_seq_len))
    state, first = trainer.train_step(state, batch)
    for _ in range(3):
        state, last = trainer.train_step(state, batch)
    assert np.isfinite(float(last["loss"]))
    assert float(last["loss"]) < float(first["loss"])
