"""Gated DeltaNet layers beside gated attention with partial RoPE, every
layer a softmax-routed MoE with a gated shared expert: the chunked delta
rule against the token-by-token recurrence, the program against the plain
reference of ``perf/families/qwen3_next.py`` at a small size, the shares
tied to the uncut model, what the trainer keeps in f32 and leaves undecayed,
and what the new layers cannot do yet refused in words."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.families import qwen3_next as family
from tpu_trainer.models import moe
from tpu_trainer.models.config import GPTConfig
from tpu_trainer.models.gpt import (
    GPT, CausalSelfAttention, GatedDeltaNet, RMSNorm, computed_in_f32,
    stack_name, undecayed)
from tpu_trainer.ops.gated_delta import _unit_lower_inverse, gated_delta

# The published period (linear, linear, linear, full) at small widths: 8
# experts top-2 of which 4 are held (ids 2-5), 2 key / 4 value heads of 16
# lanes, attention 4 heads of 32 lanes over 2 K/V heads rotating 8 of them.
TINY = {
    "name": "tiny-qwen3-next", "family": "qwen3_next", "hidden_size": 64,
    "num_hidden_layers": 4, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "intermediate_size": 96,
    "moe_intermediate_size": 24, "shared_expert_intermediate_size": 40,
    "num_experts": 4, "num_experts_published": 8, "experts_held_first": 2,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "hidden_act": "silu",
    "decoder_sparse_step": 1, "mlp_only_layers": [], "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "vocab_size": 128,
    "max_position_embeddings": 128, "initializer_range": 0.02,
    "attention_dropout": 0.0,
}
UNCUT = dict(TINY, num_experts=8, experts_held_first=0)


def scaled(params, by=4.0):
    """Larger matrices than the initialiser's (logits of order one, scores
    that spread), and vectors off their initial values, so that every norm
    weight, gate and decay shows in the result."""
    def scale(path, p):
        name = str(path)
        if "router" in name:
            return p * by * 4
        if any(k in name for k in ("kernel", "embedding", "lm_head",
                                   "experts_")):
            return p * by
        return p + 0.1 * jax.random.normal(
            jax.random.PRNGKey(p.size), p.shape, p.dtype)

    return jax.tree_util.tree_map_with_path(scale, params)


def init(cfg, dtype="float32", by=4.0, **options):
    model = GPT(family.gpt_config(cfg, dtype=dtype, **options))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, scaled(params, by)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 128)


def _rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2))
                 / (jnp.sqrt(jnp.mean(want ** 2)) + 1e-12))


# --- the chunked delta rule against the recurrence ------------------------------

def _rule_inputs(seq, decay=1.0, seed=0, hk=2, hv=4, dk=16, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = family._l2norm(jax.random.normal(ks[0], (2, seq, hk, dk))) / 4.0
    k = family._l2norm(jax.random.normal(ks[1], (2, seq, hk, dk)))
    v = jax.random.normal(ks[2], (2, seq, hv, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (2, seq, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, seq, hv)))
    return q, k, v, g, beta


# Four chunks of 16, one chunk and a bit, fewer tokens than a chunk, two
# chunks of 32; and decays so steep that the in-chunk cumulative log decay
# spans hundreds (exp of it underflows in f32).
@pytest.mark.parametrize("seq,chunk,decay,dtype,tol", [
    (64, 16, 1.0, "float32", 1e-5), (37, 16, 1.0, "float32", 1e-5),
    (9, 16, 1.0, "float32", 1e-5), (64, 32, 1.0, "float32", 1e-5),
    (48, 16, 40.0, "float32", 1e-5), (64, 16, 1.0, "bfloat16", 3e-2),
    (37, 16, 40.0, "bfloat16", 3e-2)])
def test_the_chunked_rule_is_the_recurrence(seq, chunk, decay, dtype, tol):
    args = _rule_inputs(seq, decay)
    cast = tuple(x.astype(dtype) if i < 3 else x for i, x in enumerate(args))
    wide = tuple(x.astype(jnp.float32) for x in cast)
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, seq, 4, 8))

    def chunked(*a):
        return gated_delta(*a, chunk=chunk)[0]

    with jax.default_matmul_precision("highest"):
        got, low = jax.jit(lambda *a: gated_delta(*a, chunk=chunk))(*cast)
        want = jax.jit(family.delta_rule)(*wide)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(chunked(*a) * weight),
                                 argnums=range(5)))(*cast)
        wants = jax.jit(jax.grad(
            lambda *a: jnp.sum(family.delta_rule(*a) * weight),
            argnums=range(5)))(*wide)
    assert got.dtype == jnp.float32 and bool(jnp.all(jnp.isfinite(got)))
    assert _rel(got, want) < tol
    if decay > 1:
        assert float(low) < -200            # exp(low) underflows in f32
    for got_g, want_g in zip(grads, wants):
        assert bool(jnp.all(jnp.isfinite(got_g.astype(jnp.float32))))
        assert _rel(got_g, want_g) < 10 * tol


def test_the_triangle_is_inverted_exactly():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)), -1)
    a = a / 8.0
    with jax.default_matmul_precision("highest"):
        t = _unit_lower_inverse(a)
        back = (jnp.eye(64) + a) @ t
    assert float(jnp.max(jnp.abs(back - jnp.eye(64)))) < 1e-5


def test_the_references_two_forms_of_the_rule_agree(monkeypatch):
    """The recurrence in checkpointed segments (what the cell's reference
    runs), one segment, and the whole sequence as one triangular system (what
    the bf16 control walks)."""
    args = _rule_inputs(50, seed=3)
    with jax.default_matmul_precision("highest"):
        want = family.delta_rule(*args)
        assert _rel(family.delta_rule_closed(*args), want) < 1e-5
        monkeypatch.setattr(family, "SEGMENT", 1000)
        assert _rel(family.delta_rule(*args), want) < 1e-6


# --- the model against the reference -------------------------------------------

# f32: only the order of additions differs. bf16: the program rounds every
# activation to 8 bits of mantissa where the reference keeps 24, and a
# choice of expert flips near a tie (the comparison here is not routed
# alike). At 4x the initialiser's matrices this small stack is chaotic: the
# f32 reference's own A_log gradient moves by 2x under a 2^-9 relative
# perturbation of the weights, so bf16 is compared at the initialiser's
# scale (`by`), as the cell runs.
TOL = {"float32": {"logits": 2e-5, "loss": 1e-5, "grad": 3e-4, "by": 4.0},
       "bfloat16": {"logits": 0.05, "loss": 1e-3, "grad": 0.4, "by": 1.0}}


@jax.jit
def _reference(params, tokens):
    return family.forward(params, tokens, TINY), jax.value_and_grad(
        family.loss)(params, tokens, TINY, 2)


@pytest.mark.parametrize("dtype,unroll", [
    ("float32", True), ("float32", False), ("bfloat16", True)])
def test_program_matches_the_reference(tokens, dtype, unroll):
    tol = TOL[dtype]
    model, params = init(TINY, dtype, tol["by"], scan_unroll=unroll)

    def loss(p):
        return model.apply({"params": p}, tokens, labels=tokens,
                           train=True)[1]

    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(model.apply)({"params": params}, tokens)
        got_loss, got_grad = jax.jit(jax.value_and_grad(loss))(params)
        want, (want_loss, want_grad) = _reference(params, tokens)
    assert logits.shape == (2, 40, 128) and logits.dtype == jnp.float32
    assert _rel(logits, want) < tol["logits"]
    assert abs(float(got_loss) - float(want_loss)) < tol["loss"] * float(
        want_loss)
    leaves = jax.tree_util.tree_leaves_with_path(want_grad)
    assert len(leaves) == 36
    for (path, want_leaf), got_leaf in zip(
            leaves, jax.tree_util.tree_leaves(got_grad)):
        assert float(jnp.linalg.norm(want_leaf)) > 0, path
        assert _rel(got_leaf, want_leaf) < tol["grad"], path


def test_the_parameter_tree_and_its_count():
    model, params = init(TINY)
    cfg = model.config
    assert cfg.layer_kinds() == (("linear_attention", "moe"),) * 3 + (
        ("attention", "moe"),)
    assert set(params) == {"embed_tokens", "lm_head", "norm",
                           "layers_linear_attention_moe",
                           "layers_attention_moe"}
    mixer = params["layers_linear_attention_moe"]["linear_attn"]
    # [q, k, v, z] = 32 + 32 + 64 + 64 columns; [b, a] = 4 + 4; the taps
    # over q, k, v: 128 channels.
    assert {jax.tree_util.keystr(p): a.shape for p, a in
            jax.tree_util.tree_leaves_with_path(mixer)} == {
        "['A_log']": (3, 4), "['conv_weight']": (3, 128, 4),
        "['dt_bias']": (3, 4), "['in_proj_ba']['kernel']": (3, 64, 8),
        "['in_proj_qkvz']['kernel']": (3, 64, 192),
        "['norm']['weight']": (3, 16), "['out_proj']['kernel']": (3, 64, 64)}
    attention = params["layers_attention_moe"]["attention"]
    assert attention["q_proj"]["kernel"].shape == (1, 64, 2 * 4 * 32)
    assert attention["k_proj"]["kernel"].shape == (1, 64, 64)
    assert attention["o_proj"]["kernel"].shape == (1, 128, 64)
    assert attention["q_layernorm"]["weight"].shape == (1, 32)
    experts = params["layers_attention_moe"]["moe_mlp"]
    assert set(experts) == {"router", "experts_gate", "experts_up",
                            "experts_down", "shared_expert"}
    assert experts["router"]["kernel"].shape == (1, 64, 8)
    assert experts["experts_up"].shape == (1, 4, 64, 24)
    assert set(experts["shared_expert"]) == {"gate_proj", "up_proj",
                                             "down_proj", "gate"}
    assert experts["shared_expert"]["gate"]["kernel"].shape == (1, 64, 1)
    count = sum(p.size for p in jax.tree_util.tree_leaves(params))
    assert count == cfg.num_parameters() == family.param_count(TINY)
    # The initialiser: A in (0, 16), dt_bias 1, the gated norm's weight 1.
    fresh = model.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))["params"]
    fresh = fresh["layers_linear_attention_moe"]["linear_attn"]
    a = jnp.exp(fresh["A_log"])
    assert float(a.min()) > 0 and float(a.max()) <= 16
    assert bool(jnp.all(fresh["dt_bias"] == 1))
    assert bool(jnp.all(fresh["norm"]["weight"] == 1))


# --- the shares and the uncut model ---------------------------------------------

def _routed_share(layer, first, count=2):
    return {"router": layer["router"],
            **{k: layer[k][first:first + count]
               for k in ("experts_gate", "experts_up", "experts_down")}}


def _summed_over_shares(cfg, layer, h):
    """What an expert-parallel deployment of four chips computes: each its
    routed part, summed by the exchange, and the gated shared expert ONCE."""
    routed_only = dataclasses.replace(
        cfg, moe_shared_experts=0, moe_shared_expert_width=None,
        moe_shared_expert_gate=False)
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
        lambda a: a[1], params["layers_linear_attention_moe"]["moe_mlp"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))

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
            cut = dict(UNCUT, num_experts=2, experts_held_first=first)
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
    holds of each expert layer, the partial results are summed with the
    shared expert once, the mixers run whole, and the loss is the uncut
    reference's."""
    cfg = family.gpt_config(UNCUT, dtype="float32")
    _, params = init(UNCUT)
    norm = RMSNorm(eps=cfg.norm_eps)

    def layer(p, x, kind):
        h = norm.apply({"params": p["operator_norm"]}, x)
        if kind[0] == "linear_attention":
            x = x + GatedDeltaNet(cfg).apply({"params": p["linear_attn"]}, h)
        else:
            x = x + CausalSelfAttention(cfg).apply(
                {"params": p["attention"]}, h)
        h = norm.apply({"params": p["ffn_norm"]}, x)
        return x + _summed_over_shares(cfg, p["moe_mlp"], h)

    @jax.jit
    def summed_loss(params):
        seen = {}
        x = params["embed_tokens"]["embedding"][tokens]
        for kind in cfg.layer_kinds():
            i = seen.get(kind, 0)
            seen[kind] = i + 1
            x = layer(jax.tree_util.tree_map(
                lambda a: a[i], params[stack_name(kind)]), x, kind)
        logits = norm.apply({"params": params["norm"]}, x) @ params[
            "lm_head"].T
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1))

    share = jax.tree_util.tree_map_with_path(
        lambda path, p: p[:, 2:6] if "experts_" in str(path) else p, params)
    with jax.default_matmul_precision("highest"):
        got = float(summed_loss(params))
        want = float(jax.jit(
            lambda p: family.loss(p, tokens, UNCUT, 2))(params))
        alone = float(jax.jit(
            lambda p: family.loss(p, tokens, TINY, 2))(share))
    assert abs(got - want) < 1e-5 * want
    assert abs(alone - want) > 1e-3


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

    one = _trainer({}, 1, gradient_accumulation_steps=1,
                   mixed_precision="bf16")
    state = one.init_state(0)
    mixer = state.params_c["layers_linear_attention_moe"]["linear_attn"]
    for name in GatedDeltaNet.F32_LEAVES:        # [count, heads]: 2-D
        assert mixer[name].dtype == jnp.float32 and mixer[name].ndim == 2
    assert mixer["in_proj_qkvz"]["kernel"].dtype == jnp.bfloat16
    assert mixer["conv_weight"].dtype == jnp.bfloat16
    assert computed_in_f32(("layers_linear_attention_moe", "linear_attn",
                            "A_log"))
    assert not computed_in_f32(("elsewhere", "A_log"))
    mask = decay_mask(state.params)
    mixer = mask["layers_linear_attention_moe"]["linear_attn"]
    assert {k for k, v in mixer.items() if v is False} == {"A_log",
                                                           "dt_bias"}
    assert mixer["norm"]["weight"] is False
    assert mixer["in_proj_qkvz"]["kernel"] and mixer["conv_weight"]
    attention = mask["layers_attention_moe"]["attention"]
    assert attention["q_layernorm"]["weight"] is False
    shared = mask["layers_attention_moe"]["moe_mlp"]["shared_expert"]
    assert shared["gate"]["kernel"] and shared["up_proj"]["kernel"]
    assert undecayed(("layers_linear_attention_moe", "linear_attn", "A_log"))


def test_a_step_counts_the_delta_rule():
    batch = np.random.default_rng(0).integers(0, 128, size=(2, 32),
                                              dtype=np.int32)
    one = _trainer({}, 1, gradient_accumulation_steps=2,
                   mixed_precision="fp32", batch_size=1)
    state, metrics = one.train_step(one.init_state(0), batch)
    # 2 x 32 tokens through 3 linear-attention layers.
    assert float(metrics["gated_delta_tokens"]) == 2 * 32 * 3
    assert float(metrics["gated_delta_min_log_decay"]) < 0.0
    assert float(metrics["moe_rows_held"]) > 0
    assert np.isfinite(float(metrics["loss"]))


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
        with pytest.raises(NotImplementedError, match="delta rule"):
            fn(params, key, prompt, config=cfg, max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="Gated DeltaNet"):
        init_cache(cfg, 1)
    with pytest.raises(NotImplementedError, match="Gated DeltaNet"):
        init_paged_cache(dataclasses.replace(
            cfg, decode_paged=True, paged_num_blocks=4, paged_max_blocks=2), 1)
    with pytest.raises(NotImplementedError, match="Gated DeltaNet"):
        model.apply({"params": params}, prompt, decode=True,
                    mutable=["cache"])
    with pytest.raises(NotImplementedError):
        ServingEngine(params, cfg)
    with pytest.raises(NotImplementedError, match="reset its state"):
        model.apply({"params": params}, jnp.zeros((1, 8), jnp.int32),
                    segment_ids=jnp.ones((1, 8), jnp.int32))


@pytest.mark.parametrize("axis,words", [
    ("stage", "layers differ"), ("sequence", "layers differ"),
    ("tensor", "linear_attention layers"), ("expert", "moe_experts_held")])
def test_mesh_axes_the_model_does_not_run_under_are_refused(axis, words):
    with pytest.raises(ValueError, match=words):
        _trainer({axis: 2}, 2)


@pytest.mark.parametrize("axis,words", [
    ("stage", "layers differ"), ("sequence", "layers differ"),
    ("tensor", "linear_attention layers")])
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
    with pytest.raises(ValueError, match="linear_num_key_heads"):
        replace(good, linear_num_key_heads=0)
    with pytest.raises(ValueError, match="multiple of the key heads"):
        replace(good, linear_num_value_heads=3)
    with pytest.raises(ValueError, match="even number of lanes"):
        replace(good, partial_rotary_factor=0.3)
    with pytest.raises(ValueError, match="hybrid stack"):
        GPTConfig(attention_output_gate=True)
    with pytest.raises(ValueError, match="hybrid stack"):
        GPTConfig(partial_rotary_factor=0.5)
    with pytest.raises(ValueError, match="gates the shared expert"):
        replace(good, moe_shared_experts=0, moe_shared_expert_width=None)
    with pytest.raises(ValueError, match="has no option"):
        family.gpt_config(dict(TINY, hidden_act="gelu"))
    with pytest.raises(ValueError, match="has no option"):
        family.gpt_config(dict(TINY, mlp_only_layers=[0]))
