"""Latent attention, a shared expert beside the routed ones, an untied head
and the multi-token-prediction module: the program against the plain
reference of ``perf/families/mla_moe.py`` at a small size, the latent-attention
kernels under the interpreter at the published head widths, the shares tied
to the uncut model, and what the new layers cannot do yet refused loudly."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import controls_mla_moe as controls
from perf.families import mla_moe as family
from tpu_trainer.models import moe
from tpu_trainer.models.config import GPTConfig
from tpu_trainer.models.gpt import (
    GPT, MLP, LatentAttention, RMSNorm, stack_name)
from tpu_trainer.ops import flash_mla
from tpu_trainer.ops.attention import flash_attention, mla_attention

# A leading dense layer, two sparse layers and the prediction module; 16
# experts top-4 of which 4 are held (ids 4-7), one shared expert.
TINY = {
    "name": "tiny-mla", "family": "mla_moe", "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "qk_head_dim": 24, "v_head_dim": 16,
    "rope_interleave": True, "rope_theta": 32000000, "rope_scaling": None,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "experts_held_first": 4, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "moe_layer_freq": 1, "hidden_act": "silu",
    "attention_bias": False, "rms_norm_eps": 1e-06, "vocab_size": 256,
    "max_position_embeddings": 256, "initializer_range": 0.02,
    "attention_dropout": 0.0, "num_nextn_predict_layers": 1,
    "mtp_loss_weight": 0.3, "untied_head": True,
}
UNCUT = dict(TINY, n_routed_experts=16, experts_held_first=0)
LEAVES = 53     # 12 + 19 in the two stacks, 19 + 4 in the module, 3 outside


def scaled(params, by=6.0):
    """Larger matrices than the initialiser's, so that logits are of order
    one, attention is far from uniform and the router's scores spread."""
    def scale(path, p):
        name = str(path)
        if "router" in name:    # scores far from one half, gates far apart
            return p * by * 4
        return p * by if any(k in name for k in (
            "kernel", "embedding", "lm_head", "experts_")) else p

    return jax.tree_util.tree_map_with_path(scale, params)


def init(cfg, dtype="float32", **options):
    model = GPT(family.gpt_config(cfg, dtype=dtype, **options))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, scaled(params)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 256)


# f32: only the order of additions differs. bf16: the program rounds every
# activation to 8 bits of mantissa where the reference keeps 24, and a
# choice of expert flips near a tie (the comparison here is not routed
# alike): read here 0.04-0.06 relative on the logits, where the faults below
# read 0.16 and more; the gradient of a router's kernel up to 0.5, where a
# state left unchanged reads 1.
TOL = {"float32": {"logits": 2e-5, "loss": 1e-5, "grad": 3e-4},
       "bfloat16": {"logits": 0.10, "loss": 1e-2, "grad": 0.6}}


@jax.jit
def _reference(params, tokens):
    return family.forward(params, tokens, TINY), jax.value_and_grad(
        family.loss)(params, tokens, TINY, 2)


def _rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2))
                 / (jnp.sqrt(jnp.mean(want ** 2)) + 1e-12))


def _program(model, params, tokens, logits=True):
    def loss(p):
        return model.apply({"params": p}, tokens, labels=tokens)[1]

    with jax.default_matmul_precision("highest"):
        if logits:
            logits, _ = jax.jit(model.apply)({"params": params}, tokens)
        return logits, jax.jit(jax.value_and_grad(loss))(params)


@pytest.mark.parametrize("dtype,unroll", [
    ("float32", True), ("float32", False), ("bfloat16", True),
    pytest.param("bfloat16", False, marks=pytest.mark.slow)])
def test_program_matches_the_reference(tokens, dtype, unroll):
    """Logits, the loss with its MTP term and the gradient of every leaf."""
    model, params = init(TINY, dtype, scan_unroll=unroll)
    tol = TOL[dtype]
    logits, (loss, grads) = _program(model, params, tokens)
    want_logits, (want_loss, want_grads) = _reference(params, tokens)
    assert float(jnp.std(want_logits)) > 0.3
    assert _rel(logits, want_logits) < tol["logits"]
    assert abs(float(loss) - float(want_loss)) < tol["loss"] * float(want_loss)
    got_flat = jax.tree_util.tree_leaves_with_path(grads)
    want_flat = jax.tree_util.tree_leaves(want_grads)
    assert len(got_flat) == len(want_flat) == LEAVES
    for (path, got), want in zip(got_flat, want_flat):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:   # a buffer: no gradient reaches it
            assert not np.any(np.asarray(got)) and not np.any(
                np.asarray(want)), name
            continue
        assert float(jnp.max(jnp.abs(want))) > 0, name
        assert _rel(got, want) < tol["grad"], name


def test_the_mtp_term_is_in_the_loss_and_in_the_step_metrics(tokens):
    """``loss = CE + 0.3 x CE_mtp``, each the reference's; a forward without
    labels does not run the module; the term rides the step's counters."""
    from tpu_trainer.utils import telemetry

    model, params = init(TINY)

    @jax.jit
    def counted(params, labels):
        with telemetry.counters() as counts:
            out = model.apply({"params": params}, tokens, labels=labels)
        return out, telemetry.flat_counts(counts)

    ce, mtp = jax.jit(lambda p: family.loss_terms(p, tokens, TINY))(params)
    assert float(mtp) > 1.0 and abs(float(mtp) - float(ce)) > 1e-3
    (logits, loss), flat = counted(params, tokens)
    assert abs(float(flat["mtp_loss"]) - float(mtp)) < 1e-5 * float(mtp)
    assert abs(float(loss) - float(ce + 0.3 * mtp)) < 1e-5 * float(loss)
    assert flat["moe_rows_held"].shape == ()    # 3 expert layers, summed
    (same, none), flat = counted(params, None)
    assert none is None and "mtp_loss" not in flat
    assert float(jnp.max(jnp.abs(same - logits))) == 0.0
    off = GPT(dataclasses.replace(model.config, mtp_layers=0))
    without = {k: v for k, v in params.items() if k != "mtp"}
    same, loss = jax.jit(lambda p: off.apply(
        {"params": p}, tokens, labels=tokens))(without)
    assert float(jnp.max(jnp.abs(same - logits))) == 0.0
    assert abs(float(loss) - float(ce)) < 1e-5 * float(ce)


@pytest.fixture(scope="module")
def bf16_program(tokens):
    model, params = init(TINY, "bfloat16")
    return params, jax.jit(model.apply)({"params": params}, tokens)[0]


@pytest.mark.parametrize("fault", [*controls.REFERENCE_FAULTS,
                                   *controls.PARAMS_FAULTS])
def test_a_fault_fails_the_comparison(tokens, bf16_program, fault):
    """What the logits must see, at the bf16 tolerance (the loosest), each
    planted on the reference's side (perf/controls_mla_moe.py, which reads
    them on the chip through the cell's own comparison). Read here over
    three seeds: the program 0.037-0.062, the faults 0.16-0.58."""
    params, logits = bf16_program
    reference_params = controls.PARAMS_FAULTS.get(fault, lambda p: p)(params)
    with controls.planted(family, fault):
        want = jax.jit(lambda p: family.forward(p, tokens, TINY))(
            reference_params)
    assert _rel(logits, want) > 1.3 * TOL["bfloat16"]["logits"], fault


def _mtp_leaves(grads):
    return {jax.tree_util.keystr(path): g for path, g
            in jax.tree_util.tree_leaves_with_path(grads["mtp"])
            if "expert_bias" not in jax.tree_util.keystr(path)}


def test_the_mtp_faults_show_in_the_gradient_alone(tokens):
    """No logit shows the prediction module. Its labels shifted by one on the
    reference's side, or its loss's gradient dropped in the program: the loss
    barely moves, the module's leaves read off by their own size."""
    model, params = init(TINY)
    _, (want_loss, want) = _reference(params, tokens)
    with controls.planted(family, "mtp_labels_shifted"):
        loss, shifted = jax.jit(jax.value_and_grad(
            lambda p: family.loss(p, tokens, TINY, 2)))(params)
    assert abs(float(loss) - float(want_loss)) < 0.05 * float(want_loss)
    for name, g in _mtp_leaves(shifted).items():
        assert _rel(g, _mtp_leaves(want)[name]) > 0.5, name
    with controls.mtp_backward_dropped():
        _, (loss, dropped) = _program(GPT(model.config), params, tokens,
                                      logits=False)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert not any(np.any(np.asarray(g)) for g in _mtp_leaves(dropped).values())
    trunk = dropped["layers_attention_moe"]["attention"]["o_proj"]["kernel"]
    assert 0.01 < _rel(
        trunk, want["layers_attention_moe"]["attention"]["o_proj"]["kernel"])
    assert all(np.any(np.asarray(g)) for g in _mtp_leaves(want).values())


def test_the_reference_routed_by_another_choice(tokens):
    """Given the experts another computation chose, the reference routes by
    them and still hands out its own choice; given its own, nothing moves;
    the module's layer takes a choice of its own or routes by itself."""
    _, params = init(TINY)
    forward = jax.jit(lambda p, choice: family.forward_and_choices(
        p, tokens, TINY, choice))
    loss = jax.jit(lambda p, choice: family.loss(p, tokens, TINY, 2, choice))
    logits, own = forward(params, None)
    assert len(own) == 2 and own[0].shape == (2, 48, 4)
    same, again = forward(params, own)
    assert float(jnp.max(jnp.abs(same - logits))) == 0.0
    assert all(bool(jnp.all(a == b)) for a, b in zip(own, again))
    forced = [jnp.broadcast_to(jnp.arange(4, 8), o.shape) for o in own]
    other, again = forward(params, forced)
    assert _rel(other, logits) > 0.05
    assert bool(jnp.all(again[0] == own[0]))
    plain = float(loss(params, None))
    assert abs(float(loss(params, own)) - plain) < 1e-6
    assert abs(float(loss(params, [*own, forced[0]])) - plain) > 1e-4


# --- the kernels, under the interpreter at the published head widths ----------

def _dense(qn, qr, kn, kr, v, scale):
    s = qn.shape[1]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn)
              + jnp.einsum("bqhd,bkd->bhqk", qr, kr)) * scale
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _operands(seq, dtype, b=1, h=2, nope=128, rope=64, dv=128):
    ks = jax.random.split(jax.random.PRNGKey(seq), 6)
    shapes = [(b, seq, h, nope), (b, seq, h, rope), (b, seq, h, nope),
              (b, seq, rope), (b, seq, h, dv), (b, seq, h, dv)]
    *ops, w = [jax.random.normal(k, s, dtype) for k, s in zip(ks, shapes)]
    return ops, w.astype(jnp.float32)


# SHA-256 over the five gradients (q_nope, q_rope, k_nope, k_rope, v, as
# float32) that the SPLIT pair of PR 32 (a dk/dv kernel and a dq kernel, each
# with its own score evaluation) gave the cases below under the interpreter,
# recorded on the parent commit of PR 33. The one-pass backward sums every
# product over the same index in the same order, so with every gradient dot
# in the split pair's natural form it gives the same bits.
_SPLIT_PAIR_GRADS = {
    (256, "float32"):
        "c9e1c111069bb5910745239d1a31bc3a13558fbef097b427a6538f985b1c6ffc",
    (1024, "bfloat16"):
        "2cc37c6d0ab208ba0d740f1ef050f1bd02331def65f16dfeae5d79c5d3448a8f",
    (1536, "float32"):
        "319a585862fb7582bad78373ecf0316b2bbc498d1c50bde0f725c82ad1604ff9",
}


@pytest.mark.parametrize("against", ["dense_f32", "split_pair"])
@pytest.mark.parametrize("seq,dtype,tol", [
    (256, jnp.float32, 2e-6), (1024, jnp.bfloat16, 8e-3),
    pytest.param(1536, jnp.float32, 2e-6, marks=pytest.mark.slow)])
def test_the_latent_attention_kernels_at_192_and_128(
        seq, dtype, tol, against, monkeypatch):
    """Forward and the one-pass backward (one block; 3 and 6 causal pairs of
    512 x 512 blocks, dq accumulated over a row's K/V blocks in VMEM): the
    result and all five gradients, the shared key's summed over the heads,
    against dense float32 attention; and against the split pair before it,
    bit for bit in the natural forms of the dots, the 64-lane part's d-row
    forms beside them (``dq_rope`` alone differs, and only here: the CPU's
    dot sums a contraction of both operands' lanes in another order; the
    chip gave the same bits, `perf/records/pr33.*variants.json`)."""
    ops, w = _operands(seq, dtype)
    scale = 192 ** -0.5

    def kernel(*a):
        return jnp.sum(flash_mla.mla_flash_attention(
            *a, scale=scale, interpret=True).astype(jnp.float32) * w)

    def dense(*a):
        return jnp.sum(_dense(*[x.astype(jnp.float32) for x in a], scale) * w)

    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(kernel, argnums=(0, 1, 2, 3, 4))(*ops)
        if against == "split_pair":
            monkeypatch.setattr(flash_mla, "_d_row_form", lambda width: False)
            natural = jax.grad(kernel, argnums=(0, 1, 2, 3, 4))(*ops)
            digest = hashlib.sha256(b"".join(
                np.asarray(g.astype(jnp.float32)).tobytes() for g in natural))
            assert digest.hexdigest() == _SPLIT_PAIR_GRADS[
                seq, jnp.dtype(dtype).name]
            for i, (g, ng) in enumerate(zip(grads, natural)):
                assert _rel(g, ng) <= (tol / 4 if i == 1 else 0.0)
            return
        want, want_grads = jax.value_and_grad(
            dense, argnums=(0, 1, 2, 3, 4))(*ops)
    assert abs(float(got) - float(want)) < 50 * tol * abs(float(want)) + 1.0
    for g, wg in zip(grads, want_grads):
        assert g.dtype == dtype and g.shape == wg.shape
        assert _rel(g, wg) < tol


def _padded(qn, qr, kn, kr, v):
    """The lab's variant (A): q and k padded to ONE 256-lane per-head part,
    the shared key broadcast to the heads."""
    b, s, h, _ = qn.shape
    pad = jnp.zeros((b, s, h, 64), qn.dtype)
    q = jnp.concatenate([qn, qr, pad], -1).reshape(b, s, h * 256)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr[:, :, None], qr.shape),
                         pad], -1).reshape(b, s, h * 256)
    return flash_mla.parts_attention(
        ((q, k),), v.reshape(b, s, -1), shared=(False,), heads=h,
        scale=192 ** -0.5, interpret=True).reshape(v.shape)


def test_the_padded_layout_gives_what_the_split_one_gives():
    """One padded part through the same bodies as the two parts."""
    ops, _ = _operands(256, jnp.float32)
    split = flash_mla.mla_flash_attention(
        *ops, scale=192 ** -0.5, interpret=True)
    assert float(jnp.max(jnp.abs(_padded(*ops) - split))) < 1e-5


def test_the_padded_layout_through_the_one_pass_backward():
    """The backward is written over the tuple of parts too: one folded
    256-lane part (no head-major q, no per-head partials of a shared key)
    over three block pairs gives the gradients the two parts give; the
    broadcast's transpose sums the shared key's over the heads."""
    ops, w = _operands(1024, jnp.float32)

    def through(attention):
        return jax.grad(lambda *a: jnp.sum(attention(*a) * w),
                        argnums=(0, 1, 2, 3, 4))(*ops)

    split = through(lambda *a: flash_mla.mla_flash_attention(
        *a, scale=192 ** -0.5, interpret=True))
    for g, wg in zip(through(_padded), split):
        assert g.shape == wg.shape and _rel(g, wg) < 1e-5


def _pallas_grids(jaxpr):
    from jax._src import core

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield tuple(eqn.params["grid_mapping"].grid)
        for sub in core.jaxprs_in_params(eqn.params):
            yield from _pallas_grids(sub)


@pytest.mark.parametrize("seq,pairs", [(256, 1), (1024, 3), (4096, 36)])
def test_the_backward_is_one_call_over_the_causal_pairs(seq, pairs):
    """Forward + backward trace to two kernels (the split pair made three),
    and the backward's grid holds the block pairs on and under the diagonal
    and no other: no step is there to be skipped."""
    shapes = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (
        (1, seq, 2, 128), (1, seq, 2, 64), (1, seq, 2, 128), (1, seq, 64),
        (1, seq, 2, 128))]

    def loss(*a):
        return jnp.sum(flash_mla.mla_flash_attention(
            *a, scale=1.0).astype(jnp.float32))

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*shapes)
    blocks = -(-seq // 512)
    assert list(_pallas_grids(traced.jaxpr)) == [
        (1, 2, blocks), (1, 2, pairs)]
    assert "bwd_fused" in str(traced.jaxpr.pretty_print(name_stack=True))


def test_a_sequence_the_blocks_do_not_divide_is_refused():
    (qn, qr, kn, kr, v), _ = _operands(256, jnp.float32)
    cut = lambda a: a[:, :200]                            # noqa: E731
    assert flash_mla.fits(4096) and flash_mla.fits(384)
    assert not flash_mla.fits(200) and not flash_mla.fits(640)
    with pytest.raises(NotImplementedError, match="multiple of 512"):
        flash_mla.mla_flash_attention(
            *map(cut, (qn, qr, kn, kr, v)), scale=1.0, interpret=True)


def test_the_model_through_the_kernels(monkeypatch):
    """The dispatch (``ops/attention.mla_attention``) takes the kernels where
    a TPU is (here: the interpreter) and the sequence fits, the plain path
    otherwise; the model's loss and gradients agree through both."""
    cfg = dict(TINY, max_position_embeddings=128)
    model, params = init(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 128), 0, 256)
    _, (plain_loss, plain) = _program(model, params, toks, logits=False)
    calls = []
    healthy = flash_mla.mla_flash_attention
    monkeypatch.setattr(flash_mla, "mla_flash_attention",
                        lambda *a, **k: calls.append(k) or healthy(*a, **k))
    monkeypatch.setenv("TPU_TRAINER_FLASH_INTERPRET", "1")
    _, (loss, grads) = _program(GPT(model.config), params, toks, logits=False)
    assert calls and all(k["interpret"] for k in calls)
    assert abs(float(loss) - float(plain_loss)) < 1e-5 * float(plain_loss)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(plain)):
        assert _rel(g, w) < 3e-4 or not np.any(np.asarray(w))
    # Eight tokens (the initialiser's) do not fit: the plain path, no call.
    calls.clear()
    (qn, qr, kn, kr, v), _ = _operands(8, jnp.float32, nope=16, rope=8, dv=16)
    mla_attention(qn, qr, kn, kr, v, scale=1.0)
    assert not calls


# One digest a call, recorded on the parent commit (PR 31) under the
# interpreter: `ops/flash.py` computes at head widths 64 and 128 what it did.
_PARENT_DIGESTS = {
    (64, False):
        "e84eb0fafd4e7d6366ff00dbc2ce6b934673e1323aff9a94dad135fdecf25831",
    (64, True):
        "5868b5f8b125a9d5ac8dd9fb641a095a471eacc3954befd0007aa36000bf73b7",
    (128, False):
        "f2ad3fe3897efdfdc11b14f05931b0d4ac0d1b7da6c09580969e38f9503a7ec9",
}


@pytest.mark.parametrize("d,rope", list(_PARENT_DIGESTS))
def test_flash_attention_at_64_and_128_is_bit_for_bit_the_parents(
        d, rope, monkeypatch):
    monkeypatch.setenv("TPU_TRAINER_FLASH_INTERPRET", "1")
    ks = jax.random.split(jax.random.PRNGKey(d), 4)
    q, k, v, w = [jax.random.normal(key, (1, 256, 2, d), jnp.float32)
                  for key in ks]
    extra = {}
    if rope:
        from tpu_trainer.ops.rope import rope_tables
        extra = {"rope": rope_tables(256, d, 1e4)}

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, **extra) * w)

    out, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    digest = hashlib.sha256(b"".join(
        np.asarray(a).tobytes() for a in (out, *grads))).hexdigest()
    assert digest == _PARENT_DIGESTS[(d, rope)]


# --- the share is a share of the model ----------------------------------------

def _routed_share(layer, first):
    """The routed part of a share: experts ``[first, first + 4)``."""
    return {k: v[first:first + 4] if k.startswith("experts_") else v
            for k, v in layer.items() if k != "shared_expert"}


def _summed_over_shares(uncut_cfg, layer, h):
    """What four chips with 4 of the 16 experts each give an expert layer:
    the four routed parts, and the shared expert ONCE."""
    routed_only = dataclasses.replace(uncut_cfg, moe_shared_experts=0)
    out = moe.SharedExpert(uncut_cfg).apply(
        {"params": layer["shared_expert"]}, h)
    for first in (0, 4, 8, 12):             # the exchange's sum
        cfg = dataclasses.replace(routed_only, moe_experts_held=(first, 4))
        out = out + moe.MoEMLP(cfg).apply(
            {"params": _routed_share(layer, first)}, h)[0]
    return out


def test_the_shares_of_one_layer_add_up_to_the_uncut_layer():
    uncut_cfg = family.gpt_config(UNCUT, dtype="float32")
    _, params = init(UNCUT)
    layer = jax.tree_util.tree_map(
        lambda a: a[1], params["layers_attention_moe"]["moe_mlp"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))

    @jax.jit
    def run(layer, h):
        routed, _ = family.routed_experts(h, layer, UNCUT)
        shared = family.shared_expert(h, layer)
        whole, _ = moe.MoEMLP(uncut_cfg).apply({"params": layer}, h)
        # A share as a chip holds it: its routed part and the shared expert
        # whole; the reference, given the same share, gives the same.
        shares, references = [], []
        for first in (0, 4, 8, 12):
            cfg = dataclasses.replace(uncut_cfg, moe_experts_held=(first, 4))
            share = dict(_routed_share(layer, first),
                         shared_expert=layer["shared_expert"])
            shares.append(moe.MoEMLP(cfg).apply({"params": share}, h)[0])
            cut = dict(UNCUT, n_routed_experts=4, experts_held_first=first)
            references.append(family.routed_experts(h, share, cut)[0]
                              + family.shared_expert(h, share))
        return (routed, shared, whole, shares, references,
                _summed_over_shares(uncut_cfg, layer, h))

    with jax.default_matmul_precision("highest"):
        routed, shared, whole, shares, references, summed = run(layer, h)
    close = lambda a, b: float(jnp.max(jnp.abs(a - b))) < 1e-5  # noqa: E731
    want = routed + shared
    assert float(jnp.std(shared)) > 0.05 and float(jnp.std(routed)) > 0.05
    assert close(whole, want)
    assert all(close(a, b) for a, b in zip(shares, references))
    # Summed as they are, the shared expert counts four times ...
    assert close(sum(shares), want + 3 * shared)
    # ... the routed parts and the shared expert ONCE are the layer.
    assert close(summed, want)


def test_the_shares_summed_at_each_layer_give_the_uncut_loss(tokens):
    """Four chips, each with 4 of the 16 experts: every chip computes what it
    holds of each expert layer (the prediction module's too), the partial
    results are summed with the shared expert once, and the loss with its
    MTP term is the uncut reference's."""
    cfg = family.gpt_config(UNCUT, dtype="float32")
    _, params = init(UNCUT)
    norm = RMSNorm(eps=cfg.norm_eps)

    def block(p, x, ffn):
        h = norm.apply({"params": p["operator_norm"]}, x)
        x = x + LatentAttention(cfg).apply({"params": p["attention"]}, h)
        h = norm.apply({"params": p["ffn_norm"]}, x)
        if ffn == "dense":
            return x + MLP(cfg).apply({"params": p["mlp"]}, h)
        return x + _summed_over_shares(cfg, p["moe_mlp"], h)

    def cross_entropy(params, x, final_norm, shift):
        logits = norm.apply({"params": final_norm}, x) @ params["lm_head"].T
        logp = jax.nn.log_softmax(logits[:, :-shift], axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, shift:, None], axis=-1))

    @jax.jit
    def summed_loss(params):
        seen = {}
        embedding = params["embed_tokens"]["embedding"]
        x = embedding[tokens]
        for kind in cfg.layer_kinds():
            i = seen.get(kind, 0)
            seen[kind] = i + 1
            x = block(jax.tree_util.tree_map(
                lambda a: a[i], params[stack_name(kind)]), x, kind[1])
        loss = cross_entropy(params, x, params["norm"], 1)
        mtp = params["mtp"]
        both = jnp.concatenate(
            [norm.apply({"params": mtp["enorm"]},
                        embedding[jnp.roll(tokens, -1, axis=1)]),
             norm.apply({"params": mtp["hnorm"]}, x)], axis=-1)
        x = block(mtp["block"], both @ mtp["eh_proj"]["kernel"], "moe")
        return loss + 0.3 * cross_entropy(params, x, mtp["norm"], 2)

    # A single share's parameters: the cut shows in its loss.
    share = jax.tree_util.tree_map_with_path(
        lambda path, p: (p[:, 4:8] if p.ndim == 4 else p[4:8])
        if "experts_" in str(path) else p, params)
    with jax.default_matmul_precision("highest"):
        got = float(summed_loss(params))
        want = float(jax.jit(
            lambda p: family.loss(p, tokens, UNCUT, 2))(params))
        alone = float(jax.jit(
            lambda p: family.loss(p, tokens, TINY, 2))(share))
    assert abs(got - want) < 1e-5 * want
    assert abs(alone - want) > 1e-3


# --- what the new layers cannot do yet ----------------------------------------

def test_decode_and_the_serving_engine_are_refused():
    from tpu_trainer.models.gpt import init_cache
    from tpu_trainer.serving.engine import ServingEngine

    model, params = init(TINY)
    with pytest.raises(NotImplementedError, match="latent attention"):
        model.apply({"params": params}, jnp.zeros((1, 1), jnp.int32),
                    decode=True)
    with pytest.raises(NotImplementedError, match="latent attention"):
        init_cache(model.config, 1)
    with pytest.raises(NotImplementedError, match="latent attention"):
        ServingEngine(params, model.config)
    with pytest.raises(NotImplementedError, match="segment_ids"):
        model.apply({"params": params}, jnp.zeros((1, 8), jnp.int32),
                    segment_ids=jnp.ones((1, 8), jnp.int32))


def _trainer(mesh_axes, devices, strategy="replicated", **training):
    from tpu_trainer.parallel.mesh import MeshConfig, make_mesh
    from tpu_trainer.training.config import TrainingConfig
    from tpu_trainer.training.trainer import ParallelConfig, Trainer

    mesh_config = MeshConfig(**{"data": 1, "fsdp": 1, **mesh_axes})
    return Trainer(
        family.gpt_config(TINY),
        TrainingConfig(batch_size=2, max_seq_len=32, **training),
        ParallelConfig(mesh=mesh_config, sharding_strategy=strategy),
        mesh=make_mesh(mesh_config, devices=jax.devices()[:devices]))


@pytest.mark.parametrize("axis", ["tensor", "sequence", "stage", "expert"])
def test_mesh_axes_latent_attention_does_not_run_under_are_refused(axis):
    with pytest.raises(ValueError, match=(
            "moe_experts_held" if axis == "expert" else
            "layers differ|latent attention")):
        _trainer({axis: 2}, 2)


def test_the_model_refuses_a_tensor_axis_at_trace_time():
    from tpu_trainer.parallel import context as ctx_lib
    from tpu_trainer.parallel.mesh import MeshConfig, make_mesh

    model, params = init(TINY)
    mesh_config = MeshConfig(data=1, fsdp=1, tensor=2)
    mesh = make_mesh(mesh_config, devices=jax.devices()[:2])
    with ctx_lib.mesh_scope(mesh), pytest.raises(
            NotImplementedError, match="'tensor' mesh axis"):
        model.apply({"params": params}, jnp.zeros((2, 8), jnp.int32))


def test_bad_fields_are_refused():
    mla = dict(kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, attention_dropout=0.0)
    assert GPTConfig(**mla).latent_attention
    with pytest.raises(ValueError, match="q_lora_rank"):
        GPTConfig(**dict(mla, q_lora_rank=None))
    with pytest.raises(ValueError, match="even"):
        GPTConfig(**dict(mla, qk_rope_head_dim=7))
    with pytest.raises(ValueError, match="num_kv_heads"):
        GPTConfig(**mla, num_heads=12, num_kv_heads=4)
    with pytest.raises(ValueError, match="qk_norm"):
        GPTConfig(**mla, qk_norm=True)
    with pytest.raises(ValueError, match="conv"):
        GPTConfig(**mla, num_layers=2, layer_types=("conv", "full_attention"))
    with pytest.raises(ValueError, match="kv_lora_rank"):
        GPTConfig(rope_interleave=True)
    with pytest.raises(ValueError, match="moe_shared_experts"):
        GPTConfig(moe_shared_experts=1)
    with pytest.raises(ValueError, match="moe_shared_experts"):
        GPTConfig(moe_shared_experts=1, num_experts=4)      # capacity path
    with pytest.raises(ValueError, match="moe_routed_scale"):
        GPTConfig(num_experts=4, moe_routed_scale=2.5)      # softmax router
    with pytest.raises(ValueError, match="mtp_layers"):
        GPTConfig(mtp_layers=2)
    with pytest.raises(ValueError, match="fused"):
        GPTConfig(mtp_layers=1, fused_loss=False)


# --- counts, the step, a mesh ---------------------------------------------------

def test_parameter_counts_are_exact():
    for cfg_file in (TINY, UNCUT):
        model, params = init(cfg_file)
        leaves = sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
        assert model.config.num_parameters() == leaves
        assert family.param_count(cfg_file) == leaves
    cut, whole = (family.gpt_config(c) for c in (TINY, UNCUT))
    # 3 expert layers (the module's with them), 12 experts of 3 * 64 * 32
    # fewer in each.
    assert whole.num_parameters() - cut.num_parameters() == 3 * 12 * 6144
    # A token flows through 4 routed experts a layer, wherever they live,
    # and through the shared expert, the head and the module.
    assert cut.num_active_parameters() == whole.num_active_parameters() \
        == whole.num_parameters() - 3 * 12 * 6144
    # By hand: one latent-attention operator, a sparse and a dense block.
    attention = (64 * 48 + 48 + 48 * 4 * 24 + 64 * 40 + 32 + 32 * 4 * 32
                 + 4 * 16 * 64)
    sparse = attention + 6144 + 4 * 6144 + 64 * 16 + 16 + 2 * 64
    dense = attention + 3 * 64 * 96 + 2 * 64
    assert (attention, sparse, dense) == (18_512, 50_400, 37_072)
    assert cut.num_parameters() == (
        2 * 256 * 64 + 64 + dense + 2 * sparse      # embedding, head, stack
        + 2 * 64 * 64 + sparse + 3 * 64) == 229_488  # the prediction module


def test_the_step_keeps_the_router_in_f32_and_counts_the_mtp_loss():
    """Two steps of the trainer on one device and under ``data`` x ``fsdp``
    (ZeRO-3 specs for the new leaves from their shapes alone): the same loss,
    the MTP term beside it, the counters of all three expert layers."""
    batch = np.random.default_rng(0).integers(0, 256, size=(4, 32),
                                              dtype=np.int32)
    one = _trainer({}, 1, gradient_accumulation_steps=2,
                   mixed_precision="bf16")
    state = one.init_state(0)
    copy = state.params_c
    for block in (copy["layers_attention_moe"], copy["mtp"]["block"]):
        assert block["moe_mlp"]["router"]["kernel"].dtype == jnp.float32
        assert block["moe_mlp"]["experts_up"].dtype == jnp.bfloat16
        assert block["moe_mlp"]["shared_expert"]["up_proj"][
            "kernel"].dtype == jnp.bfloat16
        assert block["attention"]["q_b_proj"]["kernel"].dtype == jnp.bfloat16
    assert copy["lm_head"].dtype == jnp.bfloat16
    state, metrics = one.train_step(state, batch)
    loss, mtp = float(metrics["loss"]), float(metrics["mtp_loss"])
    assert 5.0 < mtp < 6.5 and loss > mtp       # ~ln 256 each; CE + 0.3 CE'
    routed = 4 * 32 * 4 * 3         # tokens x experts a token x expert layers
    assert 0.1 * routed < float(metrics["moe_rows_held"]) < 0.5 * routed
    state, metrics = one.train_step(state, batch, telemetry=True)
    telem = metrics["telemetry"]
    assert telem["router"]["attention_moe"]["load"].shape == (2, 4)
    assert telem["router"]["mtp"]["load"].shape == (1, 4)
    assert float(metrics["mtp_loss"]) > 0

    many = _trainer({"data": 2, "fsdp": 2}, 4, "zero3",
                    gradient_accumulation_steps=1, mixed_precision="bf16")
    sharded = many.init_state(0)
    spec = sharded.params["lm_head"].sharding.spec
    assert "fsdp" in str(spec)
    assert "fsdp" in str(
        sharded.params["mtp"]["eh_proj"]["kernel"].sharding.spec)
    sharded, got = many.train_step(sharded, np.concatenate([batch, batch]))
    assert abs(float(got["loss"]) - loss) < 2e-2 * loss
    assert abs(float(got["mtp_loss"]) - mtp) < 2e-2 * mtp
