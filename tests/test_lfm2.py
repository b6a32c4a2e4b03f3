"""Models whose layers differ (conv / attention operators, dense / expert
FFNs, a share of the experts held): the program against the plain reference
of ``perf/families/lfm2_moe.py`` at a small size, the shares tied to the
uncut model, and what the new layers cannot do yet refused loudly."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import controls
from perf.families import lfm2_moe as family
from tpu_trainer.models import moe
from tpu_trainer.models.config import GPTConfig
from tpu_trainer.models.gpt import (
    GPT, MLP, CausalSelfAttention, RMSNorm, ShortConv, stack_name)
from tpu_trainer.ops.grouped_matmul import (
    gmm, gmm_reference, tgmm, tgmm_reference)

# All three layer kinds, two periods after one leading dense layer, 16
# experts top-4 of which 4 are held (ids 4-7).
TINY = {
    "name": "tiny-lfm2", "family": "lfm2_moe", "hidden_size": 64,
    "intermediate_size": 160, "moe_intermediate_size": 48,
    "num_hidden_layers": 9, "num_dense_layers": 1,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 4, "num_experts_published": 16, "experts_held_first": 4,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "use_expert_bias": True, "conv_L_cache": 3,
    "conv_bias": False, "norm_eps": 1e-05, "vocab_size": 256,
    "max_position_embeddings": 128,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "rope_theta": 1000000.0, "attention_dropout": 0.0,
    "initializer_range": 0.02,
}
UNCUT = dict(TINY, num_experts=16, experts_held_first=0)


def scaled(params, by=6.0):
    """Larger matrices than the initialiser's, so that logits are of order
    one, attention is far from uniform and the router's scores spread."""
    def scale(path, p):
        matrix = p.ndim >= 3 or "embedding" in str(path)
        return p * by if matrix and "conv_weight" not in str(path) else p

    return jax.tree_util.tree_map_with_path(scale, params)


def init(cfg, dtype="float32", **options):
    model = GPT(family.gpt_config(cfg, dtype=dtype, **options))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, scaled(params)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 256)


def _kernel_gmm(lhs, rhs, sizes, use_kernel=None):
    return gmm(lhs, rhs, sizes, use_kernel=True, interpret=True)


def _kernel_tgmm(lhs, dout, sizes, use_kernel=None):
    return tgmm(lhs, dout, sizes, use_kernel=True, interpret=True)


def _through_the_kernels(monkeypatch):
    """The expert layer's grouped matmuls (``gmm`` both ways, and the
    ``tgmm`` its bounded backward calls itself) through the Pallas kernels
    under the interpreter."""
    monkeypatch.setattr(moe, "gmm", _kernel_gmm)
    monkeypatch.setattr(moe, "tgmm", _kernel_tgmm)


# f32: only the order of additions differs. bf16: the program rounds every
# activation to 8 bits of mantissa where the reference keeps 24, and a
# choice of expert flips near a tie; measured here over four seeds 0.052-0.076
# relative on the logits, where the four faults below read 0.14-1.36.
TOL = {"float32": {"logits": 2e-5, "loss": 1e-5, "grad": 2e-4},
       "bfloat16": {"logits": 0.10, "loss": 1e-2, "grad": 0.35}}


@jax.jit
def _reference(params, tokens):
    return family.forward(params, tokens, TINY), jax.value_and_grad(
        family.loss)(params, tokens, TINY, 2)


def _rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2))
                 / (jnp.sqrt(jnp.mean(want ** 2)) + 1e-12))


@pytest.mark.parametrize("kernel", [False, True], ids=["twin", "gmm"])
@pytest.mark.parametrize("unroll", [True, False], ids=["unrolled", "rolled"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_program_matches_the_reference(tokens, dtype, unroll, kernel,
                                       monkeypatch):
    """Logits, loss and the gradient of every parameter leaf."""
    if kernel:
        _through_the_kernels(monkeypatch)
    model, params = init(TINY, dtype, scan_unroll=unroll, fused_loss=False)
    tol = TOL[dtype]

    def program_loss(p):
        return model.apply({"params": p}, tokens, labels=tokens)[1]

    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(model.apply)({"params": params}, tokens)
        loss, grads = jax.jit(jax.value_and_grad(program_loss))(params)
    want_logits, (want_loss, want_grads) = _reference(params, tokens)
    assert float(jnp.std(want_logits)) > 0.3
    assert _rel(logits, want_logits) < tol["logits"]
    assert abs(float(loss) - float(want_loss)) < tol["loss"] * float(want_loss)
    got_flat = jax.tree_util.tree_leaves_with_path(grads)
    want_flat = jax.tree_util.tree_leaves(want_grads)
    assert len(got_flat) == len(want_flat) == 33
    for (path, got), want in zip(got_flat, want_flat):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:   # a buffer: no gradient reaches it
            assert not np.any(np.asarray(got)) and not np.any(
                np.asarray(want)), name
            continue
        assert float(jnp.max(jnp.abs(want))) > 0, name
        assert _rel(got, want) < tol["grad"], name


@pytest.mark.parametrize("fault", ["taps_reversed", "softmax_gates",
                                   "unnormalised_gates", "expert_dropped"])
def test_a_fault_fails_the_comparison(tokens, fault):
    """What the comparison must see, at the bf16 tolerance (the loosest):
    the conv's taps in the wrong order, softmax in place of sigmoid routing,
    the chosen scores not normalised, one held expert's rows left out. Each
    is planted on the reference's side (perf/controls.py, which reads them
    on the chip through the cell's own comparison); the program is as it
    is."""
    model, params = init(TINY, "bfloat16")
    reference_params = controls.PARAMS_FAULTS.get(fault, lambda p: p)(params)
    logits, _ = jax.jit(model.apply)({"params": params}, tokens)
    with controls.planted(family, fault):
        want = jax.jit(lambda p: family.forward(p, tokens, TINY))(
            reference_params)
    assert _rel(logits, want) > 1.3 * TOL["bfloat16"]["logits"], fault


def test_the_reference_routed_by_another_choice(tokens):
    """Given the experts another computation chose, the reference routes by
    them and still hands out its own choice; given its own, nothing moves."""
    _, params = init(TINY)
    logits, own = family.forward_and_choices(params, tokens, TINY)
    assert len(own) == 8 and own[0].shape == (2, 48, 4)
    same, again = family.forward_and_choices(params, tokens, TINY, own)
    assert float(jnp.max(jnp.abs(same - logits))) == 0.0
    assert all(bool(jnp.all(a == b)) for a, b in zip(own, again))
    # The held experts (ids 4-7) chosen everywhere: other logits, and the
    # reference's own choice is still what its router says.
    forced = [jnp.broadcast_to(jnp.arange(4, 8), o.shape) for o in own]
    other, again = family.forward_and_choices(params, tokens, TINY, forced)
    assert _rel(other, logits) > 0.05
    assert bool(jnp.all(again[0] == own[0]))
    assert abs(float(family.loss(params, tokens, TINY, 2, own))
               - float(family.loss(params, tokens, TINY, 1))) < 1e-6


# --- the share is a share of the model ----------------------------------------

def _share(params_uncut, first, count):
    """The parameters a chip holding experts [first, first + count) has."""
    return jax.tree_util.tree_map_with_path(
        lambda path, p: p[:, first:first + count]
        if "experts_" in str(path) else p, params_uncut)


def test_the_shares_of_one_layer_add_up_to_the_uncut_layer():
    uncut_cfg = family.gpt_config(UNCUT, dtype="float32")
    _, params = init(UNCUT)
    layer = jax.tree_util.tree_map(
        lambda a: a[1], params["layers_conv_moe"]["moe_mlp"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))
    with jax.default_matmul_precision("highest"):
        want, _ = family._experts(h, layer, UNCUT)
        parts = []
        for first in (0, 4, 8, 12):
            cfg = dataclasses.replace(uncut_cfg, moe_experts_held=(first, 4))
            share = {k: v[first:first + 4] if k.startswith("experts_") else v
                     for k, v in layer.items()}
            out, _ = moe.MoEMLP(cfg).apply({"params": share}, h)
            parts.append(out)
            # The reference, given the same share, gives the same part.
            assert float(jnp.max(jnp.abs(out - family._experts(
                h, share, dict(UNCUT, num_experts=4,
                               experts_held_first=first))[0]))) < 1e-5
    assert float(jnp.std(want)) > 0.05
    assert max(float(jnp.max(jnp.abs(p))) for p in parts) > 0
    assert float(jnp.max(jnp.abs(sum(parts) - want))) < 1e-5


def test_the_shares_summed_at_each_layer_give_the_uncut_loss(tokens):
    """Four chips, each with 4 of the 16 experts: every chip computes what it
    holds of each expert layer, the partial results are summed, and the loss
    is the uncut reference's."""
    uncut_cfg = family.gpt_config(UNCUT, dtype="float32")
    _, params = init(UNCUT)
    norm = RMSNorm(eps=uncut_cfg.norm_eps)
    seen = {}
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"][tokens]
        for kind in uncut_cfg.layer_kinds():
            i = seen.get(kind, 0)
            seen[kind] = i + 1
            p = jax.tree_util.tree_map(lambda a: a[i],
                                       params[stack_name(kind)])
            h = norm.apply({"params": p["operator_norm"]}, x)
            if kind[0] == "conv":
                x = x + ShortConv(uncut_cfg).apply({"params": p["conv"]}, h)
            else:
                x = x + CausalSelfAttention(uncut_cfg).apply(
                    {"params": p["attention"]}, h)
            h = norm.apply({"params": p["ffn_norm"]}, x)
            if kind[1] == "dense":
                x = x + MLP(uncut_cfg).apply({"params": p["mlp"]}, h)
                continue
            for first in (0, 4, 8, 12):     # the exchange's sum
                cfg = dataclasses.replace(
                    uncut_cfg, moe_experts_held=(first, 4))
                share = {k: v[first:first + 4] if k.startswith("experts_")
                         else v for k, v in p["moe_mlp"].items()}
                x = x + moe.MoEMLP(cfg).apply({"params": share}, h)[0]
        x = norm.apply({"params": params["norm"]}, x)
        logits = x @ params["embed_tokens"]["embedding"].T
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    got = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
    want = family.loss(params, tokens, UNCUT, 2)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    # And a single share's loss is another number: the cut shows.
    assert abs(float(family.loss(_share(params, 4, 4), tokens, TINY, 2))
               - float(want)) > 1e-3


# --- row buffers sized by the share held, and the exact path past them ---

# One expert layer of TINY: 96 tokens x 4 choices = 384 rows, 4 of 16 experts
# held (ids 4-7), so the sorted buffers take R = 256 rows (twice the even
# share of 96, in row tiles of 128). Each load names, for groups of tokens,
# the four experts they choose; ``None`` leaves the routing to the seeded
# weights (an even load).
_LOADS = {
    "even": (None, None, 0),
    # 64 tokens choose the four held experts, 32 none: sum(counts) == R.
    "exactly_R": ([(64, (4, 5, 6, 7)), (32, (0, 1, 2, 3))], 256, 0),
    # Every choice of every token falls on a held expert: 384 > R.
    "overflow": ([(96, (4, 5, 6, 7))], 384, 1),
    # Expert 5 takes 96 of the 144 held rows, expert 7 none.
    "ragged": ([(72, (5, 0, 1, 2)), (24, (5, 4, 6, 3))], 144, 0),
}


def _layer_with_a_load(groups):
    """``(layer, params, h)``: the expert layer of TINY in float32 and an
    input whose first 16 features are the router's logits (its kernel is the
    identity on them), so that ``groups`` decides every token's choice."""
    cfg = family.gpt_config(TINY, dtype="float32")
    layer = moe.MoEMLP(cfg)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 48, 64))
    params = layer.init(jax.random.PRNGKey(0), h)["params"]
    params = {k: v * 6.0 if k.startswith("experts_") else v
              for k, v in params.items()}
    if groups is None:
        return layer, dict(params, router={
            "kernel": params["router"]["kernel"] * 30.0}), h
    logits = []
    for tokens, chosen in groups:
        row = -1.0 + 0.02 * np.arange(16)
        row[list(chosen)] = 1.0 + 0.05 * np.arange(4)
        logits.append(np.tile(row, (tokens, 1)))
    logits = np.random.default_rng(0).permutation(np.concatenate(logits))
    h = h.at[..., :16].set(jnp.asarray(
        logits, jnp.float32).reshape(2, 48, 16))
    return layer, dict(params, router={"kernel": jnp.eye(64, 16)}), h


def _layer_outputs(layer, params, h):
    """Output, its gradients by ``x``, the router's kernel and the three
    expert leaves, and the layer's step counters."""
    from tpu_trainer.utils import telemetry

    weights = jnp.cos(jnp.arange(h.size, dtype=jnp.float32)).reshape(h.shape)

    def scalar(p, h):
        out, _ = layer.apply({"params": p}, h)
        return jnp.sum(out * weights), out

    @jax.jit
    def run(p, h):
        with telemetry.counters() as counts:
            (_, out), (dp, dh) = jax.value_and_grad(
                scalar, argnums=(0, 1), has_aux=True)(p, h)
        return out, dh, dp, telemetry.flat_counts(counts)

    with jax.default_matmul_precision("highest"):
        out, dh, dp, counts = run(params, h)
    leaves = {"out": out, "x": dh, "router": dp["router"]["kernel"],
              **{k: dp[k] for k in ("experts_gate", "experts_up",
                                    "experts_down")}}
    return leaves, {k: float(v) for k, v in counts.items()}


@pytest.mark.parametrize("kernel", [False, True], ids=["twin", "gmm"])
@pytest.mark.parametrize("load", list(_LOADS))
def test_the_bounded_buffers_give_the_worst_case_result(load, kernel,
                                                        monkeypatch):
    """Output and every gradient of the layer with ``[R, .]`` row buffers
    against the same layer with all ``k*T`` rows (the formulation a layer
    that holds every expert keeps): equal whatever the load. Past ``R``
    rows the pass is counted and goes through all of them a chunk at a
    time, the grouped matmuls as ``lax.ragged_dot`` (six chunks of 64)."""
    groups, rows_held, overflowed = _LOADS[load]
    if kernel:
        _through_the_kernels(monkeypatch)
    layer, params, h = _layer_with_a_load(groups)
    assert moe._receive_rows(4 * 96, 4, 16) == 256
    got, counts = _layer_outputs(layer, params, h)
    with monkeypatch.context() as worst:
        worst.setattr(moe, "_receive_rows", lambda choices, held, e: choices)
        want, want_counts = _layer_outputs(layer, params, h)
    assert "moe_overflow_passes" not in want_counts
    assert counts["moe_overflow_passes"] == overflowed
    assert counts["moe_rows_held"] == want_counts["moe_rows_held"]
    if rows_held is None:
        assert 48 < counts["moe_rows_held"] < 192       # near 96, under R
    else:
        assert counts["moe_rows_held"] == rows_held
    if load == "ragged":
        assert abs(counts["moe_max_load"] - 96 / 144) < 1e-6
    for name, value in want.items():
        scale = float(jnp.max(jnp.abs(value)))
        assert scale > 1e-4, name
        assert float(jnp.max(jnp.abs(got[name] - value))) <= 1e-6 * scale, name


def test_only_a_layer_that_holds_a_share_traces_the_cond():
    """All experts held (every uniform MoE model): ``R == k*T``, no ``cond``
    is traced and the layer's program is the one it was. A share under half:
    one ``cond`` forward, one in the backward."""
    h = jnp.zeros((2, 48, 64))

    def program(cfg_file):
        layer = moe.MoEMLP(family.gpt_config(cfg_file, dtype="float32"))
        params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), h)["params"]

        def scalar(p, h):
            return jnp.sum(layer.apply({"params": p}, h)[0])

        return str(jax.make_jaxpr(jax.grad(scalar))(params, h))

    assert moe._receive_rows(384, 16, 16) == 384        # all held
    assert moe._receive_rows(384, 8, 16) == 384         # half of them
    assert program(UNCUT).count("cond[") == 0
    assert program(TINY).count("cond[") == 2
    # The cell's: 4 x 16,384 choices, 8 of 64 held.
    assert moe._receive_rows(65536, 8, 64) == 16384


# --- the grouped matmul with rows that are nobody's ---------------------------

@pytest.mark.parametrize("kernel", [False, True], ids=["twin", "kernel"])
def test_gmm_with_rows_past_the_sum_at_an_uneven_load(kernel):
    """sum(group_sizes) < G: one expert holds half the rows, one none; the
    rows past the sum produce zeros and get a zero gradient, and no row of a
    group is dropped."""
    g, hid, n = 640, 128, 256
    sizes = jnp.array([320, 0, 37, 91, 12], jnp.int32)       # 460 of 640
    total = int(sizes.sum())
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(keys[0], (g, hid))
    rhs = jax.random.normal(keys[1], (5, hid, n)) / 8
    dout = jax.random.normal(keys[2], (g, n))
    how = dict(use_kernel=kernel, interpret=True, tile_tokens=128)
    with jax.default_matmul_precision("highest"):
        want = gmm_reference(lhs[:total], rhs, sizes)
        got = gmm(lhs, rhs, sizes, **how)
        assert float(jnp.max(jnp.abs(got[:total] - want))) < 1e-4
        assert not np.any(np.asarray(got[total:]))
        got_w = tgmm(lhs, dout, sizes, **how)
        want_w = tgmm_reference(lhs[:total], dout[:total], sizes)
        assert float(jnp.max(jnp.abs(got_w - want_w))) < 1e-3
        assert not np.any(np.asarray(got_w[1]))         # the empty expert
        dl, dr = jax.grad(
            lambda l, r: jnp.sum(gmm(l, r, sizes, **how) * dout),
            argnums=(0, 1))(lhs, rhs)
        wl, wr = jax.grad(
            lambda l, r: jnp.sum(gmm_reference(l, r, sizes) * dout[:total]),
            argnums=(0, 1))(lhs[:total], rhs)
    assert float(jnp.max(jnp.abs(dl[:total] - wl))) < 1e-3
    assert not np.any(np.asarray(dl[total:]))
    assert float(jnp.max(jnp.abs(dr - wr))) < 1e-3


# --- what the new layers cannot do yet ----------------------------------------

def test_decode_and_the_serving_engine_are_refused():
    from tpu_trainer.models.gpt import init_cache
    from tpu_trainer.serving.engine import ServingEngine

    model, params = init(TINY)
    with pytest.raises(NotImplementedError, match="conv layer"):
        model.apply({"params": params}, jnp.zeros((1, 1), jnp.int32),
                    decode=True)
    with pytest.raises(NotImplementedError, match="conv layer"):
        init_cache(model.config, 1)
    with pytest.raises(NotImplementedError, match="serving engine"):
        ServingEngine(params, model.config)


@pytest.mark.parametrize("axis", ["stage", "sequence", "expert"])
def test_mesh_axes_the_new_layers_do_not_run_under_are_refused(axis):
    from tpu_trainer.parallel.mesh import MeshConfig, make_mesh
    from tpu_trainer.training.config import TrainingConfig
    from tpu_trainer.training.trainer import ParallelConfig, Trainer

    mesh_config = MeshConfig(data=1, fsdp=1, **{axis: 2})
    with pytest.raises(ValueError, match=(
            "moe_experts_held" if axis == "expert" else "layers differ")):
        Trainer(family.gpt_config(TINY),
                TrainingConfig(batch_size=2, max_seq_len=32),
                ParallelConfig(mesh=mesh_config),
                mesh=make_mesh(mesh_config, devices=jax.devices()[:2]))


def test_the_model_refuses_a_stage_axis_at_trace_time():
    from tpu_trainer.parallel import context as ctx_lib
    from tpu_trainer.parallel.mesh import MeshConfig, make_mesh

    model, params = init(TINY)
    mesh_config = MeshConfig(data=1, fsdp=1, stage=2)
    mesh = make_mesh(mesh_config, devices=jax.devices()[:2])
    with ctx_lib.mesh_scope(mesh), pytest.raises(
            NotImplementedError, match="'stage' mesh axis"):
        model.apply({"params": params}, jnp.zeros((2, 8), jnp.int32))


def test_bad_fields_are_refused():
    with pytest.raises(ValueError, match="layer_types"):
        GPTConfig(num_layers=2, layer_types=("conv", "window"))
    with pytest.raises(ValueError, match="layer_types"):
        GPTConfig(num_layers=3, layer_types=("conv", "conv"))
    with pytest.raises(ValueError, match="moe_router"):
        GPTConfig(num_experts=4, moe_router="tanh")
    with pytest.raises(ValueError, match="not a range"):
        GPTConfig(num_experts=4, moe_impl="dropless",
                  moe_experts_held=(2, 4))
    with pytest.raises(ValueError, match="dropless"):
        GPTConfig(num_experts=4, moe_experts_held=(0, 2))


def test_packed_documents_do_not_leak_through_the_conv_taps():
    """Two documents packed into one row: the second document's first two
    positions see zeros where the earlier taps would reach into the first."""
    cfg = family.gpt_config(TINY, dtype="float32")
    conv = ShortConv(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 20, 64))
    params = conv.init(jax.random.PRNGKey(1), x)["params"]
    segments = jnp.array([[1] * 12 + [2] * 8])
    packed = conv.apply({"params": params}, x, True, segments)
    first = conv.apply({"params": params}, x[:, :12])
    second = conv.apply({"params": params}, x[:, 12:])
    assert float(jnp.max(jnp.abs(packed[:, :12] - first))) < 1e-6
    assert float(jnp.max(jnp.abs(packed[:, 12:] - second))) < 1e-6
    unmasked = conv.apply({"params": params}, x)
    assert float(jnp.max(jnp.abs(unmasked[:, 12:14] - second[:, :2]))) > 1e-4


# --- counts, names, counters, telemetry ---------------------------------------

def test_parameter_counts_are_exact():
    for cfg_file in (TINY, UNCUT):
        model, params = init(cfg_file)
        leaves = sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
        assert model.config.num_parameters() == leaves
        assert family.param_count(cfg_file) == leaves
    cut, whole = (family.gpt_config(c) for c in (TINY, UNCUT))
    # 8 expert layers, 12 experts of 3 * 64 * 48 fewer in each.
    assert whole.num_parameters() - cut.num_parameters() == 8 * 12 * 9216
    # A token flows through 4 experts a layer, wherever they live.
    assert cut.num_active_parameters() == whole.num_active_parameters() \
        == whole.num_parameters() - 8 * 12 * 9216


def _shapes(config):
    params = jax.eval_shape(
        lambda: GPT(config).init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32)))["params"]
    return {jax.tree_util.keystr(path): leaf.shape for path, leaf
            in jax.tree_util.tree_leaves_with_path(params)}


def test_the_uniform_models_keep_their_parameter_tree():
    """With none of the new fields set, the leaf names and shapes are the
    parent commit's: a dense model and a softmax-routed MoE."""
    small = dict(vocab_size=128, hidden_size=32, num_layers=3, num_heads=4,
                 num_kv_heads=2, intermediate_size=48, max_seq_len=16)
    block = {
        "['layers']['attention']['k_proj']['kernel']": (3, 32, 16),
        "['layers']['attention']['o_proj']['kernel']": (3, 32, 32),
        "['layers']['attention']['q_proj']['kernel']": (3, 32, 32),
        "['layers']['attention']['v_proj']['kernel']": (3, 32, 16),
        "['layers']['input_layernorm']['weight']": (3, 32),
        "['layers']['post_attention_layernorm']['weight']": (3, 32),
        "['embed_tokens']['embedding']": (128, 32),
        "['norm']['weight']": (32,),
    }
    assert _shapes(GPTConfig(**small)) == {
        **block,
        "['layers']['mlp']['down_proj']['kernel']": (3, 48, 32),
        "['layers']['mlp']['gate_proj']['kernel']": (3, 32, 48),
        "['layers']['mlp']['up_proj']['kernel']": (3, 32, 48),
    }
    assert _shapes(GPTConfig(**small, num_experts=4, moe_top_k=2)) == {
        **block,
        "['layers']['moe_mlp']['experts_down']": (3, 4, 48, 32),
        "['layers']['moe_mlp']['experts_gate']": (3, 4, 32, 48),
        "['layers']['moe_mlp']['experts_up']": (3, 4, 32, 48),
        "['layers']['moe_mlp']['router']['kernel']": (3, 32, 4),
    }


def test_counters_fold_by_the_kind_they_are_counted_with():
    from tpu_trainer.utils import telemetry

    telemetry.count("rows", 1.0)            # nobody listens: a no-op
    assert not telemetry.counting()
    with telemetry.counters() as step:
        telemetry.count("rows", 2.0)
        telemetry.count("rows", 3.0)
        telemetry.count("load", 0.2, reduce="max")
        telemetry.count("load", 0.1, reduce="max")
        with telemetry.counters() as layer:     # a layer's own set
            telemetry.count("rows", 4.0)
        assert layer == {"sum": {"rows": 4.0}}
        # What a layer loop stacked, reduced by kind and folded in.
        telemetry.count_all(telemetry.reduce_counts({
            "sum": {"rows": jnp.array([1.0, 2.0])},
            "max": {"load": jnp.array([0.5, 0.3])}}))
    flat = {k: float(v) for k, v in telemetry.flat_counts(step).items()}
    assert flat == {"rows": 8.0, "load": 0.5}


def test_the_step_counts_rows_and_telemetry_stacks_per_kind():
    from tpu_trainer.parallel.mesh import MeshConfig, make_mesh
    from tpu_trainer.training.config import TrainingConfig
    from tpu_trainer.training.trainer import ParallelConfig, Trainer

    mesh_config = MeshConfig(data=1, fsdp=1)
    trainer = Trainer(
        family.gpt_config(TINY),
        TrainingConfig(batch_size=2, gradient_accumulation_steps=2,
                       max_seq_len=32, mixed_precision="bf16"),
        ParallelConfig(mesh=mesh_config, sharding_strategy="replicated"),
        mesh=make_mesh(mesh_config, devices=jax.devices()[:1]))
    state = trainer.init_state(0)
    # The step's compute-type copy of the parameters leaves the router in
    # float32, as its module computes it: rounded, tokens near a tie chose
    # other experts in the step than the same parameters choose elsewhere
    # (at tests/perf's tiny width the first step's gradient read 0.08 off
    # the reference's with the kernel rounded, 0.017 with it kept).
    copy = state.params_c["layers_conv_moe"]
    assert copy["moe_mlp"]["router"]["kernel"].dtype == jnp.float32
    assert copy["moe_mlp"]["experts_up"].dtype == jnp.bfloat16
    assert copy["conv"]["in_proj"]["kernel"].dtype == jnp.bfloat16
    batch = np.random.default_rng(0).integers(
        0, 256, size=(4, 32), dtype=np.int32)
    state, metrics = trainer.train_step(state, batch)
    routed = 4 * 32 * 4 * 8         # tokens x experts a token x expert layers
    rows = float(metrics["moe_rows_held"])
    assert 0.1 * routed < rows < 0.5 * routed and rows == int(rows)
    assert 0.25 <= float(metrics["moe_max_load"]) <= 1.0
    # 64 tokens x 4 choices a layer and micro-batch, a quarter of the experts
    # held: row buffers of 128 rows, and at this load no pass outgrew them.
    assert float(metrics["moe_overflow_passes"]) == 0
    state, metrics = trainer.train_step(state, batch, telemetry=True)
    telem = metrics["telemetry"]
    assert telem["act"]["conv_moe"]["attn_rms"].shape == (6,)
    assert telem["act"]["attention_moe"]["ffn_rms"].shape == (2,)
    assert telem["act"]["conv_dense"]["block_rms"].shape == (1,)
    assert telem["router"]["conv_moe"]["load"].shape == (6, 4)
    assert "conv_dense" not in telem["router"]
    assert telem["grad_norm"]["per_layer_conv_moe"].shape == (6,)
    assert float(metrics["moe_rows_held"]) > 0
    assert float(metrics["moe_overflow_passes"]) == 0
    # A selection bias that puts every choice on the held experts (ids 4-7):
    # each of the 8 expert layers x 2 micro-batches outgrows its buffers,
    # counted by sum, and every routed row is computed here.
    biased = jax.tree_util.tree_map_with_path(
        lambda path, p: p.at[..., 4:8].set(10.0)
        if "expert_bias" in str(path) else p, state.params)
    state = trainer.with_params_c(
        state.replace(params=biased, params_c=None))
    state, metrics = trainer.train_step(state, batch)
    assert float(metrics["moe_overflow_passes"]) == 8 * 2
    assert float(metrics["moe_rows_held"]) == routed
    assert np.isfinite(float(metrics["loss"]))
