"""The gated delta rule's Pallas kernels (``tpu_trainer/ops/gated_delta.py``)
under the interpreter on the CPU: forward and all five gradients against the
plain XLA form they replace on a TPU (``_chunked``) and against the family's
token-by-token recurrence, at the Qwen3-Next cell's lanes; which shapes take
them and which go the plain way, read from ``gated_delta_kernel_tokens`` as
a step would; the batch sharded under a mesh; the mixer through them. Mosaic's
own refusals do not show here: ``tests/test_chip_compile.py`` compiles the
same kernels for a described v5e."""

import jax
import jax.numpy as jnp
import pytest

from perf.families import qwen3_next as family
from tpu_trainer.models.gpt import GatedDeltaNet
from tpu_trainer.ops import gated_delta as gd_ops
from tpu_trainer.ops.attention import _INTERPRET_ENV
from tpu_trainer.utils import telemetry

from tests.test_qwen3_next import TINY, _rel

F32 = jnp.float32


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv(_INTERPRET_ENV, "1")


def _inputs(batch, seq, hk, hv, decay=1.0, seed=0, d=128):
    """Normalised q and k (q scaled by d^-1/2, as the mixer reads them),
    unit-scale v, log decays of a few tenths a token (times ``decay``) and
    betas in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = family._l2norm(jax.random.normal(ks[0], (batch, seq, hk, d)))
    q = q / d ** 0.5
    k = family._l2norm(jax.random.normal(ks[1], (batch, seq, hk, d)))
    v = jax.random.normal(ks[2], (batch, seq, hv, d))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (batch, seq, hv))
                                 - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, hv)))
    return q, k, v, g, beta


# The cell's lanes (d_k = d_v = 128, two value heads a key head, chunk 64)
# over two and four chunks, f32 and bf16, batch 2; one value head a key
# head; a tail that is not a whole chunk (padded); three chunks (a grid step
# a chunk); and decays so steep that the in-chunk cumulative log decay spans
# hundreds (exp of it underflows in f32).
CASES = {
    "two_chunks": dict(batch=2, seq=128, hk=2, hv=4),
    "four_chunks": dict(batch=2, seq=256, hk=1, hv=2),
    "two_chunks_bf16": dict(batch=2, seq=128, hk=1, hv=2, dtype="bfloat16"),
    "four_chunks_bf16": dict(batch=2, seq=256, hk=2, hv=4, dtype="bfloat16"),
    "one_value_head_a_key_head": dict(batch=1, seq=128, hk=2, hv=2),
    "tail": dict(batch=1, seq=200, hk=1, hv=2),
    "three_chunks": dict(batch=1, seq=192, hk=1, hv=2),
    "underflow": dict(batch=1, seq=128, hk=1, hv=2, decay=40.0),
    "underflow_bf16": dict(batch=1, seq=192, hk=1, hv=2, decay=40.0,
                           dtype="bfloat16"),
}


@pytest.mark.parametrize("case", CASES)
def test_the_kernels_are_the_chunked_rule_and_the_recurrence(
        case, interpreted):
    spec = dict(CASES[case])
    dtype = jnp.dtype(spec.pop("dtype", "float32"))
    args = _inputs(**spec)
    cast = tuple(x.astype(dtype) if i < 3 else x for i, x in enumerate(args))
    wide = tuple(x.astype(F32) for x in cast)
    assert gd_ops.kernel_path(cast[0].shape, cast[2].shape) is not None
    low_bits = dtype == jnp.bfloat16
    tol = 2e-2 if low_bits else 2e-5
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def grads(rule, operands):
        return jax.jit(jax.grad(lambda *a: jnp.sum(rule(*a) * weight),
                                argnums=range(5)))(*operands)

    def plain(*a):
        return gd_ops.gated_delta(*a)[0]

    with jax.default_matmul_precision("highest"):
        got, low = jax.jit(gd_ops.gated_delta)(*cast)
        got_g = grads(lambda *a: gd_ops.gated_delta(*a)[0], cast)
        exact = jax.jit(family.delta_rule)(*wide)
        exact_g = grads(family.delta_rule, wide)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(gd_ops, "kernel_path", lambda *a, **kw: None)
            want, want_low = jax.jit(gd_ops.gated_delta)(*cast)
            want_g = grads(plain, cast)
    assert got.shape == args[2].shape and got.dtype == F32
    assert bool(jnp.all(jnp.isfinite(got)))
    # The same roundings in the same places: far nearer the plain form than
    # either is to the recurrence.
    assert _rel(got, want) < (1e-3 if low_bits else 2e-5)
    assert _rel(got, exact) < tol
    assert float(low) == pytest.approx(float(want_low), rel=1e-6)
    if spec.get("decay", 1.0) > 1:
        assert float(low) < -200                 # exp(low) underflows in f32
    slack = 40 if spec.get("decay", 1.0) > 1 else 10
    for name, g, w, e in zip("q k v g beta".split(), got_g, want_g, exact_g):
        assert g.dtype == w.dtype, name
        assert bool(jnp.all(jnp.isfinite(g.astype(F32)))), name
        assert _rel(g, e) < slack * tol, name
        assert _rel(g, w) < 1.5 * slack * tol, name


def test_fits_is_static_and_says_what_it_takes():
    cell = ((2, 4096, 16, 128), (2, 4096, 32, 128))
    assert gd_ops.fits(*cell)
    assert gd_ops.fits((2, 4000, 16, 128), (2, 4000, 16, 128))
    assert gd_ops.fits((2, 4096, 8, 256), (2, 4096, 16, 128))
    assert not gd_ops.fits((2, 4096, 16, 64), (2, 4096, 32, 128))
    assert not gd_ops.fits((2, 4096, 16, 128), (2, 4096, 32, 64))
    assert not gd_ops.fits((2, 4096, 8, 128), (2, 4096, 32, 128))   # 4 x 64
    assert not gd_ops.fits((2, 4096, 16, 128), (2, 4096, 24, 128))
    assert not gd_ops.fits(*cell, chunk=40)
    # Off the chip and without the hook nothing takes them.
    assert gd_ops.kernel_path(*cell) is None


# --- the mixer, and which calls take the kernels -----------------------------

def _mixer_counts(seq, batch=1, **sizes):
    cfg = family.gpt_config({
        **TINY, "linear_num_key_heads": 1, "linear_num_value_heads": 2,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "max_position_embeddings": 256, **sizes}, dtype="float32")
    mixer = GatedDeltaNet(cfg)
    u = jax.random.normal(jax.random.PRNGKey(0), (batch, seq, cfg.hidden_size))
    params = mixer.init(jax.random.PRNGKey(1), u[:, :8])

    def run(params, u):
        with telemetry.counters() as counts:
            out = mixer.apply(params, u)
        return out, telemetry.flat_counts(counts)

    def loss(params, u):
        out, counts = run(params, u)
        return jnp.sum(out.astype(F32) ** 2), counts

    out, counts = jax.jit(run)(params, u)
    (_, _), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params, u)
    return out, counts, grads


def test_a_fitting_mixer_counts_its_tokens_as_kernel_tokens(interpreted):
    out, counts, grads = _mixer_counts(128, batch=2)
    assert float(counts["gated_delta_tokens"]) == 256
    assert float(counts["gated_delta_kernel_tokens"]) == 256
    assert bool(jnp.all(jnp.isfinite(out)))
    for leaf in jax.tree_util.tree_leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf))) and float(
            jnp.max(jnp.abs(leaf))) > 0


def test_the_mixer_through_the_kernels_is_the_mixer_without(monkeypatch):
    plain, counts, plain_g = _mixer_counts(128)
    assert float(counts["gated_delta_kernel_tokens"]) == 0  # the CPU path
    assert float(counts["gated_delta_tokens"]) == 128
    monkeypatch.setenv(_INTERPRET_ENV, "1")
    got, counts, got_g = _mixer_counts(128)
    assert float(counts["gated_delta_kernel_tokens"]) == 128
    assert _rel(got, plain) < 1e-4
    for g, w in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(plain_g)):
        assert _rel(g, w) < 1e-3


@pytest.mark.parametrize("why,sizes", [
    ("keys of 64 lanes", {"linear_key_head_dim": 64}),
    ("values of 64 lanes", {"linear_value_head_dim": 64}),
    ("four value heads of a chunk: 256 rows", {"linear_num_value_heads": 4}),
])
def test_what_the_kernels_do_not_take_goes_the_plain_way(
        why, sizes, interpreted):
    out, counts, _ = _mixer_counts(64, **sizes)
    assert float(counts["gated_delta_tokens"]) == 64
    assert float(counts["gated_delta_kernel_tokens"]) == 0, why
    assert bool(jnp.all(jnp.isfinite(out)))


def test_under_a_mesh_the_kernels_shard_the_batch_or_stand_aside(interpreted):
    from tpu_trainer.parallel.context import mesh_scope
    from tpu_trainer.parallel.mesh import MeshConfig, make_mesh

    args = _inputs(batch=4, seq=128, hk=1, hv=2)

    def loss(*a):
        return jnp.sum(gd_ops.gated_delta(*a)[0] ** 2)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(gd_ops, "kernel_path", lambda *a, **kw: None)
        want = jax.jit(gd_ops.gated_delta)(*args)[0]
        want_g = jax.jit(jax.grad(loss, argnums=range(5)))(*args)
    mesh = make_mesh(MeshConfig(data=2, fsdp=2), devices=jax.devices()[:4])
    with mesh_scope(mesh):
        interpret, found, b_spec = gd_ops.kernel_path(args[0].shape,
                                                      args[2].shape)
        assert interpret and found is mesh and set(b_spec) == {"data", "fsdp"}
        got = jax.jit(gd_ops.gated_delta)(*args)[0]
        got_g = jax.jit(jax.grad(loss, argnums=range(5)))(*args)
        # Three rows over four shards: no un-sharded kernel by accident.
        assert gd_ops.kernel_path((3, 128, 1, 128), (3, 128, 2, 128)) is None
    assert _rel(got, want) < 2e-5
    for name, g, w in zip("q k v g beta".split(), got_g, want_g):
        assert _rel(g, w) < 2e-4, name
