"""The state-space scan's Pallas kernels (``tpu_trainer/ops/ssd.py``) under
the interpreter on the CPU: forward and all five gradients against the plain
XLA form they replace on a TPU (``_chunked``) and against the family's
token-by-token recurrence, at the Nemotron cell's lanes; which shapes take
them and which fall back, read from ``ssd_kernel_tokens`` as a step would.
Mosaic's own refusals do not show here: ``tests/test_chip_compile.py``
compiles the same kernels for a described v5e."""

import jax
import jax.numpy as jnp
import pytest

from perf.families import nemotron_h as family
from tpu_trainer.models.gpt import Mamba2Mixer
from tpu_trainer.ops import ssd as ssd_ops
from tpu_trainer.ops.attention import _INTERPRET_ENV
from tpu_trainer.utils import telemetry

from tests.test_nemotron_h import TINY, _rel

F32 = jnp.float32


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv(_INTERPRET_ENV, "1")


def _inputs(batch, seq, heads, p, groups, n, dt_scale=1.0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (batch, seq, heads, p))
    # Time steps of the initialiser's order (0.001-0.1), so that a chunk of
    # 128 decays by a factor one can see and does not vanish.
    dt = dt_scale * jax.nn.softplus(
        jax.random.normal(ks[1], (batch, seq, heads)) - 3.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.7))
    b = 0.3 * jax.random.normal(ks[3], (batch, seq, groups, n))
    c = 0.3 * jax.random.normal(ks[4], (batch, seq, groups, n))
    return x, dt, a, b, c


# The cell's lanes (heads of 64, state 128, chunk 128, 8 heads a group) at
# two chunks, one group and two; four chunks of two heads a group; heads of
# 128 lanes (a slab a head); operands in bf16; and time steps so large that
# a chunk's decay underflows (exp(-600) = 0: a ratio of cumulative products
# would be 0 / 0).
CASES = {
    "one_group": dict(batch=1, seq=256, heads=8, p=64, groups=1),
    "two_groups_bf16": dict(batch=1, seq=256, heads=16, p=64, groups=2,
                            dtype="bfloat16"),
    "four_chunks_batch_two": dict(batch=2, seq=512, heads=4, p=64, groups=2),
    "four_chunks_bf16": dict(batch=1, seq=512, heads=2, p=64, groups=1,
                             dtype="bfloat16"),
    "heads_of_128": dict(batch=1, seq=256, heads=2, p=128, groups=1),
    "underflow": dict(batch=1, seq=256, heads=2, p=64, groups=1,
                      dt_scale=40.0),
    "underflow_bf16": dict(batch=1, seq=256, heads=4, p=64, groups=2,
                           dt_scale=40.0, dtype="bfloat16"),
}


@pytest.mark.parametrize("case", CASES)
def test_the_kernels_are_the_chunked_scan_and_the_recurrence(
        case, interpreted):
    spec = dict(CASES[case])
    dtype = jnp.dtype(spec.pop("dtype", "float32"))
    args = _inputs(n=128, **spec)
    cast = tuple(v.astype(dtype) if i in (0, 3, 4) else v
                 for i, v in enumerate(args))
    wide = tuple(v.astype(F32) for v in cast)
    assert ssd_ops.kernel_path(cast[0].shape, cast[3].shape, 128) is not None
    low_bits = dtype == jnp.bfloat16
    tol = 2e-2 if low_bits else 2e-5
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def kernels(*a):
        return ssd_ops.ssd(*a, chunk=128)

    def plain(*a):
        return ssd_ops._chunked(*a, 128, dtype)

    def grads(scan, operands):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(scan(*a) * weight), argnums=range(5)))(
                *operands)

    with jax.default_matmul_precision("highest"):
        got, low = jax.jit(kernels)(*cast)
        want, want_low = jax.jit(plain)(*cast)
        exact = jax.jit(family.scan_recurrence)(*wide)
        got_g = grads(lambda *a: kernels(*a)[0], cast)
        want_g = grads(lambda *a: plain(*a)[0], cast)
        exact_g = grads(family.scan_recurrence, wide)
    assert got.dtype == F32 and bool(jnp.all(jnp.isfinite(got)))
    # The same roundings in the same places: far nearer the plain form than
    # either is to the recurrence.
    assert _rel(got, want) < (1e-3 if low_bits else 2e-5)
    assert _rel(got, exact) < tol
    assert abs(float(low) - float(want_low)) <= 1e-5 * abs(float(want_low))
    if spec.get("dt_scale", 1.0) > 1:
        assert float(low) < -200                 # exp(low) underflows in f32
    for name, g, w, e in zip("x dt a b c".split(), got_g, want_g, exact_g):
        assert g.dtype == w.dtype, name
        assert bool(jnp.all(jnp.isfinite(g.astype(F32)))), name
        slack = 40 if spec.get("dt_scale", 1.0) > 1 else 10
        bound = slack * tol
        if name == "a" and spec.get("dt_scale", 1.0) > 1:
            # Where most decays are zero, dA is what float32 leaves of sums
            # that cancel (the plain form reads 1% off the recurrence at
            # f32): held to twice the plain form's own distance.
            bound = max(bound, 2 * _rel(w, e))
        assert _rel(g, e) < bound, name
        assert _rel(g, w) < 1.5 * bound, name


# --- which calls take the kernels ------------------------------------------------

def _mixer_counts(seq, batch=1, **sizes):
    cfg = family.gpt_config({
        **TINY, "mamba_num_heads": 2, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 1, "chunk_size": 128,
        "max_position_embeddings": 512, **sizes}, dtype="float32")
    mixer = Mamba2Mixer(cfg)
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
    out, counts, grads = _mixer_counts(256, batch=2)
    assert float(counts["ssm_tokens"]) == 512
    assert float(counts["ssd_kernel_tokens"]) == 512
    assert bool(jnp.all(jnp.isfinite(out)))
    for leaf in jax.tree_util.tree_leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf))) and float(
            jnp.max(jnp.abs(leaf))) > 0


def test_the_mixer_through_the_kernels_is_the_mixer_without(monkeypatch):
    plain, counts, plain_g = _mixer_counts(256)
    assert float(counts["ssd_kernel_tokens"]) == 0      # the plain CPU path
    assert float(counts["ssm_tokens"]) == 256
    monkeypatch.setenv(_INTERPRET_ENV, "1")
    got, counts, got_g = _mixer_counts(256)
    assert float(counts["ssd_kernel_tokens"]) == 256
    assert _rel(got, plain) < 1e-4
    for g, w in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(plain_g)):
        assert _rel(g, w) < 1e-3


@pytest.mark.parametrize("why,seq,sizes", [
    ("a sequence that is not whole chunks", 200, {}),
    ("fewer tokens than a chunk", 96, {}),
    ("a chunk that is not whole lanes", 256, {"chunk_size": 16}),
    ("state lanes under 128", 256, {"ssm_state_size": 16}),
    ("heads of 32 lanes", 256, {"mamba_num_heads": 4, "mamba_head_dim": 32}),
    ("a group of one 64-lane head", 256, {"n_groups": 2}),
])
def test_what_the_kernels_do_not_take_goes_the_plain_way(
        why, seq, sizes, interpreted):
    out, counts, _ = _mixer_counts(seq, **sizes)
    assert float(counts["ssm_tokens"]) == seq
    assert float(counts["ssd_kernel_tokens"]) == 0, why
    assert bool(jnp.all(jnp.isfinite(out)))


def test_fits_is_static_and_says_what_it_takes():
    cell = ((2, 4096, 64, 64), (2, 4096, 8, 128), 128)
    assert ssd_ops.fits(*cell)
    assert not ssd_ops.fits((2, 4000, 64, 64), (2, 4000, 8, 128), 128)
    assert not ssd_ops.fits((2, 4096, 64, 64), (2, 4096, 8, 128), 64)
    assert not ssd_ops.fits((2, 4096, 64, 64), (2, 4096, 8, 96), 128)
    assert not ssd_ops.fits((2, 4096, 64, 48), (2, 4096, 8, 128), 128)
    assert not ssd_ops.fits((2, 4096, 60, 64), (2, 4096, 8, 128), 128)
    # Off the chip and without the hook nothing takes them.
    assert ssd_ops.kernel_path(*cell) is None


def test_under_a_mesh_the_kernels_shard_the_batch_or_stand_aside(interpreted):
    from tpu_trainer.parallel.context import mesh_scope
    from tpu_trainer.parallel.mesh import MeshConfig, make_mesh

    args = _inputs(batch=4, seq=256, heads=2, p=64, groups=1, n=128)
    want = jax.jit(lambda *a: ssd_ops._chunked(*a, 128, F32)[0])(*args)
    mesh = make_mesh(MeshConfig(data=2, fsdp=2), devices=jax.devices()[:4])
    with mesh_scope(mesh):
        interpret, found, b_spec = ssd_ops.kernel_path(
            args[0].shape, args[3].shape, 128)
        assert interpret and found is mesh and set(b_spec) == {"data", "fsdp"}
        got = jax.jit(lambda *a: ssd_ops.ssd(*a, chunk=128)[0])(*args)
        grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(ssd_ops.ssd(*a, chunk=128)[0] ** 2),
            argnums=range(5)))(*args)
        # Three rows over four shards: no un-sharded kernel by accident.
        assert ssd_ops.kernel_path((3, 256, 2, 64), (3, 256, 1, 128),
                                   128) is None
    assert _rel(got, want) < 2e-5
    want_g = jax.jit(jax.grad(
        lambda *a: jnp.sum(ssd_ops._chunked(*a, 128, F32)[0] ** 2),
        argnums=range(5)))(*args)
    for g, w in zip(grads, want_g):
        assert _rel(g, w) < 2e-4
