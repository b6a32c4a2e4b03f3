"""The streaming (multi-block) flash forward with RoPE fused, and the fused
backward's multi-block body, in interpret mode.

Kept apart from ``tests/test_flash.py``, which ``conftest.py`` puts in the
slow lane wholesale: these cases run in tier-1, because what they pin —
each K block rotated once and read back from the rotated-K output — breaks
silently (a wrong ``kr3`` shows only in the backward).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_trainer.ops.attention import reference_attention
from tpu_trainer.ops.flash import flash_attention


def _steer_heads_per_program(monkeypatch, two):
    """Body of a fixture: the kernels run two heads a program (what a
    compiled d=64 kernel does, and interpret mode otherwise never) where
    ``two``; the memoised entry points are dropped before and after."""
    from tpu_trainer.ops import flash

    if two:
        monkeypatch.setattr(flash, "_heads_per_program",
                            lambda d, interpret: 2)
    flash._make_flash.cache_clear()
    yield
    flash._make_flash.cache_clear()


# (kv heads of 4, segment_ids, causal, two heads a program)
_STREAMING_CASES = [
    pytest.param(4, False, True, False, id="mha"),
    pytest.param(2, False, True, False, id="gqa"),
    pytest.param(4, True, True, False, id="mha-segments"),
    pytest.param(2, True, True, False, id="gqa-segments"),
    pytest.param(4, False, False, False, id="mha-noncausal"),
    pytest.param(4, False, True, True, id="paired-mha"),
    pytest.param(2, True, True, True, id="paired-gqa-segments"),
]


@pytest.mark.parametrize("kvh,segmented,causal,paired", _STREAMING_CASES)
class TestStreamingForward:
    """The multi-block forward at 4 x 4 blocks with RoPE fused (PR 27).

    Each K block is rotated once, by the first program that needs it, and
    later programs read it back from the rotated-K output; a block left
    unrotated (or rotated from the wrong rows) would leave the forward's
    output right wherever that block is recomputed and show only in the
    backward, which reads ``kr3``. So the residuals are compared block by
    block, beside the output and the gradients. ``paired`` steers the
    kernels to two heads a program (what a compiled d=64 kernel runs, and
    interpret mode otherwise never does): heads then share 128-lane slabs.
    """

    S, BLOCK, H = 512, 128, 4

    @pytest.fixture(autouse=True)
    def _heads_per_program(self, paired, monkeypatch):
        yield from _steer_heads_per_program(monkeypatch, two=paired)

    def _inputs(self, kvh, segmented, paired):
        from tpu_trainer.ops.rope import rope_tables

        d = 64 if paired else 32
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(40 + kvh), 3)
        q = jax.random.normal(kq, (1, self.S, self.H, d), jnp.float32)
        k = jax.random.normal(kk, (1, self.S, kvh, d), jnp.float32)
        v = jax.random.normal(kv, (1, self.S, kvh, d), jnp.float32)
        # The cut at 5s/8 = 320 falls inside a block: the boundary block is
        # mixed, off-diagonal blocks are skipped, the diagonal always runs.
        seg = (jnp.where(jnp.arange(self.S) < (5 * self.S) // 8, 1, 2)[None]
               .astype(jnp.int32) if segmented else None)
        return q, k, v, seg, rope_tables(self.S, d)

    @staticmethod
    def _oracle(q, k, v, seg, rope, causal):
        from tpu_trainer.ops.rope import apply_rotary_pos_emb

        qr, kr = apply_rotary_pos_emb(q, k, *rope)
        if causal:
            return reference_attention(qr, kr, v, segment_ids=seg)
        return jax.nn.dot_product_attention(qr, kr, v, is_causal=False)

    def test_output_and_grads_match_reference(self, kvh, segmented, causal,
                                              paired):
        q, k, v, seg, rope = self._inputs(kvh, segmented, paired)
        probe = jax.random.normal(jax.random.PRNGKey(41), q.shape)

        def flash_loss(q, k, v):
            out = flash_attention(
                q, k, v, interpret=True, rope=rope, causal=causal,
                segment_ids=seg, block_q=self.BLOCK, block_k=self.BLOCK)
            return jnp.sum(out * probe), out

        def oracle_loss(q, k, v):
            out = self._oracle(q, k, v, seg, rope, causal)
            return jnp.sum(out * probe), out

        got, out = jax.grad(flash_loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)
        want, expected = jax.grad(oracle_loss, argnums=(0, 1, 2),
                                  has_aux=True)(q, k, v)
        np.testing.assert_allclose(out, expected, atol=2e-5, rtol=2e-5)
        for g, e, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                g, e, atol=5e-5, rtol=5e-5, err_msg=f"d{name} mismatch")

    def test_residuals_are_rotated_in_every_block(self, kvh, segmented,
                                                  causal, paired):
        from tpu_trainer.ops.flash import _flash_forward
        from tpu_trainer.ops.rope import apply_rotary_pos_emb

        q, k, v, seg, rope = self._inputs(kvh, segmented, paired)
        if paired and kvh != self.H:
            # Two heads a program: the caller expands grouped K/V first.
            k, v = (jnp.repeat(x, self.H // kvh, axis=2) for x in (k, v))
        d, kernel_kvh = q.shape[-1], k.shape[2]
        fold = lambda x: x.reshape(1, self.S, -1)  # noqa: E731
        seg_f = (jax.lax.bitcast_convert_type(seg, jnp.float32)
                 if segmented else jnp.zeros((1, 1), jnp.float32))
        _, _, qr3, kr3 = _flash_forward(
            fold(q), fold(k), fold(v), jnp.zeros((1, 1), jnp.float32), seg_f,
            rope, num_heads=self.H, head_dim=d, num_kv_heads=kernel_kvh,
            causal=causal, block_q=self.BLOCK, block_k=self.BLOCK,
            interpret=True, dropout_rate=0.0, segmented=segmented)
        qr, kr = apply_rotary_pos_emb(q, k, *rope)
        for name, got, want in (("qr3", qr3, fold(qr) / np.sqrt(d)),
                                ("kr3", kr3, fold(kr))):
            for i in range(self.S // self.BLOCK):
                rows = slice(i * self.BLOCK, (i + 1) * self.BLOCK)
                np.testing.assert_allclose(
                    got[:, rows], want[:, rows], atol=1e-6, rtol=1e-6,
                    err_msg=f"{name}, block {i}")


# (kv heads of 4, causal, RoPE fused, head_dim; 64 = two heads a program)
_FUSED_BACKWARD_CASES = [
    pytest.param(4, True, True, 32, id="mha"),
    pytest.param(2, True, True, 32, id="gqa"),
    pytest.param(4, False, True, 32, id="mha-noncausal"),
    pytest.param(2, True, False, 32, id="gqa-norope"),
    pytest.param(4, True, True, 64, id="paired-mha"),
    pytest.param(2, True, True, 64, id="paired-gqa"),
    pytest.param(4, False, True, 64, id="paired-noncausal"),
    pytest.param(4, True, False, 64, id="paired-norope"),
    pytest.param(2, True, True, 128, id="wide-gqa"),
    pytest.param(4, False, False, 128, id="wide-noncausal-norope"),
]


@pytest.mark.parametrize("kvh,causal,fused_rope,d", _FUSED_BACKWARD_CASES)
class TestFusedBackward:
    """The fused backward's multi-block body at 4 x 4 blocks (PR 31).

    The body never slices a head out of the lanes (zeroed K / V lanes
    against the whole q / do slab) and, below 128 lanes a head, takes the
    three gradient dots in their d-row form with ``dk`` / ``dv`` / ``dq``
    accumulated transposed in scratch; from 128 lanes on the natural forms
    stay (the ``wide`` cases). ``backward="fused"`` addresses the kernel at
    a small ``s``; d = 64 steers it to two heads a program, what a compiled
    d=64 kernel runs (interpret mode otherwise never does). Gradients are
    compared with a dense reference block by block, so a failure names the
    block: a head that reads the other head's ``lse`` row, or a causal
    predicate off by one, shows in the diagonal blocks first.
    """

    S, BLOCK, H, RATE = 512, 128, 4, 0.25

    @pytest.fixture(autouse=True)
    def _heads_per_program(self, d, monkeypatch):
        yield from _steer_heads_per_program(monkeypatch, two=d == 64)

    def _inputs(self, kvh, fused_rope, d):
        from tpu_trainer.ops.rope import rope_tables

        kq, kk, kv, kp = jax.random.split(jax.random.PRNGKey(50 + kvh), 4)
        q = jax.random.normal(kq, (2, self.S, self.H, d), jnp.float32)
        k = jax.random.normal(kk, (2, self.S, kvh, d), jnp.float32)
        v = jax.random.normal(kv, (2, self.S, kvh, d), jnp.float32)
        probe = jax.random.normal(kp, q.shape, jnp.float32)
        return q, k, v, probe, (rope_tables(self.S, d) if fused_rope
                                else None)

    @staticmethod
    def _dense(q, k, v, rope, causal, keep=None, rate=0.0):
        """Plain attention -> (out, lse); ``keep`` [b, h, s, s] drops
        normalised weights the way the kernels do."""
        from tpu_trainer.ops.rope import apply_rotary_pos_emb

        if rope is not None:
            q, k = apply_rotary_pos_emb(q, k, *rope)
        s, group = q.shape[1], q.shape[2] // k.shape[2]
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        if causal:
            scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores,
                               -jnp.inf)
        lse = jax.nn.logsumexp(scores, axis=-1)
        p = jnp.exp(scores - lse[..., None])
        if keep is not None:
            p = jnp.where(keep, p, 0.0) / (1.0 - rate)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse

    def _kernel(self, rope, causal, **kw):
        return functools.partial(
            flash_attention, interpret=True, rope=rope, causal=causal,
            backward="fused", block_q=self.BLOCK, block_k=self.BLOCK, **kw)

    def _assert_blocks(self, got, want, tol):
        for g, e, name in zip(got, want, "qkv"):
            for i in range(self.S // self.BLOCK):
                rows = slice(i * self.BLOCK, (i + 1) * self.BLOCK)
                np.testing.assert_allclose(
                    g[:, rows], e[:, rows], atol=tol, rtol=tol,
                    err_msg=f"d{name}, block {i}")

    def test_grads_match_reference_block_by_block(self, kvh, causal,
                                                  fused_rope, d):
        q, k, v, probe, rope = self._inputs(kvh, fused_rope, d)
        kernel = self._kernel(rope, causal)
        got = jax.grad(lambda *x: jnp.sum(kernel(*x) * probe),
                       argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(
            lambda *x: jnp.sum(self._dense(*x, rope, causal)[0] * probe),
            argnums=(0, 1, 2))(q, k, v)
        self._assert_blocks(got, want, 5e-5)

    def test_lse_cotangent_folds_into_delta(self, kvh, causal, fused_rope,
                                            d):
        # What ring attention differentiates: (o, lse) with a cotangent on
        # both; the kernel sees ``dlse`` as a shift of its ``delta`` row.
        q, k, v, probe, rope = self._inputs(kvh, fused_rope, d)
        lse_probe = jax.random.normal(jax.random.PRNGKey(51),
                                      (2, self.H, self.S), jnp.float32)
        kernel = self._kernel(rope, causal, return_lse=True)

        def loss(fn, *x):
            out, lse = fn(*x)
            return jnp.sum(out * probe) + jnp.sum(lse * lse_probe)

        got = jax.grad(functools.partial(loss, kernel),
                       argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(
            functools.partial(
                loss, lambda *x: self._dense(*x, rope, causal)),
            argnums=(0, 1, 2))(q, k, v)
        self._assert_blocks(got, want, 5e-5)

    def test_dropout_masks_agree_forward_and_backward(self, kvh, causal,
                                                      fused_rope, d):
        # The dense reference drops the weights the kernel's own (interpret
        # mode) mask drops: tiles keyed by absolute position in
        # [block_q, block_k] orientation, one stream a (batch, head).
        from tpu_trainer.ops.flash import _keep_mask

        q, k, v, probe, rope = self._inputs(kvh, fused_rope, d)
        rng = jax.random.PRNGKey(52)
        seed = jax.random.bits(rng, dtype=jnp.uint32)
        keep = jnp.stack([jnp.stack([
            _keep_mask(seed, jnp.uint32(ib * self.H + ih), 0, 0, self.S,
                       self.S, self.S, self.RATE)
            for ih in range(self.H)]) for ib in range(2)])
        kernel = self._kernel(rope, causal, dropout_rate=self.RATE,
                              dropout_rng=rng)

        def kernel_loss(*x):
            out = kernel(*x)
            return jnp.sum(out * probe), out

        def dense_loss(*x):
            out = self._dense(*x, rope, causal, keep, self.RATE)[0]
            return jnp.sum(out * probe), out

        got, out = jax.grad(kernel_loss, argnums=(0, 1, 2),
                            has_aux=True)(q, k, v)
        want, expected = jax.grad(dense_loss, argnums=(0, 1, 2),
                                  has_aux=True)(q, k, v)
        np.testing.assert_allclose(out, expected, atol=5e-5, rtol=5e-5)
        self._assert_blocks(got, want, 1e-4)


def test_blocks_do_not_depend_on_the_environment(monkeypatch):
    """The block shape is a function of the call's shapes alone: a process
    started with a raised scoped-VMEM limit streams the same 512 x 512
    blocks at s=2048 (and takes the one 1024 block at s=1024)."""
    from tpu_trainer.ops import flash as flash_mod

    picked = []
    make = flash_mod._make_flash

    def spy(causal, block_q, block_k, *rest):
        picked.append((block_q, block_k))
        return make(causal, block_q, block_k, *rest)

    monkeypatch.setattr(flash_mod, "_make_flash", spy)
    q = jax.ShapeDtypeStruct((1, 2048, 2, 64), jnp.float32)
    short = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.float32)
    # (The flag in two pieces: the tree is grepped for its name to show
    # that no code reads it.)
    for args in ("", "--xla_tpu_scoped" "_vmem_limit_kib=32768"):
        monkeypatch.setenv("LIBTPU_INIT_ARGS", args)
        # A new callable each time: eval_shape remembers a trace.
        call = functools.partial(flash_attention, interpret=True)
        jax.eval_shape(call, q, q, q)
        jax.eval_shape(call, short, short, short)
    assert picked == [(512, 512), (1024, 1024)] * 2
