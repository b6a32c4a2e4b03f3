"""Pallas flash-attention kernel vs the jnp reference path (SURVEY.md C4).

The reference keeps both a fused and a manual attention path
(``/root/reference/src/models/gpt.py:199-234``); the manual path is the
numerics oracle. Same here: the Pallas kernel (run in interpreter mode on
CPU) must match ``reference_attention`` in forward values and gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_trainer.ops.attention import reference_attention
from tpu_trainer.ops.flash import flash_attention


def _rand_qkv(key, b, s, h, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, s, h, d)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


@pytest.mark.parametrize(
    "b,s,h,d,block",
    [
        (2, 256, 4, 64, 128),   # multi-block causal
        (1, 128, 2, 32, 64),    # two kv blocks per q block
        (2, 128, 3, 64, 128),   # single block (diagonal only)
        (1, 768, 2, 32, 512),   # 512 doesn't divide 768 -> auto-drop to 256
    ],
)
def test_forward_matches_reference(b, s, h, d, block):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), b, s, h, d)
    expected = reference_attention(q, k, v)
    got = flash_attention(q, k, v, block_q=block, block_k=block, interpret=True)
    np.testing.assert_allclose(got, expected, atol=2e-5, rtol=2e-5)


def test_backward_matches_reference():
    b, s, h, d = 2, 256, 2, 64
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), b, s, h, d)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(reference_attention(q, k, v)))

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
        return jnp.sum(jnp.sin(out))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for got, expected, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            got, expected, atol=5e-5, rtol=5e-5, err_msg=f"d{name} mismatch"
        )


def test_bf16_inputs_close_to_fp32_oracle():
    b, s, h, d = 1, 256, 2, 64
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b, s, h, d)
    expected = reference_attention(q, k, v)
    got = flash_attention(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
        interpret=True,
    )
    # bf16 inputs, f32 accumulation: ~1e-2 is the expected quantization floor.
    np.testing.assert_allclose(
        got.astype(jnp.float32), expected, atol=3e-2, rtol=3e-2
    )


def test_non_divisible_seq_falls_back(monkeypatch):
    # seq=100 doesn't tile into 128-blocks; wrapper must still give correct
    # causal attention (via the XLA fallback).
    b, s, h, d = 1, 100, 2, 32
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), b, s, h, d)
    expected = reference_attention(q, k, v)
    got = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(got, expected, atol=2e-5, rtol=2e-5)


class TestKernelDropout:
    """In-kernel attention dropout (counter-based mask, ops/flash.py)."""

    def _run(self, rate, rng, s=256):
        q, k, v = _rand_qkv(jax.random.PRNGKey(10), 1, s, 2, 32)
        return flash_attention(
            q, k, v, interpret=True, dropout_rate=rate, dropout_rng=rng,
        )

    def test_zero_rate_matches_no_dropout(self):
        q, k, v = _rand_qkv(jax.random.PRNGKey(11), 1, 128, 2, 32)
        base = flash_attention(q, k, v, interpret=True)
        zero = flash_attention(
            q, k, v, interpret=True, dropout_rate=0.0,
            dropout_rng=jax.random.PRNGKey(0),
        )
        np.testing.assert_array_equal(base, zero)

    def test_deterministic_per_seed_and_varies_across_seeds(self):
        r1 = self._run(0.3, jax.random.PRNGKey(1))
        r1b = self._run(0.3, jax.random.PRNGKey(1))
        r2 = self._run(0.3, jax.random.PRNGKey(2))
        np.testing.assert_array_equal(r1, r1b)
        assert not np.allclose(r1, r2)

    def test_output_is_unbiased_ish(self):
        # Dropout keeps the softmax normalizer undropped and rescales kept
        # weights by 1/(1-r): E[out] == no-dropout out. With many seeds the
        # mean converges.
        q, k, v = _rand_qkv(jax.random.PRNGKey(12), 1, 128, 1, 32)
        base = flash_attention(q, k, v, interpret=True)
        acc = np.zeros_like(np.asarray(base))
        n = 24
        for i in range(n):
            acc += np.asarray(
                flash_attention(
                    q, k, v, interpret=True, dropout_rate=0.4,
                    dropout_rng=jax.random.PRNGKey(100 + i),
                )
            )
        # Early rows attend over very few keys, so per-seed variance is huge
        # there; compare where >= 32 keys average it down.
        np.testing.assert_allclose(
            (acc / n)[:, 32:], np.asarray(base)[:, 32:], atol=0.25
        )

    def test_gradients_consistent_with_fixed_mask(self):
        # With a fixed seed the dropped function is deterministic; its
        # custom-VJP gradient must match finite differences (proving the
        # backward kernels regenerate the same mask as the forward).
        q, k, v = _rand_qkv(jax.random.PRNGKey(13), 1, 128, 1, 16)
        rng = jax.random.PRNGKey(7)
        probe = jax.random.normal(jax.random.PRNGKey(14), q.shape)

        def f(qq):
            out = flash_attention(
                qq, k, v, interpret=True, dropout_rate=0.25, dropout_rng=rng
            )
            return jnp.sum(out * probe)  # scalar, mask fixed by rng

        g = jax.grad(f)(q)
        eps = 1e-3
        direction = jax.random.normal(jax.random.PRNGKey(15), q.shape)
        fd = (f(q + eps * direction) - f(q - eps * direction)) / (2 * eps)
        analytic = jnp.sum(g * direction)
        np.testing.assert_allclose(fd, analytic, rtol=2e-2, atol=2e-2)

    def test_mask_spatial_independence(self):
        # Positions along a score row are consecutive integers, so the
        # pre-mix hash values form a Weyl progression; the two mix rounds
        # must break that lattice. Assert near-zero autocorrelation of the
        # keep mask at small lags along rows and columns (lag-correlated
        # masks would bias which attention weights co-survive).
        from tpu_trainer.ops.flash import _keep_mask

        rate = 0.5
        bq = bk = 512
        keep = np.asarray(
            _keep_mask(jnp.uint32(0xDEADBEEF), jnp.uint32(3), 0, 0,
                       bq, bk, 1024, rate)
        ).astype(np.float64)
        p = keep.mean()
        assert abs(p - (1 - rate)) < 0.01
        centered = keep - p
        var = (centered ** 2).mean()
        for lag in (1, 2, 7):
            row_corr = (centered[:, :-lag] * centered[:, lag:]).mean() / var
            col_corr = (centered[:-lag, :] * centered[lag:, :]).mean() / var
            # ~N(0, 1/sqrt(n)) for independent bits, n = 512*511 ≈ 2.6e5
            # -> sd ≈ 0.002; 0.01 is 5 sigma.
            assert abs(row_corr) < 0.01, (lag, row_corr)
            assert abs(col_corr) < 0.01, (lag, col_corr)

    def test_requires_rng(self):
        q, k, v = _rand_qkv(jax.random.PRNGKey(16), 1, 128, 1, 16)
        with pytest.raises(ValueError, match="dropout_rng"):
            flash_attention(q, k, v, interpret=True, dropout_rate=0.1)

    def test_lse_gradient_with_dropout(self):
        # The return_lse backward with dropout active: the lse cotangent
        # folds into the delta row while dp/p_drop are masked, and the dlse
        # term must multiply the *undropped* p (ds = p*(dp_drop - delta +
        # dlse)). Finite differences through a loss touching both outputs
        # guard that coupling.
        q, k, v = _rand_qkv(jax.random.PRNGKey(17), 1, 128, 1, 16)
        rng = jax.random.PRNGKey(9)
        probe_o = jax.random.normal(jax.random.PRNGKey(18), q.shape)
        probe_l = jax.random.normal(jax.random.PRNGKey(19), (1, 1, 128))

        def f(qq, kk):
            o, lse = flash_attention(
                qq, kk, v, interpret=True, dropout_rate=0.25,
                dropout_rng=rng, return_lse=True,
            )
            return jnp.sum(o * probe_o) + jnp.sum(jnp.sin(lse) * probe_l)

        gq, gk = jax.grad(f, argnums=(0, 1))(q, k)
        eps = 1e-3
        for arg, g, name in ((q, gq, "dq"), (k, gk, "dk")):
            direction = jax.random.normal(jax.random.PRNGKey(20), arg.shape)
            if name == "dq":
                fd = (f(q + eps * direction, k) - f(q - eps * direction, k)) / (2 * eps)
            else:
                fd = (f(q, k + eps * direction) - f(q, k - eps * direction)) / (2 * eps)
            analytic = jnp.sum(g * direction)
            np.testing.assert_allclose(
                fd, analytic, rtol=2e-2, atol=2e-2, err_msg=name
            )


class TestFusedRope:
    """RoPE fused into the kernel vs external rotation + reference path."""

    def _qkv_rope(self, b=2, s=256, h=2, d=32):
        from tpu_trainer.ops.rope import apply_rotary_pos_emb, rope_tables

        q, k, v = _rand_qkv(jax.random.PRNGKey(20), b, s, h, d)
        cos, sin = rope_tables(s, d)
        return q, k, v, cos, sin, apply_rotary_pos_emb

    def test_forward_matches_external_rope(self):
        # Multi-block grid (s=512, 128-blocks): exercises the per-block
        # cos/sin offsets, not just offset-zero.
        q, k, v, cos, sin, rot = self._qkv_rope(s=512)
        qr, kr = rot(q, k, cos, sin)
        expected = reference_attention(qr, kr, v)
        got = flash_attention(
            q, k, v, interpret=True, rope=(cos, sin),
            block_q=128, block_k=128,
        )
        np.testing.assert_allclose(got, expected, atol=2e-5, rtol=2e-5)

    def test_gradients_match_external_rope(self):
        # Multi-block grid: rope-path dq accumulation across kv grid steps.
        q, k, v, cos, sin, rot = self._qkv_rope(b=1, s=512, h=1, d=32)

        def loss_fused(q, k, v):
            out = flash_attention(
                q, k, v, interpret=True, rope=(cos, sin),
                block_q=128, block_k=128,
            )
            return jnp.sum(jnp.sin(out))

        def loss_ext(q, k, v):
            qr, kr = rot(q, k, cos, sin)
            return jnp.sum(jnp.sin(reference_attention(qr, kr, v)))

        g_fused = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
        g_ext = jax.grad(loss_ext, argnums=(0, 1, 2))(q, k, v)
        for got, expected, name in zip(g_fused, g_ext, "qkv"):
            np.testing.assert_allclose(
                got, expected, atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
            )

    def test_fallback_seq_applies_rope(self):
        # seq=100 takes the XLA fallback; rope must still be applied.
        q, k, v, cos, sin, rot = self._qkv_rope(b=1, s=100, h=1, d=32)
        qr, kr = rot(q, k, cos, sin)
        expected = reference_attention(qr, kr, v)
        got = flash_attention(q, k, v, interpret=True, rope=(cos, sin))
        np.testing.assert_allclose(got, expected, atol=2e-5, rtol=2e-5)


class TestSplitBackwardParity:
    """Two-kernel (split) backward vs the fused single-pass kernel.

    The split path (dkv kernel gridded over key blocks + dq kernel gridded
    over query blocks, s-independent VMEM — ops/flash.py) recomputes the
    score/probability chain per kernel from the same residuals, lse/delta
    rows, and absolute-coordinate dropout counters, so its dq/dk/dv must
    agree with the fused kernel at f32-accumulation tolerances. With
    dropout on, any mask-regeneration divergence between the two kernels
    would produce O(1) gradient errors, so the tight tolerance doubles as
    the bit-exact mask check.
    """

    def _grads(self, backward, s, h=2, kvh=None, d=32, dropout=0.0,
               rope=False, block=512):
        kvh = h if kvh is None else kvh
        key = jax.random.PRNGKey(42)
        kq, kk, kv, kd = jax.random.split(key, 4)
        q = jax.random.normal(kq, (1, s, h, d), jnp.float32)
        k = jax.random.normal(kk, (1, s, kvh, d), jnp.float32)
        v = jax.random.normal(kv, (1, s, kvh, d), jnp.float32)
        rope_t = None
        if rope:
            from tpu_trainer.ops.rope import rope_tables

            rope_t = rope_tables(s, d)
        probe = jax.random.normal(jax.random.PRNGKey(43), q.shape)

        def loss(q, k, v):
            out = flash_attention(
                q, k, v, interpret=True, block_q=block, block_k=block,
                dropout_rate=dropout,
                dropout_rng=kd if dropout > 0.0 else None,
                rope=rope_t, backward=backward,
            )
            return jnp.sum(out * probe)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def _assert_parity(self, s, **kw):
        g_fused = self._grads("fused", s, **kw)
        g_split = self._grads("split", s, **kw)
        for got, expected, name in zip(g_split, g_fused, "qkv"):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(expected), atol=1e-6, rtol=1e-6,
                err_msg=f"d{name} split-vs-fused (s={s}, {kw})",
            )

    @pytest.mark.parametrize("s", [1024, 2048, 4096])
    def test_parity_across_seq(self, s):
        self._assert_parity(s)

    @pytest.mark.parametrize("s", [1024, 2048, 4096])
    def test_parity_dropout_on(self, s):
        # Dropout masks regenerate from absolute (q, k) coordinates in
        # both split kernels; a single flipped keep bit is an O(1) error.
        self._assert_parity(s, dropout=0.2)

    def test_parity_gqa(self):
        # hp == 1 interpret path: K/V via the ip // group index map in
        # both split kernels, f32 per-query-head dk/dv partials group-
        # summed by the caller.
        self._assert_parity(1024, h=4, kvh=2, dropout=0.1)

    def test_parity_fused_rope(self):
        # Rotated residuals: the dkv kernel un-rotates dk with K-row
        # cos/sin blocks, the dq kernel un-rotates dq with Q-row blocks.
        self._assert_parity(1024, rope=True)

    def test_parity_asymmetric_blocks(self):
        g_fused = self._grads("fused", 2048, block=512)
        # Split path at a different (still 512-divisible) block shape:
        # dropout-free here, so block shape must not change the math.
        key = jax.random.PRNGKey(42)
        kq, kk, kv, _ = jax.random.split(key, 4)
        q = jax.random.normal(kq, (1, 2048, 2, 32), jnp.float32)
        k = jax.random.normal(kk, (1, 2048, 2, 32), jnp.float32)
        v = jax.random.normal(kv, (1, 2048, 2, 32), jnp.float32)
        probe = jax.random.normal(jax.random.PRNGKey(43), q.shape)

        def loss(q, k, v):
            out = flash_attention(q, k, v, interpret=True, block_q=1024,
                                  block_k=512, backward="split")
            return jnp.sum(out * probe)

        g_split = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for got, expected, name in zip(g_split, g_fused, "qkv"):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(expected), atol=1e-5, rtol=1e-5,
                err_msg=f"d{name} block-shape invariance",
            )

    def test_auto_dispatch_defaults(self):
        # s <= 2048 must keep the fused kernel BIT-identically (the
        # headline-row no-regression contract); past the threshold auto
        # selects split. backward=None vs the forced path must therefore
        # be exact array_equal, not just allclose.
        for s, expect in ((1024, "fused"), (4096, "split")):
            g_auto = self._grads(None, s)
            g_forced = self._grads(expect, s)
            for got, expected, name in zip(g_auto, g_forced, "qkv"):
                np.testing.assert_array_equal(
                    np.asarray(got), np.asarray(expected),
                    err_msg=f"d{name} auto != {expect} at s={s}",
                )

    def test_bad_backward_rejected(self):
        q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 128, 1, 16)
        with pytest.raises(ValueError, match="backward"):
            flash_attention(q, k, v, interpret=True, backward="bogus")


def test_causal_masking_is_exact():
    # Token t's output must not change when future tokens change.
    b, s, h, d = 1, 256, 1, 64
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), b, s, h, d)
    out1 = flash_attention(q, k, v, interpret=True)
    k2 = k.at[:, s // 2 :].set(99.0)
    v2 = v.at[:, s // 2 :].set(-99.0)
    out2 = flash_attention(q, k2, v2, interpret=True)
    np.testing.assert_allclose(
        out1[:, : s // 2], out2[:, : s // 2], atol=1e-6, rtol=1e-6
    )


class TestSegmentParity:
    """Packed rows vs per-document dense attention (sequence packing).

    A packed row concatenates documents with a ``segment_ids`` channel; the
    kernel's block skipping must make each document's attention identical to
    running that document alone. The oracle is therefore NOT the segmented
    reference (which shares the masking convention) but literal per-document
    slices through the plain dense path. The cut at ``5s/8`` is deliberately
    misaligned with every block size the kernel picks, so the boundary block
    is mixed — neither pure-skip nor pure-run.
    """

    def _packed(self, s, h=2, kvh=None, d=32, seed=30):
        kvh = h if kvh is None else kvh
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(kq, (1, s, h, d), jnp.float32)
        k = jax.random.normal(kk, (1, s, kvh, d), jnp.float32)
        v = jax.random.normal(kv, (1, s, kvh, d), jnp.float32)
        cut = (5 * s) // 8
        seg = jnp.where(jnp.arange(s) < cut, 1, 2)[None, :].astype(jnp.int32)
        return q, k, v, seg, cut

    @staticmethod
    def _per_document(q, k, v, cut):
        first = reference_attention(q[:, :cut], k[:, :cut], v[:, :cut])
        second = reference_attention(q[:, cut:], k[:, cut:], v[:, cut:])
        return jnp.concatenate([first, second], axis=1)

    @pytest.mark.parametrize("s", [1024, 2048, 4096])
    def test_forward_packed_vs_per_document(self, s):
        q, k, v, seg, cut = self._packed(s)
        expected = self._per_document(q, k, v, cut)
        got = flash_attention(q, k, v, interpret=True, segment_ids=seg)
        np.testing.assert_allclose(got, expected, atol=2e-5, rtol=2e-5)
        # The dense segmented reference must agree with the same oracle
        # (it is the CPU-dispatch fallback for segmented batches).
        dense = reference_attention(q, k, v, segment_ids=seg)
        np.testing.assert_allclose(dense, expected, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("s", [1024, 2048, 4096])
    def test_grads_packed_vs_per_document(self, s):
        # Segmented backward always takes the split two-kernel path, so this
        # exercises both the dkv and dq kernels' segment predicates.
        q, k, v, seg, cut = self._packed(s)
        probe = jax.random.normal(jax.random.PRNGKey(31), q.shape)

        def flash_loss(qq, kk, vv):
            out = flash_attention(
                qq, kk, vv, interpret=True, segment_ids=seg
            )
            return jnp.sum(out * probe)

        def dense_loss(qq, kk, vv):
            return jnp.sum(self._per_document(qq, kk, vv, cut) * probe)

        got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        expected = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for g, e, name in zip(got, expected, "qkv"):
            np.testing.assert_allclose(
                g, e, atol=5e-5, rtol=5e-5, err_msg=f"d{name} mismatch"
            )

    def test_gqa_packed_vs_per_document(self):
        # Grouped-query heads share kv across the segment mask; dk/dv
        # accumulate over the query-head group.
        q, k, v, seg, cut = self._packed(1024, h=4, kvh=2)
        expected = self._per_document(q, k, v, cut)
        got = flash_attention(q, k, v, interpret=True, segment_ids=seg)
        np.testing.assert_allclose(got, expected, atol=2e-5, rtol=2e-5)

        probe = jax.random.normal(jax.random.PRNGKey(32), q.shape)

        def flash_loss(qq, kk, vv):
            out = flash_attention(
                qq, kk, vv, interpret=True, segment_ids=seg
            )
            return jnp.sum(out * probe)

        def dense_loss(qq, kk, vv):
            return jnp.sum(self._per_document(qq, kk, vv, cut) * probe)

        got_g = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        exp_g = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for g, e, name in zip(got_g, exp_g, "qkv"):
            np.testing.assert_allclose(
                g, e, atol=5e-5, rtol=5e-5, err_msg=f"d{name} mismatch"
            )

    def test_uniform_segments_match_unsegmented(self):
        # All-ones segment ids are a no-op mask; outputs must match the
        # unsegmented kernel to float tolerance (the segmented path uses a
        # finite -1e30 mask constant where the causal-only path may not,
        # hence allclose rather than bit-equality).
        s = 1024
        q, k, v, _, _ = self._packed(s)
        seg = jnp.ones((1, s), jnp.int32)
        got = flash_attention(q, k, v, interpret=True, segment_ids=seg)
        plain = flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(got, plain, atol=1e-6, rtol=1e-6)

    def test_padding_isolated(self):
        # Segment 0 is padding: outputs over the real prefix must be
        # unaffected by garbage values parked in the padded tail.
        s = 1024
        q, k, v, _, _ = self._packed(s)
        cut = (3 * s) // 4 + 5  # block-misaligned non-pad prefix
        seg = jnp.where(jnp.arange(s) < cut, 1, 0)[None, :].astype(jnp.int32)
        out = flash_attention(q, k, v, interpret=True, segment_ids=seg)
        k2 = k.at[:, cut:].set(99.0)
        v2 = v.at[:, cut:].set(-99.0)
        out2 = flash_attention(q, k2, v2, interpret=True, segment_ids=seg)
        np.testing.assert_allclose(
            out[:, :cut], out2[:, :cut], atol=1e-6, rtol=1e-6
        )
        expected = reference_attention(q[:, :cut], k[:, :cut], v[:, :cut])
        np.testing.assert_allclose(
            out[:, :cut], expected, atol=2e-5, rtol=2e-5
        )

    def test_dropout_grads_consistent_with_fixed_mask(self):
        # Segments + dropout: with a fixed seed the function is
        # deterministic, and the custom-VJP gradient matching finite
        # differences proves all three kernels (forward, dkv, dq)
        # regenerate the bit-identical keep mask under segment skipping —
        # a mask disagreement at any surviving position would be an O(1)
        # gradient error, far outside the FD tolerance.
        s = 512
        q, k, v, seg, _ = self._packed(s, h=1, d=16, seed=33)
        rng = jax.random.PRNGKey(7)
        probe = jax.random.normal(jax.random.PRNGKey(34), q.shape)

        def f(qq):
            out = flash_attention(
                qq, k, v, interpret=True, dropout_rate=0.25,
                dropout_rng=rng, segment_ids=seg,
            )
            return jnp.sum(out * probe)

        g = jax.grad(f)(q)
        eps = 1e-3
        direction = jax.random.normal(jax.random.PRNGKey(35), q.shape)
        fd = (f(q + eps * direction) - f(q - eps * direction)) / (2 * eps)
        analytic = jnp.sum(g * direction)
        np.testing.assert_allclose(fd, analytic, rtol=2e-2, atol=2e-2)

    def test_dropout_masks_positions_not_segments(self):
        # The keep mask hashes absolute (q, k) coordinates, so segment ids
        # must not perturb it: uniform-segment dropout output equals
        # unsegmented dropout output.
        s = 512
        q, k, v, _, _ = self._packed(s, h=1, d=16, seed=36)
        seg = jnp.ones((1, s), jnp.int32)
        rng = jax.random.PRNGKey(9)
        got = flash_attention(
            q, k, v, interpret=True, dropout_rate=0.25, dropout_rng=rng,
            segment_ids=seg,
        )
        plain = flash_attention(
            q, k, v, interpret=True, dropout_rate=0.25, dropout_rng=rng
        )
        np.testing.assert_allclose(got, plain, atol=1e-6, rtol=1e-6)
