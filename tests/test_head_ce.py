"""Pallas fused head+CE kernel (ops/head_ce.py) vs the XLA blockwise oracle.

The interpret-mode kernel runs on CPU; ``ops/loss._chunked_ce`` — itself
pinned against a materialized-logits jnp oracle — is the numerics reference
for loss AND gradients, including ragged edge tiles (token/vocab counts
that do not divide the 256/2048 block shapes) and the shard_map'd
batch-sharded path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_trainer.ops.head_ce import pallas_head_ce
from tpu_trainer.ops.loss import _chunk_len, _chunked_ce


def _case(seed, b, s, h, V, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    emb = jax.random.normal(k1, (V, h), jnp.float32)
    x = jax.random.normal(k2, (b, s, h)).astype(dtype)
    labels = jax.random.randint(k3, (b, s), 0, V)
    mask = (jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)
            < s - 1).astype(jnp.float32)
    return emb, x, labels, mask


def _both(emb, x, labels, mask, mesh=None):
    b, s, _ = x.shape

    def oracle(e_, x_):
        return _chunked_ce(e_, x_, labels, mask, _chunk_len(b, s, 0))

    def pall(e_, x_):
        return pallas_head_ce(e_, x_, labels, mask, mesh, True)

    # jit: the partial-manual shard_map path (batch-sharded meshes) only
    # traces under jit, which is how the model invokes it.
    ro = jax.jit(jax.value_and_grad(oracle, argnums=(0, 1)))(emb, x)
    rp = jax.jit(jax.value_and_grad(pall, argnums=(0, 1)))(emb, x)
    return ro, rp


class TestHeadCEKernel:
    @pytest.mark.parametrize(
        "b,s,h,V",
        [
            (2, 16, 32, 97),     # everything smaller than one tile
            (1, 300, 64, 300),   # ragged token AND vocab edges
            (3, 128, 32, 2050),  # vocab just past one tile
        ],
    )
    def test_matches_blockwise_oracle_f32(self, b, s, h, V):
        emb, x, labels, mask = _case(V, b, s, h, V, jnp.float32)
        (l_o, g_o), (l_p, g_p) = _both(emb, x, labels, mask)
        np.testing.assert_allclose(l_o, l_p, rtol=1e-6, atol=1e-6)
        for a, c in zip(g_o, g_p):
            np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-6)

    def test_matches_oracle_bf16(self):
        # bf16 saved logits round the backward probabilities by 2^-9 (the
        # flash-backward precedent); the loss itself stays f32-exact.
        emb, x, labels, mask = _case(7, 2, 64, 32, 521, jnp.bfloat16)
        (l_o, g_o), (l_p, g_p) = _both(emb, x, labels, mask)
        np.testing.assert_allclose(l_o, l_p, rtol=1e-5, atol=1e-5)
        for a, c in zip(g_o, g_p):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(c, np.float32),
                rtol=3e-2, atol=3e-2,
            )

    def test_batch_sharded_shard_map_path(self):
        # data x fsdp sharding of the batch dim: the kernel runs per shard
        # under partial-manual shard_map; loss and grads must match the
        # unsharded oracle.
        from tpu_trainer.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(data=-1, fsdp=2))
        assert mesh.shape["data"] * mesh.shape["fsdp"] == 8
        emb, x, labels, mask = _case(11, 8, 64, 32, 521, jnp.float32)
        (l_o, g_o), (l_p, g_p) = _both(emb, x, labels, mask, mesh=mesh)
        np.testing.assert_allclose(l_o, l_p, rtol=1e-6, atol=1e-6)
        for a, c in zip(g_o, g_p):
            np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-6)

    def test_sequence_sharded_shard_map_path(self):
        # SP (round 5, VERDICT r4 #2): the sequence dim shards over the
        # `sequence` axis; the caller's global shift/mask make each
        # shard's label slice correct without a boundary exchange.
        from tpu_trainer.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(data=1, sequence=8))
        emb, x, labels, mask = _case(17, 2, 64, 32, 521, jnp.float32)
        (l_o, g_o), (l_p, g_p) = _both(emb, x, labels, mask, mesh=mesh)
        np.testing.assert_allclose(l_o, l_p, rtol=1e-6, atol=1e-6)
        for a, c in zip(g_o, g_p):
            np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-6)

    def test_batch_and_sequence_sharded_path(self):
        # dp x sp jointly: the saved-logits residual is [V, b, s] exactly
        # so this composition declares true shard positions (a flat
        # [V, T] out-spec would permute the global token order).
        from tpu_trainer.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(data=2, fsdp=2, sequence=2))
        emb, x, labels, mask = _case(19, 4, 64, 32, 300, jnp.float32)
        (l_o, g_o), (l_p, g_p) = _both(emb, x, labels, mask, mesh=mesh)
        np.testing.assert_allclose(l_o, l_p, rtol=1e-6, atol=1e-6)
        for a, c in zip(g_o, g_p):
            np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-6)

    def test_expert_axis_does_not_block_kernel(self):
        # An expert axis shards only expert params; tokens are replicated
        # over it, so the kernel runs (round 5 — was a fallback).
        from tpu_trainer.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(data=2, expert=4))
        emb, x, labels, mask = _case(23, 4, 32, 32, 300, jnp.float32)
        (l_o, g_o), (l_p, g_p) = _both(emb, x, labels, mask, mesh=mesh)
        np.testing.assert_allclose(l_o, l_p, rtol=1e-6, atol=1e-6)
        for a, c in zip(g_o, g_p):
            np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-6)

    def test_tp_loss_matches_oracle(self):
        # Single-stage TP: the vocab-sharded XLA head under a tensor-axis
        # shard_map (ops/loss._tp_loss) — loss and grads vs the unsharded
        # blockwise oracle. The embedding enters h-sharded, as stored.
        from tpu_trainer.ops.loss import _tp_loss
        from tpu_trainer.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(data=1, tensor=8))
        emb, x, labels, mask = _case(29, 2, 64, 64, 521, jnp.float32)
        b, s, _ = x.shape

        def oracle(e_, x_):
            return _chunked_ce(e_, x_, labels, mask, _chunk_len(b, s, 0))

        def tp(e_, x_):
            return _tp_loss(e_, x_, labels, mask, mesh, 0)

        ro = jax.jit(jax.value_and_grad(oracle, argnums=(0, 1)))(emb, x)
        rt = jax.jit(jax.value_and_grad(tp, argnums=(0, 1)))(emb, x)
        np.testing.assert_allclose(ro[0], rt[0], rtol=1e-6, atol=1e-6)
        for a, c in zip(ro[1], rt[1]):
            np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-6)

    def test_dispatch_gate_off_cpu(self):
        # The model-level dispatch never routes to Pallas off-TPU.
        from tpu_trainer.ops.loss import _pallas_head_ok

        x = jnp.zeros((8, 1024, 64), jnp.bfloat16)
        assert not _pallas_head_ok(x, 0)

    def test_dispatch_gate_respects_memory_bounds(self):
        # An explicit chunk_size is a memory-bounding request, and
        # very large token counts grow the unchunked [V, T] residual
        # linearly — both must keep the chunked XLA path even where the
        # platform check would otherwise pass.
        from tpu_trainer.ops.loss import _pallas_head_ok

        x = jnp.zeros((8, 1024, 64), jnp.bfloat16)
        assert not _pallas_head_ok(x, 512)          # explicit chunking
        big = jnp.zeros((32, 1024, 64), jnp.bfloat16)
        assert not _pallas_head_ok(big, 0)          # 32k tokens > cap

    def test_all_masked_rows_no_nan(self):
        # Zero-weight rows (padding) must not poison the mean.
        emb, x, labels, _ = _case(13, 2, 32, 32, 97, jnp.float32)
        mask = jnp.zeros((2, 32), jnp.float32)
        loss = pallas_head_ce(emb, x, labels, mask, None, True)
        assert np.isfinite(float(loss)) and float(loss) == 0.0
