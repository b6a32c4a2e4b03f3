"""The banded causal attention kernels of ``ops/flash.py`` (a sliding window,
or the whole prefix past ``STREAMING_MAX_SEQ``) under the Pallas
interpreter against a masked dense softmax, forward and all three
gradients; and which path the dispatch takes."""

import jax
import jax.numpy as jnp
import pytest

from tpu_trainer.ops import flash
from tpu_trainer.ops.attention import reference_attention
from tpu_trainer.ops.rope import apply_rotary_pos_emb, rope_tables

B, S, H, KVH, D = 1, 128, 4, 2, 16


@pytest.fixture(scope="module")
def operands():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KVH, D))
    v = jax.random.normal(ks[2], (B, S, KVH, D))
    do = jax.random.normal(ks[3], (B, S, H, D))
    return q, k, v, do, rope_tables(S, D, 500000.0)


def _dense(q, k, v, do, rope, window):
    """A window of the whole sequence is held to plain causal attention."""
    q, k = apply_rotary_pos_emb(q, k, *rope)
    return jnp.sum(reference_attention(
        q, k, v, window=None if window == S else window) * do)


def _band(q, k, v, do, rope, window, block):
    return jnp.sum(flash.band_attention(q, k, v, window=window, rope=rope,
                                        block=block, interpret=True) * do)


# Blocks of 32 rows: a window under a block, of one block, over one block
# (and not a multiple of it), and of the whole sequence (causal attention).
@pytest.mark.parametrize("window,block", [
    (20, 32), (32, 32), (50, 32), (S, 32)])
def test_the_band_is_a_masked_softmax(operands, window, block):
    q, k, v, do, rope = operands
    got, got_grads = jax.value_and_grad(_band, argnums=(0, 1, 2))(
        q, k, v, do, rope, window, block)
    want, want_grads = jax.value_and_grad(_dense, argnums=(0, 1, 2))(
        q, k, v, do, rope, window)
    assert float(jnp.abs(got - want)) < 1e-5 * float(jnp.abs(want))
    for g, w in zip(got_grads, want_grads):
        assert float(jnp.max(jnp.abs(g - w))) < 1e-5 * float(
            jnp.max(jnp.abs(w)))


def test_the_band_counts():
    # 8 blocks of 32, a window of 50 keys: a query block meets its own
    # block and the two before it (fewer at the start).
    assert flash.band_blocks(50, 32, 8) == 3
    assert flash.band_pairs(256, 50, 32) == 1 + 2 + 3 * 6
    assert flash.band_blocks(256, 32, 8) == 8
    assert flash.band_pairs(256, 256, 32) == 8 * 9 // 2
    # The cell's window layers: 1,024 keys in blocks of 512 at 8,192.
    assert flash.band_blocks(1024, 512, 16) == 3
    assert flash.band_pairs(8192, 1024, 512) == 1 + 2 + 3 * 14
    assert flash.band_block(8192, 128, interpret=False) == 512
    assert flash.band_block(8192, 64, interpret=False) is None


def test_the_dispatch(operands, monkeypatch):
    """No window at up to STREAMING_MAX_SEQ tokens: the streaming kernels,
    as before. A window, or a longer sequence: the banded ones. A window the
    kernels do not take runs in XLA."""
    q, k, v, _, rope = operands
    calls = []
    real = flash.band_attention
    monkeypatch.setattr(flash, "band_attention",
                        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    plain = jax.make_jaxpr(lambda q, k, v: flash.flash_attention(
        q, k, v, rope=rope, interpret=True))(q, k, v)
    assert not calls and "pallas_call" in str(plain)
    jax.make_jaxpr(lambda q, k, v: flash.flash_attention(
        q, k, v, rope=rope, interpret=True, window=40))(q, k, v)
    assert calls[-1]["window"] == 40
    monkeypatch.setattr(flash, "STREAMING_MAX_SEQ", S // 2)
    jax.make_jaxpr(lambda q, k, v: flash.flash_attention(
        q, k, v, rope=rope, interpret=True))(q, k, v)
    assert calls[-1]["window"] == S and len(calls) == 2
    dense = jax.make_jaxpr(lambda q, k, v: flash.flash_attention(
        q[:, :100], k[:, :100], v[:, :100], rope=(rope[0][:100],
                                                  rope[1][:100]),
        window=40))(q, k, v)
    assert len(calls) == 2 and "pallas_call" not in str(dense)
    with pytest.raises(NotImplementedError, match="sliding window"):
        flash.flash_attention(q, k, v, interpret=True, window=40,
                              segment_ids=jnp.ones((B, S), jnp.int32))
