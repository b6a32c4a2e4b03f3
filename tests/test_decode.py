"""KV-cache decode tests.

The reference's generation re-runs the full O(S^2) forward per token with no
KV cache (``/root/reference/src/eval/infer.py`` hot loop; SURVEY.md §3.5 and
C26). ``generate_kv`` is the cached fast path; these tests pin its
correctness against the uncached model forward and the windowed ``generate``.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_trainer.models.config import GPTConfig
from tpu_trainer.models.gpt import (
    GPT, generate, generate_bucketed, generate_kv, init_cache,
)
from tpu_trainer.utils import profiling

CFG = GPTConfig(
    vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
    max_seq_len=64, dropout=0.0, attention_dropout=0.0,
    use_flash_attention=False, dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    model = GPT(CFG)
    ids = jnp.zeros((1, 8), jnp.int32)
    return model.init(jax.random.PRNGKey(0), ids)["params"]


class TestCacheForward:
    def test_prefill_logits_match_uncached(self, params):
        """A decode=True prefill must produce the same logits as the plain
        causal forward — the cache changes the computation schedule, not the
        math."""
        model = GPT(CFG)
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
        expected, _ = model.apply({"params": params}, ids)
        cache = init_cache(CFG, 2)
        (got, _), _ = model.apply(
            {"params": params, "cache": cache}, ids, decode=True,
            mutable=["cache"],
        )
        np.testing.assert_allclose(got, expected, atol=1e-4, rtol=1e-4)

    def test_incremental_equals_prefill(self, params):
        """Feeding tokens one at a time through the cache must equal one
        prefill pass — position bookkeeping (RoPE offset, mask) is exact."""
        model = GPT(CFG)
        ids = jax.random.randint(jax.random.PRNGKey(2), (1, 10), 0, 128)
        cache = init_cache(CFG, 1)
        (want, _), _ = model.apply(
            {"params": params, "cache": cache}, ids, decode=True,
            mutable=["cache"],
        )
        cache = init_cache(CFG, 1)
        got_last = None
        for t in range(10):
            (logits, _), vars_out = model.apply(
                {"params": params, "cache": cache}, ids[:, t : t + 1],
                decode=True, mutable=["cache"],
            )
            cache = vars_out["cache"]
            got_last = logits[:, 0]
        np.testing.assert_allclose(got_last, want[:, -1], atol=1e-4, rtol=1e-4)


class TestGenerateKV:
    def test_greedy_matches_windowed_generate(self, params):
        """top_k=1 (greedy) removes sampling noise: the cached and uncached
        generators must produce identical tokens while the window never
        slides (total <= max_seq_len)."""
        ids = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, 128)
        rng = jax.random.PRNGKey(4)
        out_window = generate(
            params, rng, ids, config=CFG, max_new_tokens=12,
            temperature=1.0, top_k=1,
        )
        out_kv = generate_kv(
            params, rng, ids, config=CFG, max_new_tokens=12,
            temperature=1.0, top_k=1,
        )
        np.testing.assert_array_equal(out_window, out_kv)

    def test_prompt_preserved_and_tokens_in_vocab(self, params):
        ids = jax.random.randint(jax.random.PRNGKey(5), (1, 6), 0, 128)
        out = generate_kv(
            params, jax.random.PRNGKey(6), ids, config=CFG, max_new_tokens=10
        )
        assert out.shape == (1, 16)
        np.testing.assert_array_equal(out[:, :6], ids)
        assert int(out.max()) < 128 and int(out.min()) >= 0

    def test_overflow_rejected(self, params):
        ids = jnp.zeros((1, 60), jnp.int32)
        with pytest.raises(ValueError, match="cache size"):
            generate_kv(
                params, jax.random.PRNGKey(0), ids, config=CFG,
                max_new_tokens=10,
            )


class TestBucketedGenerate:
    """Bucketed compile shapes (VERDICT r1 weak #7): prompts of different
    lengths share one XLA compile, with unchanged sampling semantics."""

    def test_greedy_matches_exact_shapes(self, params):
        for plen in (5, 11, 16):
            ids = jax.random.randint(
                jax.random.PRNGKey(plen), (1, plen), 0, CFG.vocab_size
            )
            exact = generate(params, jax.random.PRNGKey(1), ids, config=CFG,
                             max_new_tokens=6, top_k=1)
            bucketed = generate_bucketed(
                params, jax.random.PRNGKey(1), ids, config=CFG,
                max_new_tokens=6, top_k=1,
            )
            assert bucketed.shape == (1, plen + 6)
            np.testing.assert_array_equal(np.asarray(bucketed),
                                          np.asarray(exact))

    def test_second_prompt_length_reuses_compile(self, params):
        # Three prompt lengths inside the same 16-bucket -> at most one new
        # compile of the underlying jitted generate (zero when another test
        # already populated the bucket), never one per length.
        # (Counted by the program's compile log, outermost entries only.)
        before = time.perf_counter()
        for plen in (5, 9, 13):
            ids = jnp.ones((1, plen), jnp.int32)
            generate_bucketed(params, jax.random.PRNGKey(0), ids, config=CFG,
                              max_new_tokens=4, top_k=1)
        compiled = [e for e in profiling.compile_log() if e.end > before
                    and e.kind == "compile" and e.fun_name == "jit(generate)"]
        assert len(compiled) <= 1

    def test_overflow_bucket_falls_back_to_exact(self, params):
        # true 60 + 4 == max_seq_len 64 fits, but the 64-bucket + 4 would
        # overflow: must fall back to exact shapes, same semantics.
        ids = jax.random.randint(jax.random.PRNGKey(3), (1, 60), 0,
                                 CFG.vocab_size)
        exact = generate(params, jax.random.PRNGKey(1), ids, config=CFG,
                         max_new_tokens=4, top_k=1)
        bucketed = generate_bucketed(params, jax.random.PRNGKey(1), ids,
                                     config=CFG, max_new_tokens=4, top_k=1)
        np.testing.assert_array_equal(np.asarray(bucketed), np.asarray(exact))


class TestRaggedDecode:
    """Mixed-length batched decode (VERDICT r2 item 8): one generate_kv
    call over a right-padded ragged batch must reproduce per-row single
    calls exactly (greedy — the batched rng stream differs, so top_k=1
    makes 'exactly' well-defined)."""

    def test_mixed_lengths_match_per_row_calls(self, params):
        rng = jax.random.PRNGKey(5)
        lens = [5, 11, 16]
        width = max(lens)
        rows = [
            jax.random.randint(jax.random.fold_in(rng, i), (L,), 0, 128)
            for i, L in enumerate(lens)
        ]
        padded = jnp.stack([
            jnp.pad(r, (0, width - r.shape[0])) for r in rows
        ]).astype(jnp.int32)
        new = 6
        batch_out = generate_kv(
            params, rng, padded, config=CFG, max_new_tokens=new,
            temperature=1.0, top_k=1,
            prompt_lens=jnp.asarray(lens, jnp.int32),
        )
        for i, (L, row) in enumerate(zip(lens, rows)):
            single = generate_kv(
                params, rng, row[None].astype(jnp.int32), config=CFG,
                max_new_tokens=new, temperature=1.0, top_k=1,
            )
            np.testing.assert_array_equal(
                np.asarray(batch_out)[i, :L + new],
                np.asarray(single)[0],
                err_msg=f"row {i} (len {L})",
            )
            # Beyond each row's real tokens: zero fill.
            assert np.all(np.asarray(batch_out)[i, L + new:] == 0)

    def test_uniform_lengths_unchanged_by_prompt_lens(self, params):
        rng = jax.random.PRNGKey(6)
        ids = jax.random.randint(rng, (2, 12), 0, 128)
        a = generate_kv(params, rng, ids, config=CFG, max_new_tokens=4,
                        top_k=1)
        b = generate_kv(params, rng, ids, config=CFG, max_new_tokens=4,
                        top_k=1,
                        prompt_lens=jnp.full((2,), 12, jnp.int32))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestShardedDecode:
    """Data- and tensor-sharded generate_kv on the fake 8-device mesh
    (VERDICT r2 item 8: the reference decodes batch-of-one on one device;
    here decode is just another consumer of the training shardings)."""

    def test_sharded_matches_unsharded(self, params):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tpu_trainer.parallel import sharding as shard_lib
        from tpu_trainer.parallel.mesh import (
            DATA_AXIS, MeshConfig, make_mesh,
        )

        rng = jax.random.PRNGKey(9)
        ids = jax.random.randint(rng, (4, 12), 0, 128)
        want = generate_kv(params, rng, ids, config=CFG, max_new_tokens=5,
                           top_k=1)

        mesh = make_mesh(MeshConfig(data=4, fsdp=1, tensor=2))
        sharded_params = jax.device_put(
            params,
            shard_lib.to_shardings(
                shard_lib.params_specs(params, mesh, "replicated"), mesh
            ),
        )
        ids_sharded = jax.device_put(
            ids, NamedSharding(mesh, P(DATA_AXIS, None))
        )
        got = jax.jit(
            lambda pp, rr, ii: generate_kv(pp, rr, ii, config=CFG,
                                           max_new_tokens=5, top_k=1)
        )(sharded_params, rng, ids_sharded)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
