"""Sharded (tensor-parallel) paged decode tests (ISSUE 19).

One replica = one mesh: the serving engine shards attention heads and
the paged KV pool over a single-axis device mesh (``mesh_tensor``),
with block tables / lengths / scheduling state replicated. Exactness is
by construction — gathers are exact concats, the per-head attention
math is untouched, and the final output is a psum of disjoint head
slices — so the load-bearing assertions here are BIT-identity, not
tolerances:

- ``paged_attention_sharded`` under ``shard_map`` equals the unsharded
  reference exactly (and the interpreted Pallas kernel to float
  tolerance), in both KV layouts: kv-heads sharded (``kvh % tp == 0``)
  and GQA-replicated (``tp % kvh == 0``, each device slicing its one
  kv head);
- greedy streams from a sharded engine are token-identical to the
  single-device engine across plain / chunked-prefill / prefix-cache /
  int8-pool / speculative paths, and across preempt-resume;
- the jit memo key carries mesh identity (same arch on two different
  device sets must not share a compiled step);
- the shard-streaming launch layout (``utils/checkpoint.py``
  ``export_param_shards`` / ``load_param_shards``) round-trips every
  leaf byte-identically, including axes that do not divide the world;
- a REAL cross-process worker fleet built from 1/tp param shards
  (``WorkerSupervisor(param_shard_world=tp)``) serves bit-identically
  and survives a mid-run SIGKILL with stream identity preserved.

Runs on the suite's 8 fake CPU devices (conftest sets
``xla_force_host_platform_device_count=8`` before jax imports).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_trainer.models.config import GPTConfig
from tpu_trainer.models.gpt import GPT
from tpu_trainer.ops.flash import (
    paged_attention_reference, paged_attention_sharded)
from tpu_trainer.parallel.mesh import tp_mesh
from tpu_trainer.serving.engine import ServingEngine, poisson_trace
from tpu_trainer.utils.checkpoint import (
    _pick_export_axis, export_param_shards, load_param_shards)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs >= 4 (fake) devices")


# --- kernel-level: shard_map dispatch vs the unsharded oracle --------------

def _pool_case(*, b=2, h=8, d=8, kvh=8, bsz=4, nblk=10, mb=4, seed=0):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(b, h, d), jnp.float32)
    pool_k = jnp.asarray(rs.randn(nblk, bsz, kvh, d), jnp.float32)
    pool_v = jnp.asarray(rs.randn(nblk, bsz, kvh, d), jnp.float32)
    # Block 0 is the reserved null block; live rows index past it.
    tables = jnp.asarray(rs.randint(1, nblk, size=(b, mb)), jnp.int32)
    lengths = jnp.asarray(rs.randint(1, mb * bsz + 1, size=(b,)), jnp.int32)
    return q, pool_k, pool_v, tables, lengths


class TestShardedKernel:
    @pytest.mark.parametrize("tp,kvh", [(2, 8), (4, 8), (2, 2)])
    def test_sharded_reference_bitwise_kv_sharded(self, tp, kvh):
        # kvh % tp == 0: pools shard on the kv-heads axis. Per-head
        # attention is independent and the body runs the same ops on a
        # contiguous head slice, so the psum-of-disjoint-slices result
        # must be BIT-identical to the unsharded reference.
        args = _pool_case(kvh=kvh)
        want = paged_attention_reference(*args)
        mesh = tp_mesh(tp, None)
        got = paged_attention_sharded(*args, mesh=mesh, impl="reference")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    @pytest.mark.parametrize("tp,kvh", [(4, 2), (4, 1)])
    def test_sharded_reference_bitwise_gqa_replicated(self, tp, kvh):
        # tp % kvh == 0 (kv_heads < tp): pools replicate; each device
        # slices its one kv head (axis_index // (tp // kvh)).
        args = _pool_case(kvh=kvh)
        want = paged_attention_reference(*args)
        mesh = tp_mesh(tp, None)
        got = paged_attention_sharded(*args, mesh=mesh, impl="reference")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_sharded_kernel_matches_reference(self):
        # The interpreted Pallas kernel under shard_map against the
        # unsharded pure-jnp oracle — float tolerance, not bitwise (the
        # kernel's online softmax reduces in a different order).
        args = _pool_case(kvh=8)
        want = paged_attention_reference(*args)
        mesh = tp_mesh(2, None)
        got = paged_attention_sharded(
            *args, mesh=mesh, impl="kernel", interpret=True)
        np.testing.assert_allclose(
            np.asarray(want), np.asarray(got), rtol=1e-5, atol=1e-5)

    def test_rejects_indivisible_heads(self):
        args = _pool_case(h=6, kvh=6)
        with pytest.raises(ValueError):
            paged_attention_sharded(
                *args, mesh=tp_mesh(4, None), impl="reference")


# --- engine-level: sharded replica == single-device replica ----------------

def _make_model(kvh=None):
    cfg = GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=kvh, max_seq_len=64, dropout=0.0,
        attention_dropout=0.0, dtype="float32", param_dtype="float32")
    params = GPT(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return params, cfg


def _trace(n=6, temperature=0.0):
    return poisson_trace(
        n, vocab_size=64, rate=50.0, seed=1, temperature=temperature,
        prompt_len_range=(8, 24), max_new_range=(4, 8))


def _serve(params, cfg, tp, **kw):
    eng = ServingEngine(
        params, cfg, max_batch=4, block_size=8,
        mesh_tensor=(tp if tp > 1 else None), **kw)
    fin = eng.run(_trace(), time_mode="steps")
    return {r.rid: list(r.generated) for r in fin}, eng


class TestShardedEngine:
    def test_mha_greedy_bit_match(self):
        params, cfg = _make_model()
        base, _ = _serve(params, cfg, 1)
        got, eng = _serve(params, cfg, 2)
        assert got == base
        assert eng.scheduler.pool_shard_stats()["tp"] == 2

    @pytest.mark.slow
    def test_gqa_replicated_greedy_bit_match(self):
        # kv_heads=2 < tp=4: KV pools replicate, Q heads shard.
        params, cfg = _make_model(kvh=2)
        base, _ = _serve(params, cfg, 1)
        got, _ = _serve(params, cfg, 4)
        assert got == base

    @pytest.mark.slow
    def test_chunked_int8_prefix_bit_match(self):
        params, cfg = _make_model()
        kw = dict(prefill_chunk_tokens=8, kv_int8=True, prefix_cache=True)
        base, _ = _serve(params, cfg, 1, **kw)
        got, _ = _serve(params, cfg, 2, **kw)
        assert got == base

    @pytest.mark.slow
    def test_spec_ngram_bit_match(self):
        params, cfg = _make_model()
        base, _ = _serve(params, cfg, 1, spec="ngram")
        got, _ = _serve(params, cfg, 2, spec="ngram")
        assert got == base

    def test_preempt_resume_bit_match(self):
        # A pool tight enough to force preemption mid-decode: the
        # sharded engine must preempt AND resume to the same streams
        # (same total blocks -> same scheduling decisions).
        params, cfg = _make_model()
        base, be = _serve(params, cfg, 1, num_blocks=12)
        got, se = _serve(params, cfg, 2, device_block_budget=6)
        assert be.summary()["preemptions"] > 0
        assert se.summary()["preemptions"] == be.summary()["preemptions"]
        assert got == base

    def test_device_block_budget_is_per_shard(self):
        params, cfg = _make_model()
        _, eng = _serve(params, cfg, 2, device_block_budget=9)
        st = eng.scheduler.pool_shard_stats()
        assert st == {"tp": 2, "total_pool_blocks": 18,
                      "device_pool_blocks": 9}

    def test_mesh_identity_in_jit_memo_key(self):
        # Same arch on two different device sets: the frozen config —
        # the jit memo key — must differ, or replica B would reuse
        # replica A's compiled step against the wrong devices.
        params, cfg = _make_model()
        e1 = ServingEngine(params, cfg, max_batch=4, block_size=8,
                           mesh_devices=(0, 1))
        e2 = ServingEngine(params, cfg, max_batch=4, block_size=8,
                           mesh_devices=(2, 3))
        e0 = ServingEngine(params, cfg, max_batch=4, block_size=8)
        assert e1.config.paged_tp == e2.config.paged_tp == 2
        assert e1.config != e2.config
        assert e0.config.paged_tp == 1
        assert e0.config != e1.config


# --- shard-streaming launch layout (utils/checkpoint.py) -------------------

class TestParamShardLayout:
    def _tree(self):
        rs = np.random.RandomState(3)
        return {
            "wte": {"embedding": rs.randn(257, 24).astype(np.float32)},
            "h_0": {
                "w": rs.randn(24, 96).astype(np.float32),
                "b": rs.randn(96).astype(np.float16),
                "steps": np.asarray(7, np.int32),       # 0-d leaf
                "gate": rs.randn(3, 2).astype(np.float32),  # < world
            },
        }

    def test_round_trip_lossless(self, tmp_path):
        # 257 does not divide 4: near-equal chunks (65/64/64/64) must
        # stitch back byte-identically, dtypes and 0-d leaves included.
        tree = self._tree()
        path = str(tmp_path / "shards")
        export_param_shards(tree, path, world=4)
        back = load_param_shards(path)
        flat = [("wte/embedding", tree["wte"]["embedding"]),
                ("h_0/w", tree["h_0"]["w"]), ("h_0/b", tree["h_0"]["b"]),
                ("h_0/steps", tree["h_0"]["steps"]),
                ("h_0/gate", tree["h_0"]["gate"])]
        for key, want in flat:
            node = back
            for part in key.split("/"):
                node = node[part]
            assert node.dtype == want.dtype, key
            assert node.shape == want.shape, key
            np.testing.assert_array_equal(node, want)

    def test_shards_are_fractional(self, tmp_path):
        import os

        tree = self._tree()
        path = str(tmp_path / "shards")
        export_param_shards(tree, path, world=4)
        sizes = [os.path.getsize(
            os.path.join(path, "shards", f"host{h:05d}.npz"))
            for h in range(4)]
        full = sum(leaf.nbytes for sub in tree.values()
                   for leaf in sub.values())
        # Each host's file is ~1/4 of the tree (npz framing + the small
        # whole leaves parked on host 0 add slack).
        assert max(sizes) < 0.6 * full

    def test_pick_export_axis(self):
        assert _pick_export_axis((257, 24), 4) == 0
        assert _pick_export_axis((8, 96), 4) == 1
        assert _pick_export_axis((3, 2), 4) is None
        assert _pick_export_axis((), 4) is None


# --- real cross-process worker built from 1/tp shards ----------------------

class TestShardStreamWorker:
    @pytest.mark.slow
    def test_sharded_worker_fleet_survives_sigkill(self):
        from tpu_trainer.serving.frontend import ServingFrontend
        from tpu_trainer.serving.remote import WorkerSupervisor

        params, cfg = _make_model()
        base, _ = _serve(params, cfg, 1)

        sup = WorkerSupervisor(
            params, cfg,
            engine_kwargs=dict(max_batch=4, block_size=8, mesh_tensor=2),
            param_shard_world=2,
            device_sets=[[0, 1], [2, 3]])
        try:
            # Params crossed the wire as ~1/tp host shards.
            assert sup.param_shard_bytes is not None
            ratio = max(sup.param_shard_bytes) * 2 / sup.param_bytes_full
            assert 0.5 <= ratio <= 1.5, ratio

            fe = ServingFrontend(params, cfg, replicas=2,
                                 routing="affinity", time_mode="steps",
                                 replica_factory=sup)
            fin = fe.run(_trace())
            assert {r.rid: list(r.generated) for r in fin} == base

            # SIGKILL one sharded worker mid-run: failover must rebuild
            # its streams bit-identically on the survivor.
            fe2 = ServingFrontend(params, cfg, replicas=2,
                                  routing="affinity", time_mode="steps",
                                  replica_factory=sup)
            state = {"n": 0}
            orig_step = fe2.step

            def step():
                state["n"] += 1
                if state["n"] == 3:
                    sup.sigkill()
                return orig_step()

            fe2.step = step
            fin2 = fe2.run(_trace())
            s = fe2.summary()
            assert {r.rid: list(r.generated) for r in fin2} == base
            assert int(s["worker_deaths"]) == 1
            assert int(s["accepted"]) == int(s["finished"])
        finally:
            sup.close()
