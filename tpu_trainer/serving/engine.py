"""Serving engine: jitted prefill/decode steps over the paged model path.

The engine owns a fixed slot batch (``max_batch`` rows). Every iteration
the scheduler picks ONE of:

- **prefill** — requests mid-prefill feed ``seq[cursor:cursor+chunk]``
  (width bucketed to a power of two so nearby shapes share a compile)
  at their global positional offset; with ``prefill_chunk_tokens`` set,
  a long prompt is split across iterations that alternate with decode
  steps, so no decode iteration waits more than one chunk. Chunks past
  offset 0 also attend the pooled history written by earlier chunks (or
  a shared prefix) through a static ``hist_blocks``-wide table gather.
  Feeding generated tokens too on re-admission is what makes
  recompute-preemption exact: a resumed request is indistinguishable
  from one that was never interrupted — same cache contents, same next
  sampling step.
- **decode** — every running request that finished prefill advances one
  token in a single ``[slots, 1]`` forward.

Both steps are one jitted dispatch including sampling (per-request
temperature / top-k / seed, ``serving/sampling.py``). The only
persistent device state is the KV block pools; block tables, lengths
and chunk offsets are re-broadcast from the scheduler's host mirrors
into the cache pytree *inside* the jit, so scheduling never syncs the
device. Idle and non-stepped rows have zeroed table rows and length 0:
their writes land in reserved block 0 and their sampled tokens are
ignored host-side (a mid-prefill chunk's sampled token is likewise
discarded — only the final chunk's draw, made at the same (seed, token
index) as an unchunked pass, is consumed), which keeps every step
unpredicated over the full slot batch.

``prefix_cache=True`` turns on copy-on-write prefix sharing in the
block pool (serving/paged_cache.py): after each chunk the engine
publishes newly completed full PROMPT blocks under their chained
content digest, and admission starts later identical prompts past the
shared blocks entirely.

``python -m tpu_trainer.serving.engine`` replays a seeded open-loop
Poisson arrival trace against a synthetic checkpoint and prints the
latency/throughput summary (see also benchmarks/serve_bench.py).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_trainer.models.config import GPTConfig
from tpu_trainer.models.gpt import GPT, init_paged_cache
from tpu_trainer.obs.metrics import NULL_REGISTRY
from tpu_trainer.serving.kv_store import KVBlockStore, MigrationPricer
from tpu_trainer.serving.paged_cache import PagedKVCache
from tpu_trainer.serving.sampling import sample_tokens
from tpu_trainer.serving.scheduler import Request, SamplingParams, Scheduler
from tpu_trainer.serving.spec import (
    DraftModelProposer,
    NGramProposer,
    SpecDecoder,
    _verify_step,
    draft_from_target,
)
from tpu_trainer.serving.tracing import ServingLedger, SpanTracer


def _bucket_pow2(n: int, lo: int = 8) -> int:
    w = lo
    while w < n:
        w *= 2
    return w


# Device-cache leaves that hold per-block K/V payload (int8 pools add the
# scale planes). Everything else in the cache pytree is scheduling state
# re-broadcast from host mirrors each step.
_POOL_LEAF_KEYS = ("pool_k", "pool_v", "scale_k", "scale_v")


class ServingEngine:
    """Continuous-batching engine over one model + parameter set."""

    def __init__(
        self,
        params,
        config: GPTConfig,
        *,
        max_batch: int = 8,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        max_blocks_per_request: Optional[int] = None,
        kv_int8: bool = False,
        attention: str = "auto",
        eos_id: Optional[int] = None,
        watermark_blocks: int = 0,
        prefill_chunk_tokens: Optional[int] = None,
        prefix_cache: bool = False,
        spec: str = "off",
        spec_k: int = 4,
        spec_adaptive: bool = True,
        spec_ngram_max: int = 3,
        draft_params=None,
        draft_config: Optional[GPTConfig] = None,
        spec_proposer=None,
        clock=time.perf_counter,
        trace: bool = True,
        ts_interval: int = 32,
        metric_logger=None,
        registry=None,
        mesh_tensor: Optional[int] = None,
        mesh_devices: Optional[Sequence[int]] = None,
        device_block_budget: Optional[int] = None,
        kv_store: Optional[KVBlockStore] = None,
        kv_store_bytes: Optional[int] = None,
        kv_store_dir: Optional[str] = None,
        kv_link_gbps: float = 16.0,
        role: Optional[str] = None,
    ):
        if config.latent_attention:
            raise NotImplementedError(
                "the serving engine cannot run latent attention: its cache "
                "managers hold per-head K/V blocks, not [tokens, "
                "kv_lora_rank + rope] latents, and the absorbed decode "
                "order is not built")
        if not config.uniform_layers:
            raise NotImplementedError(
                "the serving engine cannot run a model whose layers differ "
                "(GPTConfig.layer_types / num_dense_layers): a conv layer "
                "needs the two previous positions of its gated input as "
                "state, and the cache managers hold K/V blocks only")
        if spec not in ("off", "ngram", "draft"):
            raise ValueError(f"spec={spec!r} (off | ngram | draft)")
        if max_blocks_per_request is None:
            max_blocks_per_request = -(-config.max_seq_len // block_size)
        # Tensor parallel: one replica = one mesh (serving/sharding.py).
        # ``mesh_tensor`` is the mesh size; ``mesh_devices`` optionally
        # pins the exact device ids (a fleet of disjoint meshes on one
        # host); ``device_block_budget`` sizes the pool per DEVICE — with
        # kv-head-sharded pools each device holds 1/tp of every block, so
        # the replica affords budget * tp total blocks.
        tp = int(mesh_tensor) if mesh_tensor else 1
        if mesh_devices is not None:
            mesh_devices = tuple(int(d) for d in mesh_devices)
            if tp == 1 and len(mesh_devices) > 1:
                tp = len(mesh_devices)
        self.mesh_tensor = tp
        if device_block_budget is not None and num_blocks is None:
            from tpu_trainer.serving import sharding as tp_lib

            num_blocks = device_block_budget * tp_lib.shard_factor(
                config.kv_heads, tp)
        if num_blocks is None:
            # Enough for every slot to run at full context, + null block.
            num_blocks = max_batch * max_blocks_per_request + 1
        self.config = dataclasses.replace(
            config,
            dropout=0.0,
            attention_dropout=0.0,
            decode_paged=True,
            decode_ragged=False,
            paged_block_size=block_size,
            paged_num_blocks=num_blocks,
            paged_max_blocks=max_blocks_per_request,
            paged_kv_int8=kv_int8,
            paged_attention=attention,
            paged_tp=tp,
            paged_tp_devices=(mesh_devices if tp > 1 else None),
        )
        self.params = params
        self.max_batch = max_batch
        self.eos_id = eos_id
        self.clock = clock
        self.prefix_cache = prefix_cache
        # Fleet KV store (serving/kv_store.py): in-process replicas share
        # ONE object via ``kv_store``; cross-process workers each build a
        # local store from the scalar (wire-able) ``kv_store_bytes`` /
        # ``kv_store_dir`` kwargs and synchronize over the kv_* RPC verbs.
        self._owns_store = kv_store is None
        if kv_store is None and (kv_store_bytes or kv_store_dir):
            kv_store = KVBlockStore(
                host_bytes=int(kv_store_bytes) if kv_store_bytes
                else 64 << 20,
                disk_dir=kv_store_dir)
        self.kv_store = kv_store
        self._pool_leaf_idx: Optional[List[int]] = None
        self.cache_state = PagedKVCache(
            self.config, max_batch, prefix_cache=prefix_cache,
            kv_store=kv_store,
        )
        if kv_store is not None:
            self.cache_state.spill_fn = self._store_put_block
            self.cache_state.fill_fn = self._store_fill_block
            self.cache_state.raw_fill_fn = self.write_block
            self.cache_state.pricer = self._build_pricer(kv_link_gbps)
        # Speculative decoding: resolve the proposer before the
        # scheduler so admission can budget for the draft window.
        proposer = spec_proposer
        if proposer is None and spec == "ngram":
            proposer = NGramProposer(max_ngram=spec_ngram_max)
        elif proposer is None and spec == "draft":
            if draft_params is None or draft_config is None:
                raise ValueError(
                    "spec='draft' needs draft_params and draft_config "
                    "(see spec.draft_from_target)")
            if draft_config.vocab_size != config.vocab_size:
                raise ValueError("draft/target vocab mismatch")
            if draft_config.max_seq_len < config.max_seq_len:
                raise ValueError("draft max_seq_len < target max_seq_len")
            proposer = DraftModelProposer(
                draft_params, draft_config, slots=max_batch,
                block_size=block_size, attention=attention)
        self.spec_decoder = (
            SpecDecoder(proposer, k=spec_k, adaptive=spec_adaptive)
            if proposer is not None else None)
        self.scheduler = Scheduler(
            self.cache_state, watermark_blocks=watermark_blocks,
            prefill_chunk_tokens=prefill_chunk_tokens,
            spec_reserve_tokens=(
                spec_k + 1 if self.spec_decoder is not None else 0),
        )
        self.role: Optional[str] = None
        if role is not None:
            self.set_role(role)
        # Observability (serving/tracing.py): per-rid span timelines in
        # this engine's clock domain, and wall-clock attribution for the
        # run loop. Both host-side only — they can never perturb the
        # jitted path, so token streams are bit-identical trace on/off.
        self.tracer = SpanTracer(enabled=trace)
        self.scheduler.tracer = self.tracer
        self.scheduler.now_fn = self._now
        self.ledger = ServingLedger()
        self.ts_interval = int(ts_interval)
        self.metric_logger = metric_logger
        self.serve_ts: List[dict] = []
        self.device_cache = init_paged_cache(self.config, max_batch)
        if tp > 1:
            # Commit the replica's persistent device state to the mesh:
            # pools sharded on kv heads (when divisible), params sharded
            # on each leaf's largest tp-divisible axis (~P/tp resident
            # per device; the step gathers them back exactly — see
            # serving/sharding.py for why greedy streams stay
            # token-identical).
            from tpu_trainer.parallel.mesh import tp_mesh
            from tpu_trainer.serving import sharding as tp_lib

            mesh = tp_mesh(tp, self.config.paged_tp_devices)
            self.params = tp_lib.shard_params(self.params, mesh)
            self.device_cache = tp_lib.shard_cache(
                self.device_cache, mesh, self.config.kv_heads)
        self._model = GPT(self.config)
        self._step_jit = _jitted_engine_step(self.config)
        self._verify_jit = _jitted_verify_step(self.config)
        self._k_cap = 1
        self._iters = 0
        self._t0 = None
        # Per deadline-carrying terminal request: terminal_time - deadline
        # (positive = the deadline was missed by that much). Feeds the
        # deadline_miss_* summary fields.
        self._deadline_margins: List[float] = []
        self.stats: Dict[str, float] = {
            "prefill_iters": 0, "decode_iters": 0, "idle_iters": 0,
            "prefill_tokens": 0, "prefill_chunks": 0,
            "generated_tokens": 0,
            "occupancy_sum": 0.0, "occupancy_samples": 0,
            "occupancy_max": 0.0,
            "spec_steps": 0, "spec_drafted": 0, "spec_accepted": 0,
            # Per-terminal-state request counts ("failed" has no current
            # producer — see scheduler.TERMINAL_STATES).
            "finished": 0, "cancelled": 0, "deadline_exceeded": 0,
            "failed": 0,
        }
        # Live metrics plane (obs/): counters and gauges mirror the
        # cumulative stats above via set_function — read at scrape time,
        # zero hot-path cost, and exact agreement with summary() by
        # construction. Only the latency histograms observe inline, and
        # those sites are no-op method calls on the null registry.
        self.registry = registry if registry is not None else NULL_REGISTRY
        self._metrics_on = registry is not None
        self._install_metrics()

    def _install_metrics(self) -> None:
        reg = self.registry
        self._m_step_seconds = reg.histogram(
            "serve_step_seconds", "Engine step wall-clock latency")
        self._m_ttft = reg.histogram(
            "serve_ttft_seconds", "Time to first token (engine clock)")
        self._m_tpot = reg.histogram(
            "serve_tpot_seconds", "Inter-token gap (engine clock)")
        req_total = reg.counter(
            "serve_requests_total", "Terminal requests by state",
            labelnames=("state",))
        for state in self.scheduler.terminal_counts:
            req_total.labels(state=state).set_function(
                lambda s=state: self.scheduler.terminal_counts[s])
        reg.counter("serve_admissions_total", "Admission events "
                    "(re-admission after preemption/failover counts)"
                    ).set_function(lambda: self.scheduler.n_admissions)
        reg.counter("serve_preemptions_total", "Recompute preemptions"
                    ).set_function(lambda: self.scheduler.n_preemptions)
        reg.counter("serve_generated_tokens_total", "Tokens emitted"
                    ).set_function(lambda: self.stats["generated_tokens"])
        reg.counter("serve_prefill_tokens_total", "Prompt tokens prefilled"
                    ).set_function(lambda: self.stats["prefill_tokens"])
        reg.counter("serve_prompt_tokens_total", "Prompt tokens admitted"
                    ).set_function(lambda: self.scheduler.prompt_tokens)
        reg.counter("serve_prefix_hit_tokens_total",
                    "Prompt tokens served from the prefix index"
                    ).set_function(lambda: self.scheduler.prefix_hit_tokens)
        reg.counter("serve_prefix_evictions_total", "Prefix-index evictions"
                    ).set_function(
                        lambda: self.cache_state.n_prefix_evictions)
        pool = reg.gauge("serve_pool_blocks",
                         "Paged-pool fragmentation split",
                         labelnames=("kind",))
        pool.labels(kind="free").set_function(
            lambda: self.cache_state.pool.free_blocks)
        pool.labels(kind="evictable").set_function(
            lambda: self.cache_state.evictable_blocks)
        pool.labels(kind="referenced").set_function(
            lambda: self.cache_state.referenced_blocks)
        reg.gauge("serve_pool_occupancy", "Paged-pool occupancy fraction"
                  ).set_function(lambda: self.cache_state.pool.occupancy)
        reg.gauge("serve_prefix_index_entries", "Prefix-index size"
                  ).set_function(
                      lambda: self.cache_state.prefix_index_entries)
        reg.gauge("serve_queue_depth", "Requests waiting for admission"
                  ).set_function(lambda: self.queue_depth)
        reg.gauge("serve_running", "Requests in flight"
                  ).set_function(lambda: len(self.scheduler.running))
        reg.gauge("serve_outstanding_tokens", "Token-steps of work owed"
                  ).set_function(lambda: self.outstanding_tokens)
        if self.kv_store is not None:
            store, cs = self.kv_store, self.cache_state
            kvb = reg.gauge("kv_store_bytes",
                            "Fleet KV store payload bytes by tier",
                            labelnames=("tier",))
            kvb.labels(tier="host").set_function(
                lambda: store.host_bytes_used)
            kvb.labels(tier="disk").set_function(
                lambda: store.disk_bytes_used)
            kvh = reg.counter("kv_store_hits_total",
                              "Store block hits by serving tier",
                              labelnames=("tier",))
            kvh.labels(tier="host").set_function(
                lambda: store.counters["hits_host"])
            kvh.labels(tier="disk").set_function(
                lambda: store.counters["hits_disk"])
            kvt = reg.counter("kv_store_hit_tokens_total",
                              "Prompt tokens admitted from the store",
                              labelnames=("tier",))
            kvt.labels(tier="host").set_function(
                lambda: cs.store_hit_tokens_host)
            kvt.labels(tier="disk").set_function(
                lambda: cs.store_hit_tokens_disk)
            kve = reg.counter("kv_store_evictions_total",
                              "Store entries evicted by tier",
                              labelnames=("tier",))
            kve.labels(tier="host").set_function(
                lambda: store.counters["evictions_host"])
            kve.labels(tier="disk").set_function(
                lambda: store.counters["evictions_disk"])
            reg.counter("kv_store_puts_total",
                        "Blocks published into the store"
                        ).set_function(lambda: store.counters["puts"])
            reg.counter("kv_store_spills_total",
                        "Evicted device blocks demoted into the store"
                        ).set_function(lambda: cs.n_store_spills)
            reg.counter("kv_store_migrated_tails_total",
                        "Migrated raw tail blocks admitted"
                        ).set_function(
                            lambda: self.scheduler.n_migrated_tail_fills)
        if self.spec_decoder is not None:
            reg.counter("serve_spec_drafted_total", "Draft tokens proposed"
                        ).set_function(lambda: self.stats["spec_drafted"])
            reg.counter("serve_spec_accepted_total", "Draft tokens accepted"
                        ).set_function(lambda: self.stats["spec_accepted"])
            reg.gauge("serve_spec_accept_rate",
                      "Accepted / drafted (cumulative)").set_function(
                          lambda: self.stats["spec_accepted"]
                          / max(1, int(self.stats["spec_drafted"])))

    def reset_stats(self) -> None:
        """Zero counters/clock between a warm-up run and a timed run. The
        engine must be drained (no waiting/running requests); the device
        pools keep stale KV but lengths masking means it is never read."""
        assert not self.scheduler.has_work(), "reset_stats on a busy engine"
        self._iters = 0
        self._t0 = None
        self.scheduler.n_preemptions = 0
        self.scheduler.n_admissions = 0
        self.scheduler.prefix_hit_tokens = 0
        self.scheduler.prompt_tokens = 0
        for k in self.scheduler.terminal_counts:
            self.scheduler.terminal_counts[k] = 0
        self.cache_state.n_prefix_evictions = 0
        self.cache_state.n_store_spills = 0
        self.cache_state.n_store_declined = 0
        self.cache_state.store_hit_tokens_host = 0
        self.cache_state.store_hit_tokens_disk = 0
        self.scheduler.n_migrated_tail_fills = 0
        self.scheduler.n_migration_declined = 0
        if self.kv_store is not None and self._owns_store:
            # A shared (front-end-owned) store keeps its fleet counters;
            # a private one resets with the engine.
            self.kv_store.reset_stats()
        self.wall_elapsed = 0.0
        self._deadline_margins = []
        if self.spec_decoder is not None:
            self.spec_decoder.reset_stats()
        self.tracer.reset()
        self.ledger.reset()
        self.serve_ts = []
        for k in self.stats:
            self.stats[k] = 0.0 if isinstance(self.stats[k], float) else 0

    # -- one engine iteration ----------------------------------------------

    def step(self) -> List[Request]:
        """Run one scheduler iteration. Returns the requests that reached
        a terminal state this iteration: finished streams, plus anything
        the deadline sweep retired at the boundary (their blocks are
        already back in the pool)."""
        if not self._metrics_on:
            return self._step_impl()
        t0 = time.perf_counter()
        try:
            return self._step_impl()
        finally:
            self._m_step_seconds.observe(time.perf_counter() - t0)

    def _step_impl(self) -> List[Request]:
        self._iters += 1
        with self.ledger.track("host_sched"):
            terminal = self._expire_deadlines()
            kind, reqs = self.scheduler.schedule()
        if kind == "idle":
            self.stats["idle_iters"] += 1
            return terminal
        if kind == "prefill":
            terminal += self._forward(reqs, prefill=True)
            self.stats["prefill_iters"] += 1
        elif self.spec_decoder is not None:
            terminal += self._spec_decode()
            self.stats["decode_iters"] += 1
        else:
            reqs = self.scheduler.ensure_decode_blocks()
            if not reqs:          # everything preempted itself back out
                return terminal
            terminal += self._forward(reqs, prefill=False)
            self.stats["decode_iters"] += 1
        occ = self.cache_state.pool.occupancy
        self.stats["occupancy_sum"] += occ
        self.stats["occupancy_samples"] += 1
        self.stats["occupancy_max"] = max(self.stats["occupancy_max"], occ)
        return terminal

    def _expire_deadlines(self) -> List[Request]:
        """The iteration-boundary deadline sweep (scheduler.expire) plus
        the engine-side bookkeeping a terminal request needs. Skips the
        clock read entirely when nothing carries a deadline, so runs
        without deadlines are untouched."""
        s = self.scheduler
        if (all(r.deadline is None for r in s.waiting)
                and all(r.deadline is None for r in s.running)):
            return []
        now = self._now()
        expired = s.expire(now)
        for r in expired:
            r.finished_at = now
            if self.spec_decoder is not None:
                self.spec_decoder.forget(r)
            self.stats["deadline_exceeded"] += 1
            self._observe_deadline(r, now)
        return expired

    def _observe_deadline(self, r: Request, now: float) -> None:
        if r.deadline is not None:
            self._deadline_margins.append(now - r.deadline)

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or in-flight request NOW: terminal status
        ``cancelled``, slot and paged KV blocks (speculative tails
        included) back in the pool before this call returns — not at the
        next drain. False if ``rid`` is not queued or in flight here.
        The request never appears in a later ``step()`` return; callers
        doing conservation accounting count the cancel themselves."""
        req = self.scheduler.cancel(rid)
        if req is None:
            return False
        req.finished_at = self._now()
        if self.spec_decoder is not None:
            self.spec_decoder.forget(req)
        self.stats["cancelled"] += 1
        return True

    def compiled_decode_text(self) -> str:
        """Post-optimization HLO of the jitted single-token decode step at
        this engine's shapes — the serving twin of
        ``Trainer.compiled_step_text``: it shows which paged-attention
        implementation the step contains (``tpu_custom_call`` = the
        ``flash_decode`` kernel). Same jit object and shapes as the running
        step, so after a decode this hits the executable cache."""
        n = self.max_batch

        def arg(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype)

        return self._step_jit.lower(
            self.params, self.device_cache,
            arg(jnp.int32, *self.cache_state.tables.shape),
            arg(jnp.int32, n), arg(jnp.int32, n), arg(jnp.int32, n, 1),
            arg(jnp.float32, n), arg(jnp.int32, n), arg(jnp.float32, n),
            arg(jnp.uint32, n, 2), arg(jnp.int32, n),
            k_cap=self._k_cap, prefill=False, hist_blocks=0,
        ).compile().as_text()

    def _forward(self, reqs: List[Request], *, prefill: bool) -> List[Request]:
        slots = self.max_batch
        cs = self.cache_state
        # Only the stepped rows carry real tables: other running
        # requests' rows are nulled so this pass cannot touch their
        # blocks (a mid-prefill row in a decode pass would otherwise
        # take a length-0 write into its first real block).
        tables = np.zeros_like(cs.tables)
        lengths = np.zeros((slots,), np.int32)
        offsets = np.zeros((slots,), np.int32)
        hist_blocks = 0
        if prefill:
            width = _bucket_pow2(max(r.prefill_chunk for r in reqs))
            width = min(width, cs.capacity_tokens())
            ids = np.zeros((slots, width), np.int32)
            max_cursor = 0
            for r in reqs:
                seq = r.prompt + r.generated
                cur, n = r.prefill_cursor, r.prefill_chunk
                ids[r.slot, :n] = seq[cur:cur + n]
                tables[r.slot] = cs.tables[r.slot]
                lengths[r.slot] = cur + n
                offsets[r.slot] = cur
                max_cursor = max(max_cursor, cur)
                self.stats["prefill_tokens"] += n
                self.stats["prefill_chunks"] += 1
            if max_cursor > 0:
                # Static history width (blocks), pow2-bucketed so chunk
                # resumes at nearby depths share a compile. 0 keeps the
                # original no-history prefill computation bit-for-bit.
                hist_blocks = min(
                    _bucket_pow2(cs.blocks_for(max_cursor), lo=1),
                    cs.max_blocks,
                )
        else:
            ids = np.zeros((slots, 1), np.int32)
            for r in reqs:
                ids[r.slot, 0] = (r.prompt + r.generated)[-1]
                tables[r.slot] = cs.tables[r.slot]
                lengths[r.slot] = r.cached_tokens()
        temps = np.zeros((slots,), np.float32)
        topks = np.zeros((slots,), np.int32)
        topps = np.ones((slots,), np.float32)
        keys = np.zeros((slots, 2), np.uint32)
        steps = np.zeros((slots,), np.int32)
        for r in reqs:
            temps[r.slot] = r.sampling.temperature
            topks[r.slot] = r.sampling.top_k
            topps[r.slot] = r.sampling.top_p
            keys[r.slot] = r.key()
            steps[r.slot] = len(r.generated)   # index of the draw made now
            if r.sampling.top_k > self._k_cap:
                self._k_cap = r.sampling.top_k

        with self.ledger.track("dispatch"):
            self.device_cache, tokens = self._step_jit(
                self.params, self.device_cache,
                jnp.asarray(tables), jnp.asarray(lengths),
                jnp.asarray(offsets), jnp.asarray(ids),
                jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(topps),
                jnp.asarray(keys),
                jnp.asarray(steps), k_cap=self._k_cap, prefill=prefill,
                hist_blocks=hist_blocks,
            )
            tokens = np.asarray(tokens)   # host read = dispatch sync

        now = self._now()
        finished: List[Request] = []
        for r in reqs:
            if prefill:
                r.prefill_cursor += r.prefill_chunk
                cs.lengths[r.slot] = r.prefill_cursor
                self.tracer.emit(r.rid, "prefill_chunk", now,
                                 tokens=r.prefill_chunk,
                                 cursor=r.prefill_cursor)
                if self.prefix_cache:
                    self._register_prefix_blocks(r)
                if r.prefilling():
                    # Mid-prefill chunk: the sampled draw is discarded —
                    # the final chunk redraws at the same (seed, token
                    # index), so the stream matches an unchunked pass.
                    continue
            tok = int(tokens[r.slot])
            if r.token_times:
                self._m_tpot.observe(max(0.0, now - r.token_times[-1]))
            r.generated.append(tok)
            r.token_times.append(now)
            self.stats["generated_tokens"] += 1
            # Cache now holds everything fed this pass (not the new token).
            cs.lengths[r.slot] = r.context_len() - 1
            if r.first_token_at is None:
                r.first_token_at = now
                self._m_ttft.observe(max(0.0, now - r.arrival_time))
                self.tracer.emit(r.rid, "first_token", now)
            if (r.eos_id is not None and tok == r.eos_id) or (
                len(r.generated) >= r.max_new_tokens
            ):
                r.finished_at = now
                self.scheduler.retire(r)
                self.stats["finished"] += 1
                self._observe_deadline(r, now)
                finished.append(r)
        return finished

    def _spec_decode(self) -> List[Request]:
        """One speculative decode iteration: propose per-request drafts,
        pre-grow blocks for the worst-case window, verify all K+1
        positions in ONE target forward (the chunked-prefill branch at
        each row's cached offset), then emit the accepted prefix plus
        the target's correction/bonus token and rewind — host lengths
        roll back to the accept point and trailing blocks return to the
        pool the same iteration. Greedy rows emit the target argmax
        chain, so their streams bit-match non-speculative decode."""
        sd = self.spec_decoder
        cs = self.cache_state
        reqs = [r for r in self.scheduler.running
                if r.status == "running" and not r.prefilling()]
        if not reqs:
            return []
        drafts = sd.propose(reqs)
        window = {r.rid: len(drafts.get(r.rid, [])) + 1 for r in reqs}
        if all(n == 1 for n in window.values()):
            # Nothing drafted anywhere: plain single-token decode.
            reqs = self.scheduler.ensure_decode_blocks()
            if not reqs:
                return []
            return self._forward(reqs, prefill=False)
        reqs = self.scheduler.ensure_spec_blocks(reqs, window)
        if not reqs:              # everything preempted itself back out
            return []
        max_m = max(window[r.rid] - 1 for r in reqs)
        if max_m == 0:            # the drafted rows were all preempted
            return self._forward(reqs, prefill=False)

        slots = self.max_batch
        width = min(_bucket_pow2(max_m + 1, lo=2), cs.capacity_tokens())
        tables = np.zeros_like(cs.tables)
        lengths = np.zeros((slots,), np.int32)
        offsets = np.zeros((slots,), np.int32)
        ids = np.zeros((slots, width), np.int32)
        dlens = np.zeros((slots,), np.int32)
        temps = np.zeros((slots,), np.float32)
        topks = np.zeros((slots,), np.int32)
        topps = np.ones((slots,), np.float32)
        keys = np.zeros((slots, 2), np.uint32)
        steps = np.zeros((slots,), np.int32)
        max_off = 0
        for r in reqs:
            d = drafts.get(r.rid, [])
            cached = r.cached_tokens()
            seq = r.prompt + r.generated
            ids[r.slot, 0] = seq[-1]
            ids[r.slot, 1:1 + len(d)] = d
            tables[r.slot] = cs.tables[r.slot]
            offsets[r.slot] = cached
            lengths[r.slot] = cached + len(d) + 1
            dlens[r.slot] = len(d)
            temps[r.slot] = r.sampling.temperature
            topks[r.slot] = r.sampling.top_k
            topps[r.slot] = r.sampling.top_p
            keys[r.slot] = r.key()
            steps[r.slot] = len(r.generated)
            max_off = max(max_off, cached)
            if r.sampling.top_k > self._k_cap:
                self._k_cap = r.sampling.top_k
        # The window rides the chunked-prefill branch: cached context is
        # the pooled history (cached >= 1 always in decode).
        hist_blocks = min(
            _bucket_pow2(cs.blocks_for(max_off), lo=1), cs.max_blocks)

        with self.ledger.track("dispatch"):
            self.device_cache, emitted, n_acc = self._verify_jit(
                self.params, self.device_cache,
                jnp.asarray(tables), jnp.asarray(lengths),
                jnp.asarray(offsets), jnp.asarray(ids), jnp.asarray(dlens),
                jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(topps),
                jnp.asarray(keys), jnp.asarray(steps),
                k_cap=self._k_cap, hist_blocks=hist_blocks,
            )
            emitted = np.asarray(emitted)
            n_acc = np.asarray(n_acc)

        now = self._now()
        finished: List[Request] = []
        for r in reqs:
            m = int(dlens[r.slot])
            j = int(n_acc[r.slot])
            sd.observe(r, m, j)
            if m > 0:
                self.tracer.emit(r.rid, "spec_window", now, k=m, accepted=j)
            self.stats["spec_steps"] += 1
            self.stats["spec_drafted"] += m
            self.stats["spec_accepted"] += j
            done = False
            for tok in emitted[r.slot, :j + 1]:
                tok = int(tok)
                r.generated.append(tok)
                if r.token_times:
                    self._m_tpot.observe(max(0.0, now - r.token_times[-1]))
                r.token_times.append(now)
                self.stats["generated_tokens"] += 1
                if r.first_token_at is None:
                    r.first_token_at = now
                    self._m_ttft.observe(max(0.0, now - r.arrival_time))
                    self.tracer.emit(r.rid, "first_token", now)
                if (r.eos_id is not None and tok == r.eos_id) or (
                    len(r.generated) >= r.max_new_tokens
                ):
                    done = True
                    break     # tokens past EOS are never emitted
            # Host rewind: cache holds everything up to the accept point
            # (write-ahead past it is masked garbage the shrink reclaims).
            cs.lengths[r.slot] = r.context_len() - 1
            if done:
                r.finished_at = now
                sd.forget(r)
                self.scheduler.retire(r)
                self.stats["finished"] += 1
                self._observe_deadline(r, now)
                finished.append(r)
            else:
                self.scheduler.shrink_spec_blocks(r)
        return finished

    def _register_prefix_blocks(self, r: Request) -> None:
        """Publish the request's newly completed full PROMPT blocks in
        the prefix index (shared-prefix blocks are already there; the
        register is a no-op on an existing digest)."""
        cs = self.cache_state
        bsz = cs.block_size
        done = min(r.prefill_cursor, len(r.prompt)) // bsz
        if done <= r._blocks_registered:
            return
        if r._prompt_digests is None:
            r._prompt_digests = cs.block_digests(r.prompt)
        blocks = cs.slot_blocks(r.slot)
        for i in range(r._blocks_registered, done):
            cs.prefix_register(r._prompt_digests[i], blocks[i])
            if self.kv_store is not None:
                # Write-through to the fleet tier: a block computed on
                # ANY replica is addressable fleet-wide the moment it is
                # published, not only when local eviction spills it.
                self._store_put_block(r._prompt_digests[i], blocks[i])
        r._blocks_registered = done

    # -- fleet KV store: device block I/O + migration ----------------------

    def _build_pricer(self, link_gbps: float) -> MigrationPricer:
        from tpu_trainer.utils.logging import (
            OFF_CHIP_MODEL_KIND,
            device_peak_flops,
            flops_per_token,
            peak_flops_for_kind,
        )

        # The attached chip's peak; off-TPU the price model is drawn for
        # the named target (an unknown TPU kind raises).
        peak = device_peak_flops() or peak_flops_for_kind(OFF_CHIP_MODEL_KIND)
        # flops_per_token counts fwd+bwd (6N + attn); the recompute a
        # migration avoids is one forward pass — a third of that.
        fwd = flops_per_token(self.config) / 3.0
        return MigrationPricer(
            flops_per_token=fwd, device_flops=peak,
            link_bytes_per_s=float(link_gbps) * 1e9)

    def _pool_leaves(self) -> Tuple[List, List[int], object]:
        """Flatten the device cache; memoize which leaf positions are
        block pools (the structure is static — steps replace values, not
        shape). Returns (all leaves, pool leaf indices, treedef)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.device_cache)
        if self._pool_leaf_idx is None:
            self._pool_leaf_idx = [
                i for i, (path, _) in enumerate(flat)
                if getattr(path[-1], "key", None) in _POOL_LEAF_KEYS]
        return [leaf for _, leaf in flat], self._pool_leaf_idx, treedef

    @staticmethod
    def _block_index(leaf, block_id: int) -> tuple:
        """Index tuple selecting one block from a pool leaf. Per-layer
        pools are rank 4 ``[nblk, bsz, kvh, d|nbq]``; the scanned model
        stacks layers in front (rank 5, block axis 1)."""
        return (slice(None),) * (leaf.ndim - 4) + (block_id,)

    def read_block(self, block_id: int) -> List[np.ndarray]:
        """One block's K/V payload as host arrays, one per pool leaf in
        tree-flatten order — the store/wire entry format. Engines built
        from the same config flatten identically, so entries round-trip
        across the fleet."""
        leaves, idx, _ = self._pool_leaves()
        return [np.asarray(leaves[i][self._block_index(leaves[i], block_id)])
                for i in idx]

    def write_block(self, block_id: int, payload: List[np.ndarray]) -> bool:
        """Write a store/migration entry into device block ``block_id``.
        False (device untouched) on any layout mismatch — a store shared
        across differently configured engines degrades to recompute
        instead of corrupting a pool."""
        leaves, idx, treedef = self._pool_leaves()
        if len(payload) != len(idx):
            return False
        for j, arr in zip(idx, payload):
            cur = leaves[j]
            ax = cur.ndim - 4
            want = tuple(cur.shape[:ax]) + tuple(cur.shape[ax + 1:])
            if (tuple(arr.shape) != want
                    or np.dtype(arr.dtype) != np.dtype(cur.dtype)):
                return False
        for j, arr in zip(idx, payload):
            leaves[j] = leaves[j].at[
                self._block_index(leaves[j], block_id)].set(jnp.asarray(arr))
        self.device_cache = jax.tree_util.tree_unflatten(treedef, leaves)
        return True

    def _store_put_block(self, digest: bytes, block_id: int) -> bool:
        """Publish one device block into the fleet store (idempotent per
        digest). Doubles as the cache's eviction spill hook."""
        if self.kv_store is None or self.device_cache is None:
            return False
        if self.kv_store.has(digest):
            return False
        return self.kv_store.put(digest, self.read_block(block_id))

    def _store_fill_block(self, digest: bytes, block_id: int):
        """The cache's store fall-through hook: fetch ``digest`` and fill
        a freshly allocated device block. Returns the serving tier
        ("host"/"disk") or None on miss/mismatch."""
        got = self.kv_store.get(digest)
        if got is None:
            return None
        tier, payload = got
        return tier if self.write_block(block_id, payload) else None

    def set_role(self, role: Optional[str]) -> None:
        """Assign this replica's disaggregation role. ``"prefill"``
        disables decode scheduling: requests run to the end of prefill
        (sampling their first token) and then idle until the front-end
        extracts them for migration. ``"decode"``/None is a full
        engine."""
        if role not in (None, "prefill", "decode"):
            raise ValueError(f"role={role!r} (prefill | decode | None)")
        self.role = role
        self.scheduler.decode_enabled = role != "prefill"

    def migratable_rids(self) -> List[int]:
        """Requests a prefill-role replica has carried as far as it can:
        prefill complete and the first token sampled — exactly the state
        a decode replica needs to continue the stream."""
        return [r.rid for r in self.scheduler.running
                if r.status == "running" and not r.prefilling()
                and r.generated]

    def extract_request(self, rid: int):
        """Migration harvest + handoff: publish the request's full
        prompt blocks to the fleet store (digest-addressed), read its
        sub-block tail raw, then strip it out of the scheduler in
        fresh-waiting state. Returns ``(request, payload)`` with payload
        ``{"tail_ntok", "leaves"}``, or None if ``rid`` is not in a
        migratable state. Re-admission elsewhere matches the full blocks
        through the store, fills the tail raw, and resumes sampling at
        the same (seed, token_index) — bit-identical to never moving."""
        req = next(
            (r for r in self.scheduler.running if r.rid == rid), None)
        if req is None or req.prefilling() or not req.generated:
            return None
        cs = self.cache_state
        payload = {"tail_ntok": 0, "leaves": None}
        if self.kv_store is not None:
            if req._prompt_digests is None:
                req._prompt_digests = cs.block_digests(req.prompt)
            blocks = cs.slot_blocks(req.slot)
            full = len(req.prompt) // cs.block_size
            for i in range(min(full, len(blocks))):
                self._store_put_block(req._prompt_digests[i], blocks[i])
            tail = len(req.prompt) - full * cs.block_size
            if tail and full < len(blocks):
                payload = {"tail_ntok": tail,
                           "leaves": self.read_block(blocks[full])}
        if self.spec_decoder is not None:
            self.spec_decoder.forget(req)
        self.scheduler.extract(req)
        return req, payload

    def _now(self) -> float:
        if self._t0 is None:
            self._t0 = self.clock()
        return self.clock() - self._t0

    # -- load signals (the multi-replica router's inputs; also summary
    # telemetry for single-engine runs) ------------------------------------

    @property
    def queue_depth(self) -> int:
        """Requests waiting for admission."""
        return self.scheduler.queue_depth

    @property
    def outstanding_tokens(self) -> int:
        """Token-steps of work still owed (waiting + running)."""
        return self.scheduler.outstanding_tokens

    def oldest_wait_age(self, now: Optional[float] = None) -> float:
        """How long (engine clock units) the longest-waiting queued
        request has been waiting; 0.0 with an empty queue."""
        arr = self.scheduler.oldest_waiting_arrival
        if arr is None:
            return 0.0
        return max(0.0, (self._now() if now is None else now) - arr)

    def export_requests(self, *, waiting_only: bool = False):
        """Drain this engine's requeueable request state (see
        ``Scheduler.export_requests``) — the failover / shrink-teardown
        path of the multi-replica front-end."""
        return self.scheduler.export_requests(waiting_only=waiting_only)

    # -- trace replay ------------------------------------------------------

    def run(
        self,
        requests: Sequence[Request],
        *,
        time_mode: str = "wall",
        max_iters: int = 10_000_000,
        profiler=None,
    ) -> List[Request]:
        """Replay an open-loop trace: each request joins the waiting queue
        when the clock passes its ``arrival_time``. ``time_mode="wall"``
        measures arrivals in seconds; ``"steps"`` measures them in engine
        iterations — fully deterministic, for tests and replay checks.
        Returns the finished requests in input order; requests that
        ended cancelled or past their deadline are dropped from the
        return (their terminal state lives on the Request objects the
        caller already holds, and in ``summary()``).

        ``profiler`` (utils.profiling.WindowedTrace or anything with a
        ``step(i) -> context`` method) wraps each engine iteration in a
        ``jax.profiler.StepTraceAnnotation`` while its window is open —
        the serve_bench ``--profile-trace`` hook. Every ``ts_interval``
        iterations the run appends a ``kind:"serve_ts"`` sample (ledger
        fractions + as-of-now gauges) to ``self.serve_ts``."""
        if time_mode not in ("wall", "steps"):
            raise ValueError(f"time_mode={time_mode!r}")
        pending = sorted(requests, key=lambda r: (r.arrival_time, r.rid))
        pending = list(pending)
        self._t0 = self.clock()
        t_start = self._t0
        done: List[Request] = []
        while pending or self.scheduler.has_work():
            now = (
                float(self._iters) if time_mode == "steps" else self._now()
            )
            while pending and pending[0].arrival_time <= now:
                self.scheduler.add(pending.pop(0))
            if not self.scheduler.has_work():
                with self.ledger.track("idle"):
                    if time_mode == "wall":
                        time.sleep(
                            min(1e-3,
                                max(0.0, pending[0].arrival_time - now))
                        )
                    else:
                        self._iters += 1  # idle tick advances the clock
                continue
            if profiler is None:
                done.extend(self.step())
            else:
                with profiler.step(self._iters):
                    done.extend(self.step())
            if self.ts_interval and self._iters % self.ts_interval == 0:
                self._emit_ts()
            if self._iters >= max_iters:
                raise RuntimeError(f"engine did not drain in {max_iters} iters")
        self.wall_elapsed = self.clock() - t_start
        self._emit_ts(final=True)
        by_rid = {r.rid: r for r in done if r.status == "finished"}
        return [by_rid[r.rid] for r in requests if r.rid in by_rid]

    def _emit_ts(self, final: bool = False) -> dict:
        """One ``kind:"serve_ts"`` time-series sample: the ledger's
        wall-clock attribution so far plus as-of-now load gauges. Routed
        through ``metric_logger`` (the MetricLogger JSONL/TB/wandb sinks)
        when one is attached; always kept on ``self.serve_ts``."""
        s = self.stats
        gauges = {
            "t": round(self._now(), 6),
            "iter": int(self._iters),
            "queue_depth": self.queue_depth,
            "running": len(self.scheduler.running),
            "outstanding_tokens": self.outstanding_tokens,
            "occupancy": round(float(self.cache_state.pool.occupancy), 4),
            "generated_tokens": int(s["generated_tokens"]),
            "prefix_hit_rate": round(
                self.scheduler.prefix_hit_tokens
                / max(1, self.scheduler.prompt_tokens), 4),
        }
        if self.spec_decoder is not None:
            gauges["spec_accept_rate"] = round(
                s["spec_accepted"] / max(1, int(s["spec_drafted"])), 4)
        rec = self.ledger.record(gauges, final=final)
        self.serve_ts.append(rec)
        if self.metric_logger is not None:
            self.metric_logger.log_record(rec)
        return rec

    def summary(self) -> Dict[str, float]:
        s = dict(self.stats)
        n = max(1, int(s.pop("occupancy_samples")))
        s["occupancy_mean"] = s.pop("occupancy_sum") / n
        s["preemptions"] = self.scheduler.n_preemptions
        s["iters"] = self._iters
        s["prompt_tokens"] = self.scheduler.prompt_tokens
        s["prefix_hit_tokens"] = self.scheduler.prefix_hit_tokens
        s["prefix_hit_rate"] = (
            self.scheduler.prefix_hit_tokens
            / max(1, self.scheduler.prompt_tokens)
        )
        s["prefix_evictions"] = self.cache_state.n_prefix_evictions
        if self.kv_store is not None:
            cs = self.cache_state
            s["store_hit_tokens_host"] = cs.store_hit_tokens_host
            s["store_hit_tokens_disk"] = cs.store_hit_tokens_disk
            s["store_hit_tokens"] = (
                cs.store_hit_tokens_host + cs.store_hit_tokens_disk)
            s["store_spills"] = cs.n_store_spills
            s["store_declined"] = cs.n_store_declined
            s["migrated_tail_fills"] = self.scheduler.n_migrated_tail_fills
            s["migration_declined"] = self.scheduler.n_migration_declined
            for k, v in self.kv_store.stats().items():
                s[f"kv_store_{k}"] = v
        s.update(self.cache_state.fragmentation())
        s.update(self.scheduler.pool_shard_stats())
        s["queue_depth"] = self.queue_depth
        s["outstanding_tokens"] = self.outstanding_tokens
        s["oldest_wait_s"] = (
            self.oldest_wait_age() if self.scheduler.waiting else 0.0)
        if self._deadline_margins:
            # Miss slack = how far past its deadline a deadline-carrying
            # request ended (0 for the ones that made it). Absent when
            # the run carried no deadlines, so analyze gates SKIP.
            margins = np.asarray(self._deadline_margins)
            slack = np.maximum(margins, 0.0)
            s["deadline_miss_rate"] = float(np.mean(margins > 0))
            s["deadline_miss_slack_p50"] = float(np.percentile(slack, 50))
            s["deadline_miss_slack_p99"] = float(np.percentile(slack, 99))
        if self.spec_decoder is not None:
            s["spec_accept_mean"] = (
                s["spec_accepted"] / max(1, int(s["spec_steps"])))
            s["spec_accept_rate"] = (
                s["spec_accepted"] / max(1, int(s["spec_drafted"])))
            s["spec_accept_hist"] = list(self.spec_decoder.accept_hist)
        else:
            for k in ("spec_steps", "spec_drafted", "spec_accepted"):
                s.pop(k)
        if getattr(self, "wall_elapsed", 0):
            s["wall_s"] = self.wall_elapsed
            s["tokens_per_s"] = s["generated_tokens"] / self.wall_elapsed
        return s


def _engine_step(
    config, params, cache, tables, lengths, offsets, ids,
    temps, topks, topps, keys, steps, *, k_cap: int, prefill: bool,
    hist_blocks: int,
) -> Tuple[dict, jax.Array]:
    """One jitted engine step: broadcast host scheduling state into the
    cache pytree, forward, gather each row's last real logit, sample.
    ``hist_blocks`` is the static chunked-prefill history width — the
    model is built per trace with it baked into the config, so each
    (width bucket, history bucket) pair compiles once."""

    def put(path, x):
        key = getattr(path[-1], "key", None)
        if key == "tables":
            return jnp.broadcast_to(tables, x.shape)
        if key == "lengths":
            return jnp.broadcast_to(lengths, x.shape)
        if key == "offsets":
            return jnp.broadcast_to(offsets, x.shape)
        return x

    model = GPT(dataclasses.replace(config, paged_hist_blocks=hist_blocks))
    cache = jax.tree_util.tree_map_with_path(put, cache)
    if config.paged_tp > 1:
        # Sharded replica: params live sharded on the mesh — gather them
        # to replicated here (an exact concat, no arithmetic) so the
        # dense compute below is bitwise the single-device compute, and
        # pin the output cache back to the pool layout so the scatter's
        # result never drifts off the committed sharding.
        from tpu_trainer.parallel.mesh import tp_mesh
        from tpu_trainer.serving import sharding as tp_lib

        mesh = tp_mesh(config.paged_tp, config.paged_tp_devices)
        params = tp_lib.gather_params(params, mesh)
    (logits, _), vars_out = model.apply(
        {"params": params, "cache": cache}, ids, decode=True,
        mutable=["cache"],
    )
    if config.paged_tp > 1:
        vars_out = {"cache": tp_lib.constrain_cache(
            vars_out["cache"], mesh, config.kv_heads)}
    if prefill:
        last = jnp.take_along_axis(
            logits, jnp.maximum(lengths - offsets - 1, 0)[:, None, None],
            axis=1,
        )[:, 0]
    else:
        last = logits[:, 0]
    tokens = sample_tokens(
        last.astype(jnp.float32), temps, topks, topps, keys, steps,
        k_cap=k_cap,
    )
    return vars_out["cache"], tokens


@functools.lru_cache(maxsize=None)
def _jitted_engine_step(config):
    """Per-config memo of the jitted step. ``GPTConfig`` is frozen, so
    engines built with equal configs get the SAME jit object — and with
    it the same compile cache. Constructing a second identically-shaped
    engine (warm-up/timed pairs, A/B lanes, test matrices, the draft
    proposer reusing the target's step) then costs zero retraces.

    Device/mesh identity is part of the key: the config carries
    ``(paged_tp, paged_tp_devices)``, so two equal-shaped engines built
    for different device sets (or sharded vs single-device) never share
    a jit object — sharing one would dispatch the second engine's steps
    onto the first engine's devices."""
    return jax.jit(
        functools.partial(_engine_step, config),
        static_argnames=("k_cap", "prefill", "hist_blocks"),
    )


@functools.lru_cache(maxsize=None)
def _jitted_verify_step(config):
    """Same per-config sharing — and the same (paged_tp,
    paged_tp_devices) mesh-identity keying — for the speculative verify
    step."""
    return jax.jit(
        functools.partial(_verify_step, config),
        static_argnames=("k_cap", "hist_blocks"),
    )


def poisson_trace(
    n_requests: int,
    *,
    vocab_size: int,
    rate: float = 8.0,
    seed: int = 0,
    prompt_len_range: Tuple[int, int] = (8, 64),
    max_new_range: Tuple[int, int] = (8, 32),
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_id: Optional[int] = None,
) -> List[Request]:
    """Synthetic open-loop trace: exponential inter-arrivals at ``rate``
    requests per time unit, uniform prompt/output lengths, one sampling
    seed per request — all from one ``seed``, so a trace is replayable
    bit-for-bit."""
    rs = np.random.RandomState(seed)
    arrivals = np.cumsum(rs.exponential(1.0 / rate, size=n_requests))
    out = []
    for i in range(n_requests):
        plen = int(rs.randint(prompt_len_range[0], prompt_len_range[1] + 1))
        mnew = int(rs.randint(max_new_range[0], max_new_range[1] + 1))
        prompt = rs.randint(1, vocab_size, size=plen).tolist()
        out.append(Request(
            rid=i,
            prompt=[int(t) for t in prompt],
            max_new_tokens=mnew,
            sampling=SamplingParams(
                temperature=temperature, top_k=top_k, top_p=top_p,
                seed=int(rs.randint(0, 2**31 - 1)),
            ),
            arrival_time=float(arrivals[i]),
            eos_id=eos_id,
        ))
    return out


def request_metrics(reqs: Sequence[Request]) -> Dict[str, List[float]]:
    """Latency series (same time axis the engine ran on): TTFT = first
    token minus arrival, one sample per request; TPOT = every individual
    inter-token gap (a.k.a. inter-token latency). Per-GAP samples are the
    point: a monolithic prefill landing mid-decode stalls every in-flight
    stream for the whole prompt, which a per-request MEAN averages away —
    the p99 of the gaps is where that tail lives (and what chunked
    prefill is for). Falls back to the mean-gap estimate for requests
    recorded without per-token timestamps.

    ``queue_wait`` = first admission minus arrival (one sample per
    admitted request, preemption re-admissions excluded) — the phase
    TTFT hides: a request can clear admission instantly and still pay a
    long prefill, or sit queued behind a full pool. Comes from the span
    layer's ``admitted_at`` stamp, so it survives the RPC wire."""
    ttft, tpot, queue_wait = [], [], []
    for r in reqs:
        if r.admitted_at is not None:
            queue_wait.append(max(0.0, r.admitted_at - r.arrival_time))
        if r.first_token_at is None:
            continue
        ttft.append(r.first_token_at - r.arrival_time)
        if len(r.token_times) >= 2:
            tpot.extend(
                b - a for a, b in zip(r.token_times, r.token_times[1:])
            )
        elif not r.token_times:
            n_rest = len(r.generated) - 1
            if n_rest > 0 and r.finished_at is not None:
                tpot.append((r.finished_at - r.first_token_at) / n_rest)
    return {"ttft": ttft, "tpot": tpot, "queue_wait": queue_wait}


def _main() -> int:
    import argparse
    import json

    p = argparse.ArgumentParser(
        description="Replay a seeded Poisson trace through the serving "
        "engine on a synthetic checkpoint."
    )
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--rate", type=float, default=8.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--num-blocks", type=int, default=0,
                   help="KV pool blocks (0 = size for max_batch full contexts)")
    p.add_argument("--kv-int8", action="store_true")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="chunked-prefill token budget per iteration "
                        "(0 = whole-prompt prefill)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="copy-on-write prefix sharing in the block pool")
    p.add_argument("--attention", default="auto",
                   choices=("auto", "reference", "kernel"))
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0,
                   help="nucleus sampling mass (1.0 = off)")
    p.add_argument("--spec", default="off",
                   choices=("off", "ngram", "draft"),
                   help="speculative decoding proposer")
    p.add_argument("--spec-k", type=int, default=4,
                   help="max draft tokens per verify step")
    p.add_argument("--spec-draft-layers", type=int, default=1,
                   help="target layers sliced into the draft model "
                        "(--spec draft)")
    p.add_argument("--time-mode", default="wall", choices=("wall", "steps"))
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--max-seq-len", type=int, default=256)
    args = p.parse_args()

    from tpu_trainer.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    config = GPTConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_heads=args.heads,
        max_seq_len=args.max_seq_len, dropout=0.0, attention_dropout=0.0,
        dtype="float32", param_dtype="float32",
    )
    model = GPT(config)
    params = model.init(
        jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    draft_params = draft_config = None
    if args.spec == "draft":
        draft_params, draft_config = draft_from_target(
            params, config, args.spec_draft_layers)
    engine = ServingEngine(
        params, config, max_batch=args.max_batch,
        block_size=args.block_size,
        num_blocks=args.num_blocks or None,
        kv_int8=args.kv_int8, attention=args.attention,
        prefill_chunk_tokens=args.prefill_chunk or None,
        prefix_cache=args.prefix_cache,
        spec=args.spec, spec_k=args.spec_k,
        draft_params=draft_params, draft_config=draft_config,
    )
    trace = poisson_trace(
        args.requests, vocab_size=args.vocab, rate=args.rate,
        seed=args.seed, temperature=args.temperature, top_k=args.top_k,
        top_p=args.top_p,
    )
    finished = engine.run(trace, time_mode=args.time_mode)
    summary = engine.summary()
    lat = request_metrics(finished)
    for name, series in lat.items():
        if series:
            summary[f"{name}_p50"] = float(np.percentile(series, 50))
            summary[f"{name}_p99"] = float(np.percentile(series, 99))
    print(json.dumps({k: round(v, 6) if isinstance(v, float) else v
                      for k, v in sorted(summary.items())}, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
