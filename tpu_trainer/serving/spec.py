"""Speculative decoding: draft-propose / batch-verify over the paged KV
cache.

The serving engine's decode loop buys exactly one token per target-model
dispatch. Speculative decoding (Leviathan et al. 2023, "Fast Inference
from Transformers via Speculative Decoding") amortizes one target
forward over K cheaply drafted tokens: a proposer guesses the next K
tokens, the target scores all K+1 positions in ONE forward (reusing the
offset-aware in-flight+history branch of ``_paged_decode_attention`` —
the same machinery chunked prefill rides), and an acceptance rule keeps
the longest draft prefix the target agrees with plus one token the
target supplies itself. Every verify step therefore emits between 1 and
K+1 tokens at the cost of a single (slightly wider) dispatch.

Two proposers:

- ``NGramProposer`` — model-free prompt-lookup drafting (Saxena 2023):
  match the current context suffix against earlier context
  (prompt + generated) and propose the tokens that followed the most
  recent earlier occurrence. Free to compute, surprisingly effective on
  repetitive / extractive workloads, and ideal for this repo's
  CPU-testable bit-exactness-first ethos.
- ``DraftModelProposer`` — a small draft model (e.g. the target's first
  few scanned layers, ``draft_from_target``) decoding greedily over its
  OWN paged cache. The draft cache trails the true stream: each propose
  first catches up on tokens accepted since last time (one chunked feed
  at an offset — the draft reuses the very same engine step the target
  runs), then rolls K greedy decode steps forward. After verification
  the draft state rewinds to the accepted prefix.

Both proposers are DETERMINISTIC (a point-mass draft distribution),
which collapses the general two-model rejection-sampling rule to a
clean special case with the target distribution ``p`` (after the
request's temperature/top-k/top-p filtering, ``sampling.filter_logits``
— the exact distribution the non-speculative sampler draws from):

- greedy rows (``temperature == 0``): accept draft ``d_i`` iff it equals
  the target argmax at position i — so the accepted prefix plus the
  target's correction token IS the non-speculative greedy stream,
  bit for bit, no matter what the proposer guessed.
- sampled rows: accept ``d_i`` with probability ``p(d_i)`` (the
  ``min(1, p/q)`` rule with q a point mass); on rejection draw from the
  residual ``p`` with ``d_i`` masked out, renormalized; if every draft
  survives, draw the bonus token from ``p`` directly. The mixture
  ``p(d)·δ_d + (1 − p(d))·p|≠d`` is exactly ``p`` — the output
  distribution is unchanged, per the standard speculative-sampling
  argument. Draws are keyed by the engine's ``(seed, token_index)``
  scheme: the accept uniform for token index t is
  ``fold_in(fold_in(key, t), 1)``, the residual draw
  ``fold_in(fold_in(key, t), 2)``, and the bonus draw ``fold_in(key,
  t)`` — the same key the non-speculative sampler would use at that
  index.

``AdaptiveK`` shrinks the per-request draft length when the acceptance
EWMA drops (drafting costs a wider verify window and proposer work; on a
hostile stream K collapses to 1) and regrows it when drafts land.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_trainer.serving.paged_cache import PagedKVCache
from tpu_trainer.serving.sampling import filter_logits

# fold_in salts distinguishing the three draws made at one token index.
_SALT_ACCEPT = 1
_SALT_RESIDUAL = 2


# --- proposers --------------------------------------------------------------


class NGramProposer:
    """Prompt-lookup drafting: propose the continuation of the most
    recent earlier occurrence of the current context suffix, trying the
    longest n-gram first. Pure host-side Python over the token lists —
    no weights, no device work."""

    name = "ngram"

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(f"ngram range [{min_ngram}, {max_ngram}]")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def propose_one(self, context: List[int], k: int) -> List[int]:
        """Self-extending lookup: when a match's continuation runs out
        before ``k`` (the matched occurrence sits near the end — the
        short-period-cycle case), re-run the lookup with the draft so
        far appended, so a period-p loop drafts the full window."""
        out: List[int] = []
        ctx = list(context)
        while len(out) < k:
            nxt = self._lookup(ctx, k - len(out))
            if not nxt:
                break
            out.extend(nxt)
            ctx.extend(nxt)
        return out

    def _lookup(self, context: List[int], k: int) -> List[int]:
        if k <= 0 or len(context) < self.min_ngram + 1:
            return []
        for n in range(min(self.max_ngram, len(context) - 1),
                       self.min_ngram - 1, -1):
            suffix = context[-n:]
            # Most recent occurrence that ends strictly before the
            # suffix itself starts.
            for start in range(len(context) - n - 1, -1, -1):
                if context[start:start + n] == suffix:
                    cont = context[start + n:start + n + k]
                    if cont:
                        return [int(t) for t in cont]
        return []

    def propose(self, reqs, k_of: Dict[int, int]) -> Dict[int, List[int]]:
        return {r.rid: self.propose_one(r.prompt + r.generated,
                                        k_of[r.rid]) for r in reqs}

    def rewind(self, req, accepted: int) -> None:
        pass   # stateless


class DraftModelProposer:
    """Greedy draft-model proposer over its own paged cache.

    The draft pool is sized for every slot at full context, so draft
    allocation never fails and never preempts — scheduling pressure
    lives entirely in the target pool. Slot state is keyed by (slot,
    rid): a slot reused by a new request resets lazily, and a preempted
    request that resumes elsewhere simply re-feeds its stream (the
    stream is deterministic, so the rebuilt cache is identical).

    ``good[slot]`` counts the leading tokens of the TRUE stream whose
    K/V the draft cache holds; speculative feeds past it are rolled back
    by ``rewind`` after each verify (garbage K/V beyond ``good`` is
    never read — every dispatch masks by the lengths it passes)."""

    name = "draft"

    def __init__(self, draft_params, draft_config, *, slots: int,
                 block_size: int, attention: str = "auto"):
        from tpu_trainer.models.gpt import init_paged_cache

        mbpr = -(-draft_config.max_seq_len // block_size)
        self.config = dataclasses.replace(
            draft_config,
            dropout=0.0, attention_dropout=0.0,
            decode_paged=True, decode_ragged=False,
            paged_block_size=block_size,
            paged_num_blocks=slots * mbpr + 1,
            paged_max_blocks=mbpr,
            paged_kv_int8=False,
            paged_attention=attention,
        )
        self.params = draft_params
        self.slots = slots
        self.cache_state = PagedKVCache(self.config, slots)
        self.device_cache = init_paged_cache(self.config, slots)
        from tpu_trainer.serving.engine import _jitted_engine_step

        self._step_jit = _jitted_engine_step(self.config)
        self.good = np.zeros((slots,), np.int64)
        self.fed = np.zeros((slots,), np.int64)
        self.base = np.zeros((slots,), np.int64)
        self.slot_rid = -np.ones((slots,), np.int64)

    def _ensure_blocks(self, slot: int, n_tokens: int) -> None:
        cs = self.cache_state
        need = cs.blocks_for(n_tokens) - len(cs.slot_blocks(slot))
        if need > 0:
            got = cs.pool.alloc(need)
            assert got is not None, "draft pool sized for full contexts"
            cs.extend(slot, got)

    def _dispatch(self, reqs, ids, lengths, offsets, *, prefill,
                  hist_blocks, width):
        slots = self.slots
        tables = np.zeros_like(self.cache_state.tables)
        for r in reqs:
            tables[r.slot] = self.cache_state.tables[r.slot]
        zero_f = np.zeros((slots,), np.float32)
        one_f = np.ones((slots,), np.float32)
        zero_i = np.zeros((slots,), np.int32)
        keys = np.zeros((slots, 2), np.uint32)
        self.device_cache, tokens = self._step_jit(
            self.params, self.device_cache,
            jnp.asarray(tables), jnp.asarray(lengths),
            jnp.asarray(offsets), jnp.asarray(ids),
            zero_f, zero_i, one_f, keys, zero_i,
            k_cap=1, prefill=prefill, hist_blocks=hist_blocks,
        )
        return np.asarray(tokens)

    def propose(self, reqs, k_of: Dict[int, int]) -> Dict[int, List[int]]:
        from tpu_trainer.serving.engine import _bucket_pow2

        cs = self.cache_state
        for r in reqs:
            if self.slot_rid[r.slot] != r.rid:
                if cs.slot_blocks(r.slot):
                    cs.release(r.slot)
                self.slot_rid[r.slot] = r.rid
                self.good[r.slot] = 0
        max_m = max((k_of[r.rid] for r in reqs), default=0)
        if max_m <= 0:
            return {r.rid: [] for r in reqs}

        # Catch-up: feed each request's stream tokens the draft cache is
        # missing as one chunk at the cached offset — the exact chunked-
        # prefill contract the target engine uses.
        slots = self.slots
        feeds = {r.rid: r.context_len() - int(self.good[r.slot])
                 for r in reqs}
        width = min(_bucket_pow2(max(feeds.values()), lo=2),
                    cs.capacity_tokens())
        ids = np.zeros((slots, width), np.int32)
        lengths = np.zeros((slots,), np.int32)
        offsets = np.zeros((slots,), np.int32)
        max_hist = 0
        for r in reqs:
            stream = r.prompt + r.generated
            n_total = len(stream)
            cur = int(self.good[r.slot])
            self._ensure_blocks(r.slot, n_total + max_m - 1)
            ids[r.slot, :n_total - cur] = stream[cur:]
            lengths[r.slot] = n_total
            offsets[r.slot] = cur
            max_hist = max(max_hist, cur)
            self.base[r.slot] = n_total
            self.fed[r.slot] = n_total
        hist_blocks = 0
        if max_hist > 0:
            hist_blocks = min(
                _bucket_pow2(cs.blocks_for(max_hist), lo=1), cs.max_blocks)
        tokens = self._dispatch(reqs, ids, lengths, offsets, prefill=True,
                                hist_blocks=hist_blocks, width=width)
        proposals = {r.rid: [int(tokens[r.slot])] for r in reqs}

        # Roll forward: greedy single-token decode steps, feeding each
        # row its own previous draft.
        for t in range(1, max_m):
            ids1 = np.zeros((slots, 1), np.int32)
            lengths = np.zeros((slots,), np.int32)
            for r in reqs:
                ids1[r.slot, 0] = proposals[r.rid][-1]
                lengths[r.slot] = int(self.base[r.slot]) + t - 1
            tokens = self._dispatch(
                reqs, ids1, lengths, np.zeros((slots,), np.int32),
                prefill=False, hist_blocks=0, width=1)
            for r in reqs:
                proposals[r.rid].append(int(tokens[r.slot]))
                self.fed[r.slot] = int(self.base[r.slot]) + t
        return {r.rid: proposals[r.rid][:k_of[r.rid]] for r in reqs}

    def rewind(self, req, accepted: int) -> None:
        """Roll the draft cache back to the verified prefix: the first
        ``accepted`` drafts joined the true stream, anything fed beyond
        them is speculative garbage to overwrite on the next feed."""
        slot = req.slot
        if slot is None or self.slot_rid[slot] != req.rid:
            return
        self.good[slot] = min(self.base[slot] + accepted, self.fed[slot])


def draft_from_target(params, config, n_layers: int):
    """Cheap draft model: the target's FIRST ``n_layers`` scanned
    transformer layers with the embedding/norm shared (params['layers']
    leaves are stacked on axis 0). Zero extra training or storage — the
    classic truncated-self draft."""
    if not 1 <= n_layers < config.num_layers:
        raise ValueError(
            f"draft layers {n_layers} outside [1, {config.num_layers - 1}]")
    draft = dict(params)
    draft["layers"] = jax.tree_util.tree_map(
        lambda x: x[:n_layers], dict(params["layers"]))
    return draft, dataclasses.replace(config, num_layers=n_layers)


# --- adaptive draft length --------------------------------------------------


class AdaptiveK:
    """Per-request draft-length controller on an acceptance-rate EWMA:
    drafts dying (rate below ``low``) shrink K by one per step toward 1;
    drafts landing (rate above ``high``) regrow it toward ``k_max``."""

    def __init__(self, k_max: int, *, low: float = 0.3, high: float = 0.7,
                 alpha: float = 0.5):
        if k_max < 1:
            raise ValueError(f"k_max {k_max} < 1")
        self.k_max = k_max
        self.low = low
        self.high = high
        self.alpha = alpha
        self.k = k_max
        self.ewma = 1.0

    def update(self, drafted: int, accepted: int) -> int:
        if drafted > 0:
            rate = accepted / drafted
            self.ewma = (1 - self.alpha) * self.ewma + self.alpha * rate
            if self.ewma < self.low:
                self.k = max(1, self.k - 1)
            elif self.ewma > self.high:
                self.k = min(self.k_max, self.k + 1)
        return self.k


# --- the verifier -----------------------------------------------------------


def accept_emit(
    logits: jax.Array,      # [b, W, vocab] f32 — per-position target logits
    ids: jax.Array,         # [b, W] the fed window: [last token, drafts...]
    draft_lens: jax.Array,  # [b] true draft count per row (<= W-1)
    temps: jax.Array,       # [b]
    top_ks: jax.Array,      # [b]
    top_ps: jax.Array,      # [b]
    keys: jax.Array,        # [b, 2] uint32
    steps: jax.Array,       # [b] token index of the FIRST draw this step
    *,
    k_cap: int,
) -> Tuple[jax.Array, jax.Array]:
    """The acceptance rule, pure on logits (unit-testable without a
    model). Returns ``(emitted [b, W], n_acc [b])``: the host consumes
    ``emitted[:n_acc + 1]`` per row — accepted drafts followed by the
    target's correction (rejection) or bonus (all accepted) token."""
    b, w, vocab = logits.shape
    tgt = jnp.argmax(logits, axis=-1)                        # [b, W]
    scaled = filter_logits(
        logits.reshape(b * w, vocab),
        jnp.repeat(temps, w), jnp.repeat(top_ks, w),
        jnp.repeat(top_ps, w), k_cap=k_cap,
    ).reshape(b, w, vocab)
    probs = jax.nn.softmax(scaled, axis=-1)

    if w > 1:
        drafts = ids[:, 1:]                                  # [b, W-1]
        p_d = jnp.take_along_axis(
            probs[:, :-1], drafts[:, :, None], axis=-1)[..., 0]
        accept_u = jax.vmap(lambda kd, st: jax.vmap(
            lambda i: jax.random.uniform(jax.random.fold_in(
                jax.random.fold_in(kd, st + i), _SALT_ACCEPT))
        )(jnp.arange(w - 1)))(keys, steps)                   # [b, W-1]
        ok = jnp.where((temps > 0)[:, None],
                       accept_u < p_d, drafts == tgt[:, :-1])
        ok = ok & (jnp.arange(w - 1)[None, :] < draft_lens[:, None])
        n_acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=-1), axis=-1)
    else:
        n_acc = jnp.zeros((b,), jnp.int32)

    def draw_row(kd, st, row_scaled, row_ids, dlen):
        def one(i):
            kb = jax.random.fold_in(kd, st + i)
            bonus = jax.random.categorical(kb, row_scaled[i])
            if w == 1:
                return bonus
            # Residual draw for a rejection AT position i: the rejected
            # draft is row_ids[i + 1]; p with it masked, renormalized.
            d = row_ids[jnp.minimum(i + 1, w - 1)]
            resid = jnp.where(jnp.arange(vocab) == d, -jnp.inf,
                              row_scaled[i])
            rtok = jax.random.categorical(
                jax.random.fold_in(kb, _SALT_RESIDUAL), resid)
            return jnp.where(i < dlen, rtok, bonus)
        return jax.vmap(one)(jnp.arange(w))

    fix = jax.vmap(draw_row)(keys, steps, scaled, ids, draft_lens)
    iw = jnp.arange(w)[None, :]
    drafts_at = jnp.concatenate(
        [ids[:, 1:], jnp.zeros((b, 1), ids.dtype)], axis=1)  # draft at pos i
    emit_sampled = jnp.where(iw < n_acc[:, None], drafts_at, fix)
    emitted = jnp.where((temps > 0)[:, None], emit_sampled, tgt)
    return emitted, n_acc


def _verify_step(
    config, params, cache, tables, lengths, offsets, ids, draft_lens,
    temps, topks, topps, keys, steps, *, k_cap: int, hist_blocks: int,
):
    """One jitted verify step: broadcast host scheduling state into the
    cache pytree (same contract as ``engine._engine_step``), forward the
    [b, W] window through the chunked-prefill branch at each row's
    cached offset, keep ALL per-position logits, and run the acceptance
    rule in-graph — the host gets back tokens and counts, never a
    [b, W, vocab] logits transfer."""
    from tpu_trainer.models.gpt import GPT

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
        # Sharded replica: exact params all-gather in, pool-layout
        # constraint out — same contract as engine._engine_step.
        from tpu_trainer.parallel.mesh import tp_mesh
        from tpu_trainer.serving import sharding as tp_lib

        mesh = tp_mesh(config.paged_tp, config.paged_tp_devices)
        params = tp_lib.gather_params(params, mesh)
    (logits, _), vars_out = model.apply(
        {"params": params, "cache": cache}, ids, decode=True,
        mutable=["cache"],
    )
    cache_out = vars_out["cache"]
    if config.paged_tp > 1:
        cache_out = tp_lib.constrain_cache(cache_out, mesh, config.kv_heads)
    emitted, n_acc = accept_emit(
        logits.astype(jnp.float32), ids, draft_lens, temps, topks, topps,
        keys, steps, k_cap=k_cap)
    return cache_out, emitted, n_acc


# --- orchestration state ----------------------------------------------------


class SpecDecoder:
    """Host-side speculative-decode state for one engine: the proposer,
    per-request adaptive-K controllers, and the accepted-per-step
    histogram. The engine owns the device cache and the verify jit; this
    class owns everything that survives between steps."""

    def __init__(self, proposer, *, k: int, adaptive: bool = True):
        if k < 1:
            raise ValueError(f"spec_k {k} < 1")
        self.proposer = proposer
        self.k = k
        self.adaptive = adaptive
        self._ctl: Dict[int, AdaptiveK] = {}
        self.accept_hist: List[int] = []

    def k_for(self, req) -> int:
        """Draft budget for this request now: the adaptive controller's
        current K, capped so the window never drafts past max_new (an
        accepted draft + bonus may finish the request, but never
        overshoot it)."""
        k = self._ctl[req.rid].k if req.rid in self._ctl else self.k
        remaining = req.max_new_tokens - len(req.generated)
        return max(0, min(k, remaining - 1))

    def propose(self, reqs) -> Dict[int, List[int]]:
        k_of = {r.rid: self.k_for(r) for r in reqs}
        out = self.proposer.propose(reqs, k_of)
        return {rid: props[:k_of[rid]] for rid, props in out.items()}

    def observe(self, req, drafted: int, accepted: int) -> None:
        req.spec_drafted += drafted
        req.spec_accepted += accepted
        req.spec_steps += 1
        while len(self.accept_hist) <= accepted:
            self.accept_hist.append(0)
        self.accept_hist[accepted] += 1
        if self.adaptive and drafted > 0:
            ctl = self._ctl.setdefault(req.rid, AdaptiveK(self.k))
            ctl.update(drafted, accepted)
        self.proposer.rewind(req, accepted)

    def forget(self, req) -> None:
        """Drop per-request speculative state. Called on EVERY terminal
        transition — finish, preemption-free cancel, deadline expiry —
        so a cancelled request's draft-length controller (and, via the
        scheduler's vacate, its speculative KV tail blocks) can never
        leak: the proposer's slot mapping is keyed (slot, rid) and
        ``rewind`` guards against reuse, so forgetting here is the only
        cleanup a mid-speculation retire needs."""
        self._ctl.pop(req.rid, None)

    def reset_stats(self) -> None:
        self.accept_hist = []
