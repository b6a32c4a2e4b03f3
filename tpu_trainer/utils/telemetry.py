"""Training telemetry: in-graph stats, goodput ledger, spike early-warning.

Three pieces, one module (ISSUE 2):

1. **In-graph stats** — helpers the model/trainer call *inside* the jitted
   train step to compute per-layer gradient/parameter/update norms,
   activation RMS/absmax, and MoE router health (load fractions, routing
   entropy, drop rate) on-device. Collection is trace-time: the trainer
   compiles a second step variant with ``telemetry_on=True`` and calls it
   every ``--telemetry_interval`` steps, so steady-state steps run the
   original executable and pay nothing.

   The model side uses a trace-time capture stack (``capture()`` /
   ``record()``): model code checks ``capturing()`` while being traced and
   routes per-layer stats out through the layer loop's scan ``ys`` (rolled
   path) or a stacked Python list (unrolled path) — both land as
   ``[num_layers, ...]`` arrays. Pipeline schedules (``stage > 1``) skip
   activation capture (their layer loop bypasses normal AD); grad/param/
   update norms still work there because those are computed at the trainer
   level from the trees directly.

2. **Goodput ledger** — a host-side timer registry that attributes every
   wall-clock second of a run to compile, data-wait, step compute, eval,
   checkpoint save/restore, or rollback-replay. Tracked intervals are
   non-overlapping, so the attributed fractions always sum to <= 1.0 (the
   remainder is ``untracked``). ``productive_frac`` is the step-compute
   share — the "goodput" in the Google sense.

3. **Loss-spike early warning** — a rolling median/MAD z-score over the
   logged loss. Median/MAD (not mean/std) so the detector's own baseline is
   not dragged by the spike it is trying to flag; a spiking sample is never
   admitted to the window. Fires *before* the NaN that guards.check_finite
   would eventually see, giving the PR-1 rollback loop an earlier signal
   (``guards.LossSpikeError`` subclasses FloatingPointError so the existing
   handler catches it unchanged).
"""

from __future__ import annotations

import collections
import contextlib
import math
import statistics
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_trainer.utils import profiling

# --- trace-time capture ------------------------------------------------------
#
# A plain Python stack of dict containers. ``capture()`` is entered while the
# telemetry step variant is being *traced*; model code records tracers into
# the innermost container and the trainer reads them back out after
# ``model.apply`` returns — same trace level, so the tracers are valid.
# Steady-state steps trace with the stack empty and every ``capturing()``
# branch folds to the original graph.

_STACK: List["_Capture"] = []


class _Capture:
    def __init__(self, deep: bool = False):
        self.deep = deep
        self.stats: Dict[str, object] = {}


@contextlib.contextmanager
def capture(deep: bool = False):
    """Activate telemetry collection for model code traced in this block.

    ``deep=True`` additionally enables sites that change the graph's memory
    profile (e.g. logits stats, which make the otherwise-dead full-vocab
    logits live under fused/remat loss heads). Only the nan-scan debug
    forward asks for those; periodic telemetry train steps never do.
    """
    c = _Capture(deep=deep)
    _STACK.append(c)
    try:
        yield c
    finally:
        _STACK.pop()


def capturing(deep: bool = False) -> bool:
    """True while a ``capture()`` block is active (checked at trace time).
    ``capturing(deep=True)`` is True only inside a ``capture(deep=True)``."""
    if not _STACK:
        return False
    return _STACK[-1].deep if deep else True


def record(name: str, value) -> None:
    """Stash a (pytree of) array(s) under ``name`` in the active capture."""
    if _STACK:
        _STACK[-1].stats[name] = value


def pop(name: str):
    """Remove and return a recorded value (None when absent/inactive).

    Used for producer→consumer handoff within one trace: ``MoEMLP`` records
    its router stats, the enclosing ``TransformerBlock`` pops them into its
    per-layer telemetry dict.
    """
    if _STACK:
        return _STACK[-1].stats.pop(name, None)
    return None


# --- step counters ------------------------------------------------------------
#
# The same trace-time hand-off for the few device scalars the model counts in
# EVERY step (the expert layers' rows and load): ``Trainer._train_step`` opens
# ``counters()`` round ``model.apply`` and puts what was counted into the
# step's metrics. A model that counts nothing leaves the dict empty and the
# compiled step as it was.

_COUNTS: List[Dict[str, Dict[str, object]]] = []
# Key of a layer's own counters among what the layer loop stacks.
LAYER_COUNTS = "layer_counts"
# "mean" is for what is counted ONCE an apply (a loss term): it folds over
# micro-batches and never within one set.
_FOLD = {"sum": jnp.add, "max": jnp.maximum, "min": jnp.minimum}
_OVER = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min, "mean": jnp.mean}


@contextlib.contextmanager
def counters():
    """Open a set of counters, ``{kind: {name: value}}``: how a counter
    folds (``"sum"``, ``"max"``, ``"min"`` or ``"mean"``) is said where it is counted and travels
    as the tree's own key, through ``lax.scan`` as anything else does."""
    counts: Dict[str, Dict[str, object]] = {}
    _COUNTS.append(counts)
    try:
        yield counts
    finally:
        _COUNTS.pop()


def counting() -> bool:
    return bool(_COUNTS)


def count(name: str, value, reduce: str = "sum") -> None:
    """Fold ``value`` into the open counter ``name`` (or set it). A no-op
    outside ``counters()``."""
    if not _COUNTS:
        return
    kind = _COUNTS[-1].setdefault(reduce, {})
    kind[name] = _FOLD[reduce](kind[name], value) if name in kind else value


def count_all(counts: Dict[str, Dict[str, object]]) -> None:
    """Fold a whole set (an inner ``counters()``'s, reduced) into the open
    one."""
    for reduce, kind in counts.items():
        for name, value in kind.items():
            count(name, value, reduce)


def reduce_counts(stacked):
    """Counters stacked over a leading axis (the layers of a stack, the
    micro-batches of a step), each reduced by its kind."""
    return {reduce: {name: _OVER[reduce](v, axis=0)
                     for name, v in kind.items()}
            for reduce, kind in stacked.items()}


def flat_counts(counts) -> Dict[str, object]:
    """``{name: value}`` of a set of counters, for the step's metrics."""
    return {name: v for kind in counts.values() for name, v in kind.items()}


# --- on-device stat helpers --------------------------------------------------


def rms(x: jax.Array) -> jax.Array:
    """Root-mean-square of a tensor, accumulated in f32."""
    return jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32))))


def absmax(x: jax.Array) -> jax.Array:
    """Largest absolute entry, in f32."""
    return jnp.max(jnp.abs(x.astype(jnp.float32)))


def _sq_tail(leaf: jax.Array) -> jax.Array:
    """Sum of squares over all axes but the leading (layer) axis → [L]."""
    return jnp.sum(
        jnp.square(leaf.astype(jnp.float32)),
        axis=tuple(range(1, leaf.ndim)),
    )


def _tree_norm(tree) -> jax.Array:
    total = sum(
        jnp.sum(jnp.square(leaf.astype(jnp.float32)))
        for leaf in jax.tree_util.tree_leaves(tree)
    )
    return jnp.sqrt(total)


def group_norms(tree, stacked_key: str = "layers") -> Dict[str, jax.Array]:
    """Per-group L2 norms of a param-shaped tree.

    The ``stacked_key`` subtree (the nn.scan layer stack, leaves
    ``[num_layers, ...]``) reduces to one ``[num_layers]`` vector under
    ``"per_layer"``; every other top-level group reduces to a scalar. By
    construction ``sqrt(sum(per_layer**2) + sum(scalar**2)) ==
    optax.global_norm(tree)`` — pinned by tests/test_telemetry.py.
    """
    out: Dict[str, jax.Array] = {}
    for key in tree:
        if key.startswith(stacked_key):
            # "layers", or one stack a layer kind ("layers_conv_moe" ->
            # "per_layer_conv_moe") where a model's layers differ.
            per = None
            for leaf in jax.tree_util.tree_leaves(tree[key]):
                s = _sq_tail(leaf)
                per = s if per is None else per + s
            if per is not None:
                out["per_layer" + key[len(stacked_key):]] = jnp.sqrt(per)
        else:
            out[key] = _tree_norm(tree[key])
    return out


def combine_group_norms(norms: Dict[str, jax.Array]) -> jax.Array:
    """Recombine ``group_norms`` output into the global L2 norm."""
    total = sum(jnp.sum(jnp.square(v)) for v in norms.values())
    return jnp.sqrt(total)


def assemble(stats: Dict[str, object]) -> Dict[str, dict]:
    """Regroup a capture's raw stats into the nested telemetry dict.

    Input keys (all optional): ``embed_out`` / ``final_norm`` ({rms, absmax}
    scalars), ``layers`` (dict of ``[num_layers, ...]`` arrays; keys
    prefixed ``router_`` split out into their own group).
    Output: ``{"act": {...}, "router": {...}}`` — empty groups omitted.
    """
    act: Dict[str, object] = {}
    router: Dict[str, object] = {}
    for site in ("embed_out", "final_norm", "logits"):
        d = stats.get(site)
        if d:
            for k, v in d.items():
                act[f"{site}_{k}"] = v
    for key, layers in stats.items():
        if not key.startswith("layers") or not layers:
            continue
        # One stack a layer kind where layers differ: "layers_conv_moe"
        # lands under act["conv_moe"] / router["conv_moe"].
        kind = key[len("layers_"):]
        to_act = act.setdefault(kind, {}) if kind else act
        to_router = router.setdefault(kind, {}) if kind else router
        for k, v in layers.items():
            if k.startswith("router_"):
                to_router[k[len("router_"):]] = v
            else:
                to_act[k] = v
    act = {k: v for k, v in act.items() if not (isinstance(v, dict) and not v)}
    router = {k: v for k, v in router.items()
              if not (isinstance(v, dict) and not v)}
    out: Dict[str, dict] = {}
    if act:
        out["act"] = act
    if router:
        out["router"] = router
    return out


def reduce_micro(tree):
    """Collapse the leading micro-batch axis that ``lax.scan`` stacked onto
    per-micro forward stats: mean for RMS-like stats, max for absmax."""

    def walk(d):
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                out[k] = walk(v)
            elif k.endswith("absmax"):
                out[k] = jnp.max(v, axis=0)
            else:
                out[k] = jnp.mean(v, axis=0)
        return out

    return walk(tree)


def flatten_scalars(telem, prefix: str = "telemetry") -> Dict[str, float]:
    """Host-side flattening of the nested telemetry dict into JSONL/TB/wandb
    scalars: scalars pass through, ``[L]`` vectors become ``.../L03`` keys,
    higher-rank arrays (router load ``[L, E]``) emit per-layer min/max."""
    flat: Dict[str, float] = {}

    def walk(pfx, v):
        if isinstance(v, dict):
            for k in sorted(v):
                walk(f"{pfx}/{k}", v[k])
            return
        arr = np.asarray(jax.device_get(v))
        if arr.ndim == 0:
            flat[pfx] = float(arr)
        elif arr.ndim == 1:
            for i, val in enumerate(arr.tolist()):
                flat[f"{pfx}/L{i:02d}"] = float(val)
        else:
            rows = arr.reshape(arr.shape[0], -1)
            for i in range(arr.shape[0]):
                flat[f"{pfx}/L{i:02d}/max"] = float(rows[i].max())
                flat[f"{pfx}/L{i:02d}/min"] = float(rows[i].min())

    walk(prefix, telem)
    return flat


# --- nan scan ----------------------------------------------------------------

# Within-layer evaluation order of the forward: attention sublayer output,
# feed-forward sublayer output, block output (post-residual).
_LAYER_SITES = ("attn", "ffn", "block")


def nan_report(stats: Dict[str, dict]) -> dict:
    """Bisect which site first goes non-finite in a forward-only capture.

    ``stats``: the (device_get) output of ``Trainer.nan_scan`` — the
    ``assemble`` dict plus a ``loss`` scalar. Sites are checked in forward
    order: embedding → layer 0 attn → layer 0 ffn → layer 0 block → layer 1
    … → final norm → loss. Returns ``{"first_nan": {"layer", "site"} |
    None, "sites": [...]}`` where ``sites`` lists every non-finite site.
    """
    act = {k: np.asarray(jax.device_get(v))
           for k, v in stats.get("act", {}).items()}
    bad: List[dict] = []

    def check(site, layer, value):
        if value is not None and not np.all(np.isfinite(value)):
            bad.append({"site": site, "layer": layer})

    check("embed", None, act.get("embed_out_absmax"))
    per_layer = {s: act.get(f"{s}_absmax") for s in _LAYER_SITES}
    n_layers = next(
        (int(v.shape[0]) for v in per_layer.values() if v is not None), 0
    )
    for i in range(n_layers):
        for s in _LAYER_SITES:
            v = per_layer[s]
            if v is not None:
                check(s, i, v[i])
    check("final_norm", None, act.get("final_norm_absmax"))
    check("logits", None, act.get("logits_absmax"))
    loss = stats.get("loss")
    if loss is not None:
        check("loss", None, np.asarray(jax.device_get(loss)))
    return {"first_nan": bad[0] if bad else None, "sites": bad}


# --- goodput ledger ----------------------------------------------------------


class GoodputLedger:
    """Wall-clock attribution for a training run.

    Categories (``CATEGORIES``) are tracked via non-overlapping
    ``with ledger.track(cat):`` blocks, so the per-category fractions of
    total elapsed time sum to <= 1.0; the gap is reported as
    ``untracked_frac`` (host-side Python between blocks). ``record()``
    produces a JSONL-able dict (``kind: "goodput"``); ``summary_lines()``
    renders the human-readable end-of-run table.
    """

    CATEGORIES = (
        "compile",
        "data_wait",
        "step",
        "eval",
        "checkpoint_save",
        # Draining an in-flight async commit (utils/checkpoint.py
        # AsyncSaver.wait) before the next save/rollback/exit. With async
        # checkpointing on, "checkpoint_save" shrinks to the host-snapshot
        # cost and any residual commit time the run actually waited for
        # shows up here instead of inflating the save number.
        "checkpoint_commit_wait",
        "checkpoint_restore",
        "rollback_replay",
        # Elastic recovery: host-death detection -> first post-restart step,
        # accumulated per restart. Tracked by the run supervisor
        # (training/elastic.py) — a single trainer process can't see its own
        # death — and reported from the supervisor's own ledger/JSONL.
        "recovery",
        # Elastic grow-back: capacity-grant detection -> first step of the
        # re-expanded world (--allow_grow). Supervisor-side, like recovery;
        # time spent re-expanding is deliberate downtime, not a crash, so
        # it gets its own bucket (and its own analyze gate).
        "grow",
    )

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self._t0 = clock()
        self._acc: Dict[str, float] = {}
        # Padding-waste accounting (sequence packing): tokens fed vs tokens
        # that were real data. effective tok/s = non-pad tokens over step
        # time — the number packing moves.
        self._tokens = 0
        self._nonpad_tokens = 0

    def add_tokens(self, total: int, non_pad: Optional[int] = None) -> None:
        """Count one step's fed tokens; ``non_pad`` defaults to all of them
        (unpacked batches have no padding)."""
        self._tokens += int(total)
        self._nonpad_tokens += int(total if non_pad is None else non_pad)

    @contextlib.contextmanager
    def track(self, category: str):
        # Also a `goodput:<category>` span in any open profiler trace
        # (utils/profiling.py); the arithmetic below does not change.
        t = self._clock()
        try:
            with profiling.span("goodput:" + category):
                yield
        finally:
            self.add(category, self._clock() - t)

    def add(self, category: str, seconds: float) -> None:
        self._acc[category] = self._acc.get(category, 0.0) + seconds

    def seconds(self, category: str) -> float:
        return self._acc.get(category, 0.0)

    def total_seconds(self) -> float:
        return max(self._clock() - self._t0, 1e-9)

    def record(self, step: Optional[int] = None, final: bool = False) -> dict:
        total = self.total_seconds()
        tracked = sum(self._acc.values())
        rec = {
            "kind": "goodput",
            "total_seconds": total,
            "productive_frac": self._acc.get("step", 0.0) / total,
            "untracked_frac": max(0.0, 1.0 - tracked / total),
        }
        if step is not None:
            rec["step"] = step
        if final:
            rec["final"] = True
        for cat in self.CATEGORIES:
            if cat in self._acc:
                rec[f"{cat}_seconds"] = self._acc[cat]
                rec[f"{cat}_frac"] = self._acc[cat] / total
        if self._tokens:
            rec["tokens"] = self._tokens
            rec["non_pad_tokens"] = self._nonpad_tokens
            # A token ratio, NOT a wall-clock share — deliberately named
            # outside the "*_frac" namespace every goodput consumer sums.
            rec["non_pad_token_ratio"] = self._nonpad_tokens / self._tokens
            step_s = self._acc.get("step", 0.0)
            if step_s > 0:
                rec["effective_tok_per_sec"] = self._nonpad_tokens / step_s
        return rec

    def summary_lines(self) -> List[str]:
        rec = self.record(final=True)
        lines = [
            f"goodput: {rec['productive_frac']:6.1%} of "
            f"{rec['total_seconds']:.1f}s wall-clock was step compute"
        ]
        for cat in self.CATEGORIES:
            if f"{cat}_seconds" in rec:
                lines.append(
                    f"  {cat:<22} {rec[f'{cat}_seconds']:9.2f}s "
                    f"{rec[f'{cat}_frac']:6.1%}"
                )
        lines.append(
            f"  {'untracked':<22} "
            f"{rec['untracked_frac'] * rec['total_seconds']:9.2f}s "
            f"{rec['untracked_frac']:6.1%}"
        )
        if "non_pad_token_ratio" in rec:
            eff = rec.get("effective_tok_per_sec")
            eff_s = f", {eff:,.0f} effective tok/s" if eff else ""
            lines.append(
                f"  non-pad tokens: {rec['non_pad_tokens']:,} / "
                f"{rec['tokens']:,} ({rec['non_pad_token_ratio']:.1%}){eff_s}"
            )
        return lines


# --- loss-spike early warning ------------------------------------------------


class SpikeDetector:
    """Rolling median/MAD z-score over the training loss.

    ``update(loss)`` → ``(is_spike, z)``. A sample only counts as a spike
    once ``min_history`` normal samples are in the window (cold-start and
    the steep early-loss descent produce *negative* z — the median lags
    above the falling loss — and never fire). A spiking sample is not
    admitted to the window, so a sustained divergence keeps firing rather
    than normalizing itself. Non-finite losses are ignored here;
    ``guards.check_finite`` owns NaN.
    """

    def __init__(self, sigma: float = 6.0, window: int = 128,
                 min_history: int = 20):
        self.sigma = sigma
        self.window = window
        self.min_history = max(2, min_history)
        self._hist: List[float] = []

    def reset(self) -> None:
        """Forget history (call after a rollback — the restored loss level
        predates everything in the window)."""
        self._hist.clear()

    def update(self, loss) -> Tuple[bool, float]:
        if loss is None:
            return False, 0.0
        loss = float(loss)
        if not math.isfinite(loss):
            return False, 0.0
        z = 0.0
        if len(self._hist) >= self.min_history:
            med = statistics.median(self._hist)
            mad = statistics.median(abs(x - med) for x in self._hist)
            # 1.4826*MAD ≈ sigma for gaussian noise; the floor keeps a
            # perfectly flat window (MAD → 0) from flagging epsilon noise.
            scale = max(1.4826 * mad, 1e-3 * abs(med), 1e-8)
            z = (loss - med) / scale
            if self.sigma > 0 and z > self.sigma:
                return True, z
        if len(self._hist) >= self.window:
            self._hist.pop(0)
        self._hist.append(loss)
        return False, z


# --- deferred host sync ------------------------------------------------------


class DeferredFetcher:
    """Bounded window of in-flight per-step metric futures.

    jax dispatch is async: ``train_step`` returns device arrays that are
    still being computed, and the first ``float(loss)`` is where the host
    actually blocks. Reading step N's loss right after dispatching step N
    serializes host and device. Instead the CLI ``push()``es each step's
    metrics here and only materializes entries once they are ``window``
    steps old — by which time the device has long finished them, so the
    ``jax.device_get`` returns ~immediately and the host stays ahead of
    the device instead of in lockstep with it.

    Consequences the consumers accept: the spike detector, MetricLogger,
    and NaN guards see step N's numbers ``window`` steps late, so a
    divergence is detected up to ``window`` steps after it happened —
    harmless, because recovery rolls back to a checkpoint that predates
    the spike by far more than ``window`` steps anyway.

    ``push()`` returns the entries that matured this step (oldest first)
    as ``(step, host metrics, pushed_at)``, the last the host clock
    (``time.perf_counter``) at the entry's push, so that a consumer's rates
    are taken between dispatches and not between maturities;
    ``drain()`` materializes everything (eval/save/rollback/exit
    boundaries, where the state sync already paid the wait). ``transform``
    is applied to the fetched host copy at maturity — fault injections
    that mutate a loss must compose with the lagged value, not the live
    device array. ``window=0`` degrades to the old synchronous behavior.
    """

    def __init__(self, window: int = 2):
        self.window = max(0, int(window))
        self._q: collections.deque = collections.deque()

    def __len__(self) -> int:
        return len(self._q)

    def push(self, step: int, metrics: dict,
             transform=None) -> List[Tuple[int, dict, float]]:
        self._q.append((step, metrics, transform, time.perf_counter()))
        out = []
        while len(self._q) > self.window:
            out.append(self._fetch(self._q.popleft()))
        return out

    def drain(self) -> List[Tuple[int, dict, float]]:
        out = []
        while self._q:
            out.append(self._fetch(self._q.popleft()))
        return out

    @staticmethod
    def _fetch(entry) -> Tuple[int, dict, float]:
        step, metrics, transform, pushed_at = entry
        host = jax.device_get(metrics)
        if transform is not None:
            host = transform(host)
        return step, host, pushed_at


class MetricsBridge:
    """``MetricLogger`` observer that maps the training record stream
    onto an obs registry (the live /metrics plane, ISSUE 18).

    Attach via ``MetricLogger(observer=MetricsBridge(registry))`` — it
    shares the flight recorder's ``observe(record)`` contract, so the
    mapping from record fields to metrics lives in ONE place instead of
    being sprinkled through the run loop. Everything is sink-side: the
    records themselves (and therefore the JSONL stream) are identical
    with or without a bridge attached.

    Mapping: ``kind:"train"`` → step/loss/lr/tok-s/mfu gauges plus a
    step-interval latency histogram (from ``elapsed_s`` deltas);
    ``kind:"eval"`` → eval-loss gauge; ``kind:"goodput"`` → one gauge
    per ``*_frac`` category; ``kind:"rollback"`` / ``"recompile"`` →
    monotone counters. Unknown kinds count into
    ``train_records_total{kind=...}`` and are otherwise ignored.
    """

    _GAUGE_FIELDS = (
        ("loss", "train_loss", "Training loss (last logged step)"),
        ("lr", "train_learning_rate", "Learning rate"),
        ("grad_norm", "train_grad_norm", "Global gradient norm"),
        ("tokens_per_sec", "train_tokens_per_sec", "Windowed tokens/s"),
        ("effective_tokens_per_sec", "train_effective_tokens_per_sec",
         "Windowed non-pad tokens/s"),
        ("mfu", "train_mfu", "Model FLOPs utilization"),
        ("peak_mem_gb", "train_peak_mem_gb", "Peak device memory (GB)"),
    )

    def __init__(self, registry):
        self.registry = registry
        self._step = registry.gauge("train_step", "Last logged step")
        self._gauges = {
            field: registry.gauge(name, help_)
            for field, name, help_ in self._GAUGE_FIELDS}
        self._tokens = registry.counter(
            "train_tokens_total", "Tokens seen (cumulative)")
        self._step_seconds = registry.histogram(
            "train_step_seconds", "Wall-clock seconds per step "
            "(log-interval deltas averaged over the interval)")
        self._eval_loss = registry.gauge("train_eval_loss", "Held-out loss")
        self._goodput = registry.gauge(
            "train_goodput_frac", "Wall-clock fraction by category",
            labelnames=("category",))
        self._records = registry.counter(
            "train_records_total", "Records observed by kind",
            labelnames=("kind",))
        self._rollbacks = registry.counter(
            "train_rollbacks_total", "Checkpoint rollback-replay events")
        self._recompiles = registry.counter(
            "train_recompiles_total", "Train-step recompilations")
        self._last_elapsed: Optional[Tuple[int, float]] = None
        # Latest record per kind, for the /statusz human snapshot (the
        # registry keeps history-free scalars; statusz wants the whole
        # last record verbatim).
        self.n_records = 0
        self.last: dict = {}

    def statusz(self) -> dict:
        """/statusz payload: the last observed record of each kind."""
        return {"kind": "training", "records_observed": self.n_records,
                "last": dict(self.last)}

    def observe(self, record: dict) -> None:
        kind = str(record.get("kind", "train"))
        self.n_records += 1
        self.last[kind] = record
        self._records.labels(kind=kind).inc()
        if kind == "train":
            self._observe_train(record)
        elif kind == "eval" and "eval_loss" in record:
            self._eval_loss.set(float(record["eval_loss"]))
        elif kind == "goodput":
            for key, val in record.items():
                if key.endswith("_frac") and isinstance(val, (int, float)):
                    self._goodput.labels(
                        category=key[:-len("_frac")]).set(float(val))
        elif kind == "rollback":
            self._rollbacks.inc()
        elif kind == "recompile":
            self._recompiles.inc()

    def _observe_train(self, record: dict) -> None:
        step = record.get("step")
        if step is not None:
            self._step.set(float(step))
        for field, gauge in self._gauges.items():
            val = record.get(field)
            if isinstance(val, (int, float)):
                gauge.set(float(val))
        seen = record.get("tokens_seen")
        if isinstance(seen, (int, float)):
            self._tokens.set_function(lambda s=float(seen): s)
        elapsed = record.get("elapsed_s")
        if step is not None and isinstance(elapsed, (int, float)):
            if self._last_elapsed is not None:
                d_step = int(step) - self._last_elapsed[0]
                d_t = float(elapsed) - self._last_elapsed[1]
                if d_step > 0 and d_t >= 0:
                    self._step_seconds.observe(d_t / d_step)
            self._last_elapsed = (int(step), float(elapsed))
