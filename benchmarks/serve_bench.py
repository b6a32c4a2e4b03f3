"""Serving benchmark: continuous batching, chunked prefill, prefix caching.

Replays a request trace through the serving engine (``tpu_trainer.serving``)
and reports aggregate tokens/s, p50/p99 TTFT (arrival -> first token) and
per-token latency (TPOT), KV-pool occupancy, preemptions, prefill-chunk
counts and prefix-cache hit rate — then optionally runs the same requests
as sequential batch-1 ``generate_kv`` calls, the "one request at a time"
baseline continuous batching exists to beat.

Workloads (``--workload``):

- ``uniform``  — the original seeded open-loop Poisson trace.
- ``adversarial`` — short decode-heavy requests plus a few VERY long
  prompts arriving mid-decode: the monolithic-prefill worst case chunked
  prefill exists to fix (each long prefill stalls every in-flight decode).
- ``shared_prefix`` — every prompt opens with the same system-prompt
  prefix: the recompute-per-request worst case prefix caching exists to
  fix.
- ``repetitive`` — prompts built from a short repeated motif, so greedy
  continuations loop: the workload speculative decoding's n-gram
  (prompt-lookup) drafter exists for.

``--trace FILE`` replays a recorded trace instead: JSONL, one request per
line, ``{"prompt_len": int, "max_new": int, "arrival_time": float,
"prefix_id": str, "prefix_len": int, "prompt_tokens": [int]}`` (only
``prompt_len`` is required — length pairs from a real tokenizer log drop
in directly; tokens are synthesized deterministically from ``--seed``,
with requests sharing a ``prefix_id`` sharing their first ``prefix_len``
tokens — while ``prompt_tokens``, as recorded by ``infer.py --serve
--record_trace``, replays the REAL token ids when they fit the bench
vocab). ``benchmarks/traces/sample_trace.jsonl`` is a checked-in example
CI runs; ``benchmarks/traces/byte_trace.jsonl`` is a real byte-tokenizer
recording the smoke gate replays.

``--ab`` runs the workload twice as an A/B pair — unchunked vs chunked
for ``adversarial``, prefix cache off vs on for ``shared_prefix``, spec
decode off vs on when ``--spec`` is set.

``--replicas N`` routes the trace through the multi-replica front-end
(``serving/frontend.py``) instead of a single engine: ``--routing``
picks the policy, ``--ab`` becomes a random-vs-policy routing A/B over
the same multi-group shared-prefix trace (``--prefix-groups``, default
``2*replicas+2`` — more hot prefixes than replicas), ``--replica-kill
N`` adds a lane that kills one replica at front-end iteration N
mid-run, and ``--max-queue`` / ``--wait-watermark`` bound admission.
``--disagg P:D`` runs the disaggregated prefill/decode lanes over the
fleet KV store (``serving/kv_store.py``): a symmetric affinity
baseline, the same fleet sharing the digest-addressed store, and a
P-prefill/D-decode fleet migrating finished prefills — gated on fleet
hit rate beating the baseline and on migrated greedy streams staying
bit-identical to a single undisturbed engine.
Emits ``kind="frontend"`` records (aggregate tok/s, per-replica prefix
hit rates, reject rate, load imbalance, failover counts) gated by
``analyze.py --reject-tol`` and its categorical affinity-vs-random
check; the drain gate asserts every ACCEPTED request finished.

Observability rides every lane by default: per-request span timelines
(``kind="span"``), serve-loop time-series samples (``kind="serve_ts"``)
and incident records (``kind="incident"``, with ``--incident-dir``
flight-recorder dumps) land in ``--out`` next to the lane records, the
bench self-analyzes its own ``--out`` to stderr, span conservation is a
lane gate, ``--profile-trace DIR`` captures a ``jax.profiler`` trace of
the serve loop, and ``--no-trace`` is the bit-identity A/B.

    python benchmarks/serve_bench.py [--requests 32] [--concurrency 8]
    python benchmarks/serve_bench.py --workload adversarial --ab
    python benchmarks/serve_bench.py --workload repetitive --spec ngram --ab
    python benchmarks/serve_bench.py --workload shared_prefix --replicas 3 --ab
    python benchmarks/serve_bench.py --trace benchmarks/traces/sample_trace.jsonl
    python benchmarks/serve_bench.py --smoke          # CPU CI gate

Results go to stdout as a table plus one schema-versioned JSON record per
lane (``kind="serve"``); ``--out`` appends records to a JSONL file that
``python -m tpu_trainer.tools.analyze`` summarizes and ``--compare``
gates. ``--smoke`` shrinks everything to a tiny model (CI runs it under
``JAX_PLATFORMS=cpu``), adds a chunked long-prompt adversarial case, and
exits nonzero when p99 TTFT/TPOT break their gates or a trace fails to
drain.
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load_trace_file(path, *, vocab_size, max_seq_len, default_max_new,
                     seed, Request, SamplingParams, np):
    """JSONL trace -> fresh Request list. Deterministic in (file, seed):
    tails come from per-request streams, shared prefixes from per-id
    streams, so two requests with the same ``prefix_id`` really do share
    their first ``prefix_len`` tokens (the prefix cache can hit)."""
    import json

    prefix_tokens = {}

    def prefix(pid, n):
        have = prefix_tokens.get(pid, [])
        if len(have) < n:
            rs = np.random.RandomState(
                (zlib.crc32(str(pid).encode()) ^ seed) & 0x7FFFFFFF)
            have = rs.randint(1, vocab_size, size=n).tolist()
            prefix_tokens[pid] = have
        return have[:n]

    reqs = []
    with open(path) as fh:
        for i, line in enumerate(fh):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            rec = json.loads(line)
            real = rec.get("prompt_tokens")
            plen = int(rec.get("prompt_len", len(real) if real else 0))
            mnew = int(rec.get("max_new", default_max_new))
            if plen < 1 or plen + mnew > max_seq_len:
                raise ValueError(
                    f"{path}:{i + 1}: prompt_len {plen} + max_new {mnew} "
                    f"does not fit max_seq_len {max_seq_len}")
            if real is not None:
                # A real recording (infer.py --serve --record_trace):
                # replay the actual ids when the bench vocab covers them,
                # else fall back to length-only synthesis below.
                toks = [int(t) for t in real[:plen]]
                if len(toks) != plen or (toks and max(toks) >= vocab_size):
                    real = None
            if real is not None:
                prompt_ids = toks
            else:
                pfx_len = min(int(rec.get("prefix_len", 0)), plen)
                pid = rec.get("prefix_id")
                head = (prefix(pid, pfx_len)
                        if pid is not None and pfx_len else [])
                rs = np.random.RandomState(
                    (seed + 7919 * (i + 1)) & 0x7FFFFFFF)
                tail = rs.randint(
                    1, vocab_size, size=plen - len(head)).tolist()
                prompt_ids = [int(t) for t in head + tail]
            reqs.append(Request(
                rid=len(reqs),
                prompt=prompt_ids,
                max_new_tokens=mnew,
                sampling=SamplingParams(
                    temperature=float(rec.get("temperature", 0.0)),
                    top_k=int(rec.get("top_k", 0)),
                    top_p=float(rec.get("top_p", 1.0)),
                    seed=int(rec.get("seed", 1000 + i)),
                ),
                arrival_time=float(rec.get("arrival_time", 0.0)),
            ))
    if not reqs:
        raise ValueError(f"trace {path} has no requests")
    return reqs


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--concurrency", type=int, default=8,
                   help="engine slot batch (max concurrent requests)")
    p.add_argument("--rate", type=float, default=0.0,
                   help="Poisson arrival rate, req/s (<= 0: all at t=0)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--prompt-len", default="32,64",
                   help="min,max prompt length (uniform)")
    p.add_argument("--max-new", type=int, default=32,
                   help="tokens generated per request (uniform, so the "
                        "sequential baseline compiles once)")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--num-blocks", type=int, default=0,
                   help="KV pool blocks (0 = full-context sizing)")
    p.add_argument("--kv-int8", action="store_true")
    p.add_argument("--attention", default="auto",
                   choices=("auto", "reference", "kernel"))
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--max-seq-len", type=int, default=256)
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="chunked-prefill token budget per iteration "
                        "(0 = whole-prompt prefill)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="copy-on-write prefix sharing in the KV pool")
    p.add_argument("--workload", default="uniform",
                   choices=("uniform", "adversarial", "shared_prefix",
                            "repetitive"))
    p.add_argument("--spec", default="off",
                   choices=("off", "ngram", "draft"),
                   help="speculative decoding proposer; with --ab, lanes "
                        "become spec off vs on")
    p.add_argument("--spec-k", type=int, default=4,
                   help="max draft tokens per verify step")
    p.add_argument("--spec-draft-layers", type=int, default=1,
                   help="target layers sliced into the draft model "
                        "(--spec draft)")
    p.add_argument("--motif-len", type=int, default=6,
                   help="repetitive workload: repeated-motif period")
    p.add_argument("--trace", default=None, metavar="FILE",
                   help="replay a recorded JSONL trace instead of a "
                        "synthetic workload (see module docstring)")
    p.add_argument("--long-prompt-len", type=int, default=0,
                   help="adversarial workload: long-prompt length "
                        "(0 = max_seq_len - max_new)")
    p.add_argument("--n-long", type=int, default=2,
                   help="adversarial workload: number of long prompts")
    p.add_argument("--prefix-len", type=int, default=0,
                   help="shared_prefix workload: shared system-prompt "
                        "tokens (0 = half of min prompt len)")
    p.add_argument("--prefix-groups", type=int, default=0,
                   help="shared_prefix workload: distinct system prompts, "
                        "round-robin over requests (0 = auto: 1 for a "
                        "single engine, 2*replicas+2 with --replicas — "
                        "more groups than replicas is what routing can "
                        "exploit)")
    p.add_argument("--mesh-tensor", type=int, default=0,
                   help="tensor-parallel mesh width per replica: shard the "
                        "paged KV pool + attention heads over N devices "
                        "(one replica = one mesh). Alone, runs the "
                        "sharded-vs-single-device A/B (kind='serve' "
                        "records stamped with tp / per-device pool blocks "
                        "/ wire bytes per worker); with --workers, every "
                        "worker process serves from its own N-device mesh "
                        "with params shipped as 1/N shards. On CPU use "
                        "XLA_FLAGS=--xla_force_host_platform_device_count"
                        "=8 to fake the devices")
    p.add_argument("--device-block-budget", type=int, default=0,
                   help="with --mesh-tensor: KV pool blocks per DEVICE "
                        "(total pool = budget x shard factor; 0 = size "
                        "the total pool to the workload's concurrent "
                        "working set, so one device's budget is ~1/N of "
                        "what the trace needs — the capacity case "
                        "sharding exists for)")
    p.add_argument("--replicas", type=int, default=0,
                   help="run the multi-replica front-end with N engine "
                        "replicas instead of one engine (0 = single "
                        "engine; serving/frontend.py)")
    p.add_argument("--routing", default="affinity",
                   choices=("affinity", "random", "least_loaded"),
                   help="front-end routing policy (--replicas); with --ab "
                        "the lanes become random vs this policy")
    p.add_argument("--workers", type=int, default=0,
                   help="route the trace through N CROSS-PROCESS worker "
                        "replicas (the serving/worker.py RPC runtime) "
                        "behind the same front-end; with --ab, lane A is "
                        "the identical fleet in-process — the transport "
                        "A/B on one trace, stamping per-request RPC "
                        "overhead on the rpc record. A CPU-fleet tool: "
                        "a TPU chip belongs to ONE process, this parent "
                        "builds the params (so it holds the chip before "
                        "it spawns) and N workers cannot share what is "
                        "left — run it with JAX_PLATFORMS=cpu; on a chip "
                        "use --replicas (in-process fleet)")
    p.add_argument("--worker-kill", type=int, default=0,
                   help="with --workers: add a lane that SIGKILLs one "
                        "worker process at this front-end iteration "
                        "(worker_kill fault) and proves cross-process "
                        "failover drains")
    p.add_argument("--disagg", default=None, metavar="P:D",
                   help="disaggregated prefill/decode lanes: P prefill + "
                        "D decode replicas over the fleet KV block "
                        "store. Runs a symmetric affinity baseline, the "
                        "same fleet sharing the digest store, and the "
                        "role-split fleet migrating finished prefills; "
                        "gates fleet hit rate above the baseline and "
                        "migrated greedy streams bit-identical to a "
                        "single undisturbed engine. With --workers the "
                        "lanes run cross-process (kv_put/kv_get RPC)")
    p.add_argument("--kv-store-mb", type=int, default=0,
                   help="fleet KV block store host-tier budget in MiB "
                        "(0 = no store; --disagg defaults it to 64)")
    p.add_argument("--replica-kill", type=int, default=0,
                   help="with --replicas: add a lane that kills one "
                        "replica at this front-end iteration "
                        "(replica_kill fault) and proves failover drains")
    p.add_argument("--worker-hang", type=int, default=0,
                   help="with --workers: add a lane that SIGSTOPs one "
                        "worker process at this front-end iteration "
                        "(worker_hang fault) — a hang, not a death: the "
                        "per-call RPC timeout must fence the suspect and "
                        "failover must drain")
    p.add_argument("--net-fault", default=None, metavar="SPEC",
                   help="with --workers: add a lane armed with this "
                        "fault plan (e.g. net_delay@4,net_drop@8 — "
                        "kinds net_delay/net_drop/net_garble/net_hang), "
                        "driving transient and lethal transport faults "
                        "through the framed RPC layer")
    p.add_argument("--deadline", type=float, default=0.0,
                   help="front-end lanes: attach an absolute completion "
                        "deadline of arrival + this many seconds to every "
                        "request in the timed run (0 = off); expiries "
                        "count as deadline_exceeded, not drain failures, "
                        "and the record gains deadline-miss rate/slack")
    p.add_argument("--rpc-timeout", type=float, default=0.0,
                   help="with --workers: per-call RPC timeout in seconds "
                        "after the first step response (0 = supervisor "
                        "default); bounds the stall a hung worker causes")
    p.add_argument("--max-queue", type=int, default=0,
                   help="front-end per-replica waiting-queue bound "
                        "(0 = requests, i.e. no rejects from depth)")
    p.add_argument("--wait-watermark", type=float, default=0.0,
                   help="front-end oldest-wait admission watermark, "
                        "seconds (0 = off)")
    p.add_argument("--ab", action="store_true",
                   help="run the workload as an A/B lane pair: unchunked "
                        "vs chunked (adversarial), prefix off vs on "
                        "(shared_prefix); implies --no-baseline")
    p.add_argument("--no-baseline", action="store_true",
                   help="skip the sequential generate_kv comparison")
    p.add_argument("--out", default=None,
                   help="append the schema-versioned record(s) to this JSONL")
    p.add_argument("--smoke", action="store_true",
                   help="tiny-model CI gate: 16-request uniform trace plus "
                        "a chunked long-prompt adversarial case (implies "
                        "--no-baseline)")
    p.add_argument("--ttft-p99-gate", type=float, default=0.0,
                   help="seconds; > 0 gates p99 TTFT and exits 1 past it "
                        "(--smoke defaults this to 60)")
    p.add_argument("--tpot-p99-gate", type=float, default=0.0,
                   help="seconds; > 0 gates p99 TPOT and exits 1 past it "
                        "(--smoke defaults this to 60)")
    p.add_argument("--profile-trace", default=None, metavar="DIR",
                   help="capture a jax.profiler trace of the timed "
                        "serving iterations into DIR/<lane> (each engine "
                        "iteration wrapped in a StepTraceAnnotation "
                        "labelled 'serve'); single-engine lanes only")
    p.add_argument("--incident-dir", default=None, metavar="DIR",
                   help="front-end lanes: dump flight-recorder incident "
                        "reports (failover / worker death / fence / "
                        "drain failure) into DIR/<lane>/...")
    p.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                   help="front-end lanes: serve /metrics + /healthz + "
                        "/statusz on PORT (0 = ephemeral) for each timed "
                        "lane, scrape it live from a sidecar thread, and "
                        "gate on (a) every scrape answering under 1s even "
                        "mid-failover and (b) the terminal counters of "
                        "the final scrape agreeing EXACTLY with the "
                        "drain-time summary")
    p.add_argument("--no-trace", action="store_true",
                   help="disable span tracing + serve_ts telemetry (the "
                        "bit-identity A/B for 'tracing is free'; on by "
                        "default)")
    args = p.parse_args(argv)

    from tpu_trainer.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.profile_trace and (args.replicas > 0 or args.workers > 0):
        p.error("--profile-trace profiles the single-engine serve loop; "
                "drop --replicas/--workers to use it")
    if args.metrics_port is not None and not (
            args.replicas > 0 or args.workers > 0):
        p.error("--metrics-port drives the front-end lanes; add "
                "--replicas N or --workers N to use it")

    if args.workers > 0:
        if args.replicas > 0 and args.replicas != args.workers:
            p.error("--workers and --replicas are the same fleet size; "
                    "give one of them")
        # Worker lanes reuse the whole front-end lane machinery; the
        # fleet size IS the replica count, just cross-process.
        args.replicas = args.workers
    if (args.worker_hang > 0 or args.net_fault) and args.workers <= 0:
        p.error("--worker-hang/--net-fault need --workers (they fault "
                "the RPC transport)")

    args._disagg_roles = None
    if args.disagg:
        try:
            n_pre, n_dec = (int(x) for x in args.disagg.split(":"))
        except ValueError:
            n_pre = n_dec = 0
        if n_pre < 1 or n_dec < 1:
            p.error("--disagg wants P:D with at least one prefill and "
                    "one decode replica (e.g. 1:2)")
        if args.replicas not in (0, n_pre + n_dec):
            p.error(f"--disagg {args.disagg} is a fleet of "
                    f"{n_pre + n_dec}; --replicas/--workers disagree")
        args.replicas = n_pre + n_dec
        if args.kv_store_mb <= 0:
            args.kv_store_mb = 64
        args._disagg_roles = ["prefill"] * n_pre + ["decode"] * n_dec

    if args.smoke:
        args.requests = 16
        args.concurrency = 4
        args.hidden, args.layers, args.heads = 64, 2, 2
        args.vocab, args.max_seq_len = 256, 64
        args.prompt_len, args.max_new = "4,12", 8
        args.block_size = 8
        if args.mesh_tensor > 1:
            # Head-sharded lanes need heads % tp == 0; the 2-head smoke
            # model can only split 2 ways, so grow it just enough.
            args.heads = max(4, args.mesh_tensor)
        if args.replicas > 0:
            # Multi-replica smoke needs prompts long enough to hold full
            # shared blocks, else no prefix key exists and the routing
            # A/B degenerates to cold-start noise.
            args.prompt_len = "24,40"
            if args.prefix_len == 0:
                args.prefix_len = 16
        args.no_baseline = True
        if args.ttft_p99_gate == 0.0:
            args.ttft_p99_gate = 60.0
        if args.tpot_p99_gate == 0.0:
            args.tpot_p99_gate = 60.0
    if args.ab:
        args.no_baseline = True

    if args.mesh_tensor > 1:
        if args.heads % args.mesh_tensor:
            p.error(f"--mesh-tensor {args.mesh_tensor} must divide "
                    f"--heads {args.heads} (head-sharded decode)")
        if args.spec == "draft":
            p.error("--mesh-tensor composes with --spec ngram, not draft")

    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    if args.mesh_tensor > 1 and len(jax.devices()) < args.mesh_tensor:
        p.error(f"--mesh-tensor {args.mesh_tensor} needs that many "
                f"devices; found {len(jax.devices())} (on CPU, set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=8)")

    from tpu_trainer.models.config import GPTConfig
    from tpu_trainer.models.gpt import GPT, generate_kv
    from tpu_trainer.serving.engine import (
        ServingEngine, poisson_trace, request_metrics)
    from tpu_trainer.serving.scheduler import Request, SamplingParams
    from tpu_trainer.serving.tracing import span_record
    from tpu_trainer.utils.logging import SCHEMA_VERSION

    plo, phi = (int(x) for x in args.prompt_len.split(","))
    cfg = GPTConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_heads=args.heads,
        max_seq_len=args.max_seq_len, dropout=0.0, attention_dropout=0.0,
        dtype="float32", param_dtype="float32",
    )
    params = GPT(cfg).init(
        jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    def uniform_trace():
        # Fresh Request objects each run (the engine mutates them);
        # greedy sampling so both paths do identical per-token work.
        trace = poisson_trace(
            args.requests, vocab_size=args.vocab,
            rate=args.rate if args.rate > 0 else 1.0, seed=args.seed,
            prompt_len_range=(plo, phi),
            max_new_range=(args.max_new, args.max_new), temperature=0.0,
        )
        if args.rate <= 0:
            for r in trace:
                r.arrival_time = 0.0
        return trace

    def adversarial_trace():
        """Short decode-heavy requests at t=0; long prompts arrive while
        those decode, so their prefill lands mid-stream — the p99 TPOT
        adversary. Unchunked, each long prefill stalls every decode for
        the full prompt; chunked, for at most one chunk."""
        long_len = args.long_prompt_len or (args.max_seq_len - args.max_new)
        long_len = min(long_len, args.max_seq_len - args.max_new)
        n_long = min(args.n_long, args.requests - 1)
        rs = np.random.RandomState(args.seed)
        trace = []
        for i in range(args.requests - n_long):
            plen = int(rs.randint(plo, phi + 1))
            # Varied decode lengths desynchronize the slot waves: slots
            # free one at a time, so the FIFO-queued longs are admitted
            # while neighbouring slots are still mid-decode — the
            # contention the adversary needs (uniform max_new would let
            # whole waves finish together and the long prefills run
            # against empty slots, stalling nobody).
            mnew = int(rs.randint(max(2, args.max_new // 2),
                                  args.max_new * 3 // 2 + 1))
            trace.append(Request(
                rid=i,
                prompt=rs.randint(1, args.vocab, size=plen).tolist(),
                max_new_tokens=mnew,
                sampling=SamplingParams(temperature=0.0, seed=100 + i),
                arrival_time=0.0,
            ))
        for j in range(n_long):
            trace.append(Request(
                rid=args.requests - n_long + j,
                prompt=rs.randint(1, args.vocab, size=long_len).tolist(),
                max_new_tokens=args.max_new,
                sampling=SamplingParams(temperature=0.0, seed=900 + j),
                arrival_time=0.05 * (j + 1),   # mid-decode arrival
            ))
        return trace

    def shared_prefix_trace():
        """Prompts open with a shared system prompt; tails differ. With
        ``--prefix-groups G`` there are G distinct system prompts round-
        robined over the requests — the multi-replica case: more hot
        prefixes than replicas is the traffic affinity routing exploits
        (random routing scatters each group over every replica, so every
        replica pays every group's cold prefill)."""
        pfx_len = args.prefix_len or max(args.block_size, plo // 2)
        pfx_len = min(pfx_len, plo - 1)
        groups = args.prefix_groups
        if groups <= 0:
            groups = 1 if args.replicas <= 0 else 2 * args.replicas + 2
        rs = np.random.RandomState(args.seed)
        systems = [rs.randint(1, args.vocab, size=pfx_len).tolist()
                   for _ in range(groups)]
        trace = []
        for i in range(args.requests):
            plen = int(rs.randint(plo, phi + 1))
            tail = rs.randint(1, args.vocab, size=plen - pfx_len).tolist()
            trace.append(Request(
                rid=i,
                prompt=[int(t) for t in systems[i % groups] + tail],
                max_new_tokens=args.max_new,
                sampling=SamplingParams(temperature=0.0, seed=100 + i),
                arrival_time=0.0,
            ))
        return trace

    def repetitive_trace():
        """Prompts that loop a short motif. A tiny greedy model locks
        onto the periodicity almost immediately, so the n-gram drafter's
        prompt lookup predicts whole windows — the best case speculative
        decoding is benchmarked against (spec-off A lane shows the same
        stream one token per dispatch)."""
        rs = np.random.RandomState(args.seed)
        trace = []
        for i in range(args.requests):
            plen = int(rs.randint(plo, phi + 1))
            period = max(2, min(args.motif_len, plen))
            motif = rs.randint(1, args.vocab, size=period).tolist()
            prompt = (motif * (plen // period + 1))[:plen]
            trace.append(Request(
                rid=i,
                prompt=[int(t) for t in prompt],
                max_new_tokens=args.max_new,
                sampling=SamplingParams(temperature=0.0, seed=100 + i),
                arrival_time=0.0,
            ))
        return trace

    if args.trace:
        def make_trace():
            return _load_trace_file(
                args.trace, vocab_size=args.vocab,
                max_seq_len=args.max_seq_len, default_max_new=args.max_new,
                seed=args.seed, Request=Request,
                SamplingParams=SamplingParams, np=np)
        workload = f"trace:{os.path.basename(args.trace)}"
    else:
        make_trace = {"uniform": uniform_trace,
                      "adversarial": adversarial_trace,
                      "shared_prefix": shared_prefix_trace,
                      "repetitive": repetitive_trace}[args.workload]
        workload = args.workload

    if args.replicas > 0:
        return _run_frontend_lanes(args, params, cfg, make_trace, workload)
    if args.mesh_tensor > 1:
        return _run_mesh_lanes(args, params, cfg, make_trace, workload)

    draft_params = draft_config = None
    if args.spec == "draft":
        from tpu_trainer.serving import draft_from_target

        draft_params, draft_config = draft_from_target(
            params, cfg, args.spec_draft_layers)

    obs_records = []   # kind:"span"/"serve_ts" riding --out next to lanes

    def run_lane(lane, prefill_chunk, prefix_cache, trace_fn=make_trace,
                 wl=None, spec="off"):
        engine = ServingEngine(
            params, cfg, max_batch=args.concurrency,
            block_size=args.block_size, num_blocks=args.num_blocks or None,
            kv_int8=args.kv_int8, attention=args.attention,
            prefill_chunk_tokens=prefill_chunk or None,
            prefix_cache=prefix_cache,
            spec=spec, spec_k=args.spec_k,
            draft_params=draft_params, draft_config=draft_config,
            trace=not args.no_trace,
        )
        engine.run(trace_fn())        # warm-up: compiles every step shape
        engine.reset_stats()
        prof = None
        if args.profile_trace:
            from tpu_trainer.utils.profiling import WindowedTrace

            # One trace dir per lane; the window opens on the first timed
            # iteration (compiles were paid by the warm-up run above).
            prof = WindowedTrace(os.path.join(args.profile_trace, lane),
                                 start=0, num_steps=64, label="serve")
        try:
            finished = engine.run(trace_fn(), profiler=prof)
        finally:
            if prof is not None:
                prof.close()
        summary = engine.summary()
        lat = request_metrics(finished)
        drained = all(len(r.generated) >= min(r.max_new_tokens, 1)
                      for r in finished)
        record = {
            "kind": "serve",
            "schema_version": SCHEMA_VERSION,
            "workload": wl or workload,
            "lane": lane,
            "n_requests": len(finished),
            "concurrency": args.concurrency,
            "rate": args.rate,
            "block_size": args.block_size,
            "kv_int8": bool(args.kv_int8),
            "attention": args.attention,
            "prefill_chunk": int(prefill_chunk),
            "prefix_cache": bool(prefix_cache),
            "model": {"hidden": args.hidden, "layers": args.layers,
                      "heads": args.heads, "vocab": args.vocab},
            "tokens_per_s": round(summary["tokens_per_s"], 2),
            "generated_tokens": int(summary["generated_tokens"]),
            "wall_s": round(summary["wall_s"], 4),
            "occupancy_mean": round(summary["occupancy_mean"], 4),
            "occupancy_max": round(summary["occupancy_max"], 4),
            "preemptions": int(summary["preemptions"]),
            "prefill_iters": int(summary["prefill_iters"]),
            "decode_iters": int(summary["decode_iters"]),
            "prefill_chunks": int(summary["prefill_chunks"]),
            "prompt_tokens": int(summary["prompt_tokens"]),
            "prefix_hit_tokens": int(summary["prefix_hit_tokens"]),
            "prefix_hit_rate": round(summary["prefix_hit_rate"], 4),
            "prefix_evictions": int(summary["prefix_evictions"]),
            "pool_free_blocks": int(summary["pool_free_blocks"]),
            "pool_evictable_blocks": int(summary["pool_evictable_blocks"]),
            "pool_referenced_blocks": int(summary["pool_referenced_blocks"]),
            "prefix_index_entries": int(summary["prefix_index_entries"]),
        }
        if spec != "off":
            record.update({
                "spec": spec,
                "spec_k": args.spec_k,
                "spec_steps": int(summary["spec_steps"]),
                "spec_drafted": int(summary["spec_drafted"]),
                "spec_accepted": int(summary["spec_accepted"]),
                "spec_accept_mean": round(summary["spec_accept_mean"], 4),
                "spec_accept_rate": round(summary["spec_accept_rate"], 4),
                "spec_accept_hist": summary["spec_accept_hist"],
            })
        for name, series in lat.items():
            if series:
                record[f"{name}_p50_s"] = round(
                    float(np.percentile(series, 50)), 5)
                record[f"{name}_p99_s"] = round(
                    float(np.percentile(series, 99)), 5)
        if engine.tracer.enabled:
            cons = engine.tracer.conservation()
            record["span_events"] = len(engine.tracer)
            record["span_conservation_ok"] = bool(cons["ok"])
            for rid in engine.tracer.rids():
                obs_records.append(span_record(
                    rid, engine.tracer.events(rid), lane=lane))
        for ts in engine.serve_ts:
            ts = dict(ts)
            ts["lane"] = lane
            obs_records.append(ts)
        return record, drained, finished

    # --- lanes --------------------------------------------------------------
    if args.ab and args.spec != "off":
        # Speculative A/B: same workload/settings, proposer off vs on.
        lanes = [("spec_off", args.prefill_chunk, args.prefix_cache, "off"),
                 ("spec_on", args.prefill_chunk, args.prefix_cache,
                  args.spec)]
    elif args.ab:
        # Chunk default: big enough that per-iteration dispatch overhead
        # amortizes (short prompts stay single-chunk → tok/s parity with
        # the unchunked lane), small enough that a long prompt still
        # splits into several chunks with decodes interleaved between.
        chunk = args.prefill_chunk or 8 * args.block_size
        if args.workload == "shared_prefix" and not args.trace:
            lanes = [("no_prefix", args.prefill_chunk, False, "off"),
                     ("prefix", args.prefill_chunk, True, "off")]
        else:
            lanes = [("unchunked", 0, args.prefix_cache, "off"),
                     ("chunked", chunk, args.prefix_cache, "off")]
    else:
        lanes = [("serve", args.prefill_chunk, args.prefix_cache,
                  args.spec)]

    records, all_drained = [], True
    for lane, chunk, pfx, spec in lanes:
        record, drained, _ = run_lane(lane, chunk, pfx, spec=spec)
        all_drained = all_drained and drained
        records.append(record)
        _print_record(record)
        print(json.dumps(record), flush=True)

    record = records[-1]   # gates/baseline read the primary (last) lane

    if not args.no_baseline:
        # Sequential baseline: the SAME requests, one batch-1 greedy
        # generate_kv call each. Prompts pad to one shared width
        # (prompt_lens carries the true length) and max_new is uniform,
        # so the whole loop is one compile, warmed before timing.
        trace = make_trace()
        width = max(len(r.prompt) for r in trace)
        rows = np.zeros((len(trace), width), np.int32)
        lens = np.zeros((len(trace),), np.int32)
        for i, r in enumerate(trace):
            rows[i, : len(r.prompt)] = r.prompt
            lens[i] = len(r.prompt)

        def one(i):
            out = generate_kv(
                params, jax.random.PRNGKey(0), jnp.asarray(rows[i:i + 1]),
                config=cfg, max_new_tokens=args.max_new, temperature=0.0,
                top_k=1, prompt_lens=jnp.asarray(lens[i:i + 1]),
            )
            return int(out[-1, -1])   # host read = hard sync
        one(0)                        # warm
        t0 = time.perf_counter()
        for i in range(len(trace)):
            one(i)
        dt = time.perf_counter() - t0
        seq_tok_s = len(trace) * args.max_new / dt
        record["sequential_tokens_per_s"] = round(seq_tok_s, 2)
        record["concurrent_speedup"] = round(
            record["tokens_per_s"] / seq_tok_s, 3)
        print(f"serial  {record['sequential_tokens_per_s']:10.1f} tok/s "
              f"sequential generate_kv -> {record['concurrent_speedup']:.2f}x "
              f"from batching", flush=True)

    if args.ab and len(records) == 2:
        a, b = records
        tok_ratio = b["tokens_per_s"] / max(a["tokens_per_s"], 1e-9)
        line = (f"A/B     {b['lane']} vs {a['lane']}: "
                f"tok/s x{tok_ratio:.2f}")
        if a.get("tpot_p99_s") and b.get("tpot_p99_s"):
            line += (f", p99 TPOT x"
                     f"{a['tpot_p99_s'] / max(b['tpot_p99_s'], 1e-9):.2f} "
                     f"better")
        if b["prefix_cache"] and not a["prefix_cache"]:
            line += f", prefix hit rate {b['prefix_hit_rate']:.2f}"
        if b.get("spec", "off") != "off":
            line += (f", {b['spec_accept_mean']:.2f} accepted drafts/step "
                     f"(rate {b['spec_accept_rate']:.2f})")
        print(line, flush=True)

    if args.out:
        with open(args.out, "a") as fh:
            for rec in records:
                fh.write(json.dumps(rec) + "\n")

    failures = []
    if not all_drained:
        failures.append("trace did not drain (unfinished requests)")
    if args.ttft_p99_gate > 0:
        p99 = record.get("ttft_p99_s")
        if p99 is None or p99 > args.ttft_p99_gate:
            failures.append(
                f"p99 TTFT {p99}s > gate {args.ttft_p99_gate}s")
    if args.tpot_p99_gate > 0:
        p99 = record.get("tpot_p99_s")
        if p99 is None or p99 > args.tpot_p99_gate:
            failures.append(
                f"p99 TPOT {p99}s > gate {args.tpot_p99_gate}s")

    if args.smoke and not args.trace:
        # The long-prompt adversarial case: two near-max prompts land
        # mid-decode with chunked prefill + prefix cache on — the exact
        # configuration the fast path exists for — gated on p99 TPOT.
        adv_record, adv_drained, _ = run_lane(
            "smoke_adversarial", args.block_size, True,
            trace_fn=adversarial_trace, wl="adversarial")
        records.append(adv_record)
        _print_record(adv_record)
        print(json.dumps(adv_record), flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(json.dumps(adv_record) + "\n")
        if not adv_drained:
            failures.append("adversarial trace did not drain")
        p99 = adv_record.get("tpot_p99_s")
        if p99 is None or p99 > args.tpot_p99_gate:
            failures.append(
                f"adversarial p99 TPOT {p99}s > gate {args.tpot_p99_gate}s")

        # Speculative-decode case: the repetitive workload with the
        # n-gram drafter, gated on (a) greedy bit-parity with the
        # spec-off stream and (b) drafts actually landing.
        off_rec, off_drained, off_fin = run_lane(
            "smoke_spec_off", 0, False,
            trace_fn=repetitive_trace, wl="repetitive")
        spec_rec, spec_drained, spec_fin = run_lane(
            "smoke_spec", 0, False,
            trace_fn=repetitive_trace, wl="repetitive", spec="ngram")
        records.extend((off_rec, spec_rec))
        for rec in (off_rec, spec_rec):
            _print_record(rec)
            print(json.dumps(rec), flush=True)
            if args.out:
                with open(args.out, "a") as fh:
                    fh.write(json.dumps(rec) + "\n")
        if not (off_drained and spec_drained):
            failures.append("repetitive spec trace did not drain")
        if ([r.generated for r in spec_fin]
                != [r.generated for r in off_fin]):
            failures.append(
                "speculative greedy streams diverge from spec-off")
        if spec_rec["spec_accept_mean"] < 0.5:
            failures.append(
                f"spec accept mean {spec_rec['spec_accept_mean']} < 0.5 "
                f"on the repetitive workload")

        # Real-recording replay: the checked-in byte-tokenizer trace
        # (infer.py --serve --record_trace) replays its true token ids
        # (byte ids < 256 fit the smoke vocab) — gated on drain.
        byte_trace = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "traces", "byte_trace.jsonl")
        if os.path.exists(byte_trace):
            def byte_trace_fn():
                return _load_trace_file(
                    byte_trace, vocab_size=args.vocab,
                    max_seq_len=args.max_seq_len,
                    default_max_new=args.max_new, seed=args.seed,
                    Request=Request, SamplingParams=SamplingParams, np=np)
            bt_rec, bt_drained, _ = run_lane(
                "smoke_byte_trace", 0, False, trace_fn=byte_trace_fn,
                wl="trace:byte_trace.jsonl", spec="ngram")
            records.append(bt_rec)
            _print_record(bt_rec)
            print(json.dumps(bt_rec), flush=True)
            if args.out:
                with open(args.out, "a") as fh:
                    fh.write(json.dumps(bt_rec) + "\n")
            if not bt_drained:
                failures.append("byte trace did not drain")
        else:
            failures.append(f"missing checked-in trace {byte_trace}")

    # Span conservation is a lane-level gate, same rank as drain: a lane
    # whose tracer holds an opened-but-never-terminated timeline dropped
    # an event somewhere in the scheduler/engine path.
    for rec in records:
        if rec.get("span_conservation_ok") is False:
            failures.append(
                f"span conservation broken in lane {rec['lane']}")

    if args.out:
        if obs_records:
            with open(args.out, "a") as fh:
                for rec in obs_records:
                    fh.write(json.dumps(rec) + "\n")
        _analyze_out(args.out)

    for f in failures:
        print(f"GATE FAIL: {f}", file=sys.stderr, flush=True)
    return 1 if failures else 0


def _analyze_out(path: str) -> None:
    """Self-analysis: run the offline analyzer over the JSONL this bench
    just wrote, reporting to stderr (stdout keeps the per-lane JSON
    lines for drivers that parse them)."""
    from tpu_trainer.tools import analyze as analyze_lib

    try:
        report = analyze_lib.summarize(analyze_lib.load_records(path))
    except (Exception, SystemExit) as e:
        print(f"serve_bench: self-analysis failed: {e}", file=sys.stderr,
              flush=True)
        return
    for line in analyze_lib.render(report):
        print(f"serve_bench: {line}", file=sys.stderr, flush=True)


def _http_get(url: str, timeout: float = 5.0):
    """GET ``url``; returns ``(status_code, body_text)``. HTTP error
    statuses are answers, not exceptions (a healthz 503 IS the datum
    the readiness-flip gate wants)."""
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _parse_prom(text: str) -> dict:
    """Prometheus v0.0.4 text → ``{'name{labels}': float}`` (comment
    lines skipped). Just enough to compare scraped counters against
    the drain-time summary."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        try:
            out[key] = float(val)
        except ValueError:
            continue
    return out


class _MetricsScraper:
    """Sidecar thread scraping a live lane's ``/metrics`` + ``/healthz``.

    Polls every ``period_s``, recording per-scrape wall latency, any
    transport errors, and every healthz status code observed. The gate
    it feeds: the telemetry plane is host-side and lock-bounded, so a
    scrape must answer fast even while a worker is being SIGKILLed and
    its streams replayed — a stall past 1 s counts as an outage."""

    def __init__(self, url: str, period_s: float = 0.05):
        self.url = url
        self.period_s = period_s
        self.latencies: list = []
        self.errors: list = []
        self.healthz_codes: set = set()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="bench-metrics-scraper", daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.is_set():
            t0 = time.perf_counter()
            try:
                code, _ = _http_get(self.url + "/metrics", timeout=5.0)
                self.latencies.append(time.perf_counter() - t0)
                if code != 200:
                    self.errors.append(f"/metrics -> {code}")
            except Exception as e:
                self.errors.append(f"/metrics: {type(e).__name__}: {e}")
            try:
                code, _ = _http_get(self.url + "/healthz", timeout=5.0)
                self.healthz_codes.add(code)
            except Exception as e:
                self.errors.append(f"/healthz: {type(e).__name__}: {e}")
            self._stop.wait(self.period_s)

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=10.0)


def _mesh_pool_geometry(args, cfg, tp):
    """(device_budget, total_blocks, shard_factor) for the mesh lanes.

    The default budget sizes the TOTAL pool to the workload's concurrent
    working set — ``concurrency`` requests at the trace's longest
    prompt+decode — so one device's budget is ~1/factor of what the
    trace needs: the single-device twin only fits the workload because
    it is granted the whole fleet's blocks (the A/B stays block-for-
    block identical), while a real single device would be ``budget``
    blocks short. That is the capacity case sharding exists for, and
    ``peak_pool_blocks > device_pool_blocks`` in the record proves the
    row exercised it."""
    from tpu_trainer.serving.sharding import shard_factor

    factor = shard_factor(cfg.kv_heads, tp)
    if args.device_block_budget > 0:
        budget = args.device_block_budget
    else:
        plo, phi = (int(x) for x in args.prompt_len.split(","))
        per_req = -(-(phi + args.max_new) // args.block_size)
        budget = -(-(args.concurrency * per_req + 2) // factor)
    return budget, budget * factor, factor


def _run_mesh_lanes(args, params, cfg, make_trace, workload) -> int:
    """Sharded-decode lanes (``--mesh-tensor N`` without ``--workers``):
    the same trace through (A) a single-device engine granted the whole
    fleet's block budget and (B) a tensor-parallel engine whose KV pool
    is head-sharded over N devices at ``--device-block-budget`` blocks
    each — same total pool, same scheduling, so greedy streams must be
    token-identical (``tp_token_match``, a gate). A third leg replays
    the trace through a real cross-process worker whose params arrived
    as 1/N host shards (``WorkerSupervisor(param_shard_world=N)``),
    stamping ``wire_bytes_per_worker`` / ``wire_ratio`` (gated to
    ~full/N) and ``shard_stream_token_match`` on the sharded record."""
    import json

    import numpy as np

    from tpu_trainer.serving.engine import ServingEngine, request_metrics
    from tpu_trainer.serving.frontend import ServingFrontend
    from tpu_trainer.serving.remote import WorkerSupervisor
    from tpu_trainer.serving.tracing import span_record
    from tpu_trainer.utils.logging import SCHEMA_VERSION

    tp = args.mesh_tensor
    budget, total_blocks, factor = _mesh_pool_geometry(args, cfg, tp)
    obs_records = []

    def run_lane(lane, **kw):
        engine = ServingEngine(
            params, cfg, max_batch=args.concurrency,
            block_size=args.block_size, kv_int8=args.kv_int8,
            attention=args.attention,
            prefill_chunk_tokens=args.prefill_chunk or None,
            prefix_cache=args.prefix_cache,
            spec=args.spec, spec_k=args.spec_k,
            trace=not args.no_trace, **kw)
        engine.run(make_trace())      # warm-up: compiles every step shape
        engine.reset_stats()
        finished = engine.run(make_trace())
        s = engine.summary()
        lat = request_metrics(finished)
        drained = all(len(r.generated) >= min(r.max_new_tokens, 1)
                      for r in finished)
        record = {
            "kind": "serve",
            "schema_version": SCHEMA_VERSION,
            "workload": workload,
            "lane": lane,
            "n_requests": len(finished),
            "concurrency": args.concurrency,
            "block_size": args.block_size,
            "kv_int8": bool(args.kv_int8),
            "prefill_chunk": int(args.prefill_chunk),
            "prefix_cache": bool(args.prefix_cache),
            "model": {"hidden": args.hidden, "layers": args.layers,
                      "heads": args.heads, "vocab": args.vocab},
            "tokens_per_s": round(s["tokens_per_s"], 2),
            "generated_tokens": int(s["generated_tokens"]),
            "wall_s": round(s["wall_s"], 4),
            "occupancy_mean": round(s["occupancy_mean"], 4),
            "occupancy_max": round(s["occupancy_max"], 4),
            "preemptions": int(s["preemptions"]),
            "prefix_hit_rate": round(s["prefix_hit_rate"], 4),
            # Sharded-pool geometry (scheduler.pool_shard_stats): the
            # scheduler budgets blocks PER SHARD — every device holds
            # device_pool_blocks; head-sharding leaves block indices
            # meaningful fleet-wide, so tables/lengths stay replicated.
            "tp": int(s["tp"]),
            "device_pool_blocks": int(s["device_pool_blocks"]),
            "total_pool_blocks": int(s["total_pool_blocks"]),
            "peak_pool_blocks": int(round(
                s["occupancy_max"] * s["total_pool_blocks"])),
        }
        record["exceeds_device_budget"] = bool(
            record["peak_pool_blocks"] > budget)
        for name, series in lat.items():
            if series:
                record[f"{name}_p50_s"] = round(
                    float(np.percentile(series, 50)), 5)
                record[f"{name}_p99_s"] = round(
                    float(np.percentile(series, 99)), 5)
        if engine.tracer.enabled:
            record["span_events"] = len(engine.tracer)
            record["span_conservation_ok"] = bool(
                engine.tracer.conservation()["ok"])
            for rid in engine.tracer.rids():
                obs_records.append(span_record(
                    rid, engine.tracer.events(rid), lane=lane))
        streams = {r.rid: list(r.generated) for r in finished}
        return record, drained, streams

    failures = []
    rec_a, drained_a, streams_a = run_lane(
        "single", num_blocks=total_blocks)
    rec_b, drained_b, streams_b = run_lane(
        f"sharded_tp{tp}", mesh_tensor=tp, device_block_budget=budget)
    rec_b["tp_token_match"] = bool(streams_b == streams_a)
    rec_b["tok_s_vs_single"] = round(
        rec_b["tokens_per_s"] / max(rec_a["tokens_per_s"], 1e-9), 3)
    if not (drained_a and drained_b):
        failures.append("mesh lane did not drain")
    if not rec_b["tp_token_match"]:
        failures.append(
            f"sharded tp={tp} greedy streams diverge from single-device")

    # Shard-streaming leg: a REAL worker process builds the same tp
    # engine from 1/N param shards (two-phase host_shards layout) —
    # what actually crosses the wire to each host of a tp fleet.
    sup = WorkerSupervisor(
        params, cfg,
        engine_kwargs=dict(
            max_batch=args.concurrency, block_size=args.block_size,
            kv_int8=args.kv_int8, attention=args.attention,
            prefill_chunk_tokens=args.prefill_chunk or None,
            prefix_cache=args.prefix_cache,
            spec=args.spec, spec_k=args.spec_k,
            mesh_tensor=tp, device_block_budget=budget,
            trace=not args.no_trace),
        param_shard_world=tp,
        device_sets=[list(range(tp))])
    try:
        fe = ServingFrontend(
            params, cfg, replicas=1, routing="affinity", seed=args.seed,
            replica_factory=sup, trace=not args.no_trace)
        fin = fe.run(make_trace())
        worker_streams = {r.rid: list(r.generated) for r in fin}
    finally:
        sup.close()
    per_worker = max(sup.param_shard_bytes)
    rec_b["wire_bytes_per_worker"] = int(per_worker)
    rec_b["param_bytes_full"] = int(sup.param_bytes_full)
    rec_b["wire_ratio"] = round(
        per_worker * tp / max(sup.param_bytes_full, 1), 3)
    rec_b["shard_stream_token_match"] = bool(worker_streams == streams_b)
    # npz per-shard framing adds a little; anything near 1/tp of the
    # full tree per worker is "shipped as shards", 1.0x means it was
    # not sharded at all.
    if not 0.5 <= rec_b["wire_ratio"] <= 1.25:
        failures.append(
            f"wire bytes/worker {per_worker} x tp {tp} is "
            f"{rec_b['wire_ratio']}x the full tree "
            f"({sup.param_bytes_full}) — params were not shard-streamed")
    if not rec_b["shard_stream_token_match"]:
        failures.append(
            "shard-streamed worker streams diverge from the in-process "
            "sharded engine")

    records = [rec_a, rec_b]
    for rec in records:
        _print_record_mesh(rec)
        print(json.dumps(rec), flush=True)
    print(f"A/B     sharded_tp{tp} vs single: tok/s "
          f"x{rec_b['tok_s_vs_single']:.2f}, token match "
          f"{rec_b['tp_token_match']}, wire/worker "
          f"{rec_b['wire_bytes_per_worker']} B "
          f"({rec_b['wire_ratio']:.2f}x full/tp)", flush=True)

    for rec in records:
        if rec.get("span_conservation_ok") is False:
            failures.append(
                f"span conservation broken in lane {rec['lane']}")
    if args.ttft_p99_gate > 0:
        p99 = rec_b.get("ttft_p99_s")
        if p99 is None or p99 > args.ttft_p99_gate:
            failures.append(
                f"p99 TTFT {p99}s > gate {args.ttft_p99_gate}s")

    if args.out:
        with open(args.out, "a") as fh:
            for rec in records + obs_records:
                fh.write(json.dumps(rec) + "\n")
        _analyze_out(args.out)
    for f in failures:
        print(f"GATE FAIL: {f}", file=sys.stderr, flush=True)
    return 1 if failures else 0


def _print_record_mesh(r) -> None:
    print(f"{r['lane']:<12}{r['tokens_per_s']:10.1f} tok/s, tp={r['tp']} "
          f"pool {r['device_pool_blocks']} blocks/device x{r['tp']} = "
          f"{r['total_pool_blocks']} total (peak {r['peak_pool_blocks']}"
          f"{', exceeds one device' if r['exceeds_device_budget'] else ''})"
          f", {r['preemptions']} preemptions", flush=True)
    if "ttft_p99_s" in r:
        print(f"TTFT    p50 {r['ttft_p50_s'] * 1e3:8.1f} ms   "
              f"p99 {r['ttft_p99_s'] * 1e3:8.1f} ms", flush=True)
    if r.get("wire_bytes_per_worker") is not None:
        print(f"wire    {r['wire_bytes_per_worker']} B/worker shard vs "
              f"{r['param_bytes_full']} B full tree "
              f"({r['wire_ratio']:.2f}x full/tp), worker stream match "
              f"{r['shard_stream_token_match']}", flush=True)


def _run_frontend_lanes(args, params, cfg, make_trace, workload) -> int:
    """Multi-replica lanes (``--replicas N``): the same trace through the
    serving front-end, one lane per routing policy (``--ab``: random vs
    the chosen policy — the cache-affinity A/B) plus an optional
    mid-run ``--replica-kill`` failover lane. Emits ``kind="frontend"``
    records; the drain gate checks the front-end's conservation invariant
    (every ACCEPTED request finished — rejects are backpressure, not
    losses).

    ``--workers N`` runs the SAME lanes cross-process (each replica a
    ``serving/worker.py`` OS process behind the RPC socket): with
    ``--ab`` lane A is the identical fleet in-process, and the rpc
    record carries per-request RPC overhead — the per-rid
    submit-to-first-token delta vs the in-process lane on the same
    trace — as ``rpc_overhead_p50_s``/``rpc_overhead_p99_s``.
    ``--worker-kill I`` adds a lane that SIGKILLs a real worker process
    at front-end iteration I (the ``worker_kill`` fault);
    ``--worker-hang I`` adds the SIGSTOP fence drill (``worker_hang``:
    the per-call RPC timeout must bound the stall before failover); and
    ``--net-fault SPEC`` adds a lane armed with an arbitrary transport
    fault plan (``net_delay``/``net_drop``/``net_garble``/``net_hang``).
    ``--deadline D`` attaches ``arrival + D`` deadlines to the timed
    run's requests, so records gain deadline-miss rate/slack and the
    drain gate accepts ``deadline_exceeded`` as a terminal outcome."""
    import json

    import numpy as np

    from tpu_trainer.serving.engine import request_metrics
    from tpu_trainer.serving.frontend import ServingFrontend
    from tpu_trainer.serving.tracing import span_record
    from tpu_trainer.utils import faults
    from tpu_trainer.utils.logging import SCHEMA_VERSION

    engine_kwargs = dict(
        max_batch=args.concurrency, block_size=args.block_size,
        num_blocks=args.num_blocks or None, kv_int8=args.kv_int8,
        attention=args.attention,
        prefill_chunk_tokens=args.prefill_chunk or None,
        prefix_cache=True,
    )
    # Mesh-aware fleet: every replica serves from its own tp-device
    # mesh, replicas tiling the host's devices into disjoint sets.
    # Engine kwargs stay scalar-only (they cross the worker wire);
    # device sets travel separately — top-level spec key for workers,
    # replica_device_sets for in-process replicas.
    tp = getattr(args, "mesh_tensor", 0) or 0
    mesh_dsets = None
    if tp > 1:
        import jax

        budget, _, factor = _mesh_pool_geometry(args, cfg, tp)
        engine_kwargs.update(mesh_tensor=tp, num_blocks=None,
                             device_block_budget=budget)
        n_sets = max(1, len(jax.devices()) // tp)
        mesh_dsets = [[i * tp + j for j in range(tp)]
                      for i in range(n_sets)]
    supervisors = []
    kv_bytes = (args.kv_store_mb << 20) if args.kv_store_mb > 0 else 0
    disagg_roles = args._disagg_roles
    if disagg_roles and args.workers > 0:
        # Cross-process disagg lanes replay with open-loop arrivals even
        # when the workload says t=0: worker-local stores synchronize at
        # submit time from a catalog that learns off load snapshots, so
        # an all-at-once burst leaves nothing to share — steady-state
        # traffic (the shape the tier exists for) needs spacing wider
        # than the RPC step cadence. In-process fleets share one store
        # OBJECT, so late admissions hit it without any stagger. Greedy
        # streams are arrival-time independent, so the single-engine
        # pin and every stream gate still hold.
        inner_trace = make_trace

        def make_trace():
            trace = inner_trace()
            if all(r.arrival_time == 0.0 for r in trace):
                for i, r in enumerate(trace):
                    r.arrival_time = 0.1 * i
            return trace

    def make_supervisor(extra=None):
        from tpu_trainer.serving.remote import WorkerSupervisor

        sup_kwargs = {}
        if args.rpc_timeout > 0:
            sup_kwargs["rpc_timeout_s"] = args.rpc_timeout
        if tp > 1:
            # Shard-streaming launch: each worker's params arrive as a
            # 1/tp host-shard npz, and each worker owns one device set.
            sup_kwargs["param_shard_world"] = tp
            sup_kwargs["device_sets"] = mesh_dsets
        # Worker processes build their engines from this spec, so the
        # tracing switch must travel with it for the fleet to agree —
        # and so must the per-worker KV store budget (extra), which is
        # what the kv_put/kv_get verbs synchronize.
        sup = WorkerSupervisor(
            params, cfg,
            engine_kwargs=dict(engine_kwargs, trace=not args.no_trace,
                               **(extra or {})),
            **sup_kwargs)
        sup.prewarm(args.replicas)
        supervisors.append(sup)
        return sup

    def build(routing, sup=None, incident_dir=None, registry=None,
              kv=False, fleet_roles=None):
        kw = dict(engine_kwargs)
        if kv and kv_bytes:
            # In-process fleets build ONE shared KVBlockStore from this;
            # RPC fleets ignore it here (each worker holds a local store
            # from the supervisor's engine kwargs).
            kw["kv_store_bytes"] = kv_bytes
        return ServingFrontend(
            params, cfg, replicas=args.replicas, routing=routing,
            max_queue_depth=args.max_queue or max(args.requests, 1),
            wait_watermark=args.wait_watermark or None,
            seed=args.seed, replica_factory=sup,
            replica_device_sets=(mesh_dsets if sup is None else None),
            trace=not args.no_trace, incident_dir=incident_dir,
            registry=registry, replica_roles=fleet_roles,
            **kw,
        )

    def timed_trace():
        # Deadlines go on the TIMED run only: the warm-up run pays the
        # compiles, and expiring requests there would skip batch shapes
        # the timed run then compiles — polluting the miss metrics with
        # compile stalls the warm-up exists to remove.
        trace = make_trace()
        if args.deadline > 0:
            for r in trace:
                r.deadline = r.arrival_time + args.deadline
        return trace

    obs_records = []   # kind:"span"/"serve_ts"/"incident" riding --out
    metrics_failures = []   # --metrics-port gate violations, all lanes

    def run_lane(lane, routing, fault_spec=None, transport="inproc",
                 kv=False, fleet_roles=None):
        # Incidents dump per lane (the warm-up front-end gets no dir: a
        # compile-run artifact would shadow the timed drill's dump).
        inc_dir = (os.path.join(args.incident_dir, lane)
                   if args.incident_dir else None)
        registry = None
        if args.metrics_port is not None:
            from tpu_trainer.obs.metrics import MetricsRegistry
            registry = MetricsRegistry()
        sup = None
        if transport == "rpc":
            # Warm-up compiles inside the worker PROCESSES, so they must
            # survive into the timed run: reset() rebuilds each worker's
            # engine in place (per-config jit cache kept) and the timed
            # front-end adopts the warm processes from the pool.
            sup = make_supervisor(
                extra=({"kv_store_bytes": kv_bytes}
                       if kv and kv_bytes else None))
            build(routing, sup, kv=kv,
                  fleet_roles=fleet_roles).run(make_trace())
            sup.reset()
            fe = build(routing, sup, incident_dir=inc_dir,
                       registry=registry, kv=kv, fleet_roles=fleet_roles)
        else:
            # warm-up: compiles shapes
            build(routing, kv=kv, fleet_roles=fleet_roles).run(make_trace())
            fe = build(routing, incident_dir=inc_dir, registry=registry,
                       kv=kv, fleet_roles=fleet_roles)
        mserver = scraper = None
        if registry is not None:
            from tpu_trainer.obs.http import MetricsServer

            # The timed front-end only: the scrape plane watches the
            # drill itself, probes readiness off live replica count.
            mserver = MetricsServer(registry, port=args.metrics_port,
                                    statusz_fn=fe.statusz)
            mserver.health.add_probe("replicas_live", fe.ready)
            scraper = _MetricsScraper(mserver.url)
        try:
            if fault_spec:
                with faults.plan(fault_spec):
                    finished = fe.run(timed_trace())
            else:
                finished = fe.run(timed_trace())
        finally:
            if scraper is not None:
                scraper.stop()
        s = fe.summary()
        lat = request_metrics(finished)
        # Conservation at drain: every ACCEPTED request reached exactly
        # one terminal state (cancellation and deadline expiry are
        # outcomes, not losses).
        drained = int(s["accepted"]) == (
            int(s["finished"]) + int(s["cancelled"])
            + int(s["deadline_exceeded"]))
        record = {
            "kind": "frontend",
            "schema_version": SCHEMA_VERSION,
            "workload": workload,
            "lane": lane,
            "routing": routing,
            "transport": s["transport"],
            "workers": args.workers,
            "worker_deaths": int(s["worker_deaths"]),
            "replicas": args.replicas,
            "replicas_live": int(s["replicas_live"]),
            "n_requests": args.requests,
            "concurrency": args.concurrency,
            "block_size": args.block_size,
            "prefix_groups": args.prefix_groups,
            "model": {"hidden": args.hidden, "layers": args.layers,
                      "heads": args.heads, "vocab": args.vocab},
            "tokens_per_s": round(float(s["tokens_per_s"]), 2),
            "generated_tokens": int(s["generated_tokens"]),
            "wall_s": round(float(s["wall_s"]), 4),
            "submitted": int(s["submitted"]),
            "accepted": int(s["accepted"]),
            "rejected": int(s["rejected"]),
            "finished": int(s["finished"]),
            "cancelled": int(s["cancelled"]),
            "deadline_exceeded": int(s["deadline_exceeded"]),
            "failed": int(s["failed"]),
            "reject_rate": round(float(s["reject_rate"]), 4),
            "prompt_tokens": int(s["prompt_tokens"]),
            "prefix_hit_tokens": int(s["prefix_hit_tokens"]),
            "prefix_hit_rate": round(float(s["prefix_hit_rate"]), 4),
            # Token-weighted fleet aggregate plus the store-tier split:
            # store-hit tokens are prompt tokens whose prefill was
            # SKIPPED because the fleet store already held the blocks.
            "fleet_prefix_hit_rate": round(
                float(s["fleet_prefix_hit_rate"]), 4),
            "store_hit_tokens": int(s["store_hit_tokens"]),
            "store_hit_tokens_host": int(s["store_hit_tokens_host"]),
            "store_hit_tokens_disk": int(s["store_hit_tokens_disk"]),
            "migrations": int(s["migrations"]),
            "migrated_bytes": int(s["migrated_bytes"]),
            "load_imbalance_mean": round(float(s["load_imbalance_mean"]), 3),
            "load_imbalance_max": round(float(s["load_imbalance_max"]), 3),
            "failover_events": int(s["failover_events"]),
            "failed_over_requests": int(s["failed_over_requests"]),
            "wait_age_p50_s": round(float(s.get("wait_age_p50", 0.0)), 5),
            "wait_age_p99_s": round(float(s.get("wait_age_p99", 0.0)), 5),
            "routed": {k[len("routed_"):]: int(v) for k, v in s.items()
                       if str(k).startswith("routed_")},
            "per_replica": [
                {"replica": p["replica"], "alive": p["alive"],
                 "finished": p["finished"],
                 "generated_tokens": p["generated_tokens"],
                 "prefix_hit_rate": round(p["prefix_hit_rate"], 4),
                 **({"role": p["role"]} if p.get("role") else {}),
                 **({"store_hit_tokens": int(p["store_hit_tokens"])}
                    if p.get("store_hit_tokens") else {})}
                for p in s["per_replica"]],
        }
        for k in ("deadline_miss_rate", "deadline_miss_slack_p50",
                  "deadline_miss_slack_p99", "stall_recovery_max_s"):
            if k in s:
                record[k] = round(float(s[k]), 5)
        if "fenced" in s:
            record["fenced"] = int(s["fenced"])
        for name, series in lat.items():
            if series:
                record[f"{name}_p50_s"] = round(
                    float(np.percentile(series, 50)), 5)
                record[f"{name}_p99_s"] = round(
                    float(np.percentile(series, 99)), 5)
        if "span_conservation_ok" in s:
            record["span_events"] = int(s["span_events"])
            record["span_conservation_ok"] = bool(s["span_conservation_ok"])
        record["incidents"] = int(s["incidents"])
        if fe.tracer.enabled:
            for rid in fe.tracer.rids():
                obs_records.append(span_record(
                    rid, fe.tracer.events(rid), lane=lane))
        for ts in fe.serve_ts:
            ts = dict(ts)
            ts["lane"] = lane
            obs_records.append(ts)
        for inc in fe.incidents:
            inc = dict(inc)
            inc["lane"] = lane
            obs_records.append(inc)
        if mserver is not None:
            # Final scrape AFTER drain: every frontend_* counter is a
            # set_function mirror of the same stats summary() reads, so
            # the contract is exact equality, not a tolerance.
            final = _parse_prom(
                _http_get(mserver.url + "/metrics", timeout=5.0)[1])
            expect = {
                f'frontend_requests_total{{event="{ev}"}}': int(s[ev])
                for ev in ("submitted", "accepted", "rejected", "finished",
                           "cancelled", "deadline_exceeded", "failed")}
            expect["frontend_failover_events_total"] = int(
                s["failover_events"])
            expect["frontend_worker_deaths_total"] = int(
                s["worker_deaths"])
            if "fenced" in s:
                expect["frontend_fenced_total"] = int(s["fenced"])
            for key, want in sorted(expect.items()):
                got = final.get(key, 0.0)
                if int(got) != want:
                    metrics_failures.append(
                        f"lane {lane}: scraped {key} = {int(got)} != "
                        f"drain summary {want}")
            if scraper.errors:
                metrics_failures.append(
                    f"lane {lane}: {len(scraper.errors)} scrape errors "
                    f"(first: {scraper.errors[0]})")
            if not scraper.latencies:
                metrics_failures.append(
                    f"lane {lane}: no successful mid-run /metrics scrape")
            max_lat = max(scraper.latencies, default=0.0)
            if max_lat > 1.0:
                metrics_failures.append(
                    f"lane {lane}: /metrics stalled {max_lat:.3f}s > 1s "
                    f"during the drill")
            if 200 not in scraper.healthz_codes:
                metrics_failures.append(
                    f"lane {lane}: /healthz never reported ready (codes "
                    f"seen: {sorted(scraper.healthz_codes)})")
            # Teardown readiness flip: liveness off must read 503 while
            # the listener is still up (the final-scrape race).
            mserver.health.set_live(False)
            code, _ = _http_get(mserver.url + "/healthz", timeout=5.0)
            if code != 503:
                metrics_failures.append(
                    f"lane {lane}: /healthz returned {code} after the "
                    f"liveness flip (want 503)")
            record["metrics_port"] = mserver.port
            record["metrics_scrapes"] = len(scraper.latencies)
            record["metrics_scrape_max_s"] = round(max_lat, 4)
            mserver.close()
        if tp > 1:
            record["tp"] = tp
            record["device_pool_blocks"] = int(budget)
            record["total_pool_blocks"] = int(budget * factor)
            if transport == "rpc" and sup is not None \
                    and sup.param_shard_bytes:
                # What each worker of this fleet pulled over the wire:
                # its 1/tp host-shard npz, vs the full logical tree.
                per_worker = max(sup.param_shard_bytes)
                record["wire_bytes_per_worker"] = int(per_worker)
                record["param_bytes_full"] = int(sup.param_bytes_full)
                record["wire_ratio"] = round(
                    per_worker * tp / max(sup.param_bytes_full, 1), 3)
                if not 0.5 <= record["wire_ratio"] <= 1.25:
                    metrics_failures.append(
                        f"lane {lane}: wire ratio {record['wire_ratio']} "
                        f"— params were not shard-streamed (~1/tp each)")
        ttfts = {r.rid: r.first_token_at - r.arrival_time
                 for r in finished if r.first_token_at is not None}
        streams = {r.rid: list(r.generated) for r in finished}
        return record, drained, ttfts, streams

    workers_mode = args.workers > 0
    NO_KV = (False, None)
    if disagg_roles:
        # Disaggregation lanes: (A) the symmetric fleet on the chosen
        # routing with per-replica caches only — the baseline the fleet
        # store must beat; (B) the same symmetric fleet routed for LOAD
        # (least_loaded scatters every prefix group over every replica —
        # the per-replica-cache worst case) but sharing the digest
        # store, which turns the scattered misses back into hits; (C)
        # the role-split fleet migrating finished prefills to decode
        # replicas. Cross-process with --workers (worker-local stores
        # over the kv verbs).
        tport = "rpc" if workers_mode else "inproc"
        lanes = [("affinity_base", args.routing, None, tport, False, None),
                 ("kv_store", "least_loaded", None, tport, True, None),
                 ("disagg", args.routing, None, tport, True, disagg_roles)]
        if args.worker_kill > 0 and workers_mode:
            # The role-split fleet again, SIGKILLing a worker mid-run
            # (TPU_TRAINER_FAULT_REPLICA=0 targets the prefill replica —
            # the interesting death: it dies holding streams mid-
            # migration). Roles are a performance shape, never a
            # correctness dependency, so the decode survivors must
            # prefill the failed-over work themselves and still match
            # the single-engine pin bit-exactly.
            lanes.append(("disagg_kill", args.routing,
                          f"worker_kill@{args.worker_kill}", "rpc",
                          True, disagg_roles))
    elif workers_mode:
        # Transport A/B: the same trace, same routing, same fleet size —
        # in-process vs one-OS-process-per-replica over RPC.
        lanes = ([("inproc", args.routing, None, "inproc") + NO_KV]
                 if args.ab else [])
        lanes.append(("rpc", args.routing, None, "rpc") + NO_KV)
        if args.worker_kill > 0:
            lanes.append(("worker_kill", args.routing,
                          f"worker_kill@{args.worker_kill}", "rpc") + NO_KV)
        if args.worker_hang > 0:
            lanes.append(("worker_hang", args.routing,
                          f"worker_hang@{args.worker_hang}", "rpc") + NO_KV)
        if args.net_fault:
            lanes.append(
                ("net_fault", args.routing, args.net_fault, "rpc") + NO_KV)
    elif args.ab:
        b_routing = args.routing if args.routing != "random" else "affinity"
        lanes = [("random", "random", None, "inproc") + NO_KV,
                 (b_routing, b_routing, None, "inproc") + NO_KV]
    else:
        lanes = [(args.routing, args.routing, None, "inproc") + NO_KV]
    if args.replica_kill > 0 and not workers_mode and not disagg_roles:
        lanes.append(("replica_kill", args.routing,
                      f"replica_kill@{args.replica_kill}", "inproc") + NO_KV)

    records, all_drained, lane_ttfts, lane_streams = [], True, {}, {}
    try:
        for lane, routing, spec, transport, kv, fleet_roles in lanes:
            rec, drained, ttfts, streams = run_lane(
                lane, routing, spec, transport, kv, fleet_roles)
            all_drained = all_drained and drained
            records.append(rec)
            lane_ttfts[lane] = ttfts
            lane_streams[lane] = streams
    finally:
        for sup in supervisors:
            sup.close()

    tp_failures = []
    if tp > 1 and records:
        # Sharded parity across lanes: sampling is (seed, token_index)-
        # keyed and scheduling is shared, so every lane of the same
        # trace — including the fault drills, whose failover preserves
        # stream identity — must agree token-for-token on every request
        # both lanes finished. Divergence means the sharded compute
        # path leaked into the tokens.
        base_lane = records[0]["lane"]
        for rec in records:
            if rec["lane"] == "rpc":
                base_lane = "rpc"      # the no-fault cross-process lane
        base = lane_streams[base_lane]
        for rec in records:
            if rec["lane"] == base_lane:
                continue
            s = lane_streams[rec["lane"]]
            rec["tp_token_match"] = all(
                base[rid] == gen for rid, gen in s.items() if rid in base)
            if not rec["tp_token_match"]:
                tp_failures.append(
                    f"lane {rec['lane']}: sharded streams diverge from "
                    f"lane {base_lane}")

    disagg_failures = []
    if disagg_roles and records:
        # The correctness pin: a single undisturbed engine serves the
        # whole trace alone. Store fills and prefill->decode migration
        # are pure data movement of bit-exact K/V, so every store lane's
        # greedy streams must match it token for token; and the fleet
        # store must earn its bytes — token-weighted fleet hit rate
        # strictly above the per-replica-cache baseline.
        from tpu_trainer.serving.engine import ServingEngine

        pin_eng = ServingEngine(
            params, cfg, max_batch=args.concurrency,
            block_size=args.block_size, num_blocks=args.num_blocks or None,
            kv_int8=args.kv_int8, attention=args.attention,
            prefill_chunk_tokens=args.prefill_chunk or None,
            prefix_cache=True, trace=False)
        pin = {r.rid: list(r.generated)
               for r in pin_eng.run(make_trace())}
        base = next(r for r in records if r["lane"] == "affinity_base")
        for rec in records:
            if rec["lane"] == "affinity_base":
                continue
            streams = lane_streams[rec["lane"]]
            rec["disagg_token_match"] = all(
                pin[rid] == gen for rid, gen in streams.items()
                if rid in pin)
            rec["baseline_prefix_hit_rate"] = base["prefix_hit_rate"]
            if not rec["disagg_token_match"]:
                disagg_failures.append(
                    f"lane {rec['lane']}: store-filled/migrated greedy "
                    f"streams diverge from the single undisturbed engine")
            if rec["store_hit_tokens"] < 1:
                disagg_failures.append(
                    f"lane {rec['lane']}: the fleet store skipped no "
                    f"prefill tokens (store_hit_tokens == 0)")
        # The scattered-but-shared lane must RECOVER affinity's hit rate
        # (its win is load balance at equal hits: every group's cold
        # prefill is paid once fleet-wide either way); the disagg lane
        # must strictly BEAT it — decode admission skips prefill work
        # the prefill tier already paid.
        # In-process the store is one shared object, so recovery is
        # exact up to a small admission-order slack. Cross-process the
        # sync is submit-time opportunistic (catalog learns from load
        # snapshots), so the recovery RATE depends on arrival spacing
        # vs step cadence — there the store_hit_tokens gate above
        # proves the verbs moved real blocks, and the recovered rate is
        # reported, not gated.
        kvr = next(r for r in records if r["lane"] == "kv_store")
        if (not workers_mode and kvr["fleet_prefix_hit_rate"]
                < base["prefix_hit_rate"] - 0.05):
            disagg_failures.append(
                f"lane kv_store: fleet prefix hit rate "
                f"{kvr['fleet_prefix_hit_rate']} below the per-replica "
                f"affinity baseline {base['prefix_hit_rate']}")
        dis = next(r for r in records if r["lane"] == "disagg")
        if dis["fleet_prefix_hit_rate"] <= base["prefix_hit_rate"]:
            disagg_failures.append(
                f"lane disagg: fleet prefix hit rate "
                f"{dis['fleet_prefix_hit_rate']} not strictly above the "
                f"per-replica affinity baseline {base['prefix_hit_rate']}")
        if dis["migrations"] < 1:
            disagg_failures.append(
                "disagg lane migrated no requests (prefill replicas "
                "never handed a stream to a decode replica)")
        kill = next((r for r in records if r["lane"] == "disagg_kill"),
                    None)
        if kill is not None and not kill.get("worker_deaths"):
            disagg_failures.append(
                "disagg_kill lane observed no worker death (the fault "
                "never fired — nothing was proven)")

    if workers_mode and args.ab and len(records) >= 2:
        a = next(r for r in records if r["transport"] == "inproc")
        b = next(r for r in records if r["transport"] == "rpc")
        # Per-request RPC overhead: the submit-to-first-token delta of
        # the SAME rid on the SAME trace, rpc minus in-process — what
        # the wire (framing + socket + worker dispatch) actually costs,
        # with queueing/compile effects cancelled by identical routing.
        deltas = [lane_ttfts[b["lane"]][rid] - t
                  for rid, t in lane_ttfts[a["lane"]].items()
                  if rid in lane_ttfts[b["lane"]]]
        if deltas:
            b["rpc_overhead_p50_s"] = round(
                float(np.percentile(deltas, 50)), 5)
            b["rpc_overhead_p99_s"] = round(
                float(np.percentile(deltas, 99)), 5)
        b["inproc_tokens_per_s"] = a["tokens_per_s"]
        b["tok_s_vs_inproc"] = round(
            b["tokens_per_s"] / max(a["tokens_per_s"], 1e-9), 3)
    elif args.ab and len(records) >= 2:
        a, b = records[0], records[1]
        # The categorical affinity-vs-random gate (tools/analyze.py)
        # reads both hit rates out of the SAME A/B record.
        b["random_prefix_hit_rate"] = a["prefix_hit_rate"]
        b["tok_s_vs_random"] = round(
            b["tokens_per_s"] / max(a["tokens_per_s"], 1e-9), 3)

    for rec in records:
        _print_frontend_record(rec)
        print(json.dumps(rec), flush=True)
    if disagg_roles and records:
        base = next(r for r in records if r["lane"] == "affinity_base")
        dis = next(r for r in records if r["lane"] == "disagg")
        print(f"A/B     disagg {args.disagg} vs symmetric baseline: "
              f"fleet hit {dis['fleet_prefix_hit_rate']:.2f} vs "
              f"{base['prefix_hit_rate']:.2f}, {dis['migrations']} "
              f"migrations ({dis['migrated_bytes']} B), store-hit "
              f"tokens {dis['store_hit_tokens']}, stream match "
              f"{'bit-exact' if dis['disagg_token_match'] else 'DIVERGED'}",
              flush=True)
    elif workers_mode:
        if args.ab and len(records) >= 2:
            b = next(r for r in records if r["transport"] == "rpc")
            print(f"A/B     rpc vs in-process: tok/s "
                  f"x{b['tok_s_vs_inproc']:.2f}, RPC overhead p50 "
                  f"{(b.get('rpc_overhead_p50_s') or 0) * 1e3:.1f} ms "
                  f"p99 {(b.get('rpc_overhead_p99_s') or 0) * 1e3:.1f} ms",
                  flush=True)
    elif args.ab and len(records) >= 2:
        a, b = records[0], records[1]
        print(f"A/B     {b['lane']} vs random routing: prefix hit rate "
              f"{b['prefix_hit_rate']:.2f} vs {a['prefix_hit_rate']:.2f}, "
              f"tok/s x{b['tok_s_vs_random']:.2f}", flush=True)

    if args.out:
        with open(args.out, "a") as fh:
            for rec in records + obs_records:
                fh.write(json.dumps(rec) + "\n")
        _analyze_out(args.out)

    failures = []
    if not all_drained:
        failures.append(
            "front-end did not drain (an accepted request never reached "
            "a terminal state: finished/cancelled/deadline_exceeded)")
    for rec in records:
        if rec.get("span_conservation_ok") is False:
            failures.append(
                f"span conservation broken in lane {rec['lane']}")
    if args.ttft_p99_gate > 0:
        p99 = records[-1].get("ttft_p99_s")
        if p99 is None or p99 > args.ttft_p99_gate:
            failures.append(
                f"p99 TTFT {p99}s > gate {args.ttft_p99_gate}s")
    failures.extend(tp_failures)
    failures.extend(disagg_failures)
    failures.extend(metrics_failures)
    for f in failures:
        print(f"GATE FAIL: {f}", file=sys.stderr, flush=True)
    return 1 if failures else 0


def _print_frontend_record(r) -> None:
    print(f"{r['lane']:<12}{r['tokens_per_s']:10.1f} tok/s aggregate, "
          f"{r['replicas']} replicas ({r['replicas_live']} live, routing "
          f"{r['routing']}), {r['accepted']}/{r['submitted']} accepted, "
          f"{r['generated_tokens']} tokens, {r['wall_s']:.2f}s", flush=True)
    if r.get("cancelled") or r.get("deadline_exceeded"):
        line = (f"outcome {r['finished']} finished, "
                f"{r['cancelled']} cancelled, "
                f"{r['deadline_exceeded']} deadline_exceeded")
        if r.get("deadline_miss_rate") is not None:
            line += (f" | deadline miss rate {r['deadline_miss_rate']:.3f} "
                     f"slack p99 {r['deadline_miss_slack_p99']:.3f}s")
        print(line, flush=True)
    if r.get("transport") == "rpc":
        line = (f"rpc     {r['workers']} worker processes, "
                f"{r['worker_deaths']} deaths")
        if r.get("fenced"):
            line += f", {r['fenced']} fenced"
        if r.get("stall_recovery_max_s") is not None:
            line += f", max stall {r['stall_recovery_max_s']:.2f}s"
        if r.get("rpc_overhead_p99_s") is not None:
            line += (f", RPC overhead p50 "
                     f"{r['rpc_overhead_p50_s'] * 1e3:.1f} ms p99 "
                     f"{r['rpc_overhead_p99_s'] * 1e3:.1f} ms")
        print(line, flush=True)
    if "ttft_p50_s" in r:
        print(f"TTFT    p50 {r['ttft_p50_s'] * 1e3:8.1f} ms   "
              f"p99 {r['ttft_p99_s'] * 1e3:8.1f} ms", flush=True)
    if r.get("metrics_scrapes") is not None:
        print(f"metrics {r['metrics_scrapes']} live scrapes on "
              f":{r['metrics_port']}, max latency "
              f"{r['metrics_scrape_max_s'] * 1e3:.1f} ms", flush=True)
    if r.get("span_conservation_ok") is not None or r.get("incidents"):
        print(f"spans   {r.get('span_events', 0)} events, conservation "
              f"{'ok' if r.get('span_conservation_ok') else 'BROKEN'} | "
              f"incidents {r.get('incidents', 0)}", flush=True)
    if r.get("store_hit_tokens") or r.get("migrations"):
        line = (f"store   fleet hit {r['fleet_prefix_hit_rate']:.2f}, "
                f"store-hit tokens {r['store_hit_tokens']} "
                f"(host {r['store_hit_tokens_host']} / disk "
                f"{r['store_hit_tokens_disk']}), migrations "
                f"{r['migrations']} ({r['migrated_bytes']} B)")
        if r.get("disagg_token_match") is not None:
            line += (f", stream match "
                     f"{'bit-exact' if r['disagg_token_match'] else 'DIVERGED'}")
        print(line, flush=True)
    per = "/".join(f"{p['prefix_hit_rate']:.2f}" for p in r["per_replica"])
    print(f"fleet   prefix hit rate {r['prefix_hit_rate']:.2f} "
          f"(per-replica {per}) | reject rate {r['reject_rate']:.3f} "
          f"({r['rejected']}/{r['submitted']}) | load imbalance mean "
          f"{r['load_imbalance_mean']:.2f} max {r['load_imbalance_max']:.2f}"
          f" | failovers {r['failover_events']} "
          f"({r['failed_over_requests']} reqs) | routed {r['routed']}",
          flush=True)


def _print_record(record) -> None:
    tag = record["lane"]
    print(f"{tag:<8}{record['tokens_per_s']:10.1f} tok/s over "
          f"{record['n_requests']} reqs (concurrency "
          f"{record['concurrency']}, {record['generated_tokens']} tokens, "
          f"{record['wall_s']:.2f}s, chunk={record['prefill_chunk'] or '-'}"
          f", prefix={'on' if record['prefix_cache'] else 'off'})",
          flush=True)
    if "ttft_p50_s" in record:
        print(f"TTFT    p50 {record['ttft_p50_s'] * 1e3:8.1f} ms   "
              f"p99 {record['ttft_p99_s'] * 1e3:8.1f} ms", flush=True)
    if "tpot_p50_s" in record:
        print(f"TPOT    p50 {record['tpot_p50_s'] * 1e3:8.1f} ms   "
              f"p99 {record['tpot_p99_s'] * 1e3:8.1f} ms", flush=True)
    print(f"pool    occupancy mean {record['occupancy_mean']:.2f} "
          f"max {record['occupancy_max']:.2f}, "
          f"{record['preemptions']} preemptions, "
          f"{record['prefill_chunks']} prefill chunks, "
          f"prefix hit rate {record['prefix_hit_rate']:.2f} "
          f"({record['prefix_hit_tokens']}/{record['prompt_tokens']} "
          f"prompt tokens)", flush=True)
    if record.get("spec", "off") != "off":
        print(f"spec    {record['spec']} k={record['spec_k']}: "
              f"{record['spec_accept_mean']:.2f} accepted drafts/step "
              f"(rate {record['spec_accept_rate']:.2f}, "
              f"{record['spec_accepted']}/{record['spec_drafted']} over "
              f"{record['spec_steps']} verify steps) "
              f"hist {record['spec_accept_hist']}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
