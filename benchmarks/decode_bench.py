"""Generation-throughput benchmark (the decode table in results.md).

Measures the three generation paths (``generate`` = the reference's
windowed semantics, ``generate_bucketed`` = compile-shape bucketing,
``generate_kv`` = KV-cached decode) at the standard settings, plus a GQA
variant and batch>1 rows for the cached path. Timing: best of 3 windows,
one warm call first (compile excluded), wall clock over generated tokens.

    python benchmarks/decode_bench.py [--model-size small] [--rounds 3] \
        [--out decode.jsonl]

``--out`` appends the same record as a schema-versioned JSONL line
(``kind="decode"``) that ``python -m tpu_trainer.tools.analyze``
summarizes and ``--compare`` gates (kv-path tok/s regression fails CI).

Reference anchor: the O(S^2) per-token full re-forward loop at
``/root/reference/src/eval/infer.py:60-66``.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sync(out):
    # A host read of the chained result waits for the whole generate
    # (same sync as bench.py's loss readback).
    return int(out[-1, -1])


def _time_call(fn, rounds):
    _sync(fn())
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        _sync(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--model-size", default="small")
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--out", default=None,
                   help="append the schema-versioned record to this JSONL")
    args = p.parse_args()

    from tpu_trainer.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp

    from tpu_trainer.models.config import GPTConfig
    from tpu_trainer.models.gpt import (
        GPT, generate, generate_bucketed, generate_kv)

    cfg = GPTConfig.preset(args.model_size, dropout=0.0,
                           attention_dropout=0.0)
    rng = jax.random.PRNGKey(0)
    params = GPT(cfg).init(rng, jnp.zeros((1, 8), jnp.int32))["params"]

    cases = [("prompt 128, +256", 128, 256), ("prompt 768, +128", 768, 128)]
    rows = []  # (setting, path, batch, tok/s) -> JSON line at the end
    for name, plen, new in cases:
        ids = jax.random.randint(rng, (1, plen), 0, cfg.vocab_size)
        for path, fn in [
            ("windowed", lambda: generate(
                params, rng, ids, config=cfg, max_new_tokens=new)),
            ("bucketed", lambda: generate_bucketed(
                params, rng, ids, config=cfg, max_new_tokens=new)),
            ("kv", lambda: generate_kv(
                params, rng, ids, config=cfg, max_new_tokens=new)),
        ]:
            dt = _time_call(fn, args.rounds)
            rows.append((name, path, 1, new / dt))
            print(f"{name:18s} {path:9s} bs=1  {new / dt:8.0f} tok/s",
                  flush=True)

    # Batch>1 cached decode: throughput counts all rows' new tokens.
    for bs in (4, 8):
        plen, new = 768, 128
        ids = jax.random.randint(rng, (bs, plen), 0, cfg.vocab_size)
        fn = lambda: generate_kv(  # noqa: E731
            params, rng, ids, config=cfg, max_new_tokens=new)
        dt = _time_call(fn, args.rounds)
        rows.append((f"prompt {plen}, +{new}", "kv", bs, bs * new / dt))
        print(f"prompt {plen}, +{new} kv        bs={bs}  "
              f"{bs * new / dt:8.0f} tok/s", flush=True)

    # GQA: 3 KV heads shared by 4-query-head groups (the round-3 row).
    import dataclasses as dc

    gqa_cfg = dc.replace(cfg, num_kv_heads=3)
    gqa_params = GPT(gqa_cfg).init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    ids = jax.random.randint(rng, (1, 768), 0, cfg.vocab_size)
    dt = _time_call(
        lambda: generate_kv(gqa_params, rng, ids, config=gqa_cfg,
                            max_new_tokens=128),
        args.rounds,
    )
    rows.append(("prompt 768, +128", "kv-gqa3", 1, 128 / dt))
    print(f"prompt 768, +128   kv-gqa3   bs=1  {128 / dt:8.0f} tok/s",
          flush=True)

    # Machine-readable record (the same contract as bench.py's JSON line),
    # schema-stamped so tools/analyze.py can summarize and gate it.
    import json

    from tpu_trainer.utils.logging import SCHEMA_VERSION

    record = {
        "kind": "decode",
        "schema_version": SCHEMA_VERSION,
        "metric": "decode_tok_per_sec",
        "model_size": args.model_size,
        "rows": [
            {"setting": s, "path": p, "batch": b, "tok_per_sec": round(t, 1)}
            for s, p, b, t in rows
        ],
    }
    print(json.dumps(record), flush=True)
    if args.out:
        with open(args.out, "a") as fh:
            fh.write(json.dumps(record) + "\n")


if __name__ == "__main__":
    main()
