"""Capture + attribute an xplane trace of the training step.

Round 4's optimization narrative in ``results.md`` was driven by manual
xplane spelunking; this makes it a one-command harness: build the same
trainer/step as ``bench.py``, trace a few steady-state steps with
``jax.profiler``, then aggregate device-side HLO op durations into a
ranked table (``hlo_stats`` via the tensorboard-plugin converter — the
only xplane reader in this image; its protobuf bindings are stale, so we
call the pywrap entry point directly).

Usage (mirrors bench.py's config flags):

    python benchmarks/profile_step.py --num-experts 8 --moe-top-k 2
    python benchmarks/profile_step.py --model-size medium --batch-size 8

Prints total device time per step and the top-N op groups with their
share, plus a category rollup (matmul / pallas kernels / elementwise /
copies / other).
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run_bench  # noqa: E402  (reuses the bench config builder)


def _capture(args) -> str:
    """Run the bench config under a windowed jax.profiler trace; return the
    xplane.pb path."""
    import jax

    from tpu_trainer.data.dummy import create_dummy_dataloader
    from tpu_trainer.models.config import GPTConfig
    from tpu_trainer.parallel.mesh import make_mesh
    from tpu_trainer.parallel.mesh import MeshConfig
    from tpu_trainer.training.config import TrainingConfig
    from tpu_trainer.training.trainer import ParallelConfig, Trainer

    common = dict(
        max_seq_len=args.seq_len,
        use_flash_attention=True,
        gradient_checkpointing=bool(args.remat),
        dropout=0.1,
        attention_dropout=0.1,
    )
    if args.num_experts:
        common.update(num_experts=args.num_experts, moe_top_k=args.moe_top_k,
                      router_z_weight=1e-3)
    for pair in args.model_flag or []:
        key, _, val = pair.partition("=")
        cur = getattr(GPTConfig(), key)
        common[key] = (val.lower() in ("1", "true", "yes")
                       if isinstance(cur, bool) else type(cur)(val))
    model_config = GPTConfig.preset(args.model_size, **common)
    mesh = make_mesh(MeshConfig())
    trainer = Trainer(
        model_config,
        TrainingConfig(batch_size=args.batch_size, max_seq_len=args.seq_len,
                       gradient_accumulation_steps=args.accum,
                       mixed_precision="bf16", log_interval=10**9),
        ParallelConfig(MeshConfig(), "replicated", cpu_offload=args.offload,
                       offload_dtype=args.offload_dtype),
        mesh=mesh,
    )
    loader = create_dummy_dataloader(
        batch_size=args.batch_size * args.accum, seq_len=args.seq_len,
        vocab_size=model_config.vocab_size, num_batches=args.steps + 8,
    )
    it = iter(loader)
    state = trainer.init_state()
    for _ in range(3):
        state, metrics = trainer.train_step(state, next(it))
    float(metrics["loss"])

    out_dir = args.trace_dir or tempfile.mkdtemp(prefix="xplane_")
    with jax.profiler.trace(out_dir):
        for _ in range(args.steps):
            state, metrics = trainer.train_step(state, next(it))
        float(metrics["loss"])
    paths = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise SystemExit(f"no xplane.pb under {out_dir}")
    return paths[-1]


def _hlo_stats(xplane_path: str):
    """xplane -> list of (op_name, program, category, total_us, occurrences).

    Calls the tensorboard-plugin pywrap converter directly (the python
    protobuf shims around it are stale in this image).
    """
    from tensorflow.python.profiler.internal import _pywrap_profiler_plugin

    raw = _pywrap_profiler_plugin.xspace_to_tools_data(
        [xplane_path], "hlo_stats", {}
    )
    data = raw[0] if isinstance(raw, tuple) else raw
    if isinstance(data, bytes):
        try:
            data = gzip.decompress(data)
        except OSError:
            pass
        data = data.decode("utf-8", "replace")
    return json.loads(data)


# Fallback classifier for converter builds whose hlo_stats omits the
# "HLO op category" column (or leaves it blank): first pattern matching
# the op name or HLO text wins. When the converter does emit categories,
# its (more precise) labels are used as-is and this table is bypassed.
_CATS = [
    ("flash kernel", re.compile(r"flash|custom-call.*pallas|attn", re.I)),
    ("head_ce kernel", re.compile(r"head_ce|_head_ce_fwd", re.I)),
    ("matmul", re.compile(r"^(fusion\.)?(convolution|dot|einsum)|%dot", re.I)),
    ("copy/convert", re.compile(r"copy|convert|transpose|bitcast", re.I)),
    ("elementwise", re.compile(r"fusion|add|multiply|select", re.I)),
]


def _fallback_category(name: str, expr: str) -> str:
    for label, pat in _CATS:
        if pat.search(name) or pat.search(expr):
            return label
    return "other"


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--model-size", default="small")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--accum", type=int, default=1)
    p.add_argument("--remat", type=int, default=0)
    p.add_argument("--offload", action="store_true")
    p.add_argument("--offload-dtype", default="float32")
    p.add_argument("--num-experts", type=int, default=0)
    p.add_argument("--moe-top-k", type=int, default=1)
    p.add_argument("--model-flag", action="append", default=[])
    p.add_argument("--trace-dir", default=None)
    p.add_argument("--top", type=int, default=40)
    p.add_argument("--xplane", default=None,
                   help="skip capture; attribute an existing .xplane.pb")
    args = p.parse_args()

    from tpu_trainer.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    path = args.xplane or _capture(args)
    print(f"# xplane: {path}", file=sys.stderr)
    table = _hlo_stats(path)
    # hlo_stats gviz-ish JSON: {"cols": [...], "rows": [{"c": [{"v": ...}]}]}
    cols = [c.get("label") or c.get("id") for c in table["cols"]]
    idx = {name: i for i, name in enumerate(cols)}
    rows = []
    for r in table["rows"]:
        vals = [c.get("v") if isinstance(c, dict) else c for c in r["c"]]
        rows.append(vals)

    def col(vals, *names, default=None):
        for n in names:
            if n in idx:
                return vals[idx[n]]
        return default

    agg = {}
    for vals in rows:
        name = str(col(vals, "HLO op name", default=""))
        expr = str(col(vals, "HLO op text", default=""))
        cat = str(col(vals, "HLO op category", default="") or "").strip()
        if not cat or cat.lower() == "none":
            cat = _fallback_category(name, expr)
        us = float(col(vals, "Total self time (us)", default=0) or 0)
        occ = int(col(vals, "#Occurrences", default=0) or 0)
        key = re.sub(r"\.\d+$", "", name)
        # Generic fusions are a meaningless bucket: split by output shape
        # (the "= <type>" token of the HLO text) so distinct computations
        # with the same anonymous name stay distinct.
        m = re.search(r"=\s*(\(?[a-z0-9]+\[[^\]]*\])", expr)
        if m and key in ("fusion", "copy", "convert_element_type"):
            key = f"{key} {m.group(1)}"
        a = agg.setdefault(key, {"us": 0.0, "occ": 0, "cat": cat,
                                 "expr": expr[:110]})
        a["us"] += us
        a["occ"] += occ
    total = sum(a["us"] for a in agg.values())
    nsteps = args.steps
    print(f"# columns: {cols}", file=sys.stderr)
    print(f"total device time: {total/1e3:.2f} ms over {nsteps} steps "
          f"-> {total/1e3/nsteps:.2f} ms/step")
    print(f"{'ms/step':>9}  {'%':>5}  {'occ':>5}  name  [category]")
    for key, a in sorted(agg.items(), key=lambda kv: -kv[1]["us"])[:args.top]:
        print(f"{a['us']/1e3/nsteps:9.3f}  {100*a['us']/total:5.1f}  "
              f"{a['occ']:5d}  {key}  [{a['cat']}]")
        if a["expr"]:
            print(f"{'':23}{a['expr']}")
    bycat = {}
    for a in agg.values():
        bycat[a["cat"]] = bycat.get(a["cat"], 0.0) + a["us"]
    print("\n# category rollup (ms/step)")
    for cat, us in sorted(bycat.items(), key=lambda kv: -kv[1]):
        print(f"{us/1e3/nsteps:9.3f}  {100*us/total:5.1f}  {cat}")


if __name__ == "__main__":
    main()
