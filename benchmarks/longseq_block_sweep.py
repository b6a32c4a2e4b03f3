"""Block-shape x backward-path sweep for the flash kernel's long-sequence
STREAMING path.

Round 3 tuned block shapes at s=1024 only (`ops/flash.py:58-60`); round 4
found 1024x1024 streaming blocks overflow the default 16 MB scoped VMEM
and papered over it with a raised ``--xla_tpu_scoped_vmem_limit_kib``.
Round 5 split the backward into two s-independent kernels, so the sweep
now runs at DEFAULT compiler flags and times BOTH backward paths::

    python benchmarks/longseq_block_sweep.py [--rate 0.1]
    python benchmarks/longseq_block_sweep.py --raise-vmem   # legacy scope

Default flags are the point: the fused rows at s > 2048 are *expected* to
FAIL with a scoped-VMEM overflow here (that is the measurement — the
full-row dq residency does not fit), while the split rows run everywhere.
``--raise-vmem`` restores the old 48 MB scope for an apples-to-apples
fused-vs-split comparison under the flag bench.py used to set. The flag
must be set before libtpu loads, hence a process-level switch rather than
a per-row one.

Prints one line per (s, bq, bk, backward): ms/iter and achieved TFLOP/s
(causal attention FLOPs 2*2*s^2*d per head-batch... reported as the PaLM
full-S^2 convention divided by 2 for causality — the same convention
either way across rows, so relative ordering is what matters).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Parse the scope switch BEFORE importing jax: LIBTPU_INIT_ARGS is read
# once at libtpu load.
_RAISE = "--raise-vmem" in sys.argv
if _RAISE and "scoped_vmem" not in os.environ.get("LIBTPU_INIT_ARGS", ""):
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "")
        + " --xla_tpu_scoped_vmem_limit_kib=49152"
    ).strip()

import jax
import jax.numpy as jnp


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--rate", type=float, default=0.1,
                   help="attention dropout rate (0 disables the mask path)")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--raise-vmem", action="store_true",
                   help="raise the scoped-VMEM limit to 48 MB (the legacy "
                        "bench.py flag) for the fused-path comparison")
    p.add_argument("--backward", default="both",
                   choices=("both", "fused", "split"))
    args = p.parse_args()

    from tpu_trainer.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from tpu_trainer.ops.flash import flash_attention

    assert any(d.platform == "tpu" for d in jax.devices())
    h, d = 12, 64
    rng = jax.random.PRNGKey(0)
    impls = (("fused", "split") if args.backward == "both"
             else (args.backward,))
    for s in (2048, 4096, 8192):
        b = 8192 // s  # constant tokens per call
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, s, h, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, s, h, d), jnp.bfloat16)
        flops = 4 * b * h * s * s * d / 2  # causal fwd; bwd adds ~2x

        for bq, bk in ((512, 512), (1024, 512), (512, 1024), (1024, 1024),
                       (2048, 512)):
            if s % bq or s % bk or bq > s or bk > s:
                continue
            for impl in impls:

                def run(qq, kk, vv):
                    def loss(vv_):
                        return jnp.sum(flash_attention(
                            qq, kk, vv_, block_q=bq, block_k=bk,
                            dropout_rate=args.rate,
                            dropout_rng=jax.random.PRNGKey(5),
                            backward=impl,
                        ).astype(jnp.float32))

                    return jax.value_and_grad(loss)(vv)

                tag = f"s={s} bq={bq} bk={bk} bwd={impl}"
                try:
                    f = jax.jit(run)
                    out = f(q, k, v)
                    jax.block_until_ready(out)
                    float(out[0])
                    best = float("inf")
                    for _ in range(3):
                        t0 = time.perf_counter()
                        for _ in range(args.iters):
                            out = f(q, k, v)
                        float(out[0])  # sync: host read of the chain
                        best = min(best,
                                   (time.perf_counter() - t0) / args.iters)
                    print(f"{tag}: {best * 1e3:8.3f} ms  "
                          f"~{3 * flops / best / 1e12:6.1f} TF/s (fwd+bwd)")
                except Exception as e:  # noqa: BLE001 - survive OOMs
                    print(f"{tag}: FAILED "
                          f"({str(e).splitlines()[0][:90]})")


if __name__ == "__main__":
    main()
