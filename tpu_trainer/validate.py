"""One-command on-hardware validation lane (VERDICT r3 item 7).

The CPU test suite cannot reach the compiled-only TPU code paths: the
Pallas kernels run there in interpret mode (one head per program,
multiply-xorshift dropout hash), while a compiled TPU run uses head-PAIR
programs at d=64, the core's hardware PRNG in fixed 512x512 tiles, and the
odd-head zero-pad; ``pinned_host`` offload likewise only exists on the
chip. This module re-proves all of them
with ONE command, meant to run every round after any kernel change::

    python -m tpu_trainer.validate --tpu

Checks (each prints PASS/FAIL/SKIP; exit code 1 on any failure, and
without a TPU — nothing here can pass by being skipped):

1-9.  The flash-kernel checks from round 3 (hw-PRNG determinism/variation,
      dropout unbiasedness, mask equality across tilings and iteration
      orders, linear-in-v gradient identity under mixed fwd/bwd tiling,
      odd-head-count outputs + grads, GQA vs repeated-KV oracle), the
      head+CE kernel, and the streaming forward with fused RoPE at the
      360M benchmark cell's attention shape against the dense reference.
10.   Offload bitwise: the ``pinned_host``-offloaded train step produces
      bit-identical losses to the on-device step over 5 steps (f32
      storage), on the real chip's memory spaces.
11.   Offload int8: the blockwise-quantized stream trains to a loss within
      5% of the exact run over 8 steps.
12.   A compiled bf16 train step (flash kernel + fused CE + optimizer)
      runs and the loss is finite — the full production graph, not just
      the kernel.
13.   (>=2 devices only; SKIP on one chip) a 1F1B pipeline step on a real
      ``stage`` axis.
"""

from __future__ import annotations

import sys

FAILURES = []


def check(name, ok, detail=""):
    print(f"{'PASS' if ok else 'FAIL'}  {name}  {detail}")
    if not ok:
        FAILURES.append(name)


def skip(name, why):
    print(f"SKIP  {name}  ({why})")


def _kernel_checks():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_trainer.ops.flash import _keep, flash_attention

    def mask_via_kernel(bq, bk, seq, order, seed=0xFEEDBEEF, rate=0.25):
        """Extract the hw keep mask for the full [seq, seq] block grid,
        generating per (bq, bk) block in the given iteration order."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kern(seed_ref, o_ref):
            blocks = [(a, c) for a in range(0, seq, bq)
                      for c in range(0, seq, bk)]
            if order == "kmajor":
                blocks = [(a, c) for c in range(0, seq, bk)
                          for a in range(0, seq, bq)]
            for a, c in blocks:
                m = _keep(seed_ref[0, 0], jnp.uint32(5), a, c, bq, bk, seq,
                          rate, True)
                o_ref[a:a + bq, c:c + bk] = m.astype(jnp.int32)

        return np.asarray(pl.pallas_call(
            kern,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_shape=jax.ShapeDtypeStruct((seq, seq), jnp.int32),
        )(jnp.full((1, 1), seed, jnp.uint32)))

    b, s, h, d = 2, 1024, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.bfloat16)
    rng = jax.random.PRNGKey(7)

    # 1. determinism / seed variation
    f = jax.jit(lambda q, k, v, r: flash_attention(
        q, k, v, dropout_rate=0.25, dropout_rng=r))
    o1, o2 = np.asarray(f(q, k, v, rng)), np.asarray(f(q, k, v, rng))
    o3 = np.asarray(f(q, k, v, jax.random.PRNGKey(8)))
    check("determinism per seed", np.array_equal(o1, o2))
    check("varies across seeds", not np.allclose(o1, o3))

    # 2. unbiasedness
    base = np.asarray(jax.jit(
        lambda q, k, v: flash_attention(q, k, v))(q, k, v)).astype(np.float64)
    acc = np.zeros_like(base)
    n = 32
    for i in range(n):
        acc += np.asarray(f(q, k, v, jax.random.PRNGKey(100 + i))
                          ).astype(np.float64)
    err = np.abs((acc / n)[:, 64:] - base[:, 64:]).mean()
    check("dropout unbiasedness", err < 0.05, f"mean|bias|={err:.4f}")

    # 3+4. mask tile equality across tilings and orders
    big = mask_via_kernel(1024, 1024, 1024, "qmajor")
    small = mask_via_kernel(512, 512, 1024, "qmajor")
    small_k = mask_via_kernel(512, 512, 1024, "kmajor")
    check("mask equal across tilings", np.array_equal(big, small),
          f"keep rate {big.mean():.4f}")
    check("mask equal across orders", np.array_equal(small, small_k))

    # 5. linear-in-v fd with mixed fwd(1024)/bwd(512) tiling
    qf, kf, vf = (x.astype(jnp.float32) for x in (q[:1], k[:1], v[:1]))
    probe = jax.random.normal(jax.random.PRNGKey(14), qf.shape, jnp.float32)
    direction = jax.random.normal(jax.random.PRNGKey(15), vf.shape,
                                  jnp.float32)

    def loss(vv):
        return jnp.sum(flash_attention(
            qf, kf, vv, dropout_rate=0.25, dropout_rng=rng) * probe)

    an = float(jnp.sum(jax.jit(jax.grad(loss))(vf) * direction))
    lp = jax.jit(loss)
    fd = (float(lp(vf + direction)) - float(lp(vf - direction))) / 2.0
    rel = abs(fd - an) / max(abs(an), 1e-9)
    check("linear-in-v grad identity", rel < 0.05,
          f"relerr={rel:.2e} (eval rounding ~1e-2 on this chip)")

    # 6. odd head count (zero-pad head)
    q25 = jax.random.normal(ks[0], (1, 256, 25, 64), jnp.bfloat16)
    k25 = jax.random.normal(ks[1], (1, 256, 25, 64), jnp.bfloat16)
    v25 = jax.random.normal(ks[2], (1, 256, 25, 64), jnp.bfloat16)

    def loss25(qq):
        return jnp.sum(flash_attention(qq, k25, v25).astype(jnp.float32))

    out25 = np.asarray(jax.jit(lambda a, b_, c: flash_attention(a, b_, c))(
        q25, k25, v25))
    out24 = np.asarray(jax.jit(lambda a, b_, c: flash_attention(a, b_, c))(
        q25[:, :, :24], k25[:, :, :24], v25[:, :, :24]))
    outlast = np.asarray(jax.jit(lambda a, b_, c: flash_attention(a, b_, c))(
        q25[:, :, 23:25], k25[:, :, 23:25], v25[:, :, 23:25]))
    ok = np.allclose(out25[:, :, :24], out24, atol=2e-2) and np.allclose(
        out25[:, :, 24], outlast[:, :, 1], atol=2e-2)
    check("odd head count (25)", ok)
    g25 = jax.jit(jax.grad(loss25))(q25)
    check("odd head grads finite",
          np.isfinite(np.asarray(g25, dtype=np.float32)).all())

    # 7. GQA (2 kv heads for 4 query heads) vs repeated-KV oracle
    kg = jax.random.normal(ks[1], (b, s, 2, d), jnp.bfloat16)
    vg = jax.random.normal(ks[2], (b, s, 2, d), jnp.bfloat16)
    got = np.asarray(jax.jit(lambda a, b_, c: flash_attention(a, b_, c))(
        q, kg, vg))
    krep = jnp.repeat(kg, 2, axis=2)
    vrep = jnp.repeat(vg, 2, axis=2)
    want = np.asarray(jax.jit(lambda a, b_, c: flash_attention(a, b_, c))(
        q, krep, vrep))
    check("GQA vs repeated-KV oracle", np.allclose(got, want, atol=2e-2))

    # 8. Pallas fused head+CE (ops/head_ce.py) vs the XLA blockwise loss —
    # compiled path at headline-like shapes (incl. the ragged vocab edge).
    from tpu_trainer.ops.head_ce import pallas_head_ce
    from tpu_trainer.ops.loss import _chunk_len, _chunked_ce

    bh, sh, hh, V = 4, 1024, 256, 50257
    kk = jax.random.split(jax.random.PRNGKey(21), 3)
    embw = jax.random.normal(kk[0], (V, hh), jnp.float32) * 0.02
    xh = jax.random.normal(kk[1], (bh, sh, hh), jnp.bfloat16)
    labs = jax.random.randint(kk[2], (bh, sh), 0, V)
    maskh = (jax.lax.broadcasted_iota(jnp.int32, (bh, sh), 1)
             < sh - 1).astype(jnp.float32)

    def _o(e_, x_):
        return _chunked_ce(e_, x_, labs, maskh, _chunk_len(bh, sh, 0))

    def _p(e_, x_):
        return pallas_head_ce(e_, x_, labs, maskh, None, False)

    (lo, go) = jax.jit(jax.value_and_grad(_o, argnums=(0, 1)))(embw, xh)
    (lp, gp) = jax.jit(jax.value_and_grad(_p, argnums=(0, 1)))(embw, xh)
    dl = abs(float(lo) - float(lp))
    de = float(jnp.max(jnp.abs(go[0] - gp[0])))
    dx = float(jnp.max(jnp.abs(go[1].astype(jnp.float32)
                               - gp[1].astype(jnp.float32))))
    check("fused head+CE kernel vs XLA loss",
          dl < 1e-4 and de < 1e-4 and dx < 1e-4,
          f"dloss={dl:.1e} dE={de:.1e} dx={dx:.1e}")

    # 9. The streaming forward (512 x 512 blocks, RoPE fused, two heads a
    # program) and the fused backward at the 360M benchmark cell's shape —
    # 15 heads over 5 K/V heads, so the zero head and the K/V expansion are
    # in — against the dense f32 reference with RoPE applied outside.
    from tpu_trainer.ops.attention import reference_attention
    from tpu_trainer.ops.rope import apply_rotary_pos_emb, rope_tables

    bc, sc, hc, kvc = 4, 2048, 15, 5
    kc = jax.random.split(jax.random.PRNGKey(27), 4)
    qc = jax.random.normal(kc[0], (bc, sc, hc, d), jnp.bfloat16)
    kc_ = jax.random.normal(kc[1], (bc, sc, kvc, d), jnp.bfloat16)
    vc = jax.random.normal(kc[2], (bc, sc, kvc, d), jnp.bfloat16)
    probe_c = jax.random.normal(kc[3], qc.shape, jnp.float32)
    tabs = rope_tables(sc, d)

    def kernel_loss(q_, k_, v_):
        out = flash_attention(q_, k_, v_, rope=tabs)
        return jnp.sum(out.astype(jnp.float32) * probe_c), out

    def dense_loss(q_, k_, v_):
        qr_, kr_ = apply_rotary_pos_emb(q_, k_, *tabs)
        out = reference_attention(qr_, kr_, v_)
        return jnp.sum(out * probe_c), out

    def grads_and_out(f, *xs):
        return jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))(*xs)

    got_g, got_o = grads_and_out(kernel_loss, qc, kc_, vc)
    with jax.default_matmul_precision("highest"):
        want_g, want_o = grads_and_out(
            dense_loss, *(x.astype(jnp.float32) for x in (qc, kc_, vc)))
    errs = [float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)))
            for g, w in zip((got_o, *got_g), (want_o, *want_g))]
    check("streaming forward + backward vs dense reference "
          f"[{bc}, {sc}, {hc}/{kvc}, {d}]",
          errs[0] < 3e-2 and max(errs[1:]) < 1e-1,
          "max|kernel - reference|: out={:.2e} dq={:.2e} dk={:.2e} "
          "dv={:.2e}".format(*errs))

    # 10. The state-space scan's kernels (forward, and the backward through
    # the states the forward leaves) at the Nemotron cell's lanes, eight
    # chunks, against the plain XLA form in f32 at the highest precision.
    from tpu_trainer.ops import ssd as ssd_ops

    bs, ss, hs, ps, gs, ns = 2, 1024, 16, 64, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(36), 6)
    scan_args = (
        jax.random.normal(ks[0], (bs, ss, hs, ps), jnp.bfloat16),
        jax.nn.softplus(jax.random.normal(ks[1], (bs, ss, hs)) - 4.0),
        -jnp.exp(jax.random.uniform(ks[2], (hs,), maxval=2.77)),
        *((0.5 * jax.random.normal(k, (bs, ss, gs, ns))).astype(jnp.bfloat16)
          for k in ks[3:5]))
    probe_s = jax.random.normal(ks[5], (bs, ss, hs, ps), jnp.float32)
    assert ssd_ops.kernel_path(scan_args[0].shape, scan_args[3].shape, 128)

    def scan_grads(scan, *xs):
        def loss(*v):
            out = scan(*v)
            return jnp.sum(out * probe_s), out
        return jax.jit(jax.grad(loss, argnums=range(5), has_aux=True))(*xs)

    got_g, got_y = scan_grads(
        lambda *v: ssd_ops.ssd(*v, chunk=128)[0], *scan_args)
    with jax.default_matmul_precision("highest"):
        want_g, want_y = scan_grads(
            lambda *v: ssd_ops._chunked(*v, 128, jnp.dtype(jnp.float32))[0],
            *(v.astype(jnp.float32) for v in scan_args))

    def rel(g, w):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        return float(jnp.sqrt(jnp.mean((g - w) ** 2) / jnp.mean(w ** 2)))

    errs = [rel(g, w) for g, w in zip((got_y, *got_g), (want_y, *want_g))]
    check(f"state-space scan kernels vs plain f32 [{bs}, {ss}, {hs}/{gs}, "
          f"{ps}, {ns}]", errs[0] < 1e-2 and max(errs[1:]) < 3e-2,
          "rel rms: y={:.2e} dx={:.2e} ddt={:.2e} da={:.2e} db={:.2e} "
          "dc={:.2e}".format(*errs))


def _tiny_trainer(offload=False, offload_dtype="float32",
                  mixed_precision="fp32", flash=False, mesh_kw=None,
                  model_kw=None):
    from tpu_trainer.models.config import GPTConfig
    from tpu_trainer.parallel.mesh import MeshConfig
    from tpu_trainer.training.config import TrainingConfig
    from tpu_trainer.training.trainer import ParallelConfig, Trainer

    model = GPTConfig(
        vocab_size=256, hidden_size=128, num_layers=2, num_heads=2,
        max_seq_len=128, dropout=0.0, attention_dropout=0.0,
        use_flash_attention=flash, **(model_kw or {}),
    )
    train = TrainingConfig(
        batch_size=2, max_seq_len=128, gradient_accumulation_steps=1,
        mixed_precision=mixed_precision, warmup_steps=2, max_steps=50,
    )
    return Trainer(
        model, train,
        ParallelConfig(MeshConfig(**(mesh_kw or {"data": 1, "fsdp": -1})),
                       "zero3", cpu_offload=offload,
                       offload_dtype=offload_dtype),
    )


def _offload_checks():
    import numpy as np

    batch = np.random.default_rng(0).integers(0, 256, (2, 128), np.int32)

    def run(offload, dtype="float32", steps=5):
        t = _tiny_trainer(offload=offload, offload_dtype=dtype)
        if offload and not t.cpu_offload:
            return None
        state = t.init_state(seed=0)
        out = []
        for _ in range(steps):
            state, m = t.train_step(state, batch)
            out.append(float(m["loss"]))
        return out

    base = run(False)
    off = run(True)
    if off is None:
        skip("offload bitwise", "no pinned_host memory space here")
    else:
        check("offload bitwise (f32 storage)", off == base,
              f"losses {off[-1]:.6f} vs {base[-1]:.6f}")
    q = run(True, "int8", steps=8)
    base8 = run(False, steps=8)
    if q is None:
        skip("offload int8", "no pinned_host memory space here")
    else:
        rel = abs(q[-1] - base8[-1]) / max(abs(base8[-1]), 1e-9)
        check("offload int8 curve", rel < 0.05 and q[-1] < q[0],
              f"rel={rel:.3f}")


def _step_checks():
    import jax
    import numpy as np

    # 12. the full production graph: bf16 + flash kernel + fused CE.
    t = _tiny_trainer(mixed_precision="bf16", flash=True)
    state = t.init_state(seed=0)
    batch = np.random.default_rng(1).integers(0, 256, (2, 128), np.int32)
    state, m = t.train_step(state, batch)
    loss = float(m["loss"])
    check("bf16 flash train step", np.isfinite(loss), f"loss={loss:.4f}")
    ma = t.step_memory_analysis(state, batch)
    check("compiled memory_analysis", ma is not None and ma["peak_bytes"] > 0,
          f"peak={ma['peak_bytes'] / 2**20:.1f} MiB" if ma else "")

    # 13. 1F1B on a real stage axis (needs >= 2 devices).
    if jax.device_count() >= 2:
        t2 = _tiny_trainer(
            mixed_precision="bf16", flash=True,
            mesh_kw={"data": 1, "fsdp": 1, "stage": 2},
            model_kw={"pipeline_schedule": "1f1b",
                      "pipeline_microbatches": 2},
        )
        st = t2.init_state(seed=0)
        st, m2 = t2.train_step(st, batch)
        check("1F1B pipeline step", np.isfinite(float(m2["loss"])))
    else:
        skip("1F1B pipeline step", "needs >= 2 devices; CPU suite covers it")


def main(argv=None) -> int:
    import argparse

    import jax

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--tpu", action="store_true",
                   help="kept for old command lines: a TPU is always "
                        "required (this lane certifies compiled kernels)")
    p.parse_args(argv)
    from tpu_trainer.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if not any(d.platform == "tpu" for d in jax.devices()):
        print("FAIL  no TPU present (run the CPU suite for interpret mode)")
        return 1
    _kernel_checks()
    _offload_checks()
    _step_checks()
    print(f"\n{len(FAILURES)} failure(s)" if FAILURES
          else "\nall checks passed")
    return 1 if FAILURES else 0


if __name__ == "__main__":
    sys.exit(main())
