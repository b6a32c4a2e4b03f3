"""Compile the main-path Pallas kernels for a DESCRIBED TPU v5e.

No chip is attached here: the TPU compiler that ships with jaxlib lowers
each kernel for ``v5e:2x2`` from shapes alone, and raises what the chip's
compiler would raise (tiling rules, VMEM limits) — the things interpret
mode cannot see. A compile that passes is not a chip run.

The topology is described inside the module-scoped ``topo`` fixture — never
at import, in a ``skipif`` or a ``parametrize`` argument — so every xdist
worker collects the same tests and only the worker that runs this file
loads libtpu. Keep every such test in THIS file.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from perf import program_trace
from tpu_trainer.ops.flash import flash_attention, flash_decode
from tpu_trainer.ops.grouped_matmul import gmm, tgmm
from tpu_trainer.ops.head_ce import pallas_head_ce


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-device executable is written to the persistent cache but
    # cannot be read back without a chip; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """``chip(shape, dtype)`` -> ShapeDtypeStruct placed on one v5e."""
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


def _compile(fn, *shapes) -> str:
    """Lower + compile for the described chip; return the compiled HLO."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


# --- training kernels ------------------------------------------------------

@pytest.mark.parametrize("b,s,h,kvh,d,rope", [
    (8, 1024, 12, 12, 64, False), (2, 4096, 12, 12, 64, False),
    (4, 2048, 16, 16, 128, False), (2, 4096, 32, 2, 128, False),
    (4, 2048, 15, 5, 64, True), (4, 2048, 32, 32, 64, True),
    (2, 2048, 8, 2, 128, True), (4, 1536, 16, 16, 64, True)])
def test_flash_attention_fwd_and_grad(chip, b, s, h, kvh, d, rope):
    # s=1024: fused backward (the `small` preset's training shape);
    # s=4096: the split dkv/dq backward; d=128: the queued configurations,
    # and the Nemotron cell's micro-batch (32 heads over 2 K/V heads at
    # 4,096 tokens, nothing rotated).
    # With RoPE fused: the benchmark's two cells (the 360M's 15 heads over
    # 5 K/V heads, padded and expanded to 16; the 1.7B's 32) and a d=128
    # GQA, all through the streaming forward at 512 x 512 blocks: lane
    # rolls, [block_q, 128] state, K read back from the rotated-K output.
    # Their gradient is the fused backward's multi-block body (PR 31: the
    # gradient dots in their d-row form, accumulators transposed in VMEM
    # scratch); s=1536 walks 3 x 3 blocks, not a power of two.
    q = chip((b, s, h, d), jnp.bfloat16)
    kv = chip((b, s, kvh, d), jnp.bfloat16)
    tabs = (chip((s, d), jnp.float32),) * 2 if rope else ()

    def fwd(q, k, v, *tabs):
        return flash_attention(q, k, v, rope=tabs or None)

    def loss(q, k, v, *tabs):
        return fwd(q, k, v, *tabs).astype(jnp.float32).sum()

    _compile(fwd, q, kv, kv, *tabs)
    _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, *tabs)


@pytest.mark.parametrize("s", [1024, 2048])
def test_flash_attention_rope_dropout_fused(chip, s):
    # s=2048 streams: the hardware-PRNG mask beside the full-width state.
    b, h, d = 8, 12, 64
    x = chip((b, s, h, d), jnp.bfloat16)
    tab = chip((s, d), jnp.float32)
    key = chip((2,), jnp.uint32)

    def loss(q, k, v, cos, sin, key):
        out = flash_attention(q, k, v, rope=(cos, sin), dropout_rate=0.1,
                              dropout_rng=jax.random.wrap_key_data(key))
        return out.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x, tab, tab, key)


@pytest.mark.parametrize("window", [1024, None])
def test_banded_flash_attention_at_the_window_cells_widths(chip, window):
    # The Mellum cell's micro-batch: 32 heads of 128 over 4 K/V heads at
    # 8,192 tokens, RoPE in XLA; its window layers (a band of 1,024) and its
    # full layer (no window: past 4,096 tokens the banded kernels carry the
    # causal prefix, the streaming forward's VMEM growing with s).
    b, s, h, kvh, d = 2, 8192, 32, 4, 128
    q = chip((b, s, h, d), jnp.bfloat16)
    kv = chip((b, s, kvh, d), jnp.bfloat16)
    tab = chip((s, d), jnp.float32)

    def loss(q, k, v, cos, sin):
        return flash_attention(q, k, v, rope=(cos, sin), window=window
                               ).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, tab, tab)
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def _scan_as_on_a_tpu(monkeypatch):
    """`ops/ssd.py` dispatches on `jax.devices()`, which is the CPU here:
    steer the shapes its kernels take to them, compiled, as a TPU would."""
    from tpu_trainer.ops import ssd as ssd_ops

    def path(*shapes):
        return (False, None, None) if ssd_ops.fits(*shapes) else None

    monkeypatch.setattr(ssd_ops, "kernel_path", path)
    monkeypatch.setattr("tpu_trainer.models.gpt.ssd_kernel_path", path)


def test_chunked_scan_fwd_and_grad(chip, monkeypatch):
    """`ops/ssd.py` at the Nemotron cell's micro-batch (2 x 4,096 tokens, 64
    heads of 64 lanes, 8 groups, state 128, chunk 128) through its Pallas
    kernels, as a TPU takes it: Mosaic must accept the forward, the forward
    that keeps its chunks' starting states and the backward, and what they
    leave in HBM is the operands' order (`y` in f32, the starts 134 MB: 0.35e9
    B of temporaries), never a `[2, 32, 64, 128, 128]` f32 tensor (268 MB
    each) nor a `reduce_window` cumulative sum."""
    from tpu_trainer.ops import ssd as ssd_ops

    _scan_as_on_a_tpu(monkeypatch)
    b, s, heads, p, groups, n = 2, 4096, 64, 64, 8, 128
    x = chip((b, s, heads, p), jnp.bfloat16)
    dt = chip((b, s, heads), jnp.float32)
    a = chip((heads,), jnp.float32)
    bc = chip((b, s, groups, n), jnp.bfloat16)

    def fwd(x, dt, a, b_in, c_in):
        return ssd_ops.ssd(x, dt, a, b_in, c_in, chunk=128)

    def loss(x, dt, a, b_in, c_in):
        y, low = fwd(x, dt, a, b_in, c_in)
        return y.sum() + low

    text = _compile(fwd, x, dt, a, bc, bc)
    assert "reduce-window" not in text
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, dt, a, bc, bc).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 and "reduce-window" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_chunked_scan_with_the_mixers_epilogue_fwd_and_grad(chip, monkeypatch):
    """The same scan with the skip, the gate and the grouped norm taken in
    (`gate=`), at the cell's micro-batch: Mosaic must accept the forward that
    writes the normalised bf16 output, the forward that also keeps `y + D x`
    and the `[b, G, s, 1]` column of `rstd`, and the backward that reads them
    (the 512-lane reductions, the resident rows of the skip's and the
    weight's sums), and the gradient reaches all eight operands. No f32 `y`
    and no f32 cotangent of it is left in HBM: less than the scan alone."""
    from tpu_trainer.ops import ssd as ssd_ops

    _scan_as_on_a_tpu(monkeypatch)
    b, s, heads, p, groups, n = 2, 4096, 64, 64, 8, 128
    x = chip((b, s, heads, p), jnp.bfloat16)
    dt = chip((b, s, heads), jnp.float32)
    a = chip((heads,), jnp.float32)
    bc = chip((b, s, groups, n), jnp.bfloat16)
    z = chip((b, s, heads * p), jnp.bfloat16)
    skip = chip((heads,), jnp.float32)
    weight = chip((heads * p,), jnp.float32)

    def fwd(x, dt, a, b_in, c_in, z, skip, weight):
        return ssd_ops.ssd(x, dt, a, b_in, c_in, chunk=128,
                           gate=(z, skip, weight, 1e-5))[0]

    def loss(*args):
        return fwd(*args).astype(jnp.float32).sum()

    args = (x, dt, a, bc, bc, z, skip, weight)
    text = _compile(fwd, *args)
    assert "reduce-window" not in text
    compiled = jax.jit(jax.grad(loss, argnums=range(8))).lower(
        *args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 and "reduce-window" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_mamba_mixer_value_and_grad_through_its_checkpoint(chip, monkeypatch):
    """One `Mamba2Mixer` at the cell's widths and micro-batch (2 x 4,096
    tokens of 2,688 lanes, bf16): value and gradient through the module's own
    `jax.checkpoint`, so the recomputed forward (the kernel that keeps its
    starts) and the backward kernel sit where a step has them."""
    from perf import registry
    from tpu_trainer.models.gpt import Mamba2Mixer

    _scan_as_on_a_tpu(monkeypatch)
    cell = registry.workload("train-nemotron-twotower-ep16-1chip")
    cfg_file = registry.config(cell["config"])
    cfg = registry.family(cfg_file).gpt_config(cfg_file, dtype="bfloat16")
    mixer = Mamba2Mixer(cfg)
    u = chip((2, 4096, cfg.hidden_size), jnp.bfloat16)
    params = jax.eval_shape(
        mixer.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 8, cfg.hidden_size), jnp.bfloat16))
    params = jax.tree_util.tree_map(
        lambda leaf: chip(leaf.shape, leaf.dtype), params)

    def loss(params, u):
        return mixer.apply(params, u).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, u).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 and "reduce-window" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6e9


def _rule_as_on_a_tpu(monkeypatch):
    """`ops/gated_delta.py` dispatches on `jax.devices()`, which is the CPU
    here: steer the shapes its kernels take to them, compiled, as a TPU
    would."""
    from tpu_trainer.ops import gated_delta as gd_ops

    def path(*shapes):
        return (False, None, None) if gd_ops.fits(*shapes) else None

    monkeypatch.setattr(gd_ops, "kernel_path", path)
    monkeypatch.setattr("tpu_trainer.models.gpt.gated_delta_kernel_path",
                        path)


def test_gated_delta_net_value_and_grad_at_the_cells_widths(chip, monkeypatch):
    """One `GatedDeltaNet` at the Qwen3-Next cell's widths and micro-batch
    (2 x 4,096 tokens of 2,048 lanes, 16 key / 32 value heads of 128, bf16):
    value and gradient compiled for the chip, the delta rule through its
    Pallas kernels as a TPU takes it. Mosaic must accept the forward that
    keeps each chunk's starting states and `T` and the backward that reads
    them; the triangle's own gradient keeps `T` alone of the inverse's
    series, and no `[C, C]` chunk matrix is left in HBM."""
    from perf import registry
    from tpu_trainer.models.gpt import GatedDeltaNet

    _rule_as_on_a_tpu(monkeypatch)
    cfg_file = registry.config("qwen3-next-80b-a3b-ep32")
    cfg = registry.family(cfg_file).gpt_config(cfg_file, dtype="bfloat16")
    mixer = GatedDeltaNet(cfg)
    u = chip((2, 4096, cfg.hidden_size), jnp.bfloat16)
    params = jax.eval_shape(
        mixer.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 8, cfg.hidden_size), jnp.bfloat16))
    params = jax.tree_util.tree_map(
        lambda leaf: chip(leaf.shape, leaf.dtype), params)

    def loss(params, u):
        return mixer.apply(params, u).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, u).compile()
    text = compiled.as_text()
    assert "gated_delta" in text
    assert text.count("tpu_custom_call") >= 2
    # 3.51e9 B when written: a larger plan is a regression of the rule's
    # memory (the step fits 2 x 4 on the chip by some 10%).
    assert compiled.memory_analysis().temp_size_in_bytes < 4.0e9


@pytest.mark.parametrize("vocab", [50257, 50304])
def test_pallas_head_ce_fwd_and_grad(chip, vocab):
    b, s, hid = 8, 1024, 768
    emb = chip((vocab, hid), jnp.float32)
    x = chip((b, s, hid), jnp.bfloat16)
    labels = chip((b, s), jnp.int32)
    mask = chip((b, s), jnp.float32)
    _compile(pallas_head_ce, emb, x, labels, mask)
    _compile(jax.grad(pallas_head_ce, argnums=(0, 1)), emb, x, labels, mask)


def test_grouped_matmul_fwd_wgrad_and_grad(chip):
    g, hid, n, e = 8192, 768, 3072, 8
    lhs = chip((g, hid), jnp.bfloat16)
    rhs = chip((e, hid, n), jnp.bfloat16)
    dout = chip((g, n), jnp.bfloat16)
    sizes = chip((e,), jnp.int32)
    kernel = dict(use_kernel=True, interpret=False)

    def loss(lhs, rhs, sizes):
        return gmm(lhs, rhs, sizes, **kernel).astype(jnp.float32).sum()

    _compile(functools.partial(gmm, **kernel), lhs, rhs, sizes)
    _compile(functools.partial(tgmm, **kernel), lhs, dout, sizes)
    _compile(jax.grad(loss, argnums=(0, 1)), lhs, rhs, sizes)


def test_grouped_matmul_with_a_share_of_the_experts_held(chip):
    # The LFM2 cell's shapes (4 x 4096 tokens x 4 choices in the buffer, 8
    # experts of 2048 x 1536 held, group sizes summing to about an eighth
    # of it): 512 x 512 tiles, whose wgrad block needs more than the
    # default 16 MiB of scoped VMEM.
    g, hid, n, e = 65536, 2048, 1536, 8
    lhs = chip((g, hid), jnp.bfloat16)
    mid = chip((g, n), jnp.bfloat16)
    up = chip((e, hid, n), jnp.bfloat16)
    down = chip((e, n, hid), jnp.bfloat16)
    sizes = chip((e,), jnp.int32)
    kernel = dict(use_kernel=True, interpret=False)

    def loss(lhs, up, down, sizes):
        out = gmm(gmm(lhs, up, sizes, **kernel), down, sizes, **kernel)
        return out.astype(jnp.float32).sum()

    _compile(functools.partial(tgmm, **kernel), lhs, mid, sizes)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), lhs, up, down, sizes)
    # The second forward is dead code under a sum: gmm, 2 dgrad, 2 wgrad.
    assert text.count('custom_call_target="tpu_custom_call"') == 5


def test_expert_layer_with_row_buffers_sized_by_the_share_held(chip):
    """The LFM2 cell's expert layer, forward and gradient, for the chip:
    16,384 tokens x 4 choices with 8 of 64 experts held, so row buffers of
    16,384 rows. The Pallas grouped matmuls are the bounded path's alone, as
    many as the worst case has (3 forward, 6 backward); the overflow, under
    ``lax.cond`` in both passes, is the compiler's own ragged dots."""
    from perf import registry
    from perf.families import lfm2_moe as family
    from tpu_trainer.models import moe

    cfg = family.gpt_config(
        registry.workload("train-lfm2-24b-ep8-1chip")["config_file"])
    assert moe._receive_rows(4 * 16384, *cfg.experts_held[1:],
                             cfg.num_experts) == 16384
    layer = moe.MoEMLP(cfg)
    h = chip((4, 4096, cfg.hidden_size), jnp.bfloat16)
    params = jax.tree_util.tree_map(
        lambda s: chip(s.shape, s.dtype),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), h)["params"])

    def loss(params, h):
        out, aux = layer.apply({"params": params}, h)
        return out.astype(jnp.float32).sum() + aux

    patch = pytest.MonkeyPatch()
    # The kernel dispatch asks for the backend; the test steers that.
    patch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        text = _compile(
            jax.value_and_grad(loss, argnums=(0, 1)), params, h)
    finally:
        patch.undo()
    kernels = [name for name in re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"', text)
        if "pallas_call" in name]
    assert len(kernels) == 9 and all("branch_1_fun" in k for k in kernels)
    assert len(re.findall(r" conditional\(", text)) == 2
    # No buffer of 4 x tokens rows of activations is left, on either side.
    assert not re.search(r"(bf16|f32)\[(65536|4,16384),\d+\]", text)


# --- serving kernel --------------------------------------------------------

# (heads, kv_heads, head_dim): GPT-2-small serving geometry, then the d=128
# geometries every queued configuration has (MHA and GQA group 4).
_DECODE_GEOMETRIES = [(12, 12, 64), (16, 16, 128), (16, 4, 128)]


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("h,kvh,d", _DECODE_GEOMETRIES)
def test_flash_decode(chip, h, kvh, d, int8):
    b, bsz, mb = 8, 16, 64
    nblk = b * mb + 1
    q = chip((b, h, d), jnp.bfloat16)
    pool = chip((nblk, bsz, kvh, d), jnp.int8 if int8 else jnp.bfloat16)
    tables = chip((b, mb), jnp.int32)
    lengths = chip((b,), jnp.int32)
    if int8:
        scale = chip((nblk, bsz, kvh, 1), jnp.float32)

        def fn(q, pk, pv, tb, ln, sk, sv):
            return flash_decode(q, pk, pv, tb, ln, k_scale=sk, v_scale=sv,
                                interpret=False)

        _compile(fn, q, pool, pool, tables, lengths, scale, scale)
    else:
        _compile(functools.partial(flash_decode, interpret=False),
                 q, pool, pool, tables, lengths)


def _latent_shapes(chip, b, s, h=32):
    """q_nope, q_rope, k_nope, the ONE shared k_rope and v at the published
    head widths."""
    return [chip((b, s, h, 128), jnp.bfloat16),
            chip((b, s, h, 64), jnp.bfloat16),
            chip((b, s, h, 128), jnp.bfloat16),
            chip((b, s, 64), jnp.bfloat16),
            chip((b, s, h, 128), jnp.bfloat16)]


@pytest.mark.parametrize("b,s", [(4, 4096), (1, 1024), (1, 13312)])
def test_latent_attention_fwd_and_grad(chip, b, s):
    """The latent-attention kernels at the published head widths (128 + 64
    score lanes, the 64 against ONE shared key; 128 value lanes, 32 heads):
    forward and the one-pass backward, whose full-row dq stays in VMEM under
    the kernel's own limit (over Mosaic's default scope from s = 4096 on).
    13,312 tokens are the most the FORWARD compiles for (its whole-sequence
    K / V blocks pass the default scope at 13,824), so no length that reaches
    the backward is left to another one."""
    from tpu_trainer.ops.flash_mla import mla_flash_attention

    shapes = _latent_shapes(chip, b, s)

    def loss(*operands):
        return jnp.sum(mla_flash_attention(
            *operands, scale=192 ** -0.5).astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *shapes)
    kernels = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"', text)
    assert len(kernels) == 2                        # fwd, one-pass bwd
    assert sorted("bwd_fused" in k for k in kernels) == [False, True]


def test_latent_attention_forward_stops_before_the_backward_does(chip):
    """One block past 13,312 tokens the forward is refused (VMEM). A forward
    that learns to go further moves the length above with it: the backward's
    resident dq rows pass its own limit between 32,768 and 36,864 tokens."""
    from tpu_trainer.ops.flash_mla import mla_flash_attention

    with pytest.raises(Exception, match="vmem"):
        _compile(functools.partial(mla_flash_attention, scale=192 ** -0.5),
                 *_latent_shapes(chip, 1, 13824))


# --- the whole train step: where the compiled kernels say they came from ---

@pytest.fixture(scope="module", params=[1, 4], ids=["one-chip", "fsdp4"])
def small_step(request, topo):
    """``[(name, target, op_name)]`` of the custom calls of the `small`
    preset's train step (one layer, 1024 tokens a row) compiled for one
    described v5e, or for the 2x2 as an ``fsdp=4`` ZeRO-3 mesh. The kernel
    dispatch asks ``jax.devices()`` whether a TPU is there; the test, not
    the program, steers that."""
    from tpu_trainer.models.config import GPTConfig
    from tpu_trainer.parallel.mesh import MeshConfig, make_mesh
    from tpu_trainer.training.config import TrainingConfig
    from tpu_trainer.training.trainer import ParallelConfig, Trainer

    chips = request.param
    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    try:
        model = dataclasses.replace(
            GPTConfig.preset("small"), num_layers=1, max_seq_len=1024,
            use_flash_attention=True, fused_loss=True,
            fused_loss_pallas=True, dropout=0.0, attention_dropout=0.0)
        accum = 2 if chips == 1 else 1
        train = TrainingConfig(
            batch_size=2, gradient_accumulation_steps=accum,
            max_seq_len=1024, mixed_precision="bf16")
        mesh_cfg = MeshConfig(data=1, fsdp=chips)
        trainer = Trainer(
            model, train,
            ParallelConfig(mesh_cfg, "zero3" if chips > 1 else "replicated"),
            mesh=make_mesh(mesh_cfg, devices=list(topo.devices)[:chips]))
        shapes = jax.eval_shape(trainer._make_state, jax.random.PRNGKey(0))
        state = jax.tree_util.tree_map(
            lambda s, sharding: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=sharding),
            shapes, trainer.state_shardings)
        batch = jax.ShapeDtypeStruct((accum, 2 * chips, 1024), jnp.int32,
                                     sharding=trainer.batch_sharding)
        text = trainer.compiled_step_text(state, batch)
    finally:
        patch.undo()
    return chips, re.findall(
        r'%?([\w.\-]+) = [^\n]*? custom-call\([^\n]*?'
        r'custom_call_target="([^"]+)"[^\n]*?op_name="([^"]*)"', text)


def test_flash_kernels_fall_in_the_flash_region_with_both_phases(small_step):
    chips, calls = small_step
    kernels = [c for c in calls if c[1] == "tpu_custom_call"]
    flash = [c for c in kernels
             if program_trace.region_of(*c) == "flash"]
    assert {program_trace.phase_of(c[2]) for c in flash} == {"fwd", "bwd"}
    # Every other kernel of the step is the head + CE, under its scope.
    rest = [c for c in kernels if c not in flash]
    assert rest and all(
        program_trace.region_of(*c) == "head_loss" for c in rest)
    if chips == 1:
        # What perf/readers/flash_roofline.py matches (PR 24) still holds.
        assert all(re.match(r"^attention(\.\d+)*$", c[0]) for c in flash)
    else:
        assert all(c[0].startswith("shard_map") for c in flash)
