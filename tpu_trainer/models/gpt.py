"""TPU-native GPT model in Flax.

Re-designs the reference model (``/root/reference/src/models/gpt.py``) as an
idiomatic JAX/Flax module. Capability parity, component by component:

- RMSNorm (reference ``gpt.py:22-67``) — float32 accumulation, bf16 out.
- RoPE (``gpt.py:70-147``) — tables recomputed on the fly, never stored in the
  checkpoint (the reference persists them as buffers — SURVEY.md §2.1 b8).
- Causal self-attention (``gpt.py:150-242``) — both the fused/"flash" path and
  the manual jnp path, selected by ``config.use_flash_attention``.
- SwiGLU MLP (``gpt.py:245-283``).
- Pre-norm transformer block (``gpt.py:286-316``) — the unit of rematerialization
  (gradient checkpointing) and of FSDP sharding granularity, mirrored here as
  the unit of ``nn.remat`` + ``nn.scan``.
- GPT with tied embeddings (``gpt.py:319-455``), normal(initializer_range) init
  (``gpt.py:350-386``), shifted cross-entropy loss (``gpt.py:450-453``).
- Autoregressive generation with temperature/top-k and context-window cropping
  (``gpt.py:457-484``) — here as a jit-compiled ``lax.fori_loop``.

Architectural choices that are TPU-first rather than translations:

- Layers are stacked via ``nn.scan`` (one traced block, parameters carry a
  leading ``[num_layers, ...]`` axis). XLA compiles the block once; the stacked
  layout is also what GSPMD shards best.
- Attention uses the BSHD layout ``[batch, seq, heads, head_dim]`` end to end;
  no transposes around the kernel.
- The model is parallelism-blind (the reference's single most load-bearing
  property — SURVEY.md §1): sharding is applied entirely outside via GSPMD.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tpu_trainer.models.config import CONV_TAPS, GPTConfig
from tpu_trainer.ops import ring
from tpu_trainer.ops.attention import (
    flash_attention, mla_attention, reference_attention, window_kernel_block,
)
from tpu_trainer.ops.dropout import residual_dropout
from tpu_trainer.ops.flash import band_pairs
from tpu_trainer.ops.loss import (
    fused_shifted_cross_entropy,
    vocab_sharded_shifted_cross_entropy,
)
from tpu_trainer.ops.gated_delta import (
    gated_delta, kernel_path as gated_delta_kernel_path)
from tpu_trainer.ops.ssd import kernel_path as ssd_kernel_path, ssd
from tpu_trainer.utils import telemetry


class RMSNorm(nn.Module):
    """Root-mean-square layer norm (reference ``gpt.py:22-67``).

    ``x * rsqrt(mean(x^2) + eps) * weight`` with float32 accumulation.
    """

    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        weight = self.param("weight", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (x32 * rms * weight).astype(self.dtype)


# RoPE lives in ops/rope.py (shared with the attention dispatch and the
# fused kernel); re-exported here for API continuity.
from tpu_trainer.ops.rope import (  # noqa: E402,F401
    apply_rotary_pos_emb,
    rope_tables,
    rotate_half,
    yarn_tables,
)


class _ProjKernel(nn.Module):
    """Bare projection weight with an ``nn.Dense``-identical parameter tree.

    Creates ``<name>/kernel`` with the same shape, init, and param dtype as
    the no-bias ``nn.Dense`` it stands in for, but returns the raw kernel so
    the caller can fuse several projections into one matmul
    (``GPTConfig.fused_projections``). Checkpoints, sharding rules
    (``parallel/sharding.py`` suffix matching), and param counting are
    unchanged either way.
    """

    features: int
    param_dtype: jnp.dtype
    kernel_init: nn.initializers.Initializer

    @nn.compact
    def __call__(self, in_features: int) -> jax.Array:
        return self.param(
            "kernel", self.kernel_init, (in_features, self.features),
            self.param_dtype,
        )


def _use_fused_projections(cfg: GPTConfig) -> bool:
    """Trace-time decision for ``cfg.fused_projections``.

    TP shards the q/k/v (and gate/up) kernels along their output dim — the
    axis the fusion concatenates — so fusing there would make GSPMD gather
    the kernel shards every step. When a mesh context is published
    (``parallel/context.py``), refuse to fuse over a >1 tensor axis even if
    the config asks for it — this covers every Trainer path (the context
    stays visible inside the pipeline's partial-manual stage body, whose
    manual axes are only {stage, sequence}). The Trainer *also* flips the
    config flag off under TP so the decision is visible in the stored
    config; entry points that never publish a mesh (``eval/infer.py``)
    rely on that config-level gate.
    """
    if not cfg.fused_projections:
        return False
    from tpu_trainer.parallel import context as ctx_lib

    mesh = ctx_lib.current_mesh()
    return mesh is None or mesh.shape.get("tensor", 1) <= 1


def _fused_projection(cfg: GPTConfig, x: jax.Array, specs) -> list:
    """Run several no-bias projections of ``x`` as ONE wide matmul.

    ``specs`` is ``[(name, features), ...]``; the per-projection kernels are
    created as separate parameters (``_ProjKernel``) and concatenated at
    trace time, so x is read from HBM once and the MXU sees a single dot.
    Returns the per-projection outputs (the split of the wide result).
    Module creation happens against the caller's compact context, so the
    parameter paths land under the calling module exactly as nn.Dense would.
    """
    kern = functools.partial(
        _ProjKernel, param_dtype=cfg.params_dtype,
        kernel_init=nn.initializers.normal(cfg.initializer_range),
    )
    in_f = x.shape[-1]
    ws = [kern(features, name=name)(in_f) for name, features in specs]
    w = jnp.concatenate(ws, axis=1)
    out = x.astype(cfg.compute_dtype) @ w.astype(cfg.compute_dtype)
    bounds = np.cumsum([features for _, features in specs])[:-1].tolist()
    return jnp.split(out, bounds, axis=-1)


def _layer_rope_tables(cfg: GPTConfig, s: int, window: int):
    """The RoPE tables of a layer: a window layer's at ``rope_theta``; a
    full layer's at YaRN's frequencies and scale where the configuration
    states YaRN (``yarn_factor``)."""
    if window or not cfg.yarn_factor:
        return rope_tables(s, cfg.head_dim, cfg.rope_theta)
    return yarn_tables(s, cfg.head_dim, cfg.rope_theta, cfg.yarn_factor,
                       cfg.yarn_original_max_position, cfg.yarn_beta_fast,
                       cfg.yarn_beta_slow, cfg.yarn_attention_factor)


def _partial_rope(q, k, rotated: int, theta: float):
    """RoPE over the first ``rotated`` lanes of each head (pairs ``(i, i +
    rotated / 2)``, frequencies ``theta^(-2i / rotated)``); the other lanes
    pass through."""
    cos, sin = rope_tables(q.shape[1], rotated, theta)
    q_rot, k_rot = apply_rotary_pos_emb(
        q[..., :rotated], k[..., :rotated], cos, sin)
    return (jnp.concatenate([q_rot, q[..., rotated:]], axis=-1),
            jnp.concatenate([k_rot, k[..., rotated:]], axis=-1))


class CausalSelfAttention(nn.Module):
    """Multi-head causal self-attention (reference ``gpt.py:150-242``).

    ``decode=True`` switches to KV-cached autoregressive mode: new keys and
    values land in a ``cache`` collection at the running position, and
    queries attend over the cache — the fast decode path the reference lacks
    (its generate re-runs the full O(S^2) forward per token, ``infer.py``
    hot loop, SURVEY.md §3.5).
    """

    config: GPTConfig
    # A window layer's window (GPTConfig.sliding_window), 0 for full
    # attention: position i then sees the keys i - window < j <= i.
    window: int = 0

    @nn.compact
    def __call__(
        self, x: jax.Array, deterministic: bool = True, decode: bool = False,
        segment_ids: Optional[jax.Array] = None,
    ) -> jax.Array:
        cfg = self.config
        b, s, _ = x.shape
        if cfg.has_sliding_window and segment_ids is not None:
            raise NotImplementedError(
                "segment_ids are not supported in a model with "
                "sliding_attention layers: the banded kernels mask by "
                "position alone")
        dense = functools.partial(
            nn.Dense,
            use_bias=False,
            dtype=cfg.compute_dtype,
            param_dtype=cfg.params_dtype,
            kernel_init=nn.initializers.normal(cfg.initializer_range),
        )
        kv_features = cfg.kv_heads * cfg.head_dim
        # With the output gate, each head of q_proj is its query lanes then
        # its gate lanes.
        q_features = cfg.attention_width * (
            2 if cfg.attention_output_gate else 1)
        if _use_fused_projections(cfg):
            # One [H, H + 2*kv] matmul instead of three: x is read from HBM
            # once and the MXU sees one wide dot; params stay separate
            # (checkpoint + sharding-rule invariance — see
            # _fused_projection / GPTConfig.fused_projections).
            q, k, v = _fused_projection(
                cfg, x,
                [("q_proj", q_features), ("k_proj", kv_features),
                 ("v_proj", kv_features)],
            )
        else:
            q = dense(features=q_features, name="q_proj")(x)
            k = dense(features=kv_features, name="k_proj")(x)
            v = dense(features=kv_features, name="v_proj")(x)

        gate = None
        if cfg.attention_output_gate:
            q, gate = jnp.split(
                q.reshape(b, s, cfg.num_heads, 2 * cfg.head_dim), 2, axis=-1)
            gate = gate.reshape(b, s, cfg.attention_width)
        # [b, s, h*d] -> [b, s, heads, head_dim] (BSHD; no BHSD transpose on
        # TPU). Under GQA the k/v head dim is num_kv_heads (< num_heads).
        q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = k.reshape(b, s, cfg.kv_heads, cfg.head_dim)
        v = v.reshape(b, s, cfg.kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            # Per head, over head_dim, BEFORE RoPE (so ahead of the kernel
            # that fuses the rotation).
            qk_norm = functools.partial(
                RMSNorm, eps=cfg.norm_eps, dtype=cfg.compute_dtype)
            q = qk_norm(name="q_layernorm")(q)
            k = qk_norm(name="k_layernorm")(k)

        rotary = cfg.rotary_embedding
        if rotary and cfg.rotated_dim < cfg.head_dim and not decode:
            # RoPE over a head's first lanes only (a hybrid stack, which
            # trains only): rotated here, and the kernel rotates nothing.
            q, k = _partial_rope(q, k, cfg.rotated_dim, cfg.rope_theta)
            rotary = False

        if decode:
            out = self._decode_attention(q, k, v)
        else:
            cos, sin = _layer_rope_tables(cfg, s, self.window)
            needs_rng = cfg.attention_dropout > 0.0 and not deterministic
            dropout_rng = self.make_rng("dropout") if needs_rng else None
            manual_ctx = ring.current_manual_context()
            sp_ctx = ring.current_context()
            if segment_ids is not None and (
                (manual_ctx is not None
                 and manual_ctx.mesh.shape[manual_ctx.axis_name] > 1)
                or (sp_ctx is not None
                    and sp_ctx.mesh.shape[sp_ctx.axis_name] > 1)
            ):
                # The ring paths rotate K/V chunks across the sequence axis;
                # segment isolation there needs per-chunk segment slices the
                # ring body does not yet carry.
                raise NotImplementedError(
                    "segment_ids are not supported under sequence parallelism"
                )
            if (manual_ctx is not None
                    and manual_ctx.mesh.shape[manual_ctx.axis_name] > 1):
                # Already inside a manual region bound to the sequence axis
                # (the SP x PP jointly-manual pipeline): x is the LOCAL
                # sequence shard here. RoPE at global positions (this
                # device's chunk offset), then the ring body directly —
                # no nested shard_map.
                sp = manual_ctx.mesh.shape[manual_ctx.axis_name]
                cos_g, sin_g = rope_tables(s * sp, cfg.head_dim,
                                           cfg.rope_theta)
                off = jax.lax.axis_index(manual_ctx.axis_name) * s
                cos_l = jax.lax.dynamic_slice(
                    cos_g, (off, 0), (s, cfg.head_dim))
                sin_l = jax.lax.dynamic_slice(
                    sin_g, (off, 0), (s, cfg.head_dim))
                q, k = apply_rotary_pos_emb(q, k, cos_l, sin_l)
                out = ring.ring_attention_manual(
                    q, k, v, sp, manual_ctx.axis_name,
                    dropout_rate=cfg.attention_dropout if needs_rng else 0.0,
                    dropout_rng=dropout_rng,
                )
            elif sp_ctx is not None and sp_ctx.mesh.shape[sp_ctx.axis_name] > 1:
                # Sequence parallelism: K/V ring over the mesh's sequence
                # axis, each chunk through the flash kernel where available
                # (ops/ring.py). Attention dropout runs per chunk.
                q, k = apply_rotary_pos_emb(q, k, cos, sin)
                out = ring.ring_attention(
                    q, k, v, sp_ctx.mesh, sp_ctx.axis_name,
                    dropout_rate=cfg.attention_dropout if needs_rng else 0.0,
                    dropout_rng=dropout_rng,
                )
            elif self.window:
                # A window layer: the banded kernels (ops/flash.py) under a
                # scope of their own inside `attention`, RoPE with them.
                with jax.named_scope("sliding_window"):
                    out = self._window_attention(
                        q, k, v, (cos, sin) if rotary else None,
                        deterministic, dropout_rng)
            elif cfg.use_flash_attention:
                # RoPE rides into the kernel (rotation happens in VMEM on
                # TPU; external otherwise — ops/attention.py decides).
                out = flash_attention(
                    q, k, v,
                    dropout_rate=cfg.attention_dropout,
                    deterministic=deterministic,
                    dropout_rng=dropout_rng,
                    # None: a hybrid stack whose other operators carry the
                    # order (the ring paths above are not reached by one:
                    # GPT._mixed_layers refuses a sequence axis).
                    rope=(cos, sin) if rotary else None,
                    segment_ids=segment_ids,
                )
            else:
                if rotary:
                    q, k = apply_rotary_pos_emb(q, k, cos, sin)
                out = reference_attention(
                    q, k, v,
                    dropout_rate=cfg.attention_dropout,
                    deterministic=deterministic,
                    dropout_rng=dropout_rng,
                    segment_ids=segment_ids,
                )

        out = out.reshape(b, s, cfg.attention_width)
        if gate is not None:
            out = out * jax.nn.sigmoid(gate)
        out = dense(features=cfg.hidden_size, name="o_proj")(out)
        out = residual_dropout(self, out, cfg.dropout, deterministic)
        return out

    def _window_attention(self, q, k, v, rope, deterministic, dropout_rng):
        """Attention of a window layer, and its counters: the tokens, those
        whose attention took the banded kernels, and the key blocks the
        kernels' band visits (a block pair a head and sequence)."""
        cfg = self.config
        b, s, h, d = q.shape
        block = (window_kernel_block(q.shape) if cfg.use_flash_attention
                 else None)
        if cfg.use_flash_attention:
            out = flash_attention(
                q, k, v, dropout_rate=cfg.attention_dropout,
                deterministic=deterministic, dropout_rng=dropout_rng,
                rope=rope, window=self.window)
        else:
            if rope is not None:
                q, k = apply_rotary_pos_emb(q, k, *rope)
            out = reference_attention(
                q, k, v, dropout_rate=cfg.attention_dropout,
                deterministic=deterministic, dropout_rng=dropout_rng,
                window=self.window)
        f32 = jnp.float32
        telemetry.count("window_attn_tokens", jnp.asarray(b * s, f32))
        telemetry.count("window_kernel_tokens",
                        jnp.asarray(b * s if block else 0, f32))
        telemetry.count("window_key_blocks", jnp.asarray(
            b * h * band_pairs(s, self.window, block) if block else 0, f32))
        return out

    def _decode_attention(self, q, k, v) -> jax.Array:
        """KV-cached attention over ``cache`` variables.

        The cache holds ``[b, max_seq_len, heads, head_dim]`` per layer plus
        the running length ``idx``; a call with ``s`` tokens appends at
        ``idx`` (prefill: s = prompt length; decode: s = 1) and every query
        attends to positions ``<= its own``. RoPE is applied at the *global*
        positions ``idx..idx+s-1``.
        """
        cfg = self.config
        if cfg.decode_paged:
            return self._paged_decode_attention(q, k, v)
        b, s, h, d = q.shape
        kvh = k.shape[2]  # num_kv_heads: the GQA cache is group-fold smaller
        # Cache length: the static decode window when set (generate_kv
        # sizes it to prompt+new rounded to 128) — the buffer, the DUS
        # writes, and every attention read scale with it instead of the
        # full context limit.
        max_len = cfg.max_seq_len
        if 0 < cfg.decode_window < max_len:
            max_len = cfg.decode_window
        ck = self.variable(
            "cache", "k", jnp.zeros, (b, max_len, kvh, d), cfg.compute_dtype
        )
        cv = self.variable(
            "cache", "v", jnp.zeros, (b, max_len, kvh, d), cfg.compute_dtype
        )
        ci = self.variable(
            "cache", "idx", lambda: jnp.zeros((), jnp.int32)
        )
        # Per-row left-pad sizes for ragged batches (generate_kv left-pads
        # mixed-length prompts to a shared frontier): row r's positions
        # < pad[r] are padding — excluded from attention windows and from
        # RoPE position counting. The variable only exists (and the
        # per-row machinery only traces) when the caller statically asked
        # for ragged decode — uniform batches keep the cheaper shared-
        # position path.
        ragged = cfg.decode_ragged
        if ragged:
            cp = self.variable(
                "cache", "pad", lambda: jnp.zeros((b,), jnp.int32)
            )
            pad = cp.value
        idx = ci.value

        cos, sin = rope_tables(max_len, d, cfg.rope_theta)
        if ragged:
            # Logical (post-pad) positions per row; clamped at 0 for the
            # pad region itself (whose outputs are never read).
            gpos = idx + jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)
            lpos = jnp.maximum(gpos - pad[:, None], 0)          # [b, s]
            q, k = apply_rotary_pos_emb(q, k, cos[lpos], sin[lpos])
        else:
            cos_s = jax.lax.dynamic_slice(cos, (idx, 0), (s, d))
            sin_s = jax.lax.dynamic_slice(sin, (idx, 0), (s, d))
            q, k = apply_rotary_pos_emb(q, k, cos_s, sin_s)

        k_all = jax.lax.dynamic_update_slice(ck.value, k, (0, idx, 0, 0))
        v_all = jax.lax.dynamic_update_slice(cv.value, v, (0, idx, 0, 0))
        if not self.is_initializing():
            ck.value = k_all
            cv.value = v_all
            ci.value = idx + s

        if kvh != h:
            # Expand K/V heads to the query heads' groups for the einsum
            # (decode batches are small; the cache itself stays compact).
            from tpu_trainer.ops.attention import repeat_kv

            k_all, v_all = repeat_kv(k_all, v_all, h)
        scale = 1.0 / (d ** 0.5)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_all) * scale
        q_pos = idx + jax.lax.broadcasted_iota(jnp.int32, (s, max_len), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (s, max_len), 1)
        if ragged:
            # Causal, excluding each row's left padding. Pad-region queries
            # keep their self position so their (never-read) softmax rows
            # stay finite — an empty window would put NaN into this
            # position's residual stream and poison later layers' cached
            # K/V.
            allowed = (k_pos[None] <= q_pos[None]) & (
                (k_pos[None] >= pad[:, None, None])
                | (k_pos[None] == q_pos[None])
            )
            scores = jnp.where(
                allowed[:, None], scores, jnp.finfo(scores.dtype).min
            )
        else:
            allowed = k_pos <= q_pos
            scores = jnp.where(
                allowed[None, None], scores, jnp.finfo(scores.dtype).min
            )
        weights = jax.nn.softmax(
            scores.astype(jnp.float32), axis=-1
        ).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v_all)

    def _paged_decode_attention(self, q, k, v) -> jax.Array:
        """KV-cached attention over a PAGED cache (``cfg.decode_paged``).

        Instead of one contiguous ``[b, max_len, ...]`` buffer per row, KV
        history lives in fixed-size blocks inside a shared pool
        (``[num_blocks, block_size, kvh, d]``), addressed through per-row
        block tables — the serving engine allocates/frees blocks from a
        free list so memory scales with tokens actually cached, not with
        slots * context limit (tpu_trainer/serving/paged_cache.py).

        Cache-variable contract (the engine writes ``tables``/
        ``lengths``/``offsets`` from its host-side state before every
        call):

        - prefill (``s > 1``): row r's tokens are a CHUNK starting at
          global position ``offsets[r]`` (0 = classic whole-prompt
          prefill); ``lengths[r]`` is the row's total cached tokens
          AFTER this chunk, so the chunk's true width is ``lengths[r] -
          offsets[r]`` within the right-padded ``s`` (attention masks
          beyond it; padded positions scatter into the null block 0).
          Attention runs over this call's in-flight k/v plus — when
          ``cfg.paged_hist_blocks > 0`` — the first ``paged_hist_blocks``
          pooled blocks of each row, masked to positions strictly below
          ``offsets[r]`` (the history deposited by earlier chunks or a
          shared prefix). History k/v precede the in-flight k/v in the
          softmax's key order, i.e. in ascending global position — the
          same order the monolithic pass reduces in, which is what keeps
          chunked greedy streams bit-identical. ``lengths`` is left
          as-is (it already counts the tokens deposited so far).
          The speculative-decode verifier (serving/spec.py) rides this
          exact branch: it feeds [last accepted token + K drafts] as a
          chunk at ``offsets[r] = cached_tokens`` and consumes the
          model's per-position logits for the whole window (the model
          always returns ``[b, s, vocab]``; slicing to the last position
          is the caller's choice), scoring all K+1 candidates in one
          forward. Rejected positions' pool writes are harmless: the
          host rewinds ``lengths`` and every read masks by it.
        - decode (``s == 1``): the new token writes at position
          ``lengths[r]`` of row r's table and attends over ``lengths[r]
          + 1`` pooled positions (flash_decode kernel or the jnp
          reference, ``cfg.paged_attention``); ``lengths`` increments.
          ``offsets`` is ignored (broadcast as zeros).

        Tensor parallel (``cfg.paged_tp > 1``): both branches run the
        identical per-head math under a head-sharded ``shard_map`` over
        the replica's mesh (serving/sharding.py) — decode via
        ``ops.flash.paged_attention_sharded``, prefill via the local
        ``attend`` closure — closing with an exact disjoint-slice
        all-reduce, so sharded greedy streams stay token-identical to
        the single-device engine. Tables/lengths/offsets remain
        replicated host mirrors; only the pools (when ``kvh % tp == 0``)
        and the heads axis of activations split.
        """
        cfg = self.config
        b, s, h, d = q.shape
        kvh = k.shape[2]
        bsz = cfg.paged_block_size
        nblk = cfg.paged_num_blocks
        mb = cfg.paged_max_blocks
        int8 = cfg.paged_kv_int8
        from tpu_trainer.utils.quant import quant_block_len, quantize_kv_int8

        nbq = d // quant_block_len(d)
        kv_dtype = jnp.int8 if int8 else cfg.compute_dtype
        pk = self.variable(
            "cache", "pool_k", jnp.zeros, (nblk, bsz, kvh, d), kv_dtype)
        pv = self.variable(
            "cache", "pool_v", jnp.zeros, (nblk, bsz, kvh, d), kv_dtype)
        if int8:
            sk = self.variable(
                "cache", "scale_k", jnp.zeros, (nblk, bsz, kvh, nbq),
                jnp.float32)
            sv = self.variable(
                "cache", "scale_v", jnp.zeros, (nblk, bsz, kvh, nbq),
                jnp.float32)
        tb = self.variable("cache", "tables", jnp.zeros, (b, mb), jnp.int32)
        ln = self.variable("cache", "lengths", jnp.zeros, (b,), jnp.int32)
        of = self.variable("cache", "offsets", jnp.zeros, (b,), jnp.int32)
        tables, lengths, offsets = tb.value, ln.value, of.value

        cos, sin = rope_tables(mb * bsz, d, cfg.rope_theta)
        if s == 1:
            pos = lengths[:, None]                               # [b, 1]
        else:
            # Chunked prefill: row r's local position i sits at global
            # position offsets[r] + i (offsets is all-zero for the
            # classic whole-prompt pass).
            pos = offsets[:, None] + jax.lax.broadcasted_iota(
                jnp.int32, (b, s), 1)
        rope_pos = jnp.minimum(pos, mb * bsz - 1)  # pad rows may overrun
        q, k = apply_rotary_pos_emb(q, k, cos[rope_pos], sin[rope_pos])

        # Scatter this call's k/v into the pool: position p of row r lands
        # at (tables[r, p // bsz], p % bsz). Prefill padding (p >= the
        # row's true length) redirects to the reserved null block 0 —
        # written garbage there is never read (every read masks by
        # lengths), so a [b*s] flat scatter needs no predication.
        write_pos = pos
        valid = (write_pos < lengths[:, None]) if s > 1 else (
            jnp.ones((b, 1), bool))
        blk_ids = jnp.take_along_axis(
            tables, jnp.minimum(write_pos // bsz, mb - 1), axis=1)
        blk_ids = jnp.where(valid, blk_ids, 0).reshape(-1)
        offs = jnp.where(valid, write_pos % bsz, 0).reshape(-1)
        if int8:
            k_q, k_s = quantize_kv_int8(k)
            v_q, v_s = quantize_kv_int8(v)
            pool_k = pk.value.at[blk_ids, offs].set(
                k_q.reshape(b * s, kvh, d))
            pool_v = pv.value.at[blk_ids, offs].set(
                v_q.reshape(b * s, kvh, d))
            scale_k = sk.value.at[blk_ids, offs].set(
                k_s.reshape(b * s, kvh, nbq))
            scale_v = sv.value.at[blk_ids, offs].set(
                v_s.reshape(b * s, kvh, nbq))
        else:
            pool_k = pk.value.at[blk_ids, offs].set(
                k.astype(kv_dtype).reshape(b * s, kvh, d))
            pool_v = pv.value.at[blk_ids, offs].set(
                v.astype(kv_dtype).reshape(b * s, kvh, d))
            scale_k = scale_v = None

        if s > 1:
            # Prefill attention runs over the in-flight k/v (everything
            # from this chunk was just computed): ragged causal in LOCAL
            # coordinates — the chunk holds lengths - offsets true tokens
            # — keeping each pad query's self position so its (never-read)
            # softmax row stays finite, same rationale as the contiguous
            # ragged path above. With offsets == 0 this is exactly the
            # original whole-prompt mask.
            kf, vf = k, v
            if int8:
                # Attend the quantization the pool will actually hold:
                # a later decode step reads these positions back through
                # the int8 round-trip, so a multi-token window (chunked
                # prefill, speculative verify) must see the same values
                # now — otherwise a token scored here and a token scored
                # by the one-at-a-time path diverge under int8.
                from tpu_trainer.utils.quant import dequantize_kv_int8

                kf = dequantize_kv_int8(k_q, k_s, q.dtype)
                vf = dequantize_kv_int8(v_q, v_s, q.dtype)
            if kvh != h:
                from tpu_trainer.ops.attention import repeat_kv

                kf, vf = repeat_kv(kf, vf, h)
            scale = 1.0 / (d ** 0.5)
            hb = cfg.paged_hist_blocks
            hk = hv = None
            if hb > 0:
                # Non-zero-offset chunk: also attend the pooled history
                # (earlier chunks / shared prefix) — the first hb table
                # entries of each row, masked to global positions below
                # offsets[r]. Reading the post-scatter pool is safe: the
                # positions this chunk just wrote are >= offsets and
                # masked out here (the in-flight path covers them).
                from tpu_trainer.utils.quant import dequantize_kv_int8

                htab = tables[:, :hb]                       # [b, hb]
                hk = pool_k[htab].reshape(b, hb * bsz, kvh, d)
                hv = pool_v[htab].reshape(b, hb * bsz, kvh, d)
                if int8:
                    hks = scale_k[htab].reshape(b, hb * bsz, kvh, nbq)
                    hvs = scale_v[htab].reshape(b, hb * bsz, kvh, nbq)
                    hk = dequantize_kv_int8(hk, hks, q.dtype)
                    hv = dequantize_kv_int8(hv, hvs, q.dtype)
                else:
                    hk = hk.astype(q.dtype)
                    hv = hv.astype(q.dtype)
                if kvh != h:
                    from tpu_trainer.ops.attention import repeat_kv

                    hk, hv = repeat_kv(hk, hv, h)

            # In-flight (+ optional pooled-history) attention over FULL
            # q-head inputs. Extracted as a closure so the tensor-parallel
            # path can run the identical math per head shard under
            # shard_map: softmax reduces over keys only, so splitting the
            # heads axis changes no arithmetic, and kf/vf/hk/hv are
            # repeated to q heads BEFORE sharding so GQA needs no special
            # casing here (repeat-then-shard).
            def attend(q_a, kf_a, vf_a, ln_a, of_a, *hist):
                scores = jnp.einsum("bqhd,bkhd->bhqk", q_a, kf_a) * scale
                q_pos = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
                k_pos = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
                chunk_len = (ln_a - of_a)[:, None, None]
                allowed = (k_pos[None] <= q_pos[None]) & (
                    (k_pos[None] < chunk_len)
                    | (k_pos[None] == q_pos[None])
                )
                scores = jnp.where(
                    allowed[:, None], scores, jnp.finfo(scores.dtype).min)
                v_cat = vf_a
                if hist:
                    hk_a, hv_a = hist
                    h_scores = jnp.einsum(
                        "bqhd,bkhd->bhqk", q_a, hk_a) * scale
                    h_pos = jax.lax.broadcasted_iota(
                        jnp.int32, (b, hb * bsz), 1)
                    h_allowed = h_pos < of_a[:, None]       # [b, hb*bsz]
                    h_scores = jnp.where(
                        h_allowed[:, None, None], h_scores,
                        jnp.finfo(h_scores.dtype).min)
                    # History keys come FIRST: ascending global position,
                    # the same reduce order as the monolithic pass — the
                    # bit-exactness contract of chunked prefill.
                    scores = jnp.concatenate([h_scores, scores], axis=-1)
                    v_cat = jnp.concatenate([hv_a, vf_a], axis=1)
                weights = jax.nn.softmax(
                    scores.astype(jnp.float32), axis=-1).astype(q_a.dtype)
                return jnp.einsum("bhqk,bkhd->bqhd", weights, v_cat)

            hist = () if hk is None else (hk, hv)
            tp = cfg.paged_tp
            if tp > 1:
                from jax.sharding import PartitionSpec as P

                from tpu_trainer.parallel.mesh import TP_AXIS, tp_mesh
                from tpu_trainer.utils.jax_compat import shard_map

                mesh = tp_mesh(tp, cfg.paged_tp_devices)
                hl = h // tp
                head = P(None, None, TP_AXIS, None)
                in_specs = [head, head, head, P(), P()]
                in_specs += [head] * len(hist)

                def body(q_l, kf_l, vf_l, ln_l, of_l, *hist_l):
                    i = jax.lax.axis_index(TP_AXIS)
                    out_l = attend(q_l, kf_l, vf_l, ln_l, of_l, *hist_l)
                    # Disjoint head slices: the psum is an exact concat
                    # (one non-zero contributor per element).
                    full = jnp.zeros((b, s, h, d), out_l.dtype)
                    full = jax.lax.dynamic_update_slice(
                        full, out_l, (0, 0, i * hl, 0))
                    return jax.lax.psum(full, TP_AXIS)

                out = shard_map(
                    body, mesh=mesh, in_specs=tuple(in_specs),
                    out_specs=P(), check_vma=False,
                )(q, kf, vf, lengths, offsets, *hist)
            else:
                out = attend(q, kf, vf, lengths, offsets, *hist)
            new_len = lengths
        else:
            from tpu_trainer.ops import flash as flash_lib

            new_len = lengths + 1
            impl = cfg.paged_attention
            if impl == "auto":
                impl = ("kernel" if jax.default_backend() == "tpu"
                        else "reference")
            if cfg.paged_tp > 1:
                from tpu_trainer.parallel.mesh import tp_mesh

                out = flash_lib.paged_attention_sharded(
                    q[:, 0], pool_k, pool_v, tables, new_len,
                    mesh=tp_mesh(cfg.paged_tp, cfg.paged_tp_devices),
                    k_scale=scale_k, v_scale=scale_v, impl=impl,
                ).astype(q.dtype)[:, None]                # [b, 1, h, d]
            else:
                fn = (flash_lib.flash_decode if impl == "kernel"
                      else flash_lib.paged_attention_reference)
                out = fn(
                    q[:, 0], pool_k, pool_v, tables, new_len,
                    k_scale=scale_k, v_scale=scale_v,
                ).astype(q.dtype)[:, None]                # [b, 1, h, d]

        if not self.is_initializing():
            pk.value = pool_k
            pv.value = pool_v
            if int8:
                sk.value = scale_k
                sv.value = scale_v
            ln.value = new_len
        return out


_NO_LATENT_DECODE = (
    "decode is not supported under latent attention: the cache of [tokens, "
    "kv_lora_rank + rope] latents and the absorbed decode order are not built")


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2 / -V3 section 2.1), in its
    training order (nothing absorbed): ``c_q = rms(x W_qa)``, ``q = c_q
    W_qb`` (a head: ``qk_nope_head_dim`` lanes, then ``qk_rope_head_dim``
    rotated ones); ``[c_kv | k_r] = x W_kva``, ``c_kv = rms(c_kv)``,
    ``[k_nope | v] = c_kv W_kvb`` a head; ``k_r`` rotated, ONE head that all
    share; scores ``(q_nope . k_nope + q_r . k_r) / sqrt(nope + rope)``,
    causal softmax in f32, ``o = (P v) W_o``. No biases.

    The parameters keep the published layout (``q_b_proj`` ``[q_lora_rank,
    heads * (nope + rope)]`` ...); what the kernels want is cut out of the
    WEIGHTS, not the activations: the ``nope`` and ``rope`` columns of
    ``W_qb`` (and ``k_nope`` / ``v`` of ``W_kvb``) are two matmuls whose
    results are already folded ``[b, s, heads * w]``, and ``rope_interleave``
    (the rotation pairs lanes ``(2i, 2i+1)``) is a permutation of the rope
    columns to the half-split order, the same for q and k, which no score
    sees. Training and evaluation only."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True,
                 decode: bool = False,
                 segment_ids: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        if decode:
            raise NotImplementedError(_NO_LATENT_DECODE)
        if segment_ids is not None:
            raise NotImplementedError(
                "segment_ids are not supported under latent attention")
        b, s, _ = x.shape
        heads, nope, rope, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                                 cfg.qk_rope_head_dim, cfg.v_head_dim)
        cd = cfg.compute_dtype
        kern = functools.partial(
            _ProjKernel, param_dtype=cfg.params_dtype,
            kernel_init=nn.initializers.normal(cfg.initializer_range))
        norm = functools.partial(RMSNorm, eps=cfg.norm_eps, dtype=cd)
        half_split = (np.concatenate([np.arange(0, rope, 2),
                                      np.arange(1, rope, 2)])
                      if cfg.rope_interleave else np.arange(rope))

        def columns(w, width, cols):
            """Columns ``cols`` of every head's ``width`` of ``w``."""
            return w.reshape(w.shape[0], heads, width)[:, :, cols].reshape(
                w.shape[0], heads * len(cols)).astype(cd)

        x = x.astype(cd)
        w_qa = kern(cfg.q_lora_rank, name="q_a_proj")(cfg.hidden_size)
        c_q = norm(name="q_a_layernorm")(x @ w_qa.astype(cd))
        w_qb = kern(heads * (nope + rope), name="q_b_proj")(cfg.q_lora_rank)
        q_nope = (c_q @ columns(w_qb, nope + rope, np.arange(nope))
                  ).reshape(b, s, heads, nope)
        q_rope = (c_q @ columns(w_qb, nope + rope, nope + half_split)
                  ).reshape(b, s, heads, rope)
        w_kva = kern(cfg.kv_lora_rank + rope,
                     name="kv_a_proj_with_mqa")(cfg.hidden_size)
        kv_a = x @ w_kva[:, np.concatenate([
            np.arange(cfg.kv_lora_rank), cfg.kv_lora_rank + half_split])
        ].astype(cd)
        c_kv = norm(name="kv_a_layernorm")(kv_a[..., :cfg.kv_lora_rank])
        k_rope = kv_a[..., cfg.kv_lora_rank:]
        w_kvb = kern(heads * (nope + dv), name="kv_b_proj")(cfg.kv_lora_rank)
        k_nope = (c_kv @ columns(w_kvb, nope + dv, np.arange(nope))
                  ).reshape(b, s, heads, nope)
        v = (c_kv @ columns(w_kvb, nope + dv, nope + np.arange(dv))
             ).reshape(b, s, heads, dv)

        cos, sin = rope_tables(s, rope, cfg.rope_theta)
        q_rope, k_rope = apply_rotary_pos_emb(
            q_rope, k_rope[:, :, None, :], cos, sin)
        out = mla_attention(q_nope, q_rope, k_nope, k_rope[:, :, 0], v,
                            scale=float(nope + rope) ** -0.5)
        w_o = kern(cfg.hidden_size, name="o_proj")(heads * dv)
        out = out.reshape(b, s, heads * dv).astype(cd) @ w_o.astype(cd)
        return residual_dropout(self, out, cfg.dropout, deterministic)


class MLP(nn.Module):
    """SwiGLU feed-forward (reference ``gpt.py:245-283``):
    ``down(silu(gate(x)) * up(x))`` + dropout."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        cfg = self.config
        dense = functools.partial(
            nn.Dense,
            use_bias=False,
            dtype=cfg.compute_dtype,
            param_dtype=cfg.params_dtype,
            kernel_init=nn.initializers.normal(cfg.initializer_range),
        )
        if cfg.ffn_kind == "relu2":
            # Two matrices, no gate: down(relu(up x)^2).
            x = jnp.square(nn.relu(dense(cfg.intermediate_size,
                                         name="up_proj")(x)))
            x = dense(cfg.hidden_size, name="down_proj")(x)
            return residual_dropout(self, x, cfg.dropout, deterministic)
        if _use_fused_projections(cfg):
            # gate+up as one [H, 2I] matmul (see _fused_projection).
            gate, up = _fused_projection(
                cfg, x,
                [("gate_proj", cfg.intermediate_size),
                 ("up_proj", cfg.intermediate_size)],
            )
        else:
            gate = dense(cfg.intermediate_size, name="gate_proj")(x)
            up = dense(cfg.intermediate_size, name="up_proj")(x)
        act = {"silu": nn.silu, "gelu": nn.gelu}[cfg.activation]
        x = act(gate) * up
        x = dense(cfg.hidden_size, name="down_proj")(x)
        return residual_dropout(self, x, cfg.dropout, deterministic)


class ShortConv(nn.Module):
    """Gated short convolution, the sequence operator of a ``conv`` layer:
    ``[B, C, u] = split3(x W_in)``, ``z = B * u``, a depthwise causal
    convolution over the current and ``CONV_TAPS - 1`` earlier positions
    ``v_t = sum_j w[:, j] * z_{t-(L-1-j)}`` (zeros before the sequence, and
    before a packed document's first token), then ``(C * v) W_out``. No
    bias, no activation. Plain ``jax.numpy``: L - 1 shifted multiply-adds
    (``causal_taps``) beside two matmuls."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True,
                 segment_ids: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        hidden, taps = cfg.hidden_size, CONV_TAPS
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=cfg.compute_dtype,
            param_dtype=cfg.params_dtype,
            kernel_init=nn.initializers.normal(cfg.initializer_range))
        gate_in, gate_out, u = jnp.split(
            dense(3 * hidden, name="in_proj")(x), 3, axis=-1)
        z = gate_in * u
        # [channels, taps], the last tap on the current position (torch's
        # depthwise Conv1d layout and its default init, +-1/sqrt(taps)).
        bound = taps ** -0.5
        weight = self.param(
            "conv_weight",
            lambda key, shape, dtype: jax.random.uniform(
                key, shape, dtype, -bound, bound),
            (hidden, taps), cfg.params_dtype).astype(cfg.compute_dtype)
        v = causal_taps(z, weight, segment_ids=segment_ids)
        out = dense(hidden, name="out_proj")(gate_out * v)
        return residual_dropout(self, out, cfg.dropout, deterministic)


def causal_taps(z, weight, bias=None, segment_ids=None):
    """Depthwise causal convolution over the sequence: ``v_t = sum_j w[:, j]
    z_{t-(L-1-j)}`` (+ bias), ``z [batch, seq, channels]``, ``weight
    [channels, L]`` with the last tap on the current position (torch's
    depthwise Conv1d layout); zeros before the sequence, and before a packed
    document's first token. ``L - 1`` shifted multiply-adds."""
    taps = weight.shape[1]
    v = z * weight[:, taps - 1]
    for back in range(1, taps):
        shifted = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :-back]
        if segment_ids is not None:
            same = segment_ids == jnp.pad(
                segment_ids, ((0, 0), (back, 0)),
                constant_values=-1)[:, :-back]
            shifted = jnp.where(same[..., None], shifted, 0)
        v = v + shifted * weight[:, taps - 1 - back]
    return v if bias is None else v + bias


def _mamba_dt_bias(cfg: GPTConfig):
    """Mamba-2's initialiser of ``dt_bias``: the inverse softplus of time
    steps log-uniform in ``[mamba_dt_min, mamba_dt_max]``, floored."""
    lo, hi = np.log(cfg.mamba_dt_min), np.log(cfg.mamba_dt_max)

    def init(key, shape, dtype):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, lo, hi)), cfg.mamba_dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


class _Weight(nn.Module):
    """A ``[width]`` vector of ones under ``<name>/weight``: a norm's weight
    for a caller that applies the norm itself."""

    width: int

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param("weight", nn.initializers.ones, (self.width,),
                          jnp.float32)


def _mamba_core(cfg: GPTConfig, zxbcdt, conv_weight, conv_bias, dt_bias,
                a_log, skip, norm_weight):
    """A Mamba-2 mixer between its two projections: ``[z, xBC, dt]`` in,
    the gated, group-normalised ``y [batch, seq, d_in]`` out (and the scan's
    most negative in-chunk log-decay); the skip, the gate and the norm are
    ``ssd``'s epilogue. One function so that the mixer can recompute it in
    the backward (``Mamba2Mixer``)."""
    b, s, _ = zxbcdt.shape
    heads, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    groups, n = cfg.mamba_n_groups, cfg.ssm_state_size
    inner, conv_dim = cfg.mamba_inner, cfg.mamba_conv_dim
    cd, f32 = cfg.compute_dtype, jnp.float32
    z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
    with jax.named_scope("taps"):
        xbc = nn.silu(causal_taps(xbc, conv_weight.astype(cd),
                                  conv_bias.astype(cd)))
    x, b_in, c_in = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    # The scan with the skip, the gate and the grouped norm taken in.
    return ssd(x.reshape(b, s, heads, p), dt, -jnp.exp(a_log.astype(f32)),
               b_in.reshape(b, s, groups, n), c_in.reshape(b, s, groups, n),
               chunk=cfg.mamba_chunk_size, dtype=cd,
               gate=(z, skip, norm_weight, cfg.norm_eps))


class Mamba2Mixer(nn.Module):
    """The Mamba-2 state-space mixer (arXiv:2405.21060; Nemotron-H's layer,
    arXiv:2504.03624), the sequence operator of a ``mamba`` layer. With ``H``
    heads of ``P`` lanes (``d_in = H P``), ``G`` groups and ``N`` lanes of
    state: ``[z, xBC, dt] = u W_in`` (``d_in``, ``d_in + 2 G N``, ``H`` wide,
    in that order); ``xBC <- silu(causal depthwise conv with bias)``; ``[x,
    B, C] = xBC``; ``dt <- softplus(dt + dt_bias)``; ``A = -exp(A_log)``;
    per head ``h`` of group ``h // (H / G)`` the scan ``S_t = exp(dt_t A_h)
    S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D_h x_t`` (``ops/ssd.py``,
    chunked); ``y <- RMSNorm_groups(y * silu(z))``, the gate first, then a
    norm over each group's ``d_in / G`` lanes times a ``[d_in]`` weight;
    ``out = y W_out``. No bias but the conv's.

    What lies between the two projections (``_mamba_core``) is recomputed
    in the backward from ``[z, xBC, dt]``, as the family's fused kernels do:
    the taps, their silu, the scan (on a TPU the Pallas kernels of
    ``ops/ssd.py``, whose ``[chunk, chunk]`` scores and decays never leave
    VMEM, and which add the skip and apply the gate and the norm to a chunk
    while its ``y`` is still there; the states its chunks start from live
    only inside the block's backward), the gate and the norm never live
    between the passes; a block keeps its in-projection's result and the
    normalised ``y``. The step metrics say what ran: ``ssm_tokens`` the
    tokens scanned, ``ssd_kernel_tokens`` those of them whose scan took the
    kernels.

    ``A_log``, ``dt_bias`` and ``D`` are consumed in float32
    (``F32_LEAVES``: whoever keeps a compute-type copy of the parameters
    leaves them as they are), and ``A_log`` and ``D`` take no weight decay
    (``UNDECAYED``, the family's convention). Training and evaluation
    only."""

    config: GPTConfig
    F32_LEAVES = ("A_log", "dt_bias", "D")
    UNDECAYED = ("A_log", "D")

    @nn.compact
    def __call__(self, u: jax.Array, deterministic: bool = True,
                 segment_ids: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        if segment_ids is not None:
            raise NotImplementedError(
                "segment_ids are not supported under mamba layers: the scan "
                "does not reset its state at a packed document's first token")
        heads = cfg.mamba_num_heads
        inner, conv_dim = cfg.mamba_inner, cfg.mamba_conv_dim
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=cfg.compute_dtype,
            param_dtype=cfg.params_dtype,
            kernel_init=nn.initializers.normal(cfg.initializer_range))
        zxbcdt = dense(inner + conv_dim + heads, name="in_proj")(u)
        bound = cfg.mamba_conv_kernel ** -0.5

        def uniform(key, shape, dtype):
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        f32 = jnp.float32
        leaves = (
            self.param("conv_weight", uniform,
                       (conv_dim, cfg.mamba_conv_kernel), cfg.params_dtype),
            self.param("conv_bias", uniform, (conv_dim,), cfg.params_dtype),
            self.param("dt_bias", _mamba_dt_bias(cfg), (heads,), f32),
            self.param("A_log", lambda key, shape, dtype: jnp.log(
                jax.random.uniform(key, shape, dtype, 1.0, 16.0)),
                (heads,), f32),
            self.param("D", nn.initializers.ones, (heads,), f32),
            _Weight(inner, name="norm")())
        y, low = jax.checkpoint(functools.partial(_mamba_core, cfg))(
            zxbcdt, *leaves)
        tokens = u.shape[0] * u.shape[1]
        telemetry.count("ssm_tokens", jnp.asarray(tokens, f32))
        # The tokens whose scan took the Pallas kernels (``ops/ssd.py``), and
        # with it the skip, the gate and the grouped norm inside them.
        kernels = ssd_kernel_path(
            (*u.shape[:2], heads, cfg.mamba_head_dim),
            (*u.shape[:2], cfg.mamba_n_groups, cfg.ssm_state_size),
            cfg.mamba_chunk_size) is not None
        telemetry.count("ssd_kernel_tokens",
                        jnp.asarray(tokens if kernels else 0, f32))
        telemetry.count("ssd_min_log_decay", low, reduce="min")
        out = dense(cfg.hidden_size, name="out_proj")(y)
        return residual_dropout(self, out, cfg.dropout, deterministic)


def _l2_normalised(x, eps: float = 1e-6):
    """``x * rsqrt(sum(x^2) + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class GatedDeltaNet(nn.Module):
    """The Gated DeltaNet mixer (arXiv:2412.06464; Qwen3-Next's linear-
    attention layer), the sequence operator of a ``linear_attention`` layer.
    With ``H_k`` key heads of ``d_k`` lanes and ``H_v`` value heads of
    ``d_v`` (``r = H_v / H_k``):

    - ``[q, k, v, z] = x W_qkvz``, the columns grouped by key head ``h``:
      ``[q_h, k_h, v_{rh..rh+r-1}, z_{rh..rh+r-1}]``; ``[b, a] = x W_ba``,
      grouped alike (``[b_{rh..}, a_{rh..}]``);
    - ``[q, k, v] <- silu(causal depthwise conv of K taps, no bias)`` over
      their ``2 H_k d_k + H_v d_v`` channels (scope ``taps``);
    - ``beta = sigmoid(b)``; the log decay ``g = -exp(A_log) softplus(a +
      dt_bias)``, per value head and token, in float32;
    - ``q <- l2norm(q) / sqrt(d_k)``, ``k <- l2norm(k)``; value head ``j``
      reads key head ``j // r``;
    - the gated delta rule (``ops/gated_delta.py``, chunked; on a TPU the
      Pallas kernels, whose chunk matrices and carried states never leave
      VMEM): ``S' = e^g S``, ``S <- S' + k (beta (v - S'^T k))^T``, ``o =
      S^T q``;
    - ``o <- RMSNorm(o) w ⊙ silu(z)`` per value head over its ``d_v``
      lanes (the norm first, then the gate), ``out = o W_out``.

    ``A_log`` and ``dt_bias`` are consumed in float32 (``F32_LEAVES``) and
    take no weight decay (``UNDECAYED``). The step metrics count the tokens
    (``gated_delta_tokens``), those of them whose rule took the kernels
    (``gated_delta_kernel_tokens``) and the most negative in-chunk
    cumulative log decay (``gated_delta_min_log_decay``). Training and
    evaluation only."""

    config: GPTConfig
    F32_LEAVES = ("A_log", "dt_bias")
    UNDECAYED = ("A_log", "dt_bias")

    @nn.compact
    def __call__(self, u: jax.Array, deterministic: bool = True,
                 segment_ids: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        if segment_ids is not None:
            raise NotImplementedError(
                "segment_ids are not supported under linear_attention "
                "layers: the delta rule does not reset its state at a "
                "packed document's first token")
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        per, taps = hv // hk, cfg.linear_conv_kernel_dim
        b, s, _ = u.shape
        cd, f32 = cfg.compute_dtype, jnp.float32
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=cd, param_dtype=cfg.params_dtype,
            kernel_init=nn.initializers.normal(cfg.initializer_range))
        qkvz = dense(cfg.linear_conv_dim + hv * dv, name="in_proj_qkvz")(
            u).reshape(b, s, hk, 2 * dk + 2 * per * dv)
        q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + per * dv], axis=-1)
        beta, a = jnp.split(
            dense(2 * hv, name="in_proj_ba")(u).reshape(b, s, hk, 2 * per),
            2, axis=-1)
        bound = taps ** -0.5
        weight = self.param(
            "conv_weight", lambda key, shape, dtype: jax.random.uniform(
                key, shape, dtype, -bound, bound),
            (cfg.linear_conv_dim, taps), cfg.params_dtype)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,), f32)
        a_log = self.param("A_log", lambda key, shape, dtype: jnp.log(
            jax.random.uniform(key, shape, dtype, 0.0, 16.0)), (hv,), f32)
        norm_weight = _Weight(dv, name="norm")()

        with jax.named_scope("taps"):
            mixed = nn.silu(causal_taps(
                jnp.concatenate([q.reshape(b, s, hk * dk),
                                 k.reshape(b, s, hk * dk),
                                 v.reshape(b, s, hv * dv)], axis=-1),
                weight.astype(cd)))
        q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
        q = _l2_normalised(q.reshape(b, s, hk, dk)) * dk ** -0.5
        k = _l2_normalised(k.reshape(b, s, hk, dk))
        beta = jax.nn.sigmoid(beta.reshape(b, s, hv).astype(f32))
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            a.reshape(b, s, hv).astype(f32) + dt_bias.astype(f32))
        o, low = gated_delta(q, k, v.reshape(b, s, hv, dv), g, beta, dtype=cd)
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + cfg.norm_eps) * norm_weight
        o = (o * jax.nn.silu(z.reshape(b, s, hv, dv).astype(f32))).astype(cd)
        telemetry.count("gated_delta_tokens", jnp.asarray(b * s, f32))
        # The tokens whose rule took the kernels (``ops/gated_delta.py``).
        kernels = gated_delta_kernel_path(q.shape, (b, s, hv, dv)) is not None
        telemetry.count("gated_delta_kernel_tokens",
                        jnp.asarray(b * s if kernels else 0, f32))
        telemetry.count("gated_delta_min_log_decay", low, reduce="min")
        out = dense(cfg.hidden_size, name="out_proj")(o.reshape(b, s, hv * dv))
        return residual_dropout(self, out, cfg.dropout, deterministic)


class TransformerBlock(nn.Module):
    """Pre-norm block with two residuals (reference ``gpt.py:286-316``).

    Written in scan form: ``__call__(carry, seg) -> (carry, ys)`` so a single
    traced block is iterated ``num_layers`` times by ``nn.scan``. The carry
    is ``(x, aux)`` — ``aux`` accumulates the MoE load-balance loss across
    layers (zero for the dense model). The second argument is the packed
    batch's ``segment_ids`` (or None), broadcast to every layer
    (``in_axes=nn.broadcast`` on the scan). ``ys`` is normally None; under
    an active telemetry capture (utils/telemetry) it is a dict of per-layer
    activation/router stats, which the scan stacks into ``[num_layers]``
    vectors (the unrolled path stacks them by hand).
    """

    config: GPTConfig
    deterministic: bool = True
    decode: bool = False
    # The layer's kind where layers differ (GPTConfig.layer_kinds; "none"
    # for the sublayer a block of one sublayer lacks); None = the uniform
    # stack's block: attention, and experts iff the model has them.
    kind: Optional[Tuple[str, str]] = None

    @nn.compact
    def __call__(self, carry, segment_ids=None):
        cfg = self.config
        operator, ffn = self.kind or (
            "attention", "moe" if cfg.num_experts > 0 else "dense")
        norm_names = (("input_layernorm", "post_attention_layernorm")
                      if self.kind is None else ("operator_norm", "ffn_norm"))
        norm = functools.partial(
            RMSNorm, eps=cfg.norm_eps, dtype=cfg.compute_dtype)
        x, aux = carry
        attn_out = ffn_out = None
        # The sublayers' step counters (models/moe.py, Mamba2Mixer) leave
        # the layer loop beside the telemetry, under their own key, whether
        # or not telemetry is being captured.
        with (telemetry.counters() if telemetry.counting()
              else contextlib.nullcontext()) as counts:
            if operator != "none":
                residual = x
                h = norm(name=norm_names[0])(x)
                if operator == "conv":
                    h = ShortConv(cfg, name="conv")(
                        h, self.deterministic, segment_ids)
                elif operator == "mamba":
                    h = Mamba2Mixer(cfg, name="mamba")(
                        h, self.deterministic, segment_ids)
                elif operator == "linear_attention":
                    h = GatedDeltaNet(cfg, name="linear_attn")(
                        h, self.deterministic, segment_ids)
                elif operator == "sliding_attention":
                    # Named `attention` too, so that the `flash` and
                    # `attn_proj` regions hold window layers as they hold
                    # full ones.
                    h = CausalSelfAttention(
                        cfg, window=cfg.sliding_window, name="attention")(
                            h, self.deterministic, self.decode, segment_ids)
                else:
                    attention = (LatentAttention if cfg.latent_attention
                                 else CausalSelfAttention)
                    h = attention(cfg, name="attention")(
                        h, self.deterministic, self.decode, segment_ids
                    )
                attn_out = h
                x = residual + h

            if ffn != "none":
                residual = x
                h = norm(name=norm_names[1])(x)
                if ffn == "moe":
                    from tpu_trainer.models.moe import MoEMLP

                    h, layer_aux = MoEMLP(cfg, name="moe_mlp")(
                        h, self.deterministic)
                    aux = aux + layer_aux
                else:
                    h = MLP(cfg, name="mlp")(h, self.deterministic)
                ffn_out = h
                x = residual + h

        telem = None
        if telemetry.capturing():
            telem = {
                "block_rms": telemetry.rms(x),
                "block_absmax": telemetry.absmax(x),
            }
            for site, out in (("attn", attn_out), ("ffn", ffn_out)):
                if out is not None:
                    telem[f"{site}_rms"] = telemetry.rms(out)
                    telem[f"{site}_absmax"] = telemetry.absmax(out)
            router = telemetry.pop("router")
            if router is not None:
                telem.update(
                    {f"router_{k}": v for k, v in router.items()}
                )
        if counts:
            telem = {**(telem or {}), telemetry.LAYER_COUNTS: counts}
        return (x, aux), telem


# What the modules say of their own parameter leaves, for whoever keeps a
# compute-type copy of the parameters (``Trainer._cast_params``) or masks the
# weight decay (``training/optimizer.decay_mask``): asked here, by the keys
# of a leaf's path, never listed there by name.

# The modules that declare some of their leaves, by the name they go by in a
# block.
_DECLARING = {"mamba": Mamba2Mixer, "linear_attn": GatedDeltaNet}


def _declared(path_keys, what: str) -> bool:
    return any(name in path_keys and path_keys[-1] in getattr(module, what)
               for name, module in _DECLARING.items())


def computed_in_f32(path_keys) -> bool:
    """Whether a leaf is one its module consumes in float32 whatever the
    compute type: the expert layers' router (``models/moe.py``), a Mamba-2
    mixer's ``A_log``, ``dt_bias`` and ``D``, a Gated DeltaNet's ``A_log``
    and ``dt_bias``."""
    from tpu_trainer.models import moe

    return moe.computed_in_f32(path_keys) or _declared(path_keys,
                                                       "F32_LEAVES")


def undecayed(path_keys) -> bool:
    """Whether a leaf takes no weight decay: every norm's weight and every
    bias (the reference's exclusion by name, ``ddp_trainer.py:216-227``:
    modules here name them ``*norm*`` / ``*bias*``), and what a module
    declares (``Mamba2Mixer.UNDECAYED``, ``GatedDeltaNet.UNDECAYED``)."""
    return any("norm" in k.lower() or "bias" in k.lower()
               for k in path_keys) or _declared(path_keys, "UNDECAYED")


def stack_name(kind: Tuple[str, str]) -> str:
    """Parameter-tree key of the stacked layers of one (operator, ffn) kind."""
    return "layers_" + "_".join(kind)


def _publish_layer_stats(ys, key: str = "layers") -> None:
    """What the layer loop stacked (``[layers, ...]`` leaves, or None): the
    layers' counters go to the step's, reduced over the layers; the rest is
    the telemetry capture's."""
    if ys is None:
        return
    stats = dict(ys)
    telemetry.count_all(telemetry.reduce_counts(
        stats.pop(telemetry.LAYER_COUNTS, {})))
    if stats:
        telemetry.record(key, stats)


@jax.custom_vjp
def _unstack_layers(stacked):
    """Slice a stacked ``[num_layers, ...]`` param tree into per-layer trees.

    Exists for its backward: plain AD of per-layer slicing rebuilds the
    stacked cotangent through a chain of dynamic-update-slices that XLA
    materializes as one full-buffer copy per layer (measured ~0.3 ms * 12
    layers * per-matrix at headline geometry — ~12% of the step). The custom
    backward stacks the per-layer gradients with a single concatenate write
    instead.
    """
    num_layers = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    return tuple(
        jax.tree_util.tree_map(lambda a, i=i: a[i], stacked)
        for i in range(num_layers)
    )


def _unstack_fwd(stacked):
    return _unstack_layers(stacked), None


def _unstack_bwd(_, grads):
    return (jax.tree_util.tree_map(lambda *gs: jnp.stack(gs), *grads),)


_unstack_layers.defvjp(_unstack_fwd, _unstack_bwd)


# ``GPTConfig.remat_policy`` -> what a rematerialised block may keep.
_REMAT_POLICIES = {"full": None,
                   "dots": jax.checkpoint_policies.dots_saveable}


class MultiTokenPrediction(nn.Module):
    """The multi-token-prediction module (DeepSeek-V3 section 2.2), depth 1:
    ``h'_i = [rms_e(Emb(t_{i+1})) ; rms_h(h_i)] W_eh`` with ``h_i`` the main
    stack's output before its final norm, one more block of the last
    layer's kind over ``h'`` (its own weights), ``norm'`` of its own, then
    the MODEL's head; the cross entropy of position ``i`` against
    ``t_{i+2}``, a mean over the ``seq - 2`` positions that have one. The
    sequence keeps its length (the last position's input embedding wraps
    round and nothing reads it: the block is causal and the loss masks its
    last two positions). The block's auxiliary losses are not added."""

    config: GPTConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, next_embedding, hidden, head, labels):
        cfg = self.config
        norm = functools.partial(
            RMSNorm, eps=cfg.norm_eps, dtype=cfg.compute_dtype)
        x = nn.Dense(
            cfg.hidden_size, use_bias=False, dtype=cfg.compute_dtype,
            param_dtype=cfg.params_dtype,
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name="eh_proj",
        )(jnp.concatenate([norm(name="enorm")(next_embedding),
                           norm(name="hnorm")(hidden)], axis=-1))
        block = TransformerBlock
        if cfg.gradient_checkpointing:
            block = nn.remat(block, prevent_cse=False,
                             policy=_REMAT_POLICIES[cfg.remat_policy])
        (x, _), stats = block(
            cfg, deterministic=self.deterministic,
            kind=cfg.layer_kinds()[-1], name="block",
        )((x, jnp.zeros((), jnp.float32)), None)
        if stats is not None:
            _publish_layer_stats(
                jax.tree_util.tree_map(lambda a: a[None], stats),
                key="layers_mtp")
        x = norm(name="norm")(x)
        if labels is None:
            return None
        with jax.named_scope("head_loss"):
            return fused_shifted_cross_entropy(
                head, x, labels, shift=2, allow_pallas=cfg.fused_loss_pallas)


class GPT(nn.Module):
    """GPT for causal language modeling (reference ``gpt.py:319-484``)."""

    config: GPTConfig

    @nn.compact
    def __call__(
        self,
        input_ids: jax.Array,
        attention_mask: Optional[jax.Array] = None,
        labels: Optional[jax.Array] = None,
        train: bool = False,
        decode: bool = False,
        segment_ids: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Forward pass.

        ``attention_mask`` is accepted for API parity but — exactly like the
        reference (``gpt.py:203`` passes ``attn_mask=None``; SURVEY.md §2.1 b3)
        — semantics are causal-only.

        ``segment_ids`` ([b, s] int, 0 = padding, documents 1..K) isolates
        attention within packed documents and masks loss targets that would
        cross a document boundary. Unsupported under pipeline parallelism
        and sequence parallelism (NotImplementedError).

        Returns ``(logits [b, s, vocab] float32, loss | None)``.
        """
        cfg = self.config
        embed = nn.Embed(
            num_embeddings=cfg.vocab_size,
            features=cfg.hidden_size,
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            dtype=cfg.compute_dtype,
            param_dtype=cfg.params_dtype,
            name="embed_tokens",
        )
        x = embed(input_ids)
        if telemetry.capturing():
            telemetry.record("embed_out", {
                "rms": telemetry.rms(x), "absmax": telemetry.absmax(x),
            })

        policies = _REMAT_POLICIES
        carry0 = (x, jnp.zeros((), jnp.float32))
        from tpu_trainer.parallel import context as ctx_lib

        ctx_mesh = ctx_lib.current_mesh()
        stage_n = ctx_mesh.shape.get("stage", 1) if ctx_mesh is not None else 1
        manual_apply = not decode and not self.is_initializing()
        if cfg.latent_attention and decode:
            raise NotImplementedError(_NO_LATENT_DECODE)
        if cfg.latent_attention and ctx_mesh is not None:
            for axis in ("tensor", ring.SEQ_AXIS, "stage", "expert"):
                if ctx_mesh.shape.get(axis, 1) > 1:
                    raise NotImplementedError(
                        f"latent attention does not run under a {axis!r} "
                        f"mesh axis > 1: its projections have no tensor "
                        f"rules, and the ring, the pipeline and the expert "
                        f"exchange were not taught its operands")
        if cfg.uniform_layers and manual_apply and (
                stage_n > 1 or cfg.scan_unroll):
            # Shared setup for the two manual apply paths (pipeline and
            # unrolled): one detached block module, dropout-rng gating, and
            # optional remat wrapping.
            block_mod = TransformerBlock(cfg, deterministic=not train)
            needs_rng = train and (
                cfg.dropout > 0.0 or cfg.attention_dropout > 0.0
            )

            def run_block(p, carry, rng):
                rngs = {} if rng is None else {"dropout": rng}
                return block_mod.apply(
                    {"params": p}, carry, segment_ids, rngs=rngs
                )

            if cfg.gradient_checkpointing:
                run_block = jax.checkpoint(
                    run_block, prevent_cse=False,
                    policy=policies[cfg.remat_policy],
                )
        if segment_ids is not None and stage_n > 1:
            # The GPipe schedule slices microbatches itself and its 1f1b
            # variants bypass normal AD; segment plumbing there is a
            # separate project.
            raise NotImplementedError(
                "segment_ids are not supported under pipeline parallelism"
            )
        if not cfg.uniform_layers:
            x, moe_aux = self._mixed_layers(
                carry0, train, decode, segment_ids, policies, ctx_mesh)
        elif manual_apply and stage_n > 1:
            # Pipeline parallelism: the stacked layers (sharded over `stage`
            # by parallel/sharding.py) run through the GPipe schedule
            # (parallel/pipeline.py). Embedding / final norm / loss stay
            # outside, replicated over the stage axis. The MoE aux rides the
            # schedule (summed over layers, per-microbatch estimator). The
            # flash dispatch still shard_maps the kernel inside the stage
            # body — its manual region covers only batch/head axes, disjoint
            # from `stage` (ops/attention.py).
            from tpu_trainer.parallel.pipeline import pipeline_forward

            def block_fn(p, xm, rng=None):
                # [0]: the pipeline schedule carries only (x, aux) between
                # stages — per-layer telemetry ys are not collected here
                # (the 1f1b variants bypass normal AD entirely).
                return run_block(p, (xm, jnp.zeros((), jnp.float32)), rng)[0]

            rng = self.make_rng("dropout") if needs_rng else None
            # SP x PP: go jointly manual over {stage, sequence} so the
            # ring's collectives bind to this one manual region (Shardy
            # rejects a nested manual region with loop-carried ppermute).
            import contextlib as _cl

            sp_n = ctx_mesh.shape.get(ring.SEQ_AXIS, 1)
            if sp_n > 1:
                seq_cm = ring.sequence_parallel_manual(ctx_mesh)
                manual_seq = ring.SEQ_AXIS
            else:
                seq_cm = _cl.nullcontext()
                manual_seq = None
            with seq_cm:
                x, moe_aux = pipeline_forward(
                    self.variables["params"]["layers"], x, block_fn,
                    ctx_mesh, cfg.pipeline_microbatches or stage_n, rng=rng,
                    with_aux=True, manual_seq_axis=manual_seq,
                )
        elif manual_apply and cfg.scan_unroll:
            # Unrolled apply path: parameters keep the nn.scan layout
            # ([num_layers, ...] stacked leaves, created by the scan branch
            # at init — checkpoint/sharding layout unchanged), but each layer
            # runs as straight-line code on a static slice. This removes the
            # scan's stacking machinery: per-layer saved activations are
            # plain fusion outputs instead of dynamic-update-slices into
            # [num_layers, ...] buffers, and _unstack_layers turns the
            # stacked param gradient into one concatenate (see its
            # docstring). Measured ~20% faster than the rolled scan at
            # headline geometry; the rolled path remains for decode (cache
            # collection) and very deep models (compile time).
            per_layer = _unstack_layers(self.variables["params"]["layers"])
            carry = carry0
            telems = []
            for p in per_layer:
                rng = self.make_rng("dropout") if needs_rng else None
                carry, telem = run_block(p, carry, rng)
                if telem is not None:
                    telems.append(telem)
            x, moe_aux = carry
            if telems:
                # Same [num_layers, ...] stacking nn.scan's ys would give.
                _publish_layer_stats(jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *telems
                ))
        else:
            block = TransformerBlock
            if cfg.gradient_checkpointing and not decode:
                # Remat per block — the reference's activation-checkpointing
                # unit (gpt.py:440-444, fsdp_trainer.py:312-328). Policy
                # selects what survives to the backward (config.remat_policy).
                block = nn.remat(
                    block, prevent_cse=False, policy=policies[cfg.remat_policy]
                )
            layers = nn.scan(
                block,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.num_layers,
                in_axes=nn.broadcast,  # segment_ids: same array every layer
            )
            (x, moe_aux), layer_telem = layers(
                cfg, deterministic=not train, decode=decode, name="layers"
            )(carry0, segment_ids)
            _publish_layer_stats(layer_telem)

        hidden = x  # the stack's output: what the prediction module reads
        x = RMSNorm(eps=cfg.norm_eps, dtype=cfg.compute_dtype, name="norm")(x)
        if telemetry.capturing():
            telemetry.record("final_norm", {
                "rms": telemetry.rms(x), "absmax": telemetry.absmax(x),
            })
        # One scope round the head matmul and the loss, whichever branch
        # computes them: in a device trace they are `head_loss`, not the
        # anonymous rest of `GPT` (utils/profiling.py).
        with jax.named_scope("head_loss"):
            if cfg.tie_word_embeddings:
                # Weight tying (reference gpt.py:342): logits via the embedding matrix.
                head = embed.embedding
                logits = embed.attend(x).astype(jnp.float32)
            else:
                head = self.param(
                    "lm_head", nn.initializers.normal(cfg.initializer_range),
                    (cfg.vocab_size, cfg.hidden_size), cfg.params_dtype)
                logits = (x @ head.astype(cfg.compute_dtype).T
                          ).astype(jnp.float32)
            if telemetry.capturing(deep=True):
                # Between final_norm and the loss, nan-scan only: making the
                # logits live here would defeat the fused loss head's
                # memory savings on periodic telemetry steps, but without this
                # site a NaN entering in the head matmul is indistinguishable
                # from one entering in the loss math (the seq x tensor repro,
                # ROADMAP open items).
                telemetry.record("logits", {
                    "rms": telemetry.rms(logits), "absmax": telemetry.absmax(logits),
                })

            loss = None
            if labels is not None:
                # Shifted next-token cross entropy (reference gpt.py:450-453), mean
                # over batch * (seq - 1) positions, computed in float32.
                if cfg.fused_loss:
                    # Blockwise fused head+CE: full logits never materialize in
                    # either pass (ops/loss.py; the `logits` above are dead code
                    # in the training graph, which only consumes the loss).
                    loss = fused_shifted_cross_entropy(
                        head, x, labels,
                        allow_pallas=cfg.fused_loss_pallas,
                        segment_ids=segment_ids,
                    )
                else:
                    loss = _masked_shifted_mean(
                        optax_softmax_cross_entropy(logits[:, :-1, :], labels[:, 1:]),
                        segment_ids,
                    )
                if cfg.num_experts > 0:
                    # MoE auxiliaries (mean over layers). The layer returns them
                    # pre-weighted: moe_aux_weight * load-balance +
                    # router_z_weight * z-loss (models/moe.py).
                    loss = loss + moe_aux / cfg.num_layers
        if cfg.mtp_layers and (labels is not None or self.is_initializing()):
            # After the main loss, beside it: one more block predicts the
            # token after next through the same head (training only; a
            # forward without labels does not run it).
            if segment_ids is not None:
                raise NotImplementedError(
                    "segment_ids are not supported with the multi-token-"
                    "prediction loss")
            mtp_loss = MultiTokenPrediction(
                cfg, deterministic=not train, name="mtp")(
                    embed(jnp.roll(input_ids, -1, axis=1)), hidden, head,
                    labels)
            if labels is not None:
                telemetry.count("mtp_loss", mtp_loss, reduce="mean")
                loss = loss + cfg.mtp_loss_weight * mtp_loss
        return logits, loss

    def _mixed_layers(self, carry, train, decode, segment_ids, policies,
                      mesh):
        """The layer loop of a model whose layers differ
        (``GPTConfig.layer_kinds``): layers run in published order, the
        parameters of each (operator, ffn) kind stacked under
        ``layers_<operator>_<ffn>`` as ``nn.scan`` lays them out. Unrolled
        (``scan_unroll``), each layer is straight-line code on a static
        slice of its kind's stack, as in the uniform model; rolled, each run
        of consecutive layers of one kind is one ``lax.scan`` over its
        slice. Training and evaluation only."""
        cfg = self.config
        if decode:
            _refuse_state_space(cfg)
            raise NotImplementedError(
                "decode is not supported for a model whose layers differ: "
                "a conv layer needs the two previous positions of its gated "
                "input as state, which no cache holds")
        if cfg.has_mamba and mesh is not None and (
                mesh.shape.get("tensor", 1) > 1):
            raise NotImplementedError(
                "mamba layers do not run under a 'tensor' mesh axis > 1: "
                "in_proj's [z, xBC, dt] columns, the taps and the heads' "
                "A_log / dt_bias / D have no tensor-parallel rule "
                "(parallel/sharding.py replicates them)")
        if cfg.has_linear_attention and mesh is not None and (
                mesh.shape.get("tensor", 1) > 1):
            raise NotImplementedError(
                "linear_attention layers do not run under a 'tensor' mesh "
                "axis > 1: in_proj_qkvz's columns grouped by key head, the "
                "taps and the heads' A_log / dt_bias have no tensor-parallel "
                "rule (parallel/sharding.py replicates them)")
        for axis in ("stage", ring.SEQ_AXIS):
            if mesh is not None and mesh.shape.get(axis, 1) > 1:
                raise NotImplementedError(
                    f"a model whose layers differ does not run under a "
                    f"{axis!r} mesh axis > 1: the pipeline and the ring "
                    f"schedule one stacked block")
        kinds = cfg.layer_kinds()
        counts = {k: kinds.count(k) for k in dict.fromkeys(kinds)}
        if self.is_initializing():
            # Creates the stacks (one nn.scan a kind); the order the kinds
            # run in does not matter to the parameters made.
            for kind, n in counts.items():
                carry, _ = nn.scan(
                    TransformerBlock,
                    variable_axes={"params": 0},
                    split_rngs={"params": True, "dropout": True},
                    length=n, in_axes=nn.broadcast,
                )(cfg, deterministic=not train, kind=kind,
                  name=stack_name(kind))(carry, segment_ids)
            return carry
        needs_rng = train and (cfg.dropout > 0.0 or cfg.attention_dropout > 0.0)
        rng = self.make_rng("dropout") if needs_rng else None

        def runner(kind):
            block = TransformerBlock(cfg, deterministic=not train, kind=kind)

            def run(p, carry, key):
                rngs = {} if key is None else {"dropout": key}
                return block.apply({"params": p}, carry, segment_ids,
                                   rngs=rngs)

            if cfg.gradient_checkpointing:
                run = jax.checkpoint(run, prevent_cse=False,
                                     policy=policies[cfg.remat_policy])
            return run

        run = {kind: runner(kind) for kind in counts}
        stacks = {kind: self.variables["params"][stack_name(kind)]
                  for kind in counts}
        keys = (None if rng is None
                else list(jax.random.split(rng, len(kinds))))
        seen = dict.fromkeys(counts, 0)
        ys = {kind: [] for kind in counts}
        if cfg.scan_unroll:
            per_layer = {k: _unstack_layers(v) for k, v in stacks.items()}
            for i, kind in enumerate(kinds):
                carry, y = run[kind](per_layer[kind][seen[kind]], carry,
                                     None if keys is None else keys[i])
                seen[kind] += 1
                if y is not None:
                    ys[kind].append(jax.tree_util.tree_map(
                        lambda a: a[None], y))
        else:
            i = 0
            while i < len(kinds):
                kind, n = kinds[i], 1
                while i + n < len(kinds) and kinds[i + n] == kind:
                    n += 1
                lo = seen[kind]
                part = jax.tree_util.tree_map(
                    lambda a: a[lo:lo + n], stacks[kind])
                part_keys = (None if keys is None
                             else jnp.stack(keys[i:i + n]))
                carry, y = jax.lax.scan(
                    lambda c, xs, kind=kind: run[kind](xs[0], c, xs[1]),
                    carry, (part, part_keys))
                seen[kind] += n
                i += n
                if y is not None:
                    ys[kind].append(y)
        for kind, parts in ys.items():
            if parts:
                _publish_layer_stats(
                    jax.tree_util.tree_map(
                        lambda *a: jnp.concatenate(a), *parts),
                    key=stack_name(kind))
        return carry


_NO_STATE_SPACE_GENERATION = (
    "generation is not supported for a model with state-space (mamba) "
    "layers: no cache here holds a scan's [P, N] state and the taps' last "
    "positions beside keys and values (ROADMAP M8: other state than K/V)")


_NO_DELTA_RULE_GENERATION = (
    "generation is not supported for a model with linear_attention (Gated "
    "DeltaNet) layers: no cache here holds a delta rule's [d_k, d_v] state a "
    "value head and the taps' last positions beside keys and values (ROADMAP "
    "M8: other state than K/V)")


_NO_WINDOW_GENERATION = (
    "generation is not supported for a model with sliding_attention layers: "
    "no cache here releases the blocks behind a layer's window or holds "
    "window and full layers side by side (ROADMAP M5: its serving half)")


def _refuse_state_space(config: GPTConfig) -> None:
    """``generate*`` and the caches, for a model that no cache can serve."""
    if config.has_mamba:
        raise NotImplementedError(_NO_STATE_SPACE_GENERATION)
    if config.has_linear_attention:
        raise NotImplementedError(_NO_DELTA_RULE_GENERATION)
    if config.has_sliding_window:
        raise NotImplementedError(_NO_WINDOW_GENERATION)


def _masked_shifted_mean(ce: jax.Array, segment_ids) -> jax.Array:
    """Mean of per-position shifted CE ``[b, s-1]``, dropping positions whose
    next-token target crosses a packed-document boundary (or is padding).
    With ``segment_ids=None`` this is a plain mean — the unpacked path."""
    if segment_ids is None:
        return jnp.mean(ce)
    from tpu_trainer.ops.loss import segment_target_mask

    m = segment_target_mask(segment_ids)[:, :-1]
    return jnp.sum(ce * m) / jnp.maximum(jnp.sum(m), 1.0)


def optax_softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Integer-label softmax cross entropy without the optax import cycle."""
    logits = logits.astype(jnp.float32)
    log_z = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return log_z - label_logits


def count_parameters(params) -> int:
    """Total parameter count (reference ``gpt.py:487-489``)."""
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


@functools.partial(
    jax.jit, static_argnames=("config", "max_new_tokens", "temperature", "top_k")
)
def generate(
    params,
    rng: jax.Array,
    input_ids: jax.Array,
    *,
    config: GPTConfig,
    max_new_tokens: int = 100,
    temperature: float = 1.0,
    top_k: int = 50,
    prompt_len: Optional[jax.Array] = None,
    num_new: Optional[jax.Array] = None,
) -> jax.Array:
    """Autoregressive sampling (reference ``gpt.py:457-484``), fully jitted.

    Same semantics as the reference: crop context to ``max_seq_len``, divide
    logits by ``temperature``, keep the top-k logits (when ``top_k > 0``),
    sample from the resulting distribution, append. The reference's Python
    loop with a growing tensor becomes a fixed-size buffer + ``lax.fori_loop``
    (static shapes; one compile per (input width, max_new_tokens)).

    ``prompt_len`` (a traced int scalar, <= the input width) makes the input
    width a *bucket* rather than the semantic prompt length: generation
    starts at ``prompt_len`` and padding beyond it is never attended (causal
    masking makes positions >= the current index invisible). ``num_new``
    (traced, <= ``max_new_tokens``) likewise makes the new-token count a
    bucket: the loop runs only the requested steps. Together these let
    ``generate_bucketed`` reuse one compile across prompt lengths and
    new-token counts without executing padded decode steps.

    The reference recomputes the full forward each step with no KV cache
    (``infer.py`` hot loop, SURVEY.md §3.5); a windowed full forward matches
    that exactly. ``generate_kv`` is the cached fast path.
    """
    _refuse_state_space(config)
    model = GPT(config)
    b, width = input_ids.shape
    total = width + max_new_tokens
    window = min(total, config.max_seq_len)
    start_i = width if prompt_len is None else prompt_len

    buf = jnp.zeros((b, total), dtype=input_ids.dtype)
    buf = jax.lax.dynamic_update_slice(buf, input_ids, (0, 0))

    def body(i, carry):
        buf, rng = carry
        # Window of the last `window` tokens ending just before position i.
        start = jnp.clip(i - window, 0, total - window)
        ids = jax.lax.dynamic_slice(buf, (0, start), (b, window))
        logits, _ = model.apply({"params": params}, ids)
        pos = i - 1 - start  # index of the newest real token inside the window
        last = jax.lax.dynamic_slice(logits, (0, pos, 0), (b, 1, logits.shape[-1]))[:, 0]
        rng, sub = jax.random.split(rng)
        nxt = _sample(last, sub, temperature, top_k).astype(buf.dtype)
        buf = jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, i))
        return buf, rng

    n_new = max_new_tokens if num_new is None else num_new
    buf, _ = jax.lax.fori_loop(start_i, start_i + n_new, body, (buf, rng))
    return buf


def _bucket(n: int, floor: int = 16) -> int:
    """Next power of two >= n (>= floor)."""
    b = floor
    while b < n:
        b *= 2
    return b


def generate_bucketed(
    params,
    rng: jax.Array,
    input_ids: jax.Array,
    *,
    config: GPTConfig,
    max_new_tokens: int = 100,
    temperature: float = 1.0,
    top_k: int = 50,
) -> jax.Array:
    """``generate`` with bucketed compile shapes (VERDICT r1 weak #7).

    The jitted ``generate`` compiles once per (input width, max_new_tokens)
    pair — every new prompt length used to pay a full XLA compile. Here the
    prompt pads up to a power-of-two bucket and the new-token count rounds
    up likewise, with the true ``prompt_len`` passed as a *traced* scalar:
    any prompt in the same bucket reuses the compile, and the result is
    sliced back to exactly ``prompt + max_new_tokens``. Sampling semantics
    are identical (padding is never attended; the sampling loop runs the
    same positions with the same key folds).
    """
    b, true_len = input_ids.shape
    width = _bucket(true_len)
    new_bucket = _bucket(max_new_tokens)
    if (width + new_bucket > config.max_seq_len
            >= true_len + max_new_tokens) or width > config.max_seq_len:
        # Bucket rounding would engage the context-window crop earlier than
        # the exact shapes do (window = min(total, max_seq_len)); keep exact
        # reference semantics and pay the compile.
        return generate(
            params, rng, input_ids,
            config=config, max_new_tokens=max_new_tokens,
            temperature=temperature, top_k=top_k,
        )
    padded = jnp.zeros((b, width), input_ids.dtype)
    padded = jax.lax.dynamic_update_slice(padded, input_ids, (0, 0))
    buf = generate(
        params, rng, padded,
        config=config, max_new_tokens=new_bucket, temperature=temperature,
        top_k=top_k, prompt_len=jnp.asarray(true_len, jnp.int32),
        num_new=jnp.asarray(max_new_tokens, jnp.int32),
    )
    return jax.lax.dynamic_slice(
        buf, (0, 0), (b, true_len + max_new_tokens)
    )


def init_cache(config: GPTConfig, batch_size: int):
    """Zero-initialized KV cache pytree for ``generate_kv``."""
    _refuse_state_space(config)
    model = GPT(config)
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((batch_size, 1), jnp.int32),
            decode=True,
        )["cache"]
    )
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes
    )


def init_paged_cache(config: GPTConfig, batch_size: int):
    """Zero-initialized PAGED cache pytree (``config.decode_paged``): the
    block pools, per-row block tables, and lengths every layer's
    ``_paged_decode_attention`` reads. The serving engine overwrites the
    ``tables``/``lengths`` leaves from its host-side scheduler state
    before each jitted step (serving/engine.py)."""
    if not config.decode_paged:
        raise ValueError("init_paged_cache needs config.decode_paged=True")
    return init_cache(config, batch_size)


def _sample(logits, rng, temperature: float, top_k: int):
    """Temperature + top-k categorical sampling (reference gpt.py:473-482).

    ``temperature == 0`` is exact greedy argmax (temperature is static
    under jit, so this is a trace-time branch) — it used to divide by
    zero and sample NaN logits."""
    if temperature == 0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k > 0:
        kth = jax.lax.top_k(logits, min(top_k, logits.shape[-1]))[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(rng, logits)


def generate_kv(
    params,
    rng: jax.Array,
    input_ids: jax.Array,
    *,
    config: GPTConfig,
    max_new_tokens: int = 100,
    temperature: float = 1.0,
    top_k: int = 50,
    prompt_lens: Optional[jax.Array] = None,
) -> jax.Array:
    """KV-cached autoregressive sampling: one prefill pass over the prompt,
    then one single-token forward per generated token.

    Same sampling semantics as ``generate`` (temperature, top-k,
    ``max_seq_len`` context limit) but O(S) per token instead of the
    reference's O(S^2) full re-forward (``infer.py`` hot loop, SURVEY.md
    §3.5). Requires ``prompt_len + max_new_tokens <= config.max_seq_len``
    (the cache size); ``generate`` handles the windowed overflow case.

    Ragged batches: pass ``prompt_lens`` ([b] int32, true lengths of
    right-padded rows). Rows are re-packed LEFT-padded internally so every
    row shares one cache frontier; per-row pad offsets ride the cache
    collection and shift both the RoPE positions and the attention window,
    so padding is never attended and each row's positions start at its own
    first real token. Output rows come back right-padded (row r holds
    ``prompt_lens[r] + max_new_tokens`` real tokens, zero-filled beyond) —
    a mixed-length batch decodes in ONE call, where the reference's
    generator is batch-of-one (``infer.py:60-66``).

    This eager wrapper validates ``prompt_lens`` host-side (the jitted body
    only ever sees tracers, so it cannot); callers who jit *around*
    ``generate_kv`` skip this check and get the clamped-lengths behavior
    documented in the body.
    """
    _refuse_state_space(config)
    if prompt_lens is not None and not isinstance(
        jnp.asarray(prompt_lens), jax.core.Tracer
    ):
        # Concrete lengths: fail loudly on impossible values — a length
        # beyond the padded width would silently repack garbage (negative
        # left-pad duplicates tokens and the attention window degenerates).
        b, width = input_ids.shape
        vals = np.asarray(prompt_lens)
        if vals.shape != (b,) or (vals <= 0).any() or (vals > width).any():
            raise ValueError(
                f"prompt_lens must be [batch]={b} values in "
                f"[1, {width}] (the padded width); got {vals}"
            )
    return _generate_kv_jit(
        params, rng, input_ids, config=config,
        max_new_tokens=max_new_tokens, temperature=temperature,
        top_k=top_k, prompt_lens=prompt_lens,
    )


@functools.partial(
    jax.jit, static_argnames=("config", "max_new_tokens", "temperature", "top_k")
)
def _generate_kv_jit(
    params,
    rng: jax.Array,
    input_ids: jax.Array,
    *,
    config: GPTConfig,
    max_new_tokens: int,
    temperature: float,
    top_k: int,
    prompt_lens: Optional[jax.Array],
) -> jax.Array:
    import dataclasses as _dc

    if prompt_lens is not None:
        # Static switch: the per-row pad machinery only traces when asked
        # for (uniform decode keeps the cheaper shared-position path).
        config = _dc.replace(config, decode_ragged=True)
    b, prompt_len = input_ids.shape
    total = prompt_len + max_new_tokens
    if total > config.max_seq_len:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds the cache size (max_seq_len={config.max_seq_len}); "
            f"use generate() for windowed generation"
        )
    # Size the KV cache to what this call can actually fill (128-bucketed
    # so nearby shapes share a compile), not the model's context limit:
    # the decode attention's HBM reads are proportional to the cache
    # view, so a 384-token request against a 1024-token cache was paying
    # 2.7x the necessary read volume every step (VERDICT r4 #5).
    config = _dc.replace(
        config, decode_window=min(-(-total // 128) * 128, config.max_seq_len)
    )
    model = GPT(config)
    if max_new_tokens == 0:
        return input_ids
    cache = init_cache(config, b)

    pad = None
    if prompt_lens is not None:
        prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
        # In here lengths are always tracers; out-of-range values (only
        # possible when the caller jitted over the eager wrapper's
        # validation) clamp to [1, padded width] rather than repacking
        # garbage.
        prompt_lens = jnp.clip(prompt_lens, 1, prompt_len)
        pad = (prompt_len - prompt_lens).astype(jnp.int32)     # [b]
        # Right-padded -> left-padded rows (shared decode frontier).
        cols = jax.lax.broadcasted_iota(jnp.int32, (b, prompt_len), 1)
        src = jnp.clip(cols - pad[:, None], 0, prompt_len - 1)
        input_ids = jnp.where(
            cols >= pad[:, None],
            jnp.take_along_axis(input_ids, src, axis=1),
            jnp.zeros((), input_ids.dtype),
        )
        # Per-row pad offsets enter every layer's decode attention through
        # its cache variable (models/gpt.py _decode_attention).
        cache = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.broadcast_to(pad, x.shape)
            if getattr(p[-1], "key", None) == "pad" else x,
            cache,
        )

    # Prefill: one pass over the whole prompt populates every layer's cache.
    (logits, _), vars_out = model.apply(
        {"params": params, "cache": cache},
        input_ids,
        decode=True,
        mutable=["cache"],
    )
    cache = vars_out["cache"]
    rng, sub = jax.random.split(rng)
    nxt = _sample(logits[:, -1], sub, temperature, top_k).astype(input_ids.dtype)

    buf = jnp.zeros((b, total), input_ids.dtype)
    buf = jax.lax.dynamic_update_slice(buf, input_ids, (0, 0))
    buf = jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, prompt_len))

    def body(i, carry):
        buf, cache, rng = carry
        tok = jax.lax.dynamic_slice(buf, (0, i - 1), (b, 1))
        (logits, _), vars_out = model.apply(
            {"params": params, "cache": cache},
            tok,
            decode=True,
            mutable=["cache"],
        )
        rng, sub = jax.random.split(rng)
        nxt = _sample(logits[:, -1], sub, temperature, top_k).astype(buf.dtype)
        buf = jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, i))
        return buf, vars_out["cache"], rng

    buf, _, _ = jax.lax.fori_loop(
        prompt_len + 1, total, body, (buf, cache, rng)
    )
    if pad is not None:
        # Left-padded -> right-padded output rows.
        cols = jax.lax.broadcasted_iota(jnp.int32, (b, total), 1)
        src = jnp.clip(cols + pad[:, None], 0, total - 1)
        real = cols < (total - pad)[:, None]
        buf = jnp.where(
            real, jnp.take_along_axis(buf, src, axis=1),
            jnp.zeros((), buf.dtype),
        )
    return buf


if __name__ == "__main__":
    # Smoke test mirroring the reference __main__ block (gpt.py:492-508).
    config = GPTConfig.gpt2_small(dropout=0.0, attention_dropout=0.0)
    model = GPT(config)
    rng = jax.random.PRNGKey(0)
    input_ids = jax.random.randint(rng, (2, 128), 0, config.vocab_size)
    params = model.init(rng, input_ids)["params"]

    print(f"Model config: {config}")
    print(f"Estimated parameters: {config.num_parameters():,}")
    print(f"Actual parameters: {count_parameters(params):,}")

    logits, loss = model.apply({"params": params}, input_ids, labels=input_ids)
    print(f"Logits shape: {logits.shape}")
    print(f"Loss: {float(loss):.4f}")


def pipeline_1f1b_value_and_grad(model: "GPT", mesh, num_microbatches: int):
    """Build a grad_fn with ``jax.value_and_grad``'s interface for the
    1F1B pipeline schedule (``GPTConfig.pipeline_schedule == "1f1b"``).

    The GPipe path differentiates the schedule scan by AD, which keeps all
    M microbatch activations alive at the bubble point; 1F1B needs the
    backward manually interleaved with the forward, so the loss and every
    gradient come out of ONE scheduled scan (``parallel/pipeline.py
    pipeline_1f1b``) and the usual ``value_and_grad`` around ``GPT.apply``
    is bypassed. This function replicates the model's embedding, stage
    block, and head-loss computations (same modules; the head+CE always
    runs blockwise AND vocab-sharded over the stage axis here — the same
    math as either ``fused_loss`` setting, computed as 1/S slices with
    explicit collectives), assembling the full parameter-gradient pytree:
    stacked layer grads from the schedule, the tied embedding's gradient
    as head + lookup contributions, and the final norm's from the head
    VJP.

    Dropout streams are folded per (global layer, microbatch) from the
    step rng directly — self-consistent and decorrelated, but a different
    (equally valid) stream than the GPipe path's ``make_rng`` derivation;
    loss-equivalence against GPipe holds exactly with dropout off.

    Composes with sequence parallelism (a non-trivial ``sequence`` mesh
    axis: the pipeline goes jointly manual over {stage, sequence}, the
    blocks route through the in-region ring attention, and the head's CE
    reads its next-token shift from the replicated global labels) and with
    MoE (``stage_fwd`` returns the stage's aux sum; its gradient rides the
    same stage vjp via a pre-scaled cotangent seed).

    Returns ``grad_fn(params, micro_ids, rng, loss_scale) ->
    ((loss * scale, loss), grads)``.
    """
    from tpu_trainer.parallel.pipeline import pipeline_1f1b

    cfg = model.config
    S = mesh.shape["stage"]
    v = (cfg.pipeline_virtual_stages
         if cfg.pipeline_schedule == "interleaved" else 1)
    lpc = cfg.num_layers // (S * v)  # layers per chunk
    M = num_microbatches
    sq = mesh.shape.get(ring.SEQ_AXIS, 1)
    manual_seq = ring.SEQ_AXIS if sq > 1 else None
    with_aux = cfg.num_experts > 0
    needs_rng = cfg.dropout > 0.0 or cfg.attention_dropout > 0.0
    block_mod = TransformerBlock(cfg, deterministic=False)
    norm_mod = RMSNorm(eps=cfg.norm_eps, dtype=cfg.compute_dtype)
    policies = _REMAT_POLICIES

    def grad_fn(params, ids, rng, loss_scale):
        emb = params["embed_tokens"]["embedding"]
        vocab, hidden = emb.shape

        def stage_fwd(chunk_params, xm, micro_idx, chunk_idx):
            def one_layer(carry, scanned):
                li, p = scanned
                rngs = {}
                if needs_rng:
                    # Global layer index: chunk `chunk_idx` of this device
                    # is global stage chunk_idx*S + stage (v=1: == stage).
                    g_stage = chunk_idx * S + jax.lax.axis_index("stage")
                    g_layer = g_stage * lpc + li
                    key = jax.random.fold_in(rng, g_layer * M + micro_idx)
                    if manual_seq is not None:
                        # Sequence shards see local slices and hash_dropout
                        # keys by LOCAL positions: fold the shard index so
                        # chunks don't repeat one mask (same rule as
                        # pipeline_forward).
                        key = jax.random.fold_in(
                            key, jax.lax.axis_index(manual_seq))
                    rngs = {"dropout": key}
                (xc, aux), _ = block_mod.apply(
                    {"params": p}, carry, rngs=rngs)
                return (xc, aux), None

            run = one_layer
            if cfg.gradient_checkpointing:
                run = jax.checkpoint(run, prevent_cse=False,
                                     policy=policies[cfg.remat_policy])
            (y, aux), _ = jax.lax.scan(
                run, (xm, jnp.zeros((), jnp.float32)),
                (jnp.arange(lpc), chunk_params),
            )
            return (y, aux) if with_aux else y

        # --- vocab-sharded head (VERDICT r3 #1) --------------------------
        # Each stage evaluates 1/S of the LM head + CE on the last stage's
        # broadcast output; explicit pmax/psum over the stage axis stitch
        # the softmax (ops/loss.py vocab_sharded_shifted_cross_entropy —
        # custom_vjp, so AD never transposes a collective). Head FLOPs per
        # microbatch total ONE full evaluation, split S ways.
        v_s = -(-vocab // S)  # ceil: the last slice may overhang
        emb_padded = jnp.pad(emb, ((0, S * v_s - vocab), (0, 0)))

        def head_vjp(y_bc, labels_mb, micro_idx):
            off = jax.lax.axis_index("stage") * v_s
            e_slice = jax.lax.dynamic_slice(
                emb_padded, (off, 0), (v_s, hidden)
            )

            def f(yy, e_, nw_):
                xn = norm_mod.apply({"params": nw_}, yy)
                return vocab_sharded_shifted_cross_entropy(
                    e_, xn, labels_mb, vocab=vocab, axis_name="stage",
                    seq_axis=manual_seq,
                )

            loss_m, pull = jax.vjp(f, y_bc, e_slice, params["norm"])
            dy_part, de_slice, dnorm = pull(
                jnp.asarray(loss_scale / M, jnp.float32))
            # dy: the pullback's x-cotangent is this stage's vocab-slice
            # partial — one psum (in the activation dtype, what AD of the
            # bf16 forward would move) makes it the full cotangent.
            dy = jax.lax.psum(
                dy_part.astype(cfg.compute_dtype), "stage"
            )
            # Parameter-grad accumulators stay f32; the norm grad is also
            # a per-stage partial (linearity: psummed with the rest at the
            # end of the schedule).
            return (loss_m / M,
                    dy,
                    {"embedding_slice": de_slice.astype(jnp.float32),
                     "norm": jax.tree_util.tree_map(
                         lambda g: g.astype(jnp.float32), dnorm)})

        def head_finalize(acc):
            # Scatter this stage's [v_s, hidden] slice gradient into its
            # rows of the full [vocab, hidden] table (other rows zero; the
            # pipeline's final psum assembles the table from all stages).
            off = jax.lax.axis_index("stage") * v_s
            full = jax.lax.dynamic_update_slice(
                jnp.zeros((S * v_s, hidden), jnp.float32),
                acc["embedding_slice"], (off, 0),
            )[:vocab]
            return {"embedding": full, "norm": acc["norm"]}

        def emb_accum(acc, dx, ids_mb):
            # d(embedding lookup): scatter-add each token's cotangent row.
            flat = ids_mb.reshape(-1)
            return acc.at[flat].add(dx.reshape(-1, hidden))

        head_zeros = {
            "embedding_slice": jnp.zeros((v_s, hidden), jnp.float32),
            "norm": jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params["norm"]),
        }
        emb_zeros = jnp.zeros((vocab, hidden), jnp.float32)

        x = jnp.take(
            emb.astype(cfg.compute_dtype), ids, axis=0
        )  # nn.Embed semantics: cast table, then gather
        import contextlib as _cl

        seq_cm = (ring.sequence_parallel_manual(mesh) if manual_seq
                  else _cl.nullcontext())
        # aux cotangent per microbatch backward: d total_loss / d aux_layer
        # = loss_scale / (M * num_layers * sq) — matching the GPipe
        # estimator (mean over micros and seq shards, /num_layers in the
        # model's loss assembly).
        aux_args = {}
        if with_aux:
            aux_args = dict(
                with_aux=True,
                aux_seed=jnp.asarray(
                    loss_scale / (M * cfg.num_layers * sq), jnp.float32),
            )
        with seq_cm:
            out = pipeline_1f1b(
                params["layers"], x, ids, ids, stage_fwd, head_vjp,
                head_zeros, emb_accum, emb_zeros, mesh, M,
                head_finalize=head_finalize, manual_seq_axis=manual_seq,
                virtual_stages=v,
                **aux_args,
            )
        if with_aux:
            loss_mean, aux_raw, dlayers, dhead, de_lookup = out
            loss_mean = loss_mean + aux_raw / (M * cfg.num_layers * sq)
        else:
            loss_mean, dlayers, dhead, de_lookup = out
        # The lookup's cotangent arrives unscaled by loss_scale/M? No — dx
        # flowed from head_vjp's scaled seed through the stage backwards,
        # so every gradient here already carries loss_scale / M per micro,
        # summed over micros.
        grads = {
            "embed_tokens": {"embedding": dhead["embedding"] + de_lookup},
            "layers": dlayers,
            "norm": dhead["norm"],
        }
        return (loss_mean * loss_scale, loss_mean), grads

    return grad_fn
