"""Model configuration.

TPU-native re-design of the reference model config
(``/root/reference/src/models/config.py:6-102``). Differences from the reference,
by design:

- Frozen (hashable) dataclass so it can be a static argument to ``jax.jit``.
- ``num_parameters()`` is exact for the *actual* architecture (RoPE + RMSNorm +
  SwiGLU + tied embeddings). The reference's estimate counts a learned positional
  embedding the model does not have and 4 LayerNorm params/layer where RMSNorm has
  one weight vector (reference ``config.py:81-102`` — SURVEY.md §2.1 b7).
- ``activation`` defaults to ``"silu"`` and is honored; the reference declares
  ``"gelu"`` but hardcodes SiLU in the MLP (``gpt.py:280`` — SURVEY.md §2.1 b9).
- Adds the compute/parameter dtype policy (TPU bf16-compute / fp32-params recipe),
  replacing torch autocast (reference ``ddp_trainer.py:115-156``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
}


# Taps of the gated short convolution (``conv`` layers): the current and the
# two earlier positions. One value in use (``conv_L_cache`` 3), so a constant.
CONV_TAPS = 3

# ``GPTConfig.layer_types``: the sequence operators, and (blocks of one
# sublayer only) the blocks that are an FFN alone.
_OPERATORS = ("full_attention", "conv", "mamba")
_FFN_ONLY = ("moe", "mlp")


def dtype_of(name: str):
    """Map a dtype name ('float32' | 'bfloat16' | 'float16') to a jnp dtype."""
    return _DTYPES[name]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    """Configuration for the GPT model (defaults = GPT-2 124M / "small").

    Architecturally LLaMA-style — RMSNorm, RoPE, SwiGLU, no biases, pre-norm,
    tied embeddings — with GPT-2's vocabulary, mirroring the reference
    (``/root/reference/src/models/gpt.py``; SURVEY.md §2.1 b9).
    """

    # Model architecture (reference config.py:13-19)
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    # Grouped-query attention (beyond-reference, LLaMA-2/3-style): number of
    # K/V heads; None = num_heads (classic multi-head). Each group of
    # num_heads // num_kv_heads query heads shares one K/V head — the KV
    # cache, the k/v projections, and the ring-attention K/V traffic all
    # shrink by the group factor.
    num_kv_heads: Optional[int] = None
    intermediate_size: Optional[int] = None  # defaults to 4 * hidden_size
    max_seq_len: int = 1024

    # Regularization (reference config.py:21-23)
    dropout: float = 0.1
    attention_dropout: float = 0.1

    # Initialization (reference config.py:25-26)
    initializer_range: float = 0.02

    # Activation — honored here (SiLU), unlike the reference's dead field.
    activation: str = "silu"

    # RoPE base frequency (reference gpt.py:76 hardcodes 10000)
    rope_theta: float = 10000.0

    # Mixture-of-Experts (0 = dense; beyond-reference model family). When
    # num_experts > 0 every block's feed-forward becomes a routed expert
    # SwiGLU (models/moe.py): Switch-style top-1 by default, GShard-style
    # top-2 (renormalized gates, first-choice priority at capacity) with
    # moe_top_k=2. Experts shard over the mesh's 'expert' axis AND their
    # FFN dims over 'tensor' (EP x TP composes). router_z_weight adds the
    # ST-MoE router z-loss (mean logsumexp^2 of router logits — keeps
    # logits from drifting to magnitudes where softmax saturates).
    num_experts: int = 0
    moe_top_k: int = 1
    expert_capacity_factor: float = 1.25
    # Token routing implementation (models/moe.py): "gather" fills each
    # expert slot by index gather (O(T*k) integer bookkeeping + two
    # [E*C, H] gathers; measured 30% of the MoE step back at E=8);
    # "einsum" uses one-hot dispatch/combine matmuls (2*T*E*C*H FLOPs
    # each — the MXU does the routing, and GSPMD lowers EP to a clean
    # token all-to-all, which gathers do NOT give it); "auto" (default)
    # picks gather on meshes without an expert axis and einsum under
    # expert parallelism. Same semantics every way, pinned by oracle
    # tests.
    moe_dispatch: str = "auto"
    # Routing discipline (models/moe.py): "capacity" (default) is the
    # Switch/GShard scheme above — fixed per-expert slots C, tokens past
    # capacity dropped, dense [E, C, H] expert matmuls (moe_dispatch picks
    # how tokens reach the slots). "dropless" is MegaBlocks-style
    # (arXiv:2211.15841): token-choices are argsorted into expert order
    # and all three SwiGLU projections run as grouped matmuls
    # (ops/grouped_matmul.gmm) sized by the true per-expert counts — no
    # capacity_factor, drop_frac == 0 by construction, and expert compute
    # scales with the tokens actually routed instead of E*C.
    moe_impl: str = "capacity"
    moe_aux_weight: float = 0.01
    router_z_weight: float = 0.0

    # --- Layers that differ (hybrid conv / attention stacks) -------------
    # What a published architecture states, never a tuning knob; with all
    # of them at their defaults the model is the uniform stack above, with
    # its parameter tree and compiled step unchanged.
    # Per-layer sequence operator, in published order: "full_attention"
    # (CausalSelfAttention) or "conv" (the gated short convolution,
    # models/gpt.py ShortConv). None = attention in every layer. Layers of
    # one (operator, ffn) kind are stacked together
    # (``layers_<operator>_<ffn>`` leaves, [count, ...]).
    layer_types: Optional[Tuple[str, ...]] = None
    # With experts on: how many leading layers keep the dense SwiGLU.
    num_dense_layers: int = 0
    # Expert width (None = intermediate_size, the dense width).
    moe_intermediate_size: Optional[int] = None
    # Router: "softmax" (Switch/GShard, above) or "sigmoid": scores
    # s = sigmoid(x W_r) in f32, the top-k of s + b chosen (b: a
    # selection-bias buffer that no gradient reaches, held at zero: no
    # update rule is published), gates s[sel] / (sum s[sel] + 1e-6).
    # Dropless only.
    moe_router: str = "softmax"
    # The experts this chip holds, (first id, count), of num_experts (the
    # router's width): the layer routes over all of them, computes its own
    # experts' part of the result and leaves the rest out, which is what
    # expert parallelism asks of it; on one chip it runs without the
    # exchange. None = all.
    moe_experts_held: Optional[Tuple[int, int]] = None
    # RMSNorm over each head's q and k before RoPE ([head_dim] weights).
    qk_norm: bool = False
    # RMSNorm epsilon, every norm of the model.
    norm_eps: float = 1e-6

    # --- Latent attention, shared experts, an untied head, MTP ------------
    # Again what a published architecture states (the DeepSeek-V3 family's
    # keys), never a tuning knob; at their defaults nothing changes.
    # Multi-head latent attention (models/gpt.py LatentAttention) in every
    # attention layer iff kv_lora_rank is set: queries through a
    # q_lora_rank latent, keys and values through a kv_lora_rank latent, a
    # head's scores contracting qk_nope_head_dim un-rotated lanes plus
    # qk_rope_head_dim rotated ones whose key is ONE head shared by all,
    # values v_head_dim wide. rope_interleave: the rotation pairs lanes
    # (2i, 2i+1) instead of (i, i + d/2).
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    # Shared experts beside the routed ones: one SwiGLU of width
    # moe_shared_experts x the expert width over every token.
    moe_shared_experts: int = 0
    # Factor on the routed sum (the sigmoid router's normalised gates), and
    # the constant added to the chosen scores' sum before the division.
    moe_routed_scale: float = 1.0
    moe_gate_eps: float = 1e-6
    # False: the head is its own [vocab, hidden] matrix (`lm_head`).
    tie_word_embeddings: bool = True
    # Multi-token prediction (DeepSeek-V3 section 2.2), depth 1: one more
    # block over [norm(Emb(t_{i+1})); norm(h_i)] W_eh predicts t_{i+2}
    # through the model's head; loss = CE + mtp_loss_weight x CE_mtp.
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.3

    # --- State-space layers, blocks of one sublayer (Nemotron-H's keys) ---
    # Again what a published architecture states, never a tuning knob; at
    # their defaults nothing changes.
    # Every block holds ONE sublayer with its own pre-norm and residual,
    # ``h <- h + mixer(norm(h))``: a "full_attention" / "conv" / "mamba"
    # entry of ``layer_types`` is that operator alone, a "moe" / "mlp" entry
    # the expert layer / the dense FFN alone (``layer_kinds`` gives "none"
    # for the half a block lacks).
    one_sublayer_blocks: bool = False
    # A head's width where it is not hidden_size // num_heads: q and o are
    # then [hidden, num_heads * head_dim] and back.
    attention_head_dim: Optional[int] = None
    # False: attention applies no rotary embedding (no position signal of
    # its own: the state-space layers carry order).
    rotary_embedding: bool = True
    # The FFN of the dense MLP, the experts and the shared expert: "swiglu"
    # (down(act(gate x) * up x), three matrices) or "relu2"
    # (down(relu(up x)^2), two matrices, no gate).
    ffn_kind: str = "swiglu"
    # The shared expert's width where it is stated by its own key and not
    # as moe_shared_experts x the expert width.
    moe_shared_expert_width: Optional[int] = None
    # Mamba-2 (models/gpt.py Mamba2Mixer, ops/ssd.py) in every "mamba"
    # layer: mamba_num_heads heads of mamba_head_dim lanes (their product
    # is the inner width, whatever hidden_size is), B and C shared by the
    # heads of each of mamba_n_groups groups, ssm_state_size lanes of state
    # a head lane, a depthwise causal convolution of mamba_conv_kernel taps
    # (with bias) over [x, B, C], the scan computed mamba_chunk_size tokens
    # a chunk. mamba_dt_*: the range the time steps are initialised in
    # (dt_bias = softplus^-1 of dt log-uniform in [min, max], floored).
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    mamba_n_groups: int = 1
    mamba_conv_kernel: int = 4
    mamba_chunk_size: int = 128
    mamba_dt_min: float = 0.001
    mamba_dt_max: float = 0.1
    mamba_dt_floor: float = 1e-4

    # Optimization flags (reference config.py:30-32)
    use_flash_attention: bool = False
    gradient_checkpointing: bool = False
    # Rematerialization policy when gradient_checkpointing is on:
    # "full"  — save only block inputs, recompute everything (the reference's
    #           activation-checkpointing semantics; max memory savings);
    # "dots"  — save matmul outputs, recompute elementwise chains (dropout
    #           masks, norms, activations). Cheaper in compute than "full"
    #           and cuts the per-layer activation stores that dominate HBM
    #           write traffic in the unremated step.
    remat_policy: str = "full"
    # Compute the training loss via the blockwise fused LM-head + cross
    # entropy (ops/loss.py): full [batch, seq, vocab] logits never
    # materialize in either pass. Identical math to the reference's
    # F.cross_entropy over materialized logits (gpt.py:450-453); measured
    # 4.4x faster at small/bs=8/seq=1024 on v5e, where the logits buffer's
    # HBM traffic was 28% of the step. Affects the loss only — the logits
    # output of __call__ is unchanged.
    fused_loss: bool = True
    # On compiled TPU, compute the fused loss with the Pallas head kernel
    # (ops/head_ce.py): the softmax statistics ride through the head matmul
    # online (flash-attention-style), deleting the separate logsumexp HBM
    # pass over the [tokens, vocab] block, and the backward reads saved
    # compute-dtype logits instead of re-using the f32 block. Loss stays
    # exact f32; backward probabilities carry bf16 rounding (same order as
    # the flash kernel's backward). The kernel shard_maps over batch
    # (data x fsdp) AND sequence axes, and an expert axis (which shards
    # only expert params) does not prevent it. Falls back off-TPU; under a
    # stage axis the pipeline owns the head, and under single-stage TP
    # the loss routes to the vocab-sharded XLA head (ops/loss._tp_loss).
    fused_loss_pallas: bool = True
    # GPipe microbatch count when the mesh has a `stage` axis > 1
    # (parallel/pipeline.py); 0 = auto (one microbatch per stage). More
    # microbatches -> smaller pipeline bubble, smaller per-step matmuls.
    pipeline_microbatches: int = 0
    # Pipeline schedule: "gpipe" (AD of the forward scan — all M
    # microbatch activations live at the bubble point), "1f1b"
    # (manually scheduled interleaved backward — at most min(M, 2S-1)
    # stage inputs in flight, M-independent; stage blocks rematerialize
    # in the backward), or "interleaved" (virtual-stage 1F1B: each device
    # holds `pipeline_virtual_stages` non-contiguous layer chunks, cutting
    # the bubble from (S-1)/(M+S-1) to ~(S-1)/(vM+S-1) at the cost of a
    # ~v x larger saved-input window). All three compose with SP and MoE.
    pipeline_schedule: str = "gpipe"
    # Layer chunks per device under pipeline_schedule="interleaved"
    # (Megatron's virtual pipeline stages); ignored by other schedules.
    # Requires num_layers % (stages * v) == 0 and microbatches % stages
    # == 0.
    pipeline_virtual_stages: int = 2
    # Run the layer stack as an unrolled per-layer loop at apply time.
    # Parameters stay stacked [num_layers, ...] (checkpoint/sharding layout
    # unchanged — nn.scan still creates them), but each layer executes as
    # straight-line code on a static slice, with the stacked parameter
    # gradient rebuilt by one concatenate (models/gpt.py:_unstack_layers)
    # instead of the scan's per-layer dynamic-update-slice copies (~25% of
    # the headline step's device time, measured). Costs compile time (body
    # traced num_layers times); the rolled scan remains for decode and is
    # the right choice for very deep models or fast iteration.
    scan_unroll: bool = True

    # Fuse the q/k/v projections into one [H, H+2*kv] matmul and gate/up
    # into one [H, 2I] matmul (models/gpt.py): the input activations are
    # read from HBM once per fused group and the MXU sees one wide dot
    # instead of two or three narrow ones. Parameters stay separate
    # (checkpoint layout and name-based sharding rules unchanged — the
    # concatenate is a compute-graph detail). The Trainer and the decode
    # CLI force this off when the mesh's tensor axis > 1: TP shards those
    # kernels along exactly the axis the fusion concatenates.
    fused_projections: bool = True

    # --- Paged decode (the serving engine's cache layout) ---------------
    # Static switch: route _decode_attention through the PAGED KV cache —
    # fixed-size blocks in a preallocated pool addressed by per-row block
    # tables (vLLM-style PagedAttention; tpu_trainer/serving/). Set by
    # ServingEngine via dataclasses.replace; mutually exclusive with
    # decode_ragged (the contiguous ragged path). Not a training knob.
    decode_paged: bool = False
    # Pool geometry, static so the cache variables (and jit) specialize:
    # tokens per block / total blocks in the pool (block 0 reserved as the
    # null block writes of masked rows land in) / block-table width =
    # per-request capacity ceiling in blocks.
    paged_block_size: int = 16
    paged_num_blocks: int = 0
    paged_max_blocks: int = 0
    # Store the paged pools as blockwise-absmax int8 (utils/quant.py —
    # the optimizer-state scheme pointed at the KV cache): halves-to-
    # quarters pool HBM, ~1e-2 relative error on the attention output
    # (documented tolerance; greedy streams may diverge where logits are
    # near-tied).
    paged_kv_int8: bool = False
    # Decode-attention implementation over the pool: "reference" (pure
    # jnp gather — the CPU path), "kernel" (Pallas flash-decode,
    # interpret off-TPU), "auto" = kernel on TPU, reference elsewhere.
    paged_attention: str = "auto"
    # Chunked-prefill history width for THIS dispatch, in blocks: when a
    # prefill chunk starts past position 0 (the request's earlier chunks
    # or a shared prefix already sit in the pool), the chunk's queries
    # must also attend to the first ``paged_hist_blocks`` table entries
    # of pooled history. Static so the gather shape specializes with the
    # width bucket; 0 = no history read — offset-0 prefill, the original
    # monolithic path. Set per dispatch by the serving engine via
    # dataclasses.replace; never a user knob.
    paged_hist_blocks: int = 0

    # Tensor-parallel decode: shard the paged engine's dispatch over a
    # single-axis ("tp",) device mesh — Q heads split paged_tp ways, KV
    # pools shard on their kv-heads axis when divisible (else replicate:
    # the GQA kv_heads < tp mode), params commit sharded and gather to
    # replicated inside the step (serving/sharding.py). Set by
    # ServingEngine via dataclasses.replace from its mesh_tensor /
    # mesh_devices kwargs — never a user-facing model knob. Because the
    # jitted-step memos key on the (hashable) config, carrying the tp
    # degree AND the device-id tuple here is what keeps two engines with
    # otherwise-equal configs but different meshes from sharing one jit
    # (the latent wrong-device-dispatch bug at tp=1 too: an explicit
    # device set at tp=1 still changes the key).
    paged_tp: int = 1
    paged_tp_devices: Optional[Tuple[int, ...]] = None

    # Static switch for the ragged (per-row prompt length) KV-decode path:
    # set internally by generate_kv(prompt_lens=...); uniform decode keeps
    # the cheaper shared-position attention. Not a training knob.
    decode_ragged: bool = False
    # KV-cache view length for decode (0 = max_seq_len). generate_kv sets
    # this per call to prompt+new rounded up to 128: the cache allocates
    # and the decode attention reads only this prefix instead of the full
    # max_seq_len buffer — the attention's HBM reads scale with what can
    # actually be filled, not the model's context limit (VERDICT r4 #5).
    # Static, so it participates in jit specialization like the prompt
    # shape already does.
    decode_window: int = 0

    # TPU dtype policy: compute dtype for activations/matmuls; params and the
    # softmax/loss accumulations stay float32.
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self):
        if self.intermediate_size is None:
            object.__setattr__(self, "intermediate_size", 4 * self.hidden_size)
        if self.attention_head_dim is None:
            assert self.hidden_size % self.num_heads == 0, (
                f"hidden_size ({self.hidden_size}) must be divisible by "
                f"num_heads ({self.num_heads})"
            )
        # num_kv_heads stays None (= num_heads) rather than being
        # materialized: dataclasses.replace(cfg, num_heads=...) must keep
        # working on configs that never asked for GQA. Resolved via the
        # kv_heads property.
        if self.num_kv_heads is not None:
            assert self.num_heads % self.num_kv_heads == 0, (
                f"num_heads ({self.num_heads}) must be divisible by "
                f"num_kv_heads ({self.num_kv_heads})"
            )
        if self.num_experts > 0 and not (
            1 <= self.moe_top_k <= self.num_experts
        ):
            raise ValueError(
                f"moe_top_k ({self.moe_top_k}) must be in "
                f"[1, num_experts={self.num_experts}]"
            )
        if self.moe_dispatch not in ("auto", "gather", "einsum"):
            raise ValueError(
                f"unknown moe_dispatch {self.moe_dispatch!r}; "
                f"choose auto, gather, or einsum"
            )
        if self.moe_impl not in ("capacity", "dropless"):
            raise ValueError(
                f"unknown moe_impl {self.moe_impl!r}; "
                f"choose capacity or dropless"
            )
        if self.pipeline_schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(
                f"unknown pipeline_schedule {self.pipeline_schedule!r}; "
                f"choose gpipe, 1f1b, or interleaved"
            )
        if (self.pipeline_schedule == "interleaved"
                and self.pipeline_virtual_stages < 2):
            raise ValueError(
                f"pipeline_schedule='interleaved' needs "
                f"pipeline_virtual_stages >= 2 "
                f"(got {self.pipeline_virtual_stages}); v=1 is plain 1f1b"
            )
        if self.paged_attention not in ("auto", "reference", "kernel"):
            raise ValueError(
                f"unknown paged_attention {self.paged_attention!r}; "
                f"choose auto, reference, or kernel"
            )
        if self.decode_paged:
            if self.decode_ragged:
                raise ValueError(
                    "decode_paged and decode_ragged are mutually exclusive"
                )
            if self.paged_num_blocks < 2 or self.paged_max_blocks < 1:
                raise ValueError(
                    "decode_paged needs paged_num_blocks >= 2 (block 0 is "
                    "the reserved null block) and paged_max_blocks >= 1"
                )
            if not 0 <= self.paged_hist_blocks <= self.paged_max_blocks:
                raise ValueError(
                    f"paged_hist_blocks ({self.paged_hist_blocks}) must be "
                    f"in [0, paged_max_blocks={self.paged_max_blocks}]"
                )
        # TP decode feasibility + hashability: the devices tuple may
        # arrive as a JSON list (worker specs round-trip the config dict);
        # coerce so the frozen config stays a valid static jit argument.
        if self.paged_tp_devices is not None and not isinstance(
                self.paged_tp_devices, tuple):
            object.__setattr__(
                self, "paged_tp_devices",
                tuple(int(d) for d in self.paged_tp_devices))
        if self.paged_tp != 1:
            from tpu_trainer.parallel.mesh import validate_tp

            validate_tp(self.num_heads, self.kv_heads, self.paged_tp)
        if self.layer_types is not None:
            object.__setattr__(
                self, "layer_types", tuple(self.layer_types))
            known = _OPERATORS + (_FFN_ONLY if self.one_sublayer_blocks
                                  else ())
            if len(self.layer_types) != self.num_layers or any(
                    t not in known for t in self.layer_types):
                raise ValueError(
                    f"layer_types must name one of {known!r} for "
                    f"each of the {self.num_layers} layers ('moe' and "
                    f"'mlp' are blocks of an FFN alone: "
                    f"one_sublayer_blocks); got {self.layer_types!r}")
        self._check_block_keys()
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError(
                f"num_dense_layers ({self.num_dense_layers}) must be in "
                f"[0, num_layers={self.num_layers}]")
        if self.moe_router not in ("softmax", "sigmoid"):
            raise ValueError(
                f"unknown moe_router {self.moe_router!r}; "
                f"choose softmax or sigmoid")
        if self.moe_experts_held is not None:
            object.__setattr__(
                self, "moe_experts_held",
                tuple(int(v) for v in self.moe_experts_held))
            first, count = self.moe_experts_held
            if not (0 <= first and 1 <= count
                    and first + count <= self.num_experts):
                raise ValueError(
                    f"moe_experts_held (first, count) = "
                    f"{self.moe_experts_held!r} is not a range of the "
                    f"{self.num_experts} experts")
        if (self.moe_router == "sigmoid"
                or self.moe_experts_held is not None) and (
                    self.num_experts > 0 and self.moe_impl != "dropless"):
            raise ValueError(
                "the sigmoid router and a held subset of experts run "
                "through moe_impl='dropless' only")
        if self.latent_attention:
            widths = (self.q_lora_rank, self.qk_nope_head_dim,
                      self.qk_rope_head_dim, self.v_head_dim)
            if not all(isinstance(w, int) and w > 0 for w in widths) or (
                    self.qk_rope_head_dim % 2):
                raise ValueError(
                    f"latent attention (kv_lora_rank={self.kv_lora_rank}) "
                    f"needs q_lora_rank, qk_nope_head_dim, an even "
                    f"qk_rope_head_dim and v_head_dim; got {widths!r}")
            if self.num_kv_heads not in (None, self.num_heads):
                raise ValueError(
                    "latent attention has one key/value per query head "
                    "(num_kv_heads must be num_heads)")
            if self.qk_norm or self.attention_dropout > 0.0:
                raise ValueError(
                    "latent attention runs without qk_norm and without "
                    "attention dropout")
            if self.layer_types is not None and "conv" in self.layer_types:
                raise ValueError(
                    "latent attention beside conv layers is not supported")
        elif self.q_lora_rank is not None or self.rope_interleave:
            raise ValueError(
                "q_lora_rank / rope_interleave belong to latent attention "
                "(set kv_lora_rank)")
        if self.moe_shared_experts < 0 or (
                self.moe_shared_experts and (
                    self.num_experts <= 0 or self.moe_impl != "dropless")):
            raise ValueError(
                "moe_shared_experts needs routed experts beside it "
                "(num_experts > 0, moe_impl='dropless')")
        if self.moe_routed_scale != 1.0 and self.moe_router != "sigmoid":
            raise ValueError(
                "moe_routed_scale scales the sigmoid router's gates")
        if self.mtp_layers not in (0, 1):
            raise ValueError(
                f"mtp_layers ({self.mtp_layers}) must be 0 or 1: the "
                f"prediction module is built at depth 1")
        if self.mtp_layers and not self.fused_loss:
            raise ValueError(
                "the multi-token-prediction loss runs through the fused "
                "head + cross entropy (fused_loss)")
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r}; "
                f"choose from ['dots', 'full']"
            )

    def _check_block_keys(self):
        types = self.layer_types or ()
        if self.one_sublayer_blocks:
            if self.layer_types is None:
                raise ValueError(
                    "one_sublayer_blocks needs layer_types: the sublayer "
                    "of each block")
            if self.num_dense_layers or self.mtp_layers:
                raise ValueError(
                    "one_sublayer_blocks names each FFN block by its type "
                    "('moe' / 'mlp'): num_dense_layers does not apply, and "
                    "the prediction module is a block of two sublayers")
            if "moe" in types and self.num_experts <= 0:
                raise ValueError("a 'moe' block needs num_experts > 0")
        if "mamba" in types:
            sizes = (self.mamba_num_heads, self.mamba_head_dim,
                     self.ssm_state_size, self.mamba_n_groups,
                     self.mamba_conv_kernel, self.mamba_chunk_size)
            if not all(isinstance(v, int) and v > 0 for v in sizes) or (
                    self.mamba_num_heads % self.mamba_n_groups):
                raise ValueError(
                    f"a 'mamba' layer needs mamba_num_heads (a multiple of "
                    f"mamba_n_groups), mamba_head_dim, ssm_state_size, "
                    f"mamba_conv_kernel and mamba_chunk_size; got {sizes!r}")
            if self.latent_attention:
                raise ValueError(
                    "latent attention beside mamba layers is not supported")
        if self.ffn_kind not in ("swiglu", "relu2"):
            raise ValueError(
                f"unknown ffn_kind {self.ffn_kind!r}; choose swiglu or relu2")
        if self.ffn_kind == "relu2" and self.num_experts > 0 and (
                self.moe_impl != "dropless"):
            raise ValueError(
                "two-matrix relu2 experts run through moe_impl='dropless' "
                "only")
        if self.moe_shared_expert_width is not None and (
                not self.moe_shared_experts):
            raise ValueError(
                "moe_shared_expert_width is the width of the shared expert "
                "(moe_shared_experts >= 1)")
        if not self.rotary_embedding and (
                self.latent_attention or self.layer_types is None):
            raise ValueError(
                "rotary_embedding=False is for a hybrid stack (layer_types) "
                "whose other operators carry the order; latent attention "
                "always rotates")

    @property
    def head_dim(self) -> int:
        if self.attention_head_dim is not None:
            return self.attention_head_dim
        return self.hidden_size // self.num_heads

    @property
    def attention_width(self) -> int:
        """Lanes of all query heads: hidden_size unless a head's width is
        stated."""
        return self.num_heads * self.head_dim

    @property
    def shared_expert_width(self) -> int:
        return (self.moe_shared_expert_width
                if self.moe_shared_expert_width is not None
                else self.moe_shared_experts * self.expert_width)

    @property
    def ffn_matrices(self) -> int:
        return 2 if self.ffn_kind == "relu2" else 3

    @property
    def has_mamba(self) -> bool:
        return "mamba" in (self.layer_types or ())

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the taps run over: x, then B and C of every group."""
        return (self.mamba_inner
                + 2 * self.mamba_n_groups * self.ssm_state_size)

    @property
    def kv_heads(self) -> int:
        """Resolved K/V head count (num_kv_heads, defaulting to num_heads)."""
        return (self.num_kv_heads if self.num_kv_heads is not None
                else self.num_heads)

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank is not None

    @property
    def expert_width(self) -> int:
        return (self.moe_intermediate_size
                if self.moe_intermediate_size is not None
                else self.intermediate_size)

    @property
    def experts_held(self) -> Tuple[int, int]:
        """(first id, count) of the experts whose weights live here."""
        return (self.moe_experts_held if self.moe_experts_held is not None
                else (0, self.num_experts))

    @property
    def uniform_layers(self) -> bool:
        """Every layer is the same block: today's single ``layers`` stack."""
        return self.layer_types is None and (
            self.num_dense_layers == 0 or self.num_experts <= 0)

    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(operator, ffn) of each layer in published order: operator
        "attention" | "conv" | "mamba", ffn "dense" | "moe"; under
        ``one_sublayer_blocks`` one of the two is "none"."""
        ops = self.layer_types or ("full_attention",) * self.num_layers
        if self.one_sublayer_blocks:
            return tuple(
                ("none", "moe" if op == "moe" else "dense")
                if op in _FFN_ONLY
                else ("attention" if op == "full_attention" else op, "none")
                for op in ops)
        return tuple(
            ("attention" if op == "full_attention" else op,
             "moe" if self.num_experts > 0 and i >= self.num_dense_layers
             else "dense")
            for i, op in enumerate(ops))

    @property
    def compute_dtype(self):
        return dtype_of(self.dtype)

    @property
    def params_dtype(self):
        return dtype_of(self.param_dtype)

    # --- Size presets (reference config.py:41-79) ------------------------------

    @classmethod
    def gpt2_small(cls, **overrides) -> "GPTConfig":
        """GPT-2 124M-class configuration."""
        return cls(vocab_size=50257, hidden_size=768, num_layers=12, num_heads=12,
                   **overrides)

    @classmethod
    def gpt2_medium(cls, **overrides) -> "GPTConfig":
        """GPT-2 355M-class configuration."""
        return cls(vocab_size=50257, hidden_size=1024, num_layers=24, num_heads=16,
                   **overrides)

    @classmethod
    def gpt2_large(cls, **overrides) -> "GPTConfig":
        """GPT-2 774M-class configuration."""
        return cls(vocab_size=50257, hidden_size=1280, num_layers=36, num_heads=20,
                   **overrides)

    @classmethod
    def gpt2_xl(cls, **overrides) -> "GPTConfig":
        """GPT-2 1.5B-class configuration."""
        return cls(vocab_size=50257, hidden_size=1600, num_layers=48, num_heads=25,
                   **overrides)

    @classmethod
    def preset(cls, name: str, **overrides) -> "GPTConfig":
        presets = {
            "small": cls.gpt2_small,
            "medium": cls.gpt2_medium,
            "large": cls.gpt2_large,
            "xl": cls.gpt2_xl,
        }
        if name not in presets:
            raise ValueError(f"unknown model size {name!r}; choose from {sorted(presets)}")
        return presets[name](**overrides)

    def _parameter_count(self, experts_counted: int) -> int:
        h, i = self.hidden_size, self.intermediate_size
        d = self.head_dim
        # q/o full, k/v grouped
        attn = 2 * h * self.attention_width + 2 * h * self.kv_heads * d
        if self.qk_norm:
            attn += 2 * d
        if self.latent_attention:
            heads, rope = self.num_heads, self.qk_rope_head_dim
            attn = (h * self.q_lora_rank + self.q_lora_rank
                    + self.q_lora_rank * heads * (self.qk_nope_head_dim + rope)
                    + h * (self.kv_lora_rank + rope) + self.kv_lora_rank
                    + self.kv_lora_rank * heads * (
                        self.qk_nope_head_dim + self.v_head_dim)
                    + heads * self.v_head_dim * h)
        inner, conv_dim = self.mamba_inner, self.mamba_conv_dim
        operator = {"attention": attn,
                    "conv": 3 * h * h + h * CONV_TAPS + h * h,
                    # in_proj [z, xBC, dt]; taps and their bias; dt_bias,
                    # A_log, D; the gated norm; out_proj
                    "mamba": (h * (inner + conv_dim + self.mamba_num_heads)
                              + conv_dim * (self.mamba_conv_kernel + 1)
                              + 3 * self.mamba_num_heads + inner + inner * h),
                    "none": 0}
        router = h * self.num_experts
        if self.moe_router == "sigmoid":
            router += self.num_experts  # the selection bias (a buffer)
        m = self.ffn_matrices
        shared = (self.shared_expert_width if self.moe_shared_experts else 0)
        ffn = {"dense": m * h * i,
               "moe": m * h * (experts_counted * self.expert_width + shared)
               + router,
               "none": 0}
        # A norm for each sublayer a block holds.
        layers = sum(operator[op] + ffn[f]
                     + h * ((op != "none") + (f != "none"))
                     for op, f in self.layer_kinds())
        head = 0 if self.tie_word_embeddings else self.vocab_size * h
        # The prediction module: W_eh, one block of the last layer's kind,
        # its three norms.
        last_op, last_ffn = self.layer_kinds()[-1]
        mtp = self.mtp_layers * (
            2 * h * h + operator[last_op] + ffn[last_ffn] + 2 * h + 3 * h)
        return self.vocab_size * h + head + layers + mtp + h

    def num_parameters(self) -> int:
        """Exact parameter count of the actual model (what lives here: with
        ``moe_experts_held`` the held experts only).

        embed (tied with lm_head): V*H (+ V*H for an untied head)
        per layer: operator — attention 2*H*W (q/o, W = heads*head_dim = H
                   unless a head's width is stated) + 2*H*(kv_heads*head_dim)
                   (k/v), + 2*head_dim with qk_norm; or the gated short
                   conv 3*H^2 (in) + H*L (taps) + H^2 (out); no bias; or
                   Mamba-2: H*(d_in + conv_dim + heads) (in), conv_dim*(K+1)
                   (taps and their bias), 3*heads (dt_bias, A_log, D), d_in
                   (the gated norm), d_in*H (out)
                   + FFN (m = 3 matrices, SwiGLU, or 2, relu^2): m*H*I
                   (dense) or E_held*m*H*I_e + H*E router
                   (+ E selection bias under the sigmoid router)
                   (+ the shared experts' m*H*I_e each, or m*H*width where
                   the shared expert's width is stated)
                   + a RMSNorm weight vector for each sublayer the block
                   holds (2*H; H in a block of one sublayer)
        latent attention: the two latents' down- and up-projections with
                   their norms, and o_proj [heads*v_head_dim, H]
        MTP module: 2*H^2 + one expert block + 3 norms
        final RMSNorm: H
        """
        return self._parameter_count(self.experts_held[1])

    def num_active_parameters(self) -> int:
        """Parameters a single token actually flows through: for MoE, only
        the ``moe_top_k`` routed experts' FFNs count (plus the router);
        dense models: == ``num_parameters()``. This is the N that belongs
        in the 6N FLOPs/token estimate — total-parameter MFU overstates
        MoE utilization by ~E/top_k on the FFN share. (Of the whole
        layer's experts: a held subset computes its share of them.)"""
        if self.num_experts <= 0:
            return self.num_parameters()
        return self._parameter_count(self.moe_top_k)
