"""Optimizer construction (SURVEY.md C13/C14/C16).

optax chain: global-norm clip → AdamW with masked weight decay. Decay-mask
semantics follow the reference's *grouped* DDP optimizer
(``ddp_trainer.py:174-234``): parameters whose path mentions a norm layer (or
a bias, if one existed) are excluded from weight decay; everything else —
including the embedding — decays. The reference's FSDP trainer decays
everything (``fsdp_trainer.py:334-343`` — SURVEY.md §2.1 b5); the grouped
behavior is used everywhere here, as the survey prescribes.

Under GSPMD the optimizer is sharding-agnostic: the same chain runs
replicated (DDP), with sharded moments (ZeRO-2), or fully sharded (ZeRO-3) —
the global-norm clip's tree reduction becomes a partial-reduce + psum
automatically.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax

from tpu_trainer.training.config import TrainingConfig
from tpu_trainer.utils.quant import (
    QuantPack,
    dequantize_blockwise_int8,
    quantize_blockwise_int8,
)

# Leaves below this size stay f32 in the quantized-state modes: the HBM win
# is negligible and small vectors (norm gains) are where quantization noise
# would bite hardest.
_QUANT_MIN_SIZE = 65536


def decay_mask(params: Any) -> Any:
    """True where weight decay applies.

    The model says which leaves take none (``models/gpt.undecayed``): the
    norms' weights and the biases, as the reference excludes params whose
    name contains 'bias' or 'norm' (``ddp_trainer.py:216-227``), and what a
    module declares of its own leaves (a Mamba-2 mixer's ``A_log`` and
    ``D``); the projections and the (tied) embedding decay.
    """
    from tpu_trainer.models.gpt import undecayed
    from tpu_trainer.parallel.sharding import _path_keys

    def keep(path, _leaf) -> bool:
        return not undecayed(_path_keys(path))

    return jax.tree_util.tree_map_with_path(keep, params)


class ScaleByAdamQState(NamedTuple):
    """Adam state with narrow-dtype moments (``optimizer_state_dtype``)."""

    count: jax.Array  # int32 scalar
    mu: Any           # per-leaf: f32 array | bf16 array | int8 QuantPack
    nu: Any


def _q_eligible(p: jax.Array) -> bool:
    return p.ndim >= 2 and p.size >= _QUANT_MIN_SIZE


def _store_moment(x: jax.Array, state_dtype: str, *, nonneg: bool):
    if state_dtype == "int8":
        return quantize_blockwise_int8(x, nonneg=nonneg)
    return x.astype(jnp.bfloat16)


def _load_moment(packed, shape, *, nonneg: bool) -> jax.Array:
    if isinstance(packed, QuantPack):
        return dequantize_blockwise_int8(packed, shape, jnp.float32,
                                         nonneg=nonneg)
    return packed.astype(jnp.float32)


def scale_by_adam_quantized(
    b1: float, b2: float, eps: float, state_dtype: str
) -> optax.GradientTransformation:
    """``optax.scale_by_adam`` with moments stored narrow in HBM.

    Large leaves (ndim >= 2, >= 64k elements) hold ``mu``/``nu`` in
    ``state_dtype`` — ``"bfloat16"`` (a straight cast; one rounding per
    step) or ``"int8"`` (blockwise-absmax, ``nu`` in sqrt-space — see
    ``utils/quant.py``); small leaves stay exact f32. The update math is
    bitwise the optax recipe on the dequantized moments: the only delta vs
    ``optax.scale_by_adam`` is the store/load rounding.

    Why: the Adam update fusions are pure HBM traffic (~28 B/param/step at
    f32 state) and the single biggest slice of the MoE step on one chip
    (~31 ms of 108 at E=8 — the optimizer pays for every expert while
    per-token compute pays only for the active ones). int8 moments cut
    ~12 B/param/step. The same tradeoff as the 8-bit offload state, on
    device; the reference has no analogue (fp32 ``torch.optim.AdamW``,
    ``ddp_trainer.py:174-234``).
    """

    def init_fn(params):
        def zero_state(p, *, nonneg):
            if _q_eligible(p):
                return _store_moment(jnp.zeros(p.shape, jnp.float32),
                                     state_dtype, nonneg=nonneg)
            return jnp.zeros(p.shape, jnp.float32)

        return ScaleByAdamQState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree_util.tree_map(
                lambda p: zero_state(p, nonneg=False), params),
            nu=jax.tree_util.tree_map(
                lambda p: zero_state(p, nonneg=True), params),
        )

    def update_fn(updates, state, params=None):
        del params
        count_inc = optax.safe_int32_increment(state.count)
        c1 = 1.0 - b1 ** count_inc.astype(jnp.float32)
        c2 = 1.0 - b2 ** count_inc.astype(jnp.float32)

        # Flatten against the GRADS' structure: a quantized moment is a
        # QuantPack node where the grads have an array leaf, so the moment
        # trees flatten with an is-leaf predicate on the pack TYPE. A
        # params subtree that happened to use the keys {"q", "scale"}
        # cannot be mistaken for a pack and misalign this positional zip.
        is_pack = lambda x: isinstance(x, QuantPack)  # noqa: E731
        g_leaves, treedef = jax.tree_util.tree_flatten(updates)
        mu_leaves = jax.tree_util.tree_flatten(state.mu, is_leaf=is_pack)[0]
        nu_leaves = jax.tree_util.tree_flatten(state.nu, is_leaf=is_pack)[0]

        out_l, mu_l, nu_l = [], [], []
        for g, mu_s, nu_s in zip(g_leaves, mu_leaves, nu_leaves):
            g32 = g.astype(jnp.float32)
            mu = b1 * _load_moment(mu_s, g.shape, nonneg=False) \
                + (1 - b1) * g32
            nu = b2 * _load_moment(nu_s, g.shape, nonneg=True) \
                + (1 - b2) * (g32 * g32)
            out_l.append((mu / c1) / (jnp.sqrt(nu / c2) + eps))
            narrow = _q_eligible(g)
            mu_l.append(_store_moment(mu, state_dtype, nonneg=False)
                        if narrow else mu)
            nu_l.append(_store_moment(nu, state_dtype, nonneg=True)
                        if narrow else nu)

        unflatten = treedef.unflatten
        return unflatten(out_l), ScaleByAdamQState(
            count=count_inc, mu=unflatten(mu_l), nu=unflatten(nu_l)
        )

    return optax.GradientTransformation(init_fn, update_fn)


def make_optimizer(config: TrainingConfig) -> optax.GradientTransformation:
    """clip_by_global_norm → AdamW(masked decay), at unit learning rate.

    The chain is built with ``learning_rate=1.0``; the trainer scales the
    final updates by ``config.lr_at(state.step)`` itself. This keeps the
    schedule a pure function of the trainer's step counter — including across
    fp16 overflow-skipped steps, where torch semantics are "scheduler
    advances, Adam's bias-correction count does not" (GradScaler skips
    ``optimizer.step`` while the LR scheduler still ticks). AdamW's decoupled
    decay is inside the chain, so the external scaling applies
    ``p -= lr * (adam_update + wd * p)`` exactly like torch AdamW.
    """
    if config.optimizer_state_dtype != "float32":
        if config.optimizer_state_dtype not in ("bfloat16", "int8"):
            raise ValueError(
                f"optimizer_state_dtype {config.optimizer_state_dtype!r} "
                "not supported; choose float32, bfloat16, or int8"
            )
        # Same chain with narrow-moment Adam: scale_by_adam_quantized +
        # decoupled decay + descent-sign scale == optax.adamw(lr=1.0)
        # modulo the moment store/load rounding.
        return optax.chain(
            optax.clip_by_global_norm(config.grad_clip),
            scale_by_adam_quantized(
                config.beta1, config.beta2, 1e-8,
                config.optimizer_state_dtype,
            ),
            optax.add_decayed_weights(config.weight_decay, mask=decay_mask),
            optax.scale(-1.0),
        )
    return optax.chain(
        optax.clip_by_global_norm(config.grad_clip),
        optax.adamw(
            learning_rate=1.0,
            b1=config.beta1,
            b2=config.beta2,
            eps=1e-8,
            weight_decay=config.weight_decay,
            mask=decay_mask,
        ),
    )
