"""perf/reference.py against tpu_trainer/models/gpt.py at a tiny width."""

import jax
import jax.numpy as jnp
import pytest

from perf import program, reference

TINY = {
    "name": "tiny", "hidden_size": 96, "intermediate_size": 256,
    "num_hidden_layers": 3, "num_attention_heads": 6,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "max_position_embeddings": 128, "rope_theta": 100000.0,
    "rope_scaling": None, "rms_norm_eps": 1e-06, "hidden_act": "silu",
    "attention_bias": False, "mlp_bias": False, "attention_dropout": 0.0,
    "tie_word_embeddings": True, "initializer_range": 0.02,
}


@pytest.fixture(scope="module")
def setup():
    from tpu_trainer.models.gpt import GPT

    model = GPT(program.gpt_config(TINY, dtype="float32"))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # Larger weights than the initialiser's, so that the logits are of
    # order one and attention is far from uniform.
    params = jax.tree_util.tree_map(
        lambda p: p * 5 if p.ndim >= 2 else p, params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, 512)
    return model, params, tokens


def test_gqa_three_to_one(setup):
    assert TINY["num_attention_heads"] // TINY["num_key_value_heads"] == 3


def test_logits_match_the_program_in_float32(setup):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply({"params": params}, tokens)
    want = reference.forward(params, tokens, TINY)
    assert want.shape == (4, 64, 512) and want.dtype == jnp.float32
    assert float(jnp.std(want)) > 0.5
    # float32 both sides: only the order of additions differs.
    assert float(jnp.max(jnp.abs(got - want))) < 1e-3


def test_loss_matches_the_program(setup):
    model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        _, got = model.apply({"params": params}, tokens, labels=tokens)
    for rows in (1, 2, 4):
        want = reference.loss(params, tokens, TINY, rows_per_pass=rows)
        assert float(abs(got - want)) < 1e-4 * float(want)


def test_reference_is_causal(setup):
    _, params, tokens = setup
    base = reference.forward(params, tokens[:1], TINY)
    changed = tokens[:1].at[0, 40].set((tokens[0, 40] + 1) % 512)
    other = reference.forward(params, changed, TINY)
    assert float(jnp.max(jnp.abs(base[0, :40] - other[0, :40]))) == 0.0
    assert float(jnp.max(jnp.abs(base[0, 40:] - other[0, 40:]))) > 1e-3


def test_a_mask_off_by_one_is_seen(setup):
    """The comparison that decides `correct` must see a wrong mask: shift
    the program's output by one position (what a query sees if it attends
    one key too many) and the error is of the logits' own size."""
    model, params, tokens = setup
    want = reference.forward(params, tokens, TINY)
    got, _ = model.apply({"params": params}, jnp.roll(tokens, -1, axis=1))
    rel = float(jnp.sqrt(jnp.mean((got - want) ** 2))
                / jnp.sqrt(jnp.mean(want ** 2)))
    assert rel > 0.3


def test_program_fixes_are_refused():
    with pytest.raises(ValueError, match="rms_norm_eps"):
        program.gpt_config(dict(TINY, rms_norm_eps=1e-05))
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        program.gpt_config(dict(TINY, tie_word_embeddings=False))
