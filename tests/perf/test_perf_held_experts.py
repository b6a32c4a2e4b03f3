"""perf/held_experts.py: which experts a chip holds is picked from the seed's
own draw so that every seed brings the held experts the even load. The order
against hand numbers, the relabelling on a hand-made tree, and the
``train_family`` runner with the pick on the CPU at a tiny width."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import harness, held_experts, registry
from perf.families import nemotron_h as family
from perf.runners import train_family
from tests.test_nemotron_h import TINY

CELL = "train-nemotron-twotower-ep16-1chip"


@pytest.mark.parametrize("load, first, count, want", [
    # mean 10: experts 3 and 5 are nearest and take places 0-1.
    ([30, 2, 1, 10, 4, 11, 2, 20], 0, 2, [3, 5, 0, 1, 2, 4, 6, 7]),
    # the same at places 2-3: the others keep their order around them.
    ([30, 2, 1, 10, 4, 11, 2, 20], 2, 2, [0, 1, 3, 5, 2, 4, 6, 7]),
    # a tie goes to an expert that is held already, then to the lower id:
    # an even load moves nothing.
    ([5, 5, 5, 5], 1, 2, [0, 1, 2, 3]),
    ([9, 1, 5, 5], 2, 2, [0, 1, 2, 3]),
    ([5, 5, 5, 1], 2, 2, [1, 3, 0, 2]),
])
def test_order_puts_the_experts_nearest_the_mean_load_in_the_held_places(
        load, first, count, want):
    got = held_experts.order(load, first, count)
    assert got == want and sorted(got) == list(range(len(load)))


def test_order_brings_a_skewed_draw_to_the_even_load():
    rng = np.random.default_rng(3)
    # 128 experts, a heavy head: the first eight take four times their share.
    load = rng.gamma(2.0, 1.0, 128)
    load[:8] *= 4.0
    load = (load * 49152 / load.sum()).tolist()
    even = 49152 * 8 / 128
    assert sum(load[:8]) > 2 * even            # the overflow branch's side
    new = held_experts.order(load, 0, 8)
    assert abs(sum(load[e] for e in new[:8]) - even) < 0.02 * even


def test_relabel_tree_moves_one_layers_router_columns_and_bias_only():
    kernel = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 4)
    tree = {
        "layers_none_moe": {
            "moe_mlp": {"router": {"kernel": kernel},
                        "expert_bias": jnp.arange(8.0).reshape(2, 4),
                        "experts_up": jnp.ones((2, 2, 3, 4))},
            "ffn_norm": {"weight": jnp.ones((2, 4))}},
        "layers_attention_moe": {
            "moe_mlp": {"router": {"kernel": kernel}}},
    }
    got = held_experts.relabel_tree(tree, "layers_none_moe", 1, [2, 0, 1, 3])
    moe = got["layers_none_moe"]["moe_mlp"]
    np.testing.assert_array_equal(moe["router"]["kernel"][0], kernel[0])
    np.testing.assert_array_equal(moe["router"]["kernel"][1],
                                  kernel[1][:, [2, 0, 1, 3]])
    np.testing.assert_array_equal(moe["expert_bias"],
                                  [[0, 1, 2, 3], [6, 4, 5, 7]])
    # Every other leaf is the same array, not a copy.
    assert moe["experts_up"] is tree["layers_none_moe"]["moe_mlp"][
        "experts_up"]
    assert got["layers_attention_moe"]["moe_mlp"]["router"]["kernel"] is kernel
    assert got["layers_none_moe"]["ffn_norm"]["weight"] is tree[
        "layers_none_moe"]["ffn_norm"]["weight"]


def _cell(**config):
    cell = registry.workload(CELL)
    tol = dict(cell["config_file"]["reference_tolerance"]["bf16"],
               logit_rel_rms=0.1, logit_max_over_rms=0.9, loss_rel=1e-2,
               routing_flipped_frac=0.2, grad_leaf_rel=0.5)
    cell["config_file"] = dict(TINY, reference_tolerance={"bf16": tol},
                               **config)
    cell["traffic_file"] = dict(cell["traffic_file"], seq_len=64,
                                tokens_per_step=256)
    cell["job"].update(micro_batch=2, grad_accum=2)
    cell["peaks"] = registry.peaks("TPU v5 lite")
    return cell


def _initial_state(cell, seed):
    cfg, traffic, job = cell["config_file"], cell["traffic_file"], cell["job"]
    trainer = train_family.build_trainer(family, cfg, traffic, job,
                                         jax.devices()[:1])
    batch = next(registry.code("generators", traffic["generator"]).generate(
        traffic, seed=seed, vocab_size=cfg["vocab_size"]))
    return trainer, train_family.initial_state(
        trainer, family, cfg, job, seed, batch, harness.Spans())


def test_the_configuration_asks_for_the_pick_and_an_unknown_one_is_refused():
    assert registry.config("nemotron-twotower-30b-a3b-ep16")[
        "experts_held_pick"] in held_experts.PICKS
    with pytest.raises(ValueError, match="experts_held_pick"):
        _initial_state(_cell(experts_held_pick="the_busiest"), 7)


def test_the_picked_state_is_the_seeds_own_draw_with_columns_in_another_order(
        capsys):
    seed = 2 ** 31 + 11
    _, plain = _initial_state(_cell(), seed)
    capsys.readouterr()
    trainer, picked = _initial_state(
        _cell(experts_held_pick="nearest_mean_load"), seed)
    note, = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if '"experts_held_pick"' in line]
    first, count = family.held(TINY)
    width = family.router_width(TINY)
    sites = held_experts.router_sites(trainer)
    assert sites == [("layers_none_moe", 0), ("layers_none_moe", 1)]
    assert [(s["group"], s["layer"]) for s in note["layers"]] == sites
    moved = 0
    for (path, want), got in zip(
            jax.tree_util.tree_leaves_with_path(plain.params),
            jax.tree_util.tree_leaves(picked.params)):
        name = jax.tree_util.keystr(path)
        if "router" not in name:
            np.testing.assert_array_equal(got, want, err_msg=name)
            continue
        for i, site in enumerate(note["layers"]):
            # The held places hold the columns of the experts the note
            # names; every column of the seed's draw is still there, once.
            np.testing.assert_array_equal(
                got[i][:, first:first + count], want[i][:, site["held"]])
            assert sorted(map(tuple, np.asarray(got[i]).T)) == sorted(
                map(tuple, np.asarray(want[i]).T))
            moved += site["held"] != list(range(first, first + count))
    assert moved, "this seed's pick moves a column in some layer"
    assert note["even_rows_per_token"] == family.even_rows_per_token(TINY) \
        == 3 * count / width
    # The same seed, the same pick.
    _, again = _initial_state(
        _cell(experts_held_pick="nearest_mean_load"), seed)
    for a, b in zip(jax.tree_util.tree_leaves(picked.params),
                    jax.tree_util.tree_leaves(again.params)):
        np.testing.assert_array_equal(a, b)


def test_the_runner_with_the_pick_is_correct_and_says_what_it_held(capsys):
    cell = _cell(experts_held_pick="nearest_mean_load")
    result = registry.code("runners", "train_family").run(
        cell, devices=jax.devices()[:1], seed=2 ** 31 + 11, seconds=1.0,
        trace=False, process_start=time.perf_counter())
    assert result.correct and result.failed == 0
    notes = {n["note"]: n for n in map(
        json.loads, capsys.readouterr().out.splitlines())}
    picked = notes["experts_held_pick"]["layers"]
    held = notes["correct_check"]["numbers"]["held_rows_per_token"]
    # What the check's own forward then brought to the held experts is what
    # the pick counted, layer by layer, and the step (the carried compute
    # copy of the weights relabelled alike) computed the same rows.
    assert held == pytest.approx([s["rows_per_token_after"] for s in picked])
    first_calls = [what for what, _ in notes["setup"]["first_calls"]]
    assert first_calls[:3] == ["init_state", "experts_held_pick",
                               "logit_check"]
    window = notes["train_window"]
    assert window["expert_rows_per_token_and_layer"] == pytest.approx(
        sum(held) / len(held), rel=0.15)
