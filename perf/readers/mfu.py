"""The whole step's share of the chips' bf16 peak, %: the window's tokens
over its wall time (in a traced run the traced stretch, synced at both ends),
times the model FLOPs a trained token as the configuration's family counts
them (``registry.family(cfg).mfu``, the function the ``train_window`` note
calls; a family with expert layers at the rows a token and layer that the
program's counter gives), over chips x the peak of ``perf/peaks.json``.
Recomputed work is not counted. No steps in the window: ``None``."""

from perf import registry


def read(obs):
    steps = obs.counters.get("steps")
    if not steps:
        return None
    cfg = obs.cell["config_file"]
    family = registry.family(cfg)
    lo, hi = obs.window
    tokens = steps * obs.counters["tokens_per_step"]
    counted = ()
    if "moe_rows_held" in obs.counters:
        counted = (obs.counters["moe_rows_held"]
                   / (tokens * family.moe_layers(cfg)),)
    return 100.0 * family.mfu(
        cfg, obs.counters["seq_len"], tokens / (hi - lo), obs.cell["chips"],
        obs.cell["peaks"]["bf16_flops_per_s"], *counted)
