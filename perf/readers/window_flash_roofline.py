"""The window layers' attention's share of its roofline, %: the larger of
its FLOPs over the chip's bf16 peak and its least HBM bytes over its HBM
peak (``perf/peaks.json``), over the own device time of EVERY leaf op under
the scope ``sliding_window`` inside ``attention`` in the traced window,
kernels or the compiler's own fusions alike, so that it reads the same work
whatever implements the window. The work is counted by the configuration's
family (``window_flash_work``: the pairs inside each window layer's band,
forward + the backward as ``flash_work`` counts it, every window layer) for
the traced steps' sequences. A family that counts no such work, or a
program whose trace has no such scope: ``None``."""

from perf import registry
from perf.readers import scope_ms


def read(obs):
    cfg = obs.cell["config_file"]
    family = registry.family(cfg)
    if not hasattr(family, "window_flash_work"):
        return None
    ns = scope_ms.scope_ns(obs, ["sliding_window"], under=["attention"])
    if not ns:
        return None
    work = family.window_flash_work(
        cfg, obs.counters["seq_len"],
        obs.counters["steps"] * obs.counters["sequences_per_step"])
    peaks = obs.cell["peaks"]
    least_s = max(work["flops"] / peaks["bf16_flops_per_s"],
                  work["bytes"] / peaks["hbm_bytes_per_s"])
    # `ns` is summed over the chips, the work is the whole step's.
    return 100.0 * least_s / (ns / 1e9)
