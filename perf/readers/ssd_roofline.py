"""The state-space scans' share of their roofline, %: the larger of their
FLOPs over the chip's bf16 peak and their least HBM bytes over its HBM peak
(``perf/peaks.json``), over the own device time of EVERY leaf op under the
scope ``ssd`` inside ``mamba`` in the traced window, a Pallas kernel or the
compiler's own fusions alike, so that it reads the same work whatever
implements the scan. The work is counted by the configuration's family
(``ssd_work``: the chunked form at the published chunk, forward + twice that
for the backward, every state-space block) for the traced steps' tokens; a
recomputation of the forward inside the backward is time and not work. A
family that counts no such work, or a program whose trace has no such scope:
``None``."""

from perf import registry
from perf.readers import scope_ms


def read(obs):
    cfg = obs.cell["config_file"]
    family = registry.family(cfg)
    if not hasattr(family, "ssd_work"):
        return None
    ns = scope_ms.scope_ns(obs, ["ssd"], under=["mamba"])
    if not ns:
        return None
    work = family.ssd_work(
        cfg, obs.counters["steps"] * obs.counters["tokens_per_step"])
    peaks = obs.cell["peaks"]
    least_s = max(work["flops"] / peaks["bf16_flops_per_s"],
                  work["bytes"] / peaks["hbm_bytes_per_s"])
    # `ns` is summed over the chips, the work is the whole step's.
    return 100.0 * least_s / (ns / 1e9)
