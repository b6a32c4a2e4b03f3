"""The gated delta rule's share of its roofline, %: the larger of its FLOPs
over the chip's bf16 peak and its least HBM bytes over its HBM peak
(``perf/peaks.json``), over the own device time of EVERY leaf op under the
scope ``gated_delta`` inside ``linear_attn`` in the traced window, kernels
or the compiler's own fusions alike, so that it reads the same work whatever
implements the rule. The work is counted by the configuration's family
(``gated_delta_work``: the chunked form at a chunk of 64, forward + twice
that for the backward, every linear-attention layer) for the traced steps'
tokens; a recomputation of the forward inside the backward is time and not
work. A family that counts no such work, or a program whose trace has no
such scope: ``None``."""

from perf import registry
from perf.readers import scope_ms


def read(obs):
    cfg = obs.cell["config_file"]
    family = registry.family(cfg)
    if not hasattr(family, "gated_delta_work"):
        return None
    ns = scope_ms.scope_ns(obs, ["gated_delta"], under=["linear_attn"])
    if not ns:
        return None
    work = family.gated_delta_work(
        cfg, obs.counters["steps"] * obs.counters["tokens_per_step"])
    peaks = obs.cell["peaks"]
    least_s = max(work["flops"] / peaks["bf16_flops_per_s"],
                  work["bytes"] / peaks["hbm_bytes_per_s"])
    # `ns` is summed over the chips, the work is the whole step's.
    return 100.0 * least_s / (ns / 1e9)
