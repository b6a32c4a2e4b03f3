"""The latent-attention kernels' share of their roofline, %: the larger of
their FLOPs over the chip's bf16 peak and their least HBM bytes over its
HBM peak (``perf/peaks.json``), over the own device time of the Pallas
kernels under the flax scope ``attention`` in the traced window. The work is
counted by the configuration's family (``flash_work``: causal, forward and
backward, at the model's score and value lanes whatever the kernels pad to,
every latent-attention operator) for the traced steps' sequences. A family
that counts no such work, or a program whose trace has no such kernel:
``None``."""

from perf import program_trace, registry
from perf.readers import scope_ms


def read(obs):
    cfg = obs.cell["config_file"]
    family = registry.code("families", cfg.get("family", ""))
    if not hasattr(family, "flash_work"):
        return None
    ns = scope_ms.scope_ns(obs, ["attention"], kind=program_trace.PALLAS)
    if not ns:
        return None
    chips = len(program_trace.of(obs).devices)
    work = family.flash_work(
        cfg, obs.counters["seq_len"],
        obs.counters["steps"] * obs.counters["sequences_per_step"])
    peaks = obs.cell["peaks"]
    least_s = max(work["flops"] / peaks["bf16_flops_per_s"],
                  work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9 / chips)
