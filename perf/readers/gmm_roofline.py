"""The grouped-matmul kernels' share of their roofline, %: the larger of
their FLOPs over the chip's bf16 peak and their least HBM bytes over its
HBM peak (``perf/peaks.json``), over the device time of the Pallas kernels
under ``moe_mlp`` in the traced window. The work is counted by the
configuration's family (``gmm_work``) at the rows the program's counter
``moe_rows_held`` gives for the traced steps: 3 ``gmm`` forward, 3 ``gmm``
and 3 ``tgmm`` backward a pass of an expert layer. A program without the
counter or the kernels: ``None``."""

from perf import program_trace, registry
from perf.readers import scope_ms


def read(obs):
    rows = obs.counters.get("moe_rows_held")
    if not rows:
        return None
    ns = scope_ms.scope_ns(obs, ["moe_mlp"], kind=program_trace.PALLAS)
    if not ns:
        return None
    cfg = obs.cell["config_file"]
    family = registry.code("families", cfg["family"])
    chips = len(program_trace.of(obs).devices)
    passes = (obs.counters["steps"] * obs.counters["grad_accum"]
              * family.moe_layers(cfg))
    work = family.gmm_work(cfg, rows, passes)
    peaks = obs.cell["peaks"]
    least_s = max(work["flops"] / peaks["bf16_flops_per_s"],
                  work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9 / chips)
