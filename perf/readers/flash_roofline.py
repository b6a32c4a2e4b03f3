"""The training attention kernels' share of their compute roofline, %:
causal attention FLOPs, forward and backward, of the traced steps
(perf/work.py) over the chip's bf16 peak, over the kernels' device time."""

from perf import trace_reduce, work

# The Pallas attention kernels as the trace names them on one chip, forward
# and backward alike: `tpu_custom_call`s named after the flax scope they were
# called in, the model's `attention`. Under a mesh of several chips the same
# kernels are named after the dispatch's `shard_map`, and so would the
# head+CE kernel be: no name there says "attention", so a cell under a mesh
# does not list this metric until the program gives its kernels stable names
# (PERF.md, Open questions).
NAME, DETAIL = r"^attention(\.\d+)*$", r"^tpu_custom_call$"


def read(obs, *, name=NAME, detail=DETAIL):
    if obs.trace is None:
        return None
    steps = obs.counters["steps"]  # a traced run's window is the trace's
    seconds = trace_reduce.kernel_seconds(
        obs.trace, obs.trace_window, name, detail)
    if seconds <= 0:
        return None
    cfg = obs.cell["config_file"]
    # Each chip's kernels compute its own share of the step's sequences.
    sequences = obs.counters["sequences_per_step"] / len(obs.trace.devices)
    flops = steps * work.flash_train_flops(
        cfg, obs.counters["seq_len"], sequences)
    return 100.0 * (flops / obs.cell["peaks"]["bf16_flops_per_s"]) / seconds
