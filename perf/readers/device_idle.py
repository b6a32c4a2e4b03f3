"""Share of the traced window in which no operation ran on the device, %."""

from perf import trace_reduce


def read(obs):
    if obs.trace is None:
        return None
    return 100.0 * trace_reduce.idle_share(obs.trace, obs.trace_window)
