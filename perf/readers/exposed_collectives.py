"""Share of the traced window in which a collective runs on a device and no
compute does, %. A cell on one chip has no collectives: nothing to read."""

from perf import trace_reduce


def read(obs):
    if obs.trace is None or len(obs.trace.devices) < 2:
        return None
    return 100.0 * trace_reduce.exposed_collective_share(
        obs.trace, obs.trace_window)
