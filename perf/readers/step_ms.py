"""Time per optimizer step over the measured window, ms: the window's wall
time, which ends in a sync on the last step's state, over its steps."""


def read(obs):
    steps = obs.counters.get("steps")
    if not steps:
        return None
    lo, hi = obs.window
    return 1e3 * (hi - lo) / steps
