"""Share of the measured window spent inside harness spans of one name, %."""


def read(obs, *, span):
    lo, hi = obs.window
    found = obs.spans.named(span, lo, hi)
    if not found:
        return None
    return 100.0 * sum(s.dur for s in found) / (hi - lo)
