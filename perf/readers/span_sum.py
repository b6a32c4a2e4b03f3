"""Sum of the harness spans of one name over the whole run, in seconds."""


def read(obs, *, span):
    found = obs.spans.named(span)
    if not found:
        return None
    return sum(s.dur for s in found)
