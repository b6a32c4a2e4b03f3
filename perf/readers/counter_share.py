"""One of the run's counters as a share of another, %: both summed over the
measured window by the runner. Either missing or zero: ``None``."""


def read(obs, *, counter, of):
    part, whole = obs.counters.get(counter), obs.counters.get(of)
    if part is None or not whole:
        return None
    return 100.0 * part / whole
