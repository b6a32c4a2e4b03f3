"""A count or a sum of seconds over the program's compile log
(``profiling.compile_log()``): the outermost entries of the given ``kinds``
(trace | lower | compile) that ended inside the measured window
(``within="window"``) or inside the harness's ``first_call`` spans
(``within="first_call"``). Read in the traced run, like every per-layer
metric. A program that keeps no such log, or whose log no longer holds an
entry of every such span (``program_trace.log_covers``): ``None``."""

from perf import program_trace


def read(obs, *, kinds, within, value):
    log = program_trace.compile_entries() if obs.trace is not None else None
    if log is None:
        return None
    if within == "window":
        intervals = [obs.window]
    else:
        intervals = [(s.start, s.end) for s in obs.spans.named(within)]
        # A log that has lost a span's entries would read low: nothing.
        if not intervals or not program_trace.log_covers(log, intervals):
            return None
    sums = program_trace.compile_sums(log, intervals)
    return float(sum(sums[kind][value] for kind in kinds))
