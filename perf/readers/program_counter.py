"""A count or a sum of seconds over the program's compile log
(``profiling.compile_log()``): the outermost entries of the given ``kinds``
(trace | lower | compile) that ended inside the measured window
(``within="window"``) or inside the harness's ``first_call`` spans
(``within="first_call"``). Read in the traced run, like every per-layer
metric. A program that keeps no such log: ``None``."""

from perf import program_trace


def read(obs, *, kinds, within, value):
    log = program_trace.compile_entries() if obs.trace is not None else None
    if log is None:
        return None
    if within == "window":
        intervals = [obs.window]
    else:
        intervals = [(s.start, s.end) for s in obs.spans.named(within)]
        if not intervals:
            return None
    found = [e for e in program_trace.entries_within(
        program_trace.outermost(log), intervals) if e.kind in kinds]
    if value == "count":
        return float(len(found))
    return sum(e.seconds for e in found)
