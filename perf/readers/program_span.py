"""Median duration, ms, of the program's own host spans of one name
(``tpu_trainer:<span>``, ``tpu_trainer/utils/profiling.py``) that lie inside
the traced window. A program that opens no such span: ``None``."""

import statistics

from perf import program_trace


def read(obs, *, span):
    trace = program_trace.of(obs)
    if trace is None:
        return None
    found = program_trace.spans_in(trace, obs.trace_window, span)
    if not found:
        return None
    return statistics.median(s.dur for s in found) / 1e6
