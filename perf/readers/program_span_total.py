"""Host time in the program's own spans of one name (``tpu_trainer:<span>``,
``tpu_trainer/utils/profiling.py``) that lie inside the traced window,
summed and divided by the traced steps, ms a step. ``0.0`` where the trace
holds the program's spans and none of that name fell in the window;
``None`` where it holds none (a commit whose program opens no span)."""

from perf import program_trace


def read(obs, *, span):
    trace = program_trace.of(obs)
    if trace is None or not trace.spans:
        return None
    found = program_trace.spans_in(trace, obs.trace_window, span)
    return sum(s.dur for s in found) / (1e6 * obs.counters["steps"])
