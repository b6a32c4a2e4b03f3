"""Device idle in the traced window credited to the program's own host
spans, ms a step a chip: each gap between device ops (as
``device_idle_frac.train`` has them, chip by chip) goes to the innermost
``tpu_trainer:`` span inside the window (``program_trace.spans_in``) that
covers the gap's middle; the reading is what went to the spans named
``span``, averaged over the chips, over the traced steps. A gap under no
program span goes to no reading. ``0.0`` where the trace holds the
program's spans and none of that name took idle; ``None`` where it holds
none (a commit whose program opens no span)."""

import bisect
from typing import Dict

from perf import program_trace, trace_reduce


def idle_ns_by_span(trace, window) -> Dict[str, float]:
    """``{span name: idle ns}``, averaged over the chips."""
    spans = sorted(program_trace.spans_in(trace, window),
                   key=lambda s: s.dur)                  # innermost first
    by: Dict[str, float] = {}
    for events in trace.devices.values():
        busy = trace_reduce.clip(
            trace_reduce.union((e.start, e.end) for e in events), window)
        gaps = trace_reduce.subtract([window], busy)
        mids = [(start + end) // 2 for start, end in gaps]
        owner = [None] * len(gaps)
        for sp in spans:
            for i in range(bisect.bisect_left(mids, sp.start),
                           bisect.bisect_left(mids, sp.start + sp.dur)):
                if owner[i] is None:
                    owner[i] = sp.name
        for (start, end), name in zip(gaps, owner):
            if name is not None:
                by[name] = by.get(name, 0) + (end - start)
    chips = len(trace.devices)
    return {name: ns / chips for name, ns in by.items()}


def read(obs, *, span):
    trace = program_trace.of(obs)
    if trace is None or not trace.spans or not trace.devices:
        return None
    idle = idle_ns_by_span(trace, obs.trace_window)
    return idle.get(span, 0.0) / (1e6 * obs.counters["steps"])
