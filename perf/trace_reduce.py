"""From a profiler trace to numbers: busy union, idle share, kernel time by
name, exposed collectives, and the breakdown the next issue is written from.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` (nothing but JAX). It is first flattened to a
plain form (:class:`Trace`) that also loads from JSON, so the reductions can
be checked on a small recorded trace kept under ``perf/testdata/``.

What the planes look like on this runtime (TPU v5e, jax 0.9.0, libtpu
0.0.34; looked at by hand in PR 24 with ``python3 -m perf.trace_reduce
<dir>``): one plane ``/device:TPU:<n>`` per chip with the lines ``Steps``,
``XLA Modules``, ``XLA Ops`` and ``Async XLA Ops``. ``XLA Ops`` holds one
event per executed HLO instruction of the TensorCore's sequential stream
(a container such as ``while`` spans its body's events); an event's name is
the instruction's whole HLO text, ``%attention.515 = (bf16[...]) custom-call(
..., custom_call_target="tpu_custom_call", ...``, and it carries no stat
that says where in the program it came from. A Pallas kernel is a
``tpu_custom_call`` named after the scope it was called in (the flash
kernels: ``attention.<n>``). ``/host:CPU`` has one line per thread; the
``TraceAnnotation`` spans the harness writes are on the line ``python3``.
All times are nanoseconds on one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]  # [start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "perf:"  # harness spans, see perf/harness.py
# HLO instruction names of collectives, with the -start/-done halves of the
# asynchronous forms and the numbered copies ("all-gather-start.12").
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast|ragged-all-to-all)"
    r"(-start|-done)?(\.\d+)?$")


@dataclasses.dataclass
class Event:
    name: str
    start: int       # ns
    dur: int         # ns
    detail: str = ""  # where the op came from (op_name / long name), if known

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """Device op events per chip and the harness's host spans."""
    devices: Dict[int, List[Event]]
    host_spans: List[Event]

    def to_json(self) -> dict:
        def rows(events):
            return [[e.name, e.start, e.dur, e.detail] for e in events]
        return {"devices": {str(k): rows(v) for k, v in self.devices.items()},
                "host_spans": rows(self.host_spans)}

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        def events(rows):
            return [Event(*row) for row in rows]
        return cls({int(k): events(v) for k, v in data["devices"].items()},
                   events(data["host_spans"]))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"kind=(k\w+)")


def parse_hlo(text: str) -> Tuple[str, str]:
    """``(name, detail)`` of an op event whose name is HLO text: the
    instruction's name without ``%``, and what kind of op it is: the target
    of a custom call (``tpu_custom_call`` = a Pallas kernel), else a
    fusion's kind, else nothing."""
    name, _, rest = text.partition(" = ")
    found = _TARGET.search(rest) or _KIND.search(rest)
    return name.lstrip("%"), found.group(1) if found else ""


def load_xplane(path: str) -> Trace:
    """Flatten an ``.xplane.pb`` (needs jax; imported here so the reductions
    below stay importable without it)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    host_spans: List[Event] = []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                out = devices.setdefault(int(match.group(1)), [])
                for ev in line.events:
                    name, detail = parse_hlo(ev.name)
                    out.append(Event(name, int(ev.start_ns),
                                     int(ev.duration_ns), detail))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host_spans.append(Event(
                            ev.name[len(SPAN_PREFIX):], int(ev.start_ns),
                            int(ev.duration_ns)))
    for events in devices.values():
        events.sort(key=lambda e: (e.start, -e.dur))
    host_spans.sort(key=lambda e: (e.start, -e.dur))
    return Trace(devices, host_spans)


def load(path: str) -> Trace:
    if path.endswith(".json"):
        with open(path) as f:
            return Trace.from_json(json.load(f))
    return load_xplane(path)


# --- interval arithmetic -----------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals: Sequence[Interval]) -> int:
    return sum(end - start for start, end in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of ``a`` (disjoint, sorted) that no interval of ``b``
    (disjoint, sorted) covers."""
    out: List[Interval] = []
    j = 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events that contain no other event (``events`` sorted by start, longer
    first): the instructions that did the work, without the ``while`` and
    ``conditional`` containers that span them."""
    out: List[Event] = []
    for i, ev in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is not None and nxt.start < ev.end and nxt.end <= ev.end \
                and nxt.dur < ev.dur:
            continue  # ev contains the next event: a container
        out.append(ev)
    return out


def is_collective(ev: Event) -> bool:
    return bool(COLLECTIVE.match(ev.name))


# --- reductions --------------------------------------------------------------

def window_of(trace: Trace, span: Optional[str] = None) -> Interval:
    """The traced window: the host span ``span`` if the harness wrote one,
    else from the first device op's start to the last one's end."""
    if span is not None:
        for ev in trace.host_spans:
            if ev.name == span:
                return (ev.start, ev.end)
    starts = [evs[0].start for evs in trace.devices.values() if evs]
    ends = [max(e.end for e in evs) for evs in trace.devices.values() if evs]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return (min(starts), max(ends))


def busy_seconds(trace: Trace, window: Interval) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    chips in the trace."""
    per_chip = [
        total(clip(union((e.start, e.end) for e in evs), window))
        for evs in trace.devices.values()]
    if not per_chip:
        raise ValueError("the trace holds no device plane")
    return sum(per_chip) / len(per_chip) / 1e9


def idle_share(trace: Trace, window: Interval) -> float:
    return 1.0 - busy_seconds(trace, window) / ((window[1] - window[0]) / 1e9)


def _matcher(name: str, detail: Optional[str]):
    rx_name = re.compile(name)
    rx_detail = re.compile(detail) if detail is not None else None
    return lambda e: bool(rx_name.search(e.name)) and (
        rx_detail is None or bool(rx_detail.search(e.detail)))


def kernel_seconds(trace: Trace, window: Interval, name: str,
                   detail: Optional[str] = None) -> float:
    """Device seconds of the leaf ops whose name matches the regular
    expression ``name`` (and whose detail matches ``detail``, if given),
    averaged over the chips."""
    match = _matcher(name, detail)
    per_chip = []
    for evs in trace.devices.values():
        hits = [(e.start, e.end) for e in leaves(evs) if match(e)]
        per_chip.append(total(clip(union(hits), window)))
    if not per_chip:
        raise ValueError("the trace holds no device plane")
    return sum(per_chip) / len(per_chip) / 1e9


def exposed_collective_share(trace: Trace, window: Interval) -> float:
    """Share of the window in which a collective runs on a device and no
    compute does, averaged over the chips."""
    shares = []
    for evs in trace.devices.values():
        leaf = leaves(evs)
        comm = union((e.start, e.end) for e in leaf if is_collective(e))
        comp = union((e.start, e.end) for e in leaf if not is_collective(e))
        exposed = total(clip(subtract(comm, comp), window))
        shares.append(exposed / (window[1] - window[0]))
    if not shares:
        raise ValueError("the trace holds no device plane")
    return sum(shares) / len(shares)


def _op_family(name: str) -> str:
    """``%fusion.123`` -> ``fusion``: numbered copies of one op fold."""
    return re.sub(r"(\.\d+)+$", "", name.lstrip("%"))


def top_device_ops(trace: Trace, window: Interval, n: int = 10
                   ) -> List[List]:
    """The leaf ops that took most device time on the first chip, numbered
    copies folded by family and kind: ``[[name, seconds], ...]``."""
    first = trace.devices[min(trace.devices)]
    by: Dict[str, int] = {}
    for e in leaves(first):
        got = clip([(e.start, e.end)], window)
        if not got:
            continue
        key = _op_family(e.name)
        if e.detail:
            key += " <" + e.detail + ">"
        by[key] = by.get(key, 0) + total(got)
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps_by_span(trace: Trace, window: Interval, n: int = 10,
                      ignore: Sequence[str] = ("traced_window",)
                      ) -> List[List]:
    """Idle time of the first chip attributed to what the host was doing:
    each gap between device ops goes to the innermost harness span that
    covers its middle (``"(no span)"`` otherwise): ``[[span, seconds], ...]``,
    largest first."""
    first = trace.devices[min(trace.devices)]
    busy = clip(union((e.start, e.end) for e in first), window)
    gaps = subtract([window], busy)
    spans = sorted((s for s in trace.host_spans if s.name not in ignore),
                   key=lambda e: e.dur)  # innermost first
    by: Dict[str, int] = {}
    for start, end in gaps:
        mid = (start + end) // 2
        owner = "(no span)"
        for sp in spans:
            if sp.start <= mid < sp.end:
                owner = sp.name
                break
        by[owner] = by.get(owner, 0) + (end - start)
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def sample(trace: Trace, max_events: int = 400) -> Trace:
    """A small piece of a trace, for ``perf/testdata``: the first
    ``max_events`` device ops of each chip and the host spans that meet
    them."""
    devices = {k: v[:max_events] for k, v in trace.devices.items()}
    lo, hi = window_of(Trace(devices, []))
    spans = [s for s in trace.host_spans if s.start < hi and s.end > lo]
    return Trace(devices, spans[:max_events])


def describe(path: str, per_line: int = 12) -> str:
    """Planes, lines and the commonest event names of an ``.xplane.pb``,
    with one event's stats per line: what to look at by hand before
    trusting a pattern above."""
    from collections import Counter

    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            names = Counter(e.name for e in events)
            out.append(f"  line {line.name!r}: {len(events)} events")
            for name, count in names.most_common(per_line):
                out.append(f"    {count:7d} x {name[:140]}")
            first = events[0]
            stats = {k: (v if not isinstance(v, str) else v[:160])
                     for k, v in first.stats}
            out.append(f"    first event: {first.name[:80]!r} "
                       f"start_ns={first.start_ns} dur_ns={first.duration_ns} "
                       f"stats={stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    target = sys.argv[1]
    if os.path.isdir(target):
        target = find_xplane(target)
    print(describe(target))
