"""Device time by program region: what ``trace_reduce.load_xplane`` drops.

The program names its own parts (``tpu_trainer/utils/profiling.py``):
``jax.named_scope`` paths that every compiled instruction carries as
``metadata={op_name="jit(_train_step)/.../transpose(jvp(GPT))/layers/mlp/..."}``,
host spans ``tpu_trainer:<name>`` on the profiler's clock, and a compile
log. This module loads, once a run, the ``tpu_trainer:`` host spans and each
device op's ``op_name``, gives every op of the traced window one **region**
and one **phase**, and sums by both the time each op ran with no op nested in
it (a ``while`` spans its body's ops; a kernel may span the asynchronous copy
that is started during it), averaged over the chips:

    regions  collective  flash  attn_proj  mlp  conv  ssm  head_loss  norm
             embed  grad_accum  grad_finalize  optimizer  other
    phases   fwd (``jvp(`` in the path)   bwd (``transpose(``)
             opt (the trainer's three scopes)   other

Where an op's ``op_name`` comes from (PERF.md, PR 26): on this runtime a
trace event's name is the instruction's HLO text WITHOUT its metadata, so
the names are mapped through the compiled step's text, which the program
hands out (``profiling.program_texts()``). A parent commit without that
function gives the region readers nothing to read: they return ``None``.

    python3 -m perf.program_trace --workload <cell> --seed <n> --seconds <s>

is the cell's ``--trace 1`` run (``perf/run.py``) under the name the records
of ``perf/records/`` quote: the metrics this module's readers read are in the
cell's resolved list since their files name their cells (PR 37);

    python3 -m perf.program_trace <trace dir or .json> [steps [step.hlo.txt]]

prints the table of a trace by hand (a trace directory needs the compiled
step's text, ``Trainer.compiled_step_text()`` saved to a file; a ``.json``
piece carries its names).
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import statistics
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from perf import harness, trace_reduce
from perf.trace_reduce import Event, Interval

PROGRAM_SPAN_PREFIX = "tpu_trainer:"
REGIONS = ("collective", "flash", "attn_proj", "mlp", "conv", "ssm",
           "head_loss", "norm", "embed", "grad_accum", "grad_finalize",
           "optimizer", "other")
PHASES = ("fwd", "bwd", "opt", "other")
TRAINER_SCOPES = ("grad_accum", "grad_finalize", "optimizer")
PALLAS = "tpu_custom_call"

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s+(?:\(.*?\)|\S+)\s+'
    r'([a-z][a-z0-9\-]*)\(.*?op_name="((?:[^"\\]|\\.)*)"', re.M)


# --- where an op came from ----------------------------------------------------

def instructions(program_text: str) -> List[Tuple[str, str, str]]:
    """``(name, opcode, op_name)`` of every instruction of a compiled
    program's text that carries an ``op_name``."""
    return _INSTRUCTION.findall(program_text)


def op_names(program_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name``, from a compiled program's text."""
    return {name: path for name, _, path in instructions(program_text)}


def scope_region(op_name: str, kind: str = "") -> str:
    """The region the scopes in ``op_name`` say (first match), the kind of
    instruction aside: what a collective's origin is reported as."""
    segs = op_name.split("/")
    if "attention" in segs:
        return "flash" if kind == PALLAS else "attn_proj"
    if "mlp" in segs or "moe_mlp" in segs:
        return "mlp"
    # A sequence operator's module whole, as `conv_ms.train` and
    # `ssm_ms.train` read it: before `norm` (the mixer's grouped norm is the
    # mixer's), the scan's kernels and the taps inside `mamba` included, the
    # core a backward recomputes too (`.../checkpoint/mamba/...`).
    if "conv" in segs:
        return "conv"
    if "mamba" in segs:
        return "ssm"
    # Before `embed`: the tied head's matmul is `head_loss/embed_tokens/...`.
    if "head_loss" in segs:
        return "head_loss"
    if any(s == "norm" or s.endswith("_norm") or s.endswith("layernorm")
           for s in segs):
        return "norm"
    if "embed_tokens" in segs:
        return "embed"
    for scope in TRAINER_SCOPES:
        if scope in segs:
            return scope
    return "other"


def region_of(name: str, kind: str, op_name: str) -> str:
    """A device op's region: a collective by its instruction's kind
    (whatever scope asked for it), else by its scopes."""
    if trace_reduce.COLLECTIVE.match(name):
        return "collective"
    return scope_region(op_name, kind)


def phase_of(op_name: str) -> str:
    segs = op_name.split("/")
    if any(scope in segs for scope in TRAINER_SCOPES):
        return "opt"
    if "transpose(" in op_name:
        return "bwd"
    if "jvp(" in op_name:
        return "fwd"
    return "other"


# --- the trace, with what trace_reduce drops -----------------------------------

@dataclasses.dataclass
class HostSpan:
    name: str                 # without the prefix: "trainer:train_step"
    start: int                # ns, the trace's clock
    dur: int
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ProgramTrace:
    """Device op events per chip (``Event.detail`` = the op's kind, as
    ``trace_reduce`` has it), instruction name -> ``op_name``, and the
    program's own host spans."""
    devices: Dict[int, List[Event]]
    op_names: Dict[str, str]
    spans: List[HostSpan]

    def to_json(self) -> dict:
        return {
            "devices": {str(k): [[e.name, e.start, e.dur, e.detail]
                                 for e in v]
                        for k, v in self.devices.items()},
            "op_names": self.op_names,
            "spans": [[s.name, s.start, s.dur, s.attrs] for s in self.spans],
        }

    @classmethod
    def from_json(cls, data: dict) -> "ProgramTrace":
        return cls(
            {int(k): [Event(*row) for row in v]
             for k, v in data["devices"].items()},
            dict(data["op_names"]), [HostSpan(*row) for row in data["spans"]])


def _from_program(function: str) -> Any:
    """``profiling.<function>()``; ``None`` on a commit whose program has no
    such function."""
    try:
        from tpu_trainer.utils import profiling
        return getattr(profiling, function)()
    except (ImportError, AttributeError):
        return None


def program_texts() -> Dict[str, str]:
    """Compiled text of the program's steps that ran, from the program."""
    return _from_program("program_texts") or {}


def load_xplane(path: str, texts: Optional[Dict[str, str]] = None,
                devices: Optional[Dict[int, List[Event]]] = None
                ) -> ProgramTrace:
    """The device ops of an ``.xplane.pb`` as ``trace_reduce.load_xplane``
    flattens them (``devices``, where the caller holds them already), with
    what that drops: the ``tpu_trainer:`` host spans with their stats, and
    each instruction's ``op_name`` from ``texts``, the compiled programs'
    text (the running program's own, if not given)."""
    from jax.profiler import ProfileData

    if devices is None:
        devices = trace_reduce.load_xplane(path).devices
    spans: List[HostSpan] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_SPAN_PREFIX):
                    spans.append(HostSpan(
                        ev.name[len(PROGRAM_SPAN_PREFIX):],
                        int(ev.start_ns), int(ev.duration_ns),
                        {k: v for k, v in ev.stats}))
    spans.sort(key=lambda s: (s.start, -s.dur))
    names: Dict[str, str] = {}
    for text in (program_texts() if texts is None else texts).values():
        names.update(op_names(text))
    return ProgramTrace(devices, names, spans)


def load(path: str) -> ProgramTrace:
    if path.endswith(".json"):
        with open(path) as f:
            return ProgramTrace.from_json(json.load(f))
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    return load_xplane(path)


def sample(trace: ProgramTrace, max_events: int = 400) -> ProgramTrace:
    """A small piece of a trace, for ``perf/testdata``: ``max_events``
    device ops of each chip, from a fifth of them before the chip's first
    Pallas kernel (a sharded step opens with hundreds of all-gathers) to
    where the next op starts, every op cut to that piece (the ``while`` that
    spans the whole step too), and the names and spans that meet it."""
    devices = {}
    for chip, events in trace.devices.items():
        first = next((i for i, e in enumerate(events)
                      if e.detail == PALLAS), 0)
        begin = max(0, first - max_events // 5)
        end = begin + max_events
        lo = events[begin].start
        hi = (events[end].start if end < len(events)
              else max(e.end for e in events))
        devices[chip] = [
            Event(e.name, max(e.start, lo),
                  min(e.end, hi) - max(e.start, lo), e.detail)
            for e in events[:end] if e.end > lo]
    used = {e.name for evs in devices.values() for e in evs}
    lo, hi = trace_reduce.window_of(trace_reduce.Trace(devices, []))
    return ProgramTrace(
        devices, {k: v for k, v in trace.op_names.items() if k in used},
        [s for s in trace.spans if s.start < hi and s.start + s.dur > lo
         ][:max_events])


# --- the table ----------------------------------------------------------------

def own_ns(events: Sequence[Event], window: Interval
           ) -> List[Tuple[Event, int]]:
    """``(event, ns)``: the time inside ``window`` in which the event ran and
    no event nested in it did (``events`` sorted by start, longer first).
    Every instant goes to the innermost event that covers it, so the sum is
    the busy time. ``trace_reduce.leaves`` drops every event that holds
    another: the ``while`` round a step's accumulations, but also a kernel
    during which an asynchronous copy was started (on the chip, PR 26:
    3-5% of the busy time)."""
    rows: List[List] = []
    running: List[Tuple[int, List]] = []     # (end, row), innermost last
    for ev in events:
        start, end = max(ev.start, window[0]), min(ev.end, window[1])
        if end <= start:
            continue
        while running and running[-1][0] <= start:
            running.pop()
        row = [ev, end - start]
        if running:
            outer_end, outer = running[-1]
            outer[1] -= min(end, outer_end) - start
        rows.append(row)
        running.append((end, row))
    return [(ev, ns) for ev, ns in rows if ns > 0]


def region_table(trace: ProgramTrace, window: Interval, steps: int
                 ) -> Optional[Dict[str, Any]]:
    """Device ms a step a chip by region and phase over ``window`` (each op's
    own time, :func:`own_ns`), with the largest families of HLO op by region,
    the longest unattributed ops and each collective by kind and origin.
    ``None`` where the trace names no op's origin."""
    if not trace.devices or not trace.op_names or not steps:
        return None
    chips = len(trace.devices)
    per_step_ms = 1.0 / (1e6 * steps * chips)
    cells: Dict[Tuple[str, str], int] = {}
    families: Dict[str, Dict[Tuple[str, str], int]] = {}
    other: Dict[Tuple[str, str], int] = {}
    coll: Dict[Tuple[str, str, str], int] = {}
    coll_alone: Dict[Tuple[str, str, str], int] = {}
    for events in trace.devices.values():
        when: Dict[Tuple[str, str, str], List[Interval]] = {}
        for ev, ns in own_ns(events, window):
            path = trace.op_names.get(ev.name, "")
            region = region_of(ev.name, ev.detail, path)
            phase = phase_of(path)
            cells[region, phase] = cells.get((region, phase), 0) + ns
            op = trace_reduce._op_family(ev.name)
            by = families.setdefault(
                op + (" <" + ev.detail + ">" if ev.detail else ""), {})
            by[region, phase] = by.get((region, phase), 0) + ns
            if region == "other":
                other[op, path] = other.get((op, path), 0) + ns
            elif region == "collective":
                key = (op, scope_region(path), phase)
                coll[key] = coll.get(key, 0) + ns
                when.setdefault(key, []).append((ev.start, ev.end))
        # "Alone" as `exposed_collective_frac.train` has it (trace_reduce).
        compute = trace_reduce.union(
            (e.start, e.end) for e in trace_reduce.leaves(events)
            if not trace_reduce.is_collective(e))
        for key, intervals in when.items():
            alone = trace_reduce.total(trace_reduce.clip(
                trace_reduce.subtract(trace_reduce.union(intervals), compute),
                window))
            coll_alone[key] = coll_alone.get(key, 0) + alone
    by_region = {r: {p: cells.get((r, p), 0) * per_step_ms for p in PHASES}
                 for r in REGIONS}
    by_phase = {p: sum(by_region[r][p] for r in REGIONS) for p in PHASES}
    busy = sum(by_phase.values())
    return {
        "unit": "ms of device time a step a chip",
        "steps": steps, "chips": chips,
        "regions": by_region,
        "phases": by_phase,
        "busy_ms": busy,
        # `breakdown.device_ops` names its rows so (fusion <kOutput>, copy).
        "op_families": [
            {"op": op, "ms": sum(by.values()) * per_step_ms,
             "of": {r + "/" + p: ns * per_step_ms for (r, p), ns in sorted(
                 by.items(), key=lambda kv: -kv[1])[:6]}}
            for op, by in sorted(
                families.items(), key=lambda kv: -sum(kv[1].values()))[:12]],
        "longest_other": [
            {"op": op, "op_name": path, "ms": ns * per_step_ms}
            for (op, path), ns in sorted(
                other.items(), key=lambda kv: -kv[1])[:10]],
        "collectives": [
            {"kind": kind, "origin": origin, "phase": phase,
             "ms": ns * per_step_ms,
             "alone_ms": coll_alone[kind, origin, phase] * per_step_ms}
            for (kind, origin, phase), ns in sorted(
                coll.items(), key=lambda kv: -kv[1])],
    }


def table_ms(table: Dict[str, Any], regions: Optional[Sequence[str]] = None,
             phases: Optional[Sequence[str]] = None) -> float:
    return sum(table["regions"][r][p]
               for r in (regions or REGIONS) for p in (phases or PHASES))


# --- the compile log ----------------------------------------------------------

def compile_entries() -> Optional[List[Any]]:
    """The program's compile log (``profiling.compile_log()``), or ``None``
    on a commit that keeps none."""
    return _from_program("compile_log")


COMPILE_KINDS = ("trace", "lower", "compile", "cache_read")


def outermost(entries: Iterable[Any], eps: float = 1e-3) -> List[Any]:
    """Trace, lowering and compile entries that lie inside no other: a jit
    traced inside another's trace reports a duration inside the outer one,
    and a cache read lies inside its backend compile."""
    timed = [e for e in entries if e.kind != "cache_read"]
    return [e for e in timed
            if not any(o is not e and o.start - eps <= e.start
                       and e.end <= o.end + eps
                       and (o.end - o.start) > (e.end - e.start)
                       for o in timed)]


def entries_within(entries: Iterable[Any], intervals: Iterable[Interval]
                   ) -> List[Any]:
    """Entries that ended inside one of ``intervals`` (host clock)."""
    intervals = list(intervals)
    return [e for e in entries
            if any(lo <= e.end <= hi for lo, hi in intervals)]


def compile_sums(entries: Iterable[Any], intervals: Iterable[Interval]
                 ) -> Dict[str, Dict[str, float]]:
    """``{kind: {"seconds", "count"}}`` of the log's entries that ended
    inside one of ``intervals`` (host clock): the outermost ``trace``,
    ``lower`` and ``compile`` entries, and every ``cache_read`` (which lies
    inside its ``compile``, so the four do not add up: ``compile`` already
    holds the read). What ``trace_lower_s``, ``executable_s`` and
    ``recompiles.train`` read, and what a run's ``setup`` note says its
    first calls were made of."""
    entries = list(entries)
    found = entries_within(
        outermost(entries) + [e for e in entries if e.kind == "cache_read"],
        intervals)
    return {kind: {"seconds": sum(e.seconds for e in found if e.kind == kind),
                   "count": sum(1 for e in found if e.kind == kind)}
            for kind in COMPILE_KINDS}


def log_covers(entries: Iterable[Any], intervals: Iterable[Interval]) -> bool:
    """Whether the log still holds what happened in every one of
    ``intervals``: an entry that ended inside each. The program's log keeps
    its newest 4,096 entries, and tracing one large unrolled step reports
    more nested entries than that before the outer one replaces them (the
    JoyAI cell, my chip run, PR 37: everything before ``_train_step`` was
    gone), so sums over spans it no longer covers would read low."""
    entries = list(entries)
    return all(any(lo <= e.end <= hi for e in entries)
               for lo, hi in intervals)


def first_call_compiles(spans) -> Optional[Dict[str, Any]]:
    """:func:`compile_sums` over the harness's ``first_call`` spans so far
    (``harness.Spans``): what a run's set-up was made of, from what is in
    memory, and under ``complete`` whether the log still covers every one
    of them (:func:`log_covers`; each is the first call of a jitted shape,
    so each has its entries). ``None`` on a commit whose program keeps no
    compile log."""
    log = compile_entries()
    if log is None:
        return None
    intervals = [(s.start, s.end) for s in spans.named("first_call")]
    return {**compile_sums(log, intervals),
            "complete": log_covers(log, intervals)}


# --- one load and one table a run ----------------------------------------------

def of(obs) -> Optional[ProgramTrace]:
    """The run's program trace, loaded once and kept on ``obs``."""
    if getattr(obs, "program_trace", None) is None:
        if obs.trace is None:
            return None
        started = time.perf_counter()
        obs.program_trace = load_xplane(
            trace_reduce.find_xplane(os.path.join(
                harness.OUT_DIR, obs.cell["name"] + ".trace")),
            devices=obs.trace.devices)
        obs.program_trace_load_s = time.perf_counter() - started
    return obs.program_trace


def table_of(obs) -> Optional[Dict[str, Any]]:
    """The run's region table, built once: also written to
    ``perf/out/<cell>.regions.json`` and printed as a ``regions`` note."""
    if getattr(obs, "region_table", None) is None:
        trace = of(obs)
        if trace is None:
            return None
        started = time.perf_counter()
        table = region_table(trace, obs.trace_window, obs.counters["steps"])
        if table is None:
            return None
        log = compile_entries()
        table["compile_log"] = [dataclasses.asdict(e) for e in log or []
                                if e.kind != "trace" or e.seconds >= 0.01]
        table["host_spans"] = span_summary(trace, obs.trace_window)
        table["reader_seconds"] = {
            "load": getattr(obs, "program_trace_load_s", 0.0),
            "table": time.perf_counter() - started}
        obs.region_table = table
        harness.write_record(obs.cell["name"], "regions", table)
        harness.write_record(obs.cell["name"], "program_trace_sample",
                             sample(trace).to_json())
        harness.note("regions", **table)
    return obs.region_table


def spans_in(trace: ProgramTrace, window: Interval,
             name: Optional[str] = None) -> List[HostSpan]:
    """The program's spans (of one name) that lie inside the window."""
    return [s for s in trace.spans if name in (None, s.name)
            and s.start >= window[0] and s.start + s.dur <= window[1]]


def span_summary(trace: ProgramTrace, window: Interval) -> Dict[str, Any]:
    """Count and median ms of each program span inside the window."""
    by: Dict[str, List[int]] = {}
    for s in spans_in(trace, window):
        by.setdefault(s.name, []).append(s.dur)
    return {name: {"count": len(durs),
                   "median_ms": statistics.median(durs) / 1e6}
            for name, durs in sorted(by.items())}


def run_cell(argv: Sequence[str]) -> int:
    """The cell's traced run through ``perf/run.py``."""
    from perf import run

    return run.main([*argv, "--trace", "1"])


if __name__ == "__main__":
    import sys

    if sys.argv[1] == "--workload":
        sys.exit(run_cell(sys.argv[1:]))
    target = sys.argv[1]
    n_steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    if len(sys.argv) > 3:       # the compiled step's text, saved to a file
        with open(sys.argv[3]) as f:
            loaded = load_xplane(trace_reduce.find_xplane(target),
                                 {"train_step": f.read()})
    else:
        loaded = load(target)
    whole = trace_reduce.window_of(
        trace_reduce.Trace(loaded.devices, []))
    print(json.dumps(region_table(loaded, whole, n_steps), indent=1))
