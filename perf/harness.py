"""What every runner shares: the chip or no run, the compile cache, spans on
the profiler's clock, the traced window, and the result line."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, NoReturn, Optional

from perf import registry, trace_reduce

OUT_DIR = os.path.join(registry.ROOT, "out")
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = os.path.join(registry.CHECKOUT, ".jax_cache")


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def note(kind: str, **fields) -> None:
    """An earlier line of the output: plain facts, never the result."""
    print(json.dumps({"note": kind, **fields}), flush=True)


def enable_compile_cache() -> str:
    """The persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says if it is set (jax reads it itself), else the fixed directory
    ``<checkout>/.jax_cache``. Every program is cached, however quick its
    compile, so that a second run in the same checkout compiles nothing."""
    import jax

    placed = os.environ.get(CACHE_ENV)
    if not placed:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed or CACHE_DIR


def require_chips(chips: int):
    """The TPU devices this cell runs on, or :class:`NoChip`. Never the CPU."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend could be initialised
        raise NoChip(f"jax found no device: {e}") from e
    first = devices[0]
    if first.platform != "tpu":
        raise NoChip(
            f"no TPU: jax found {len(devices)} x {first.device_kind} on "
            f"platform {first.platform!r}; the benchmark runs only on the "
            f"chip")
    if len(devices) < chips:
        raise NoChip(
            f"the cell needs {chips} chip(s), jax found {len(devices)}")
    return devices[:chips]


def device_line(devices) -> Dict[str, Any]:
    """The device as JAX reports it, with the peak memory of the fullest
    chip (``memory_stats()['peak_bytes_in_use']``)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            raise RuntimeError(
                f"{d} reports no peak_bytes_in_use in memory_stats()")
        peaks.append(int(stats["peak_bytes_in_use"]))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


class CacheCounter:
    """Counts persistent-cache hits and misses (jax.monitoring events)."""

    def __init__(self):
        import jax

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            self.misses += 1


@dataclasses.dataclass
class Span:
    name: str
    start: float  # seconds, time.perf_counter
    end: float
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


class Spans:
    """Spans the harness puts round its calls into the program. Kept in
    memory on the host clock; while a trace is being taken each is also a
    ``jax.profiler.TraceAnnotation`` (named ``perf:<name>``), so it lands on
    the trace's clock beside the device ops."""

    def __init__(self):
        self.all: List[Span] = []
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        record = Span(name, time.perf_counter(), 0.0, attrs)
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(
                    trace_reduce.SPAN_PREFIX + name):
                yield record
        else:
            yield record
        record.end = time.perf_counter()
        self.all.append(record)

    def named(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> List[Span]:
        return [s for s in self.all
                if s.name == name and s.start >= lo and s.end <= hi]


class TraceWindow:
    """Takes the profiler trace of one stretch of a ``--trace 1`` run."""

    WINDOW_SPAN = "traced_window"

    def __init__(self, cell_name: str, spans: Spans):
        self.dir = os.path.join(OUT_DIR, cell_name + ".trace")
        self.spans = spans
        self.started_at: Optional[float] = None
        self.stopped_at: Optional[float] = None
        self._window_cm = None

    @property
    def open(self) -> bool:
        return self.started_at is not None and self.stopped_at is None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the interpreter's calls: not wanted
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.spans.annotate = True
        self._window_cm = self.spans.span(self.WINDOW_SPAN)
        self._window_cm.__enter__()
        self.started_at = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.stopped_at = time.perf_counter()
        self._window_cm.__exit__(None, None, None)
        self.spans.annotate = False
        jax.profiler.stop_trace()

    def load(self) -> trace_reduce.Trace:
        return trace_reduce.load_xplane(trace_reduce.find_xplane(self.dir))


@dataclasses.dataclass
class Observations:
    """What the readers of per-layer metrics may read."""
    cell: Dict[str, Any]               # the resolved cell, with its "peaks"
    spans: Spans
    window: tuple                      # (start, end) on the host clock
    counters: Dict[str, Any]
    trace: Optional[trace_reduce.Trace] = None
    trace_window: Optional[tuple] = None   # ns, on the trace's clock
    traced: Optional[tuple] = None         # (start, end) on the host clock


@dataclasses.dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    observations: Observations
    devices: Any


def attach_trace(obs: Observations, tracer: TraceWindow) -> None:
    """Load the trace a ``--trace 1`` run took into ``obs`` and keep a small
    piece of it under ``perf/out/`` (what ``perf/testdata`` is made from)."""
    obs.trace = tracer.load()
    obs.trace_window = trace_reduce.window_of(obs.trace,
                                              TraceWindow.WINDOW_SPAN)
    obs.traced = (tracer.started_at, tracer.stopped_at)
    write_record(obs.cell["name"], "trace_sample",
                 trace_reduce.sample(obs.trace).to_json())


def read_per_layer(cell: Dict[str, Any], obs: Observations) -> Dict[str, Any]:
    """Each per-layer metric through its own reader; one that finds nothing
    to read returns None and is left out of the line."""
    out = {}
    for name, spec in cell["per_layer_specs"].items():
        reader = registry.code("readers", spec["reader"])
        value = reader.read(obs, **spec.get("args", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def result_line(cell: Dict[str, Any], result: Result, trace: bool) -> str:
    device = device_line(result.devices)
    line: Dict[str, Any] = {
        "correct": bool(result.correct),
        "attempted": int(result.attempted),
        "failed": int(result.failed),
    }
    obs = result.observations
    if trace:
        line["metrics"] = read_per_layer(cell, obs)
        window_s = (obs.trace_window[1] - obs.trace_window[0]) / 1e9
        device["busy_s"] = trace_reduce.busy_seconds(
            obs.trace, obs.trace_window)
        device["window_s"] = window_s
        line["breakdown"] = {
            "device_ops": trace_reduce.top_device_ops(
                obs.trace, obs.trace_window),
            "idle_gaps": trace_reduce.idle_gaps_by_span(
                obs.trace, obs.trace_window),
        }
    else:
        line["metrics"] = {
            name: {"value": float(result.end_to_end[name]),
                   "unit": spec["unit"]}
            for name, spec in cell["end_to_end_specs"].items()}
    line["device"] = device
    return json.dumps(line)


def write_record(cell_name: str, suffix: str, data: Any) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{cell_name}.{suffix}.json")
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    return path


def fail(message: str, code: int = 1) -> NoReturn:
    print(message, file=sys.stderr, flush=True)
    raise SystemExit(code)
