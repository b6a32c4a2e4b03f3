"""Tracing: what the program says about where its time goes.

One mechanism, on ``jax.profiler``'s clock, so that what the host was doing
and what the device was doing land in the same trace file:

- **Scopes** inside the jitted programs are ``jax.named_scope`` (the flax
  module names, and ``grad_accum`` / ``grad_finalize`` / ``optimizer`` in
  ``training/trainer.py``, ``head_loss`` in ``models/gpt.py``; a Mamba-2
  mixer is ``mamba`` with ``taps`` round its depthwise convolution and
  ``ssd`` round the chunked scan, ``ops/ssd.py``; an expert layer is
  ``moe_mlp`` with ``route`` / ``experts`` / ``shared_expert``). They are
  trace-time metadata: every compiled instruction carries the path of scopes
  it came from (``op_name``) and nothing about the computation changes.
  ``jax_compilation_cache_include_metadata_in_key`` is set below so that an
  executable read back from the persistent cache names THIS tree's scopes,
  never those of the tree that wrote the entry.
- **Host spans**: ``span(name, **attrs)`` is a ``TraceAnnotation`` named
  ``tpu_trainer:<name>``. The profiler keeps it, with its attributes, only
  while a trace is open, and writes it beside the device ops; with no trace
  open it costs a list push and pop. Spans opened by the program:
  ``trainer:init_state``, ``trainer:place_batch``, ``trainer:train_step``
  (``variant`` plain or telemetry), ``trainer:eval_step``, each with
  ``step`` = the trainer's host-side call count; ``goodput:<category>``
  (``GoodputLedger.track``) and ``serve:<category>`` (``ServingLedger.track``).
- **The compile log**: ``compile_log()`` holds one entry per jaxpr trace,
  lowering to MLIR, backend compile (a read from the persistent cache and
  the executable's load are inside it) and cache retrieval that jax reports
  through ``jax.monitoring``, with the innermost open span and its step:
  which call compiled, and what the compile was made of. Of what ran
  inside a trace or a lowering (a jit traced inside another's trace, the
  functions a lowering rule traces) only the outermost is kept.
- **Program text**: ``register_program`` / ``program_texts`` hand out the
  compiled HLO text of the steps that ran (``Trainer.compiled_step_text``),
  whose ``metadata={op_name=...}`` maps an instruction to its scopes: a TPU
  trace event names its instruction and carries no scope of its own.
- **Captures**: ``trace(dir)``, ``WindowedTrace`` (the training CLI's
  ``--profile_dir`` / ``--profile_start`` / ``--profile_steps`` and
  ``serve_bench --profile-trace``: exactly the steady-state window, with a
  ``StepTraceAnnotation`` a step) and ``start_server(port)`` for a live
  attach. Traces go to ``<dir>/host_<k>`` so hosts of a pod do not collide.

``perf/program_trace.py`` reduces such a trace to device time by region and
phase; its metrics are listed in PERF.md section 3.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax

# Profiles of this program are read by scope, so a cached executable must
# carry the scopes of the tree that runs it (the default key leaves metadata
# out: "executables loaded from the cache may have stale metadata").
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

SPAN_PREFIX = "tpu_trainer:"

_open_spans = threading.local()


def _stack() -> List[Tuple[str, Optional[int]]]:
    try:
        return _open_spans.stack
    except AttributeError:
        _open_spans.stack = []
        return _open_spans.stack


class span(jax.profiler.TraceAnnotation):
    """``with span("trainer:train_step", step=n):`` — a host span on the
    profiler's clock, named ``tpu_trainer:<name>``, with ``attrs`` as its
    stats. Recorded only while a trace is open. The open spans of a thread
    are kept on a stack, for the compile log alone."""

    def __init__(self, name: str, **attrs):
        super().__init__(SPAN_PREFIX + name, **attrs)
        self._entry = (name, attrs.get("step"))

    def __enter__(self):
        _stack().append(self._entry)
        return super().__enter__()

    def __exit__(self, *exc):
        _stack().pop()
        return super().__exit__(*exc)


# --- the compile log ---------------------------------------------------------

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}


@dataclasses.dataclass(frozen=True)
class CompileEntry:
    kind: str                 # trace | lower | compile | cache_read
    seconds: float
    end: float                # time.perf_counter() when jax reported it
    fun_name: Optional[str]   # the jitted function, where jax names it
    span: Optional[str]       # innermost open span of the reporting thread
    step: Optional[int]       # that span's step
    thread: int               # threading.get_ident() of that thread

    @property
    def start(self) -> float:
        return self.end - self.seconds


# A long run that recompiles every step is the case to diagnose, not one to
# run out of memory on: the log keeps the newest entries.
_compile_log: collections.deque = collections.deque(maxlen=4096)


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    kind = _COMPILE_EVENTS.get(event)
    if kind is None:
        return
    stack = _stack()
    name, step = stack[-1] if stack else (None, None)
    entry = CompileEntry(
        kind, float(seconds), time.perf_counter(), kwargs.get("fun_name"),
        name, step, threading.get_ident())
    if kind in ("trace", "lower"):
        # jax reports what ran inside a trace or a lowering before the trace
        # or lowering itself (a jit traced inside another's trace, an eager
        # op compiled during it, the functions some lowering rules trace),
        # and one unrolled step holds thousands: keep the outermost.
        while (_compile_log and _compile_log[-1].thread == entry.thread
               and _compile_log[-1].start >= entry.start):
            _compile_log.pop()
    _compile_log.append(entry)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_log() -> List[CompileEntry]:
    """Every trace, lowering, backend compile and cache read of this process
    so far, oldest first."""
    return list(_compile_log)


# --- program text ------------------------------------------------------------

_programs: Dict[str, Callable[[], str]] = {}


def register_program(name: str, text_fn: Callable[[], str]) -> None:
    """``text_fn()`` returns the compiled HLO text of the program ``name``,
    e.g. ``train_step``. The newest registration of a name is the one kept,
    and it is kept alive: a trace is read after the run that made it has
    returned and dropped its runner."""
    _programs[name] = text_fn


def program_texts() -> Dict[str, str]:
    """Compiled HLO text of every registered program."""
    return {name: text_fn() for name, text_fn in list(_programs.items())}


# --- captures ----------------------------------------------------------------

def _host_dir(log_dir: str) -> str:
    path = os.path.join(log_dir, f"host_{jax.process_index()}")
    os.makedirs(path, exist_ok=True)
    return path


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a jax.profiler trace for the duration of the block."""
    with jax.profiler.trace(_host_dir(log_dir)):
        yield


def start_server(port: int = 9999):
    """Start the live profiler server (attach via TensorBoard capture)."""
    return jax.profiler.start_server(port)


class WindowedTrace:
    """Trace ``num_steps`` consecutive steps starting at the first step
    ``>= start``.

    Call ``step(i)`` at the top of every training step — it returns a
    context manager to run the step's work under, so traced steps carry a
    ``jax.profiler.StepTraceAnnotation`` and the trace viewer groups the
    timeline per step (a no-op context outside the window)::

        with profiler.step(i):
            ... data wait + train_step ...

    The first traced step is the first one at or past ``start`` (a resume
    that lands beyond ``start`` still opens the window — ``i == start``
    would never fire there); the trace stops after ``num_steps`` traced
    steps or at ``close()``, and never re-opens (one window per run).
    """

    def __init__(self, log_dir: Optional[str], start: int = 5,
                 num_steps: int = 5, label: str = "train"):
        self.log_dir = log_dir
        self.start = start
        self.num_steps = num_steps
        # Annotation label grouping the trace-viewer timeline: "train"
        # for training steps, "serve" for serving iterations
        # (serve_bench --profile-trace).
        self.label = label
        self._active = False
        self._stop_at: Optional[int] = None   # set when the window opens

    def step(self, i: int):
        if self.log_dir:
            if (not self._active and self._stop_at is None
                    and i >= self.start):
                jax.profiler.start_trace(_host_dir(self.log_dir))
                self._active = True
                self._stop_at = i + self.num_steps
            elif self._active and i >= self._stop_at:
                jax.profiler.stop_trace()
                self._active = False
        if self._active:
            return jax.profiler.StepTraceAnnotation(self.label, step_num=i)
        return contextlib.nullcontext()

    def close(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
