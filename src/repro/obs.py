"""Spans at the program's layer boundaries, and compile accounting.

`span(name, **args)` marks a host interval.  It is always a
`jax.profiler.TraceAnnotation`, so a profiled run shows it on the
profiler's host clock, beside the device ops.  Inside `record()` the span
is also kept in memory, with its parent, on the `time.monotonic_ns`
clock, and so is every JAX compile stage that ends while the recorder is
active, with the innermost span open on its thread:

    with obs.record() as rec:
        build_graph(store)
    rec.self_seconds("hnsw.knn"), rec.compile_seconds()

Outside `record()` a span keeps nothing: `span` returns the bare
annotation, which costs about a microsecond and records only while the
profiler runs.  The recorder lives in a context variable: `record()`
restores the previous one on exit, and a thread records into it only
when it runs in a copy of the context (`contextvars.copy_context().run`).
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from typing import Iterator, NamedTuple, Optional

import jax

# JAX's compile stages, as `jax.monitoring` reports their durations.  Every
# new jit shape traces and lowers; the backend stage then compiles, or on a
# persistent-cache hit loads, and reports the load inside its own duration.
# A jit traced inside another reports its trace inside the outer's.  So a
# stage's seconds are counted as the union of the events' intervals,
# never as their sum.
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_STAGES = (TRACE, LOWER, COMPILE, CACHE_LOAD)


class Span(NamedTuple):
    name: str
    args: dict
    parent: Optional[int]        # index of the enclosing span, same thread
    start_ns: int
    end_ns: Optional[int]        # None while the span is open


class CompileEvent(NamedTuple):
    stage: str                   # one of COMPILE_STAGES
    seconds: float
    span: Optional[int]          # innermost span open on the thread
    end_ns: int                  # when JAX reported it


class Recorder:
    """The spans and compile events of one `record()` block."""

    def __init__(self):
        self.spans: list[Span] = []
        self.compiles: list[CompileEvent] = []
        self._lock = threading.Lock()
        self._open = threading.local()

    def _stack(self) -> list:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def _closed(self, name: str) -> list:
        return [s for s in self.spans
                if s.name == name and s.end_ns is not None]

    def total_seconds(self, name: str) -> float:
        """Summed duration of the closed spans called `name`."""
        return sum(s.end_ns - s.start_ns for s in self._closed(name)) * 1e-9

    def self_seconds(self, name: str) -> float:
        """`total_seconds(name)` less what their child spans cover."""
        ids = {i for i, s in enumerate(self.spans)
               if s.name == name and s.end_ns is not None}
        covered = sum(s.end_ns - s.start_ns for s in self.spans
                      if s.parent in ids and s.end_ns is not None)
        return self.total_seconds(name) - covered * 1e-9

    def compile_events(self, lo_ns: float = float("-inf"),
                       hi_ns: float = float("inf")) -> list:
        """The compile events reported in [lo_ns, hi_ns)."""
        return [e for e in self.compiles if lo_ns <= e.end_ns < hi_ns]

    def compile_seconds(self, lo_ns: float = float("-inf"),
                        hi_ns: float = float("inf")) -> float:
        """Seconds spent in any compile stage, for the events reported in
        [lo_ns, hi_ns): the union of their intervals."""
        iv = sorted((e.end_ns - e.seconds * 1e9, e.end_ns)
                    for e in self.compile_events(lo_ns, hi_ns))
        total, reach = 0.0, float("-inf")
        for a, b in iv:
            if b > reach:
                total += b - max(a, reach)
                reach = b
        return total * 1e-9


_RECORDER: contextvars.ContextVar[Optional[Recorder]] = \
    contextvars.ContextVar("repro_obs_recorder", default=None)
_listening = False
_listen_lock = threading.Lock()


def _on_duration(event: str, seconds: float, **_) -> None:
    rec = _RECORDER.get()
    if rec is None or event not in COMPILE_STAGES:
        return
    stack = rec._stack()
    rec.compiles.append(CompileEvent(event, seconds,
                                     stack[-1] if stack else None,
                                     time.monotonic_ns()))


@contextlib.contextmanager
def record() -> Iterator[Recorder]:
    """Keep the spans and compile events of the block in a Recorder."""
    global _listening
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True
    rec = Recorder()
    token = _RECORDER.set(rec)
    try:
        yield rec
    finally:
        _RECORDER.reset(token)


class _RecordedSpan:
    __slots__ = ("rec", "name", "args", "index", "annotation")

    def __init__(self, rec: Recorder, name: str, args: dict):
        self.rec, self.name, self.args = rec, name, args

    def __enter__(self):
        self.annotation = jax.profiler.TraceAnnotation(self.name,
                                                       **self.args)
        self.annotation.__enter__()
        rec, stack = self.rec, self.rec._stack()
        with rec._lock:
            self.index = len(rec.spans)
            rec.spans.append(Span(self.name, self.args,
                                  stack[-1] if stack else None,
                                  time.monotonic_ns(), None))
        stack.append(self.index)
        return self

    def set_metadata(self, **args) -> None:
        """Add `args` to the span, as `TraceAnnotation.set_metadata` does:
        for values known only once the span's work is done."""
        self.args.update(args)
        self.annotation.set_metadata(**args)

    def __exit__(self, *exc):
        end = time.monotonic_ns()
        self.rec._stack().pop()
        spans = self.rec.spans
        spans[self.index] = spans[self.index]._replace(end_ns=end)
        self.annotation.__exit__(*exc)
        return False


def span(name: str, **args):
    """A context manager that marks `name` on the profiler's host clock
    and, inside `record()`, keeps it with `args`.  What it enters as has
    `set_metadata(**args)`, to add args before the span ends."""
    rec = _RECORDER.get()
    if rec is None:
        return jax.profiler.TraceAnnotation(name, **args)
    return _RecordedSpan(rec, name, args)
