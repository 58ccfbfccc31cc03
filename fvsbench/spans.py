"""Readings of the program's own spans and compile events (`repro.obs`).

A run that keeps a `repro.obs` recorder active from the harness's
construction to the end of the window hands it on as `run.recorder`.  The
recorder's clock, `time.monotonic_ns`, is the harness's, so the window's
work runs from the first dispatch's start to the last one's answer.
Without a recorder, or without the spans asked for, every reading here is
None; nothing here imports `repro.obs`, so a program without it reads
None too.

`idle_gaps` names each idle gap of a reduced trace by the innermost of
GAP_SPANS that holds its midpoint, so idle time inside `executor.search`
falls to the program's `executor.plan`, `executor.execute` or
`executor.anytime` where one holds it; a trace without those spans gets
`trace.idle_gaps`'s breakdown.
"""
from __future__ import annotations

import numpy as np

from fvsbench import trace as tr

GAP_SPANS = tr.GAP_SPANS + ("executor.plan", "executor.execute",
                            "executor.anytime")


def recorder(run):
    return getattr(run, "recorder", None)


def window_ns(run) -> tuple:
    """The window's work on the recorder's clock: from the first
    dispatch's start, at or after set-up's end, to the last one's
    answer."""
    if not run.dispatches:
        return float("inf"), float("inf")
    return run.dispatches[0].start * 1e9, run.dispatches[-1].done * 1e9


def span_seconds(run, names: tuple, own: bool = False):
    """Summed seconds of the recorded spans called `names` (their self
    time with `own`); None where none was recorded."""
    rec = recorder(run)
    if rec is None or not any(s.name in names for s in rec.spans):
        return None
    read = rec.self_seconds if own else rec.total_seconds
    return sum(read(n) for n in names)


def setup_compile_seconds(run):
    """Seconds of set-up in any JAX compile stage (union of the events)."""
    rec = recorder(run)
    return None if rec is None else rec.compile_seconds(
        hi_ns=window_ns(run)[0])


def window_compiles(run):
    """(backend compiles or cache loads, seconds in any compile stage)
    reported inside the window; None without a recorder."""
    rec = recorder(run)
    if rec is None:
        return None
    lo, hi = window_ns(run)
    n = sum(e.stage == "/jax/core/compile/backend_compile_duration"
            for e in rec.compile_events(lo, hi))
    return n, rec.compile_seconds(lo, hi)


def _innermost(spans: list, points: np.ndarray) -> list:
    """For each of the sorted `points`, the name of the innermost span
    (name, start, end) that holds it, or None.  Spans of one thread nest,
    so a stack of the open spans, swept in start order, finds it."""
    spans = sorted(spans, key=lambda s: (s[1], -s[2]))
    out, stack, i = [], [], 0
    for p in points:
        while i < len(spans) and spans[i][1] <= p:
            while stack and stack[-1][2] <= spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] <= p:
            stack.pop()
        out.append(stack[-1][0] if stack else None)
    return out


def idle_gaps(t: tr.Reduced, top: int = 10) -> list:
    """Idle device time in the window, summed by the innermost of
    GAP_SPANS it fell in, longest first: [[span, seconds], ...]."""
    lo, hi = t.window
    edges = np.concatenate([[lo], t.busy.ravel(), [hi]]).reshape(-1, 2)
    edges = np.clip(edges, lo, hi)
    edges = edges[edges[:, 1] > edges[:, 0]]
    names = _innermost([s for s in t.spans if s[0] in GAP_SPANS],
                       edges.mean(axis=1))
    total: dict = {}
    for (a, b), name in zip(edges, names):
        name = name or "(no span)"
        total[name] = total.get(name, 0.0) + (b - a) * 1e-9
    return sorted(([k, v] for k, v in total.items()),
                  key=lambda kv: -kv[1])[:top]
