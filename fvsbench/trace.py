"""Reduction of a JAX profiler trace to the intervals the metrics read.

A traced run writes an `.xplane.pb`.  `reduce` reads it with
`jax.profiler.ProfileData` and keeps, on the trace's one clock:

  window    the benchmark's `driver.window` span
  busy      the union of the intervals in which an operation ran on the
            device (events of each TPU plane's "XLA Ops" line), clipped
            to the window and averaged over the chips used
  modules   (name, start, end) of every device program execution ("XLA
            Modules" line), name without the `jit_` prefix and `(id)`
  ops       device seconds per operation, named `<module>/<op>`
  spans     (name, start, end) of the benchmark's host spans (`driver.*`,
            `executor.search`)

The functions below it turn those into the numbers the per-layer
metrics report.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re

import numpy as np

SPAN_PREFIXES = ("driver.", "executor.")
# the innermost host span of a dispatch names the idle gap it holds
GAP_SPANS = ("driver.form_batch", "executor.search", "driver.collect",
             "driver.wait")


@dataclasses.dataclass
class Reduced:
    window: tuple                 # (start_ns, end_ns)
    busy: np.ndarray              # (m, 2) merged device-busy intervals, ns
    busy_ns: float                # busy time in the window, chip average
    modules: list                 # [(name, start_ns, end_ns)]
    ops: dict                     # "<module>/<op>" -> device seconds
    spans: list                   # [(name, start_ns, end_ns)]
    chips: int

    @property
    def window_ns(self) -> float:
        return float(self.window[1] - self.window[0])


_MODULE_NAME = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")


def module_name(raw: str) -> str:
    return _MODULE_NAME.match(raw).group(1)


def op_name(raw: str) -> str:
    """`%fusion.11 fusion u32[4000000,64]` from an HLO instruction's text
    (instruction, opcode, first result shape)."""
    if " = " not in raw:
        return raw
    instr, rest = raw.split(" = ", 1)
    op = _OPCODE.search(" " + rest)
    shape = _SHAPE.search(rest)
    return " ".join(p for p in (instr, op and op.group(1),
                                shape and shape.group(0)) if p)


def merge(intervals: np.ndarray) -> np.ndarray:
    """Union of (start, end) intervals, sorted and disjoint."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > ends[:-1]])
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    stops = ends[np.concatenate([idx[1:] - 1, [len(iv) - 1]])]
    return np.stack([starts, stops], axis=1)


def overlap(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of [lo, hi) covered by merged intervals."""
    if len(merged) == 0 or hi <= lo:
        return 0.0
    a = np.clip(merged[:, 0], lo, hi)
    b = np.clip(merged[:, 1], lo, hi)
    return float(np.sum(b - a))


def reduce_planes(planes) -> Reduced:
    """Reduce ProfileData-like planes (objects with .name, .lines; lines
    with .name, .events; events with .name, .start_ns, .duration_ns)."""
    spans, window = [], None
    busy_sets, modules, op_events = [], [], []
    short: dict = {}                     # op names repeat: parse each once
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            iv = []
            for e in lines["XLA Ops"].events:
                raw = e.name
                name = short.get(raw)
                if name is None:
                    name = short[raw] = op_name(raw)
                start = e.start_ns
                iv.append((start, start + e.duration_ns, name))
            busy_sets.append(np.array([(a, b) for a, b, _ in iv],
                                      np.float64).reshape(-1, 2))
            mods = [(module_name(e.name), e.start_ns,
                     e.start_ns + e.duration_ns)
                    for e in lines["XLA Modules"].events] \
                if "XLA Modules" in lines else []
            modules.extend(mods)
            op_events.append((iv, sorted(mods, key=lambda m: m[1])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        s = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        spans.append(s)
                        if e.name == "driver.window":
                            window = s[1:]
    if window is None:
        raise ValueError("trace has no driver.window span")
    chips = max(1, len(busy_sets))
    merged_all = [merge(b) for b in busy_sets]
    busy_ns = sum(overlap(m, *window) for m in merged_all) / chips
    ops: dict = {}
    for iv, mods in op_events:
        starts = [m[1] for m in mods]
        for a, b, name in iv:
            if not window[0] <= a < window[1]:
                continue
            j = bisect.bisect_right(starts, a) - 1
            mod = mods[j][0] if j >= 0 and a < mods[j][2] else "?"
            key = f"{mod}/{name}"
            ops[key] = ops.get(key, 0.0) + (b - a) * 1e-9 / chips
    merged = merge(np.concatenate(busy_sets)) if busy_sets \
        else np.zeros((0, 2))
    return Reduced(window=window, busy=merged, busy_ns=busy_ns,
                   modules=modules, ops=ops, spans=spans, chips=chips)


def reduce(trace_dir: str) -> Reduced:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_planes(ProfileData.from_file(paths[-1]).planes)


# -- numbers the metrics read -----------------------------------------------

def idle_share(t: Reduced) -> float:
    return 1.0 - t.busy_ns / t.window_ns


def module_seconds(t: Reduced, names: tuple) -> float:
    """Device seconds of the programs named `names` in the window, chip
    average."""
    lo, hi = t.window
    return sum(min(b, hi) - max(a, lo) for n, a, b in t.modules
               if n in names and b > lo and a < hi) * 1e-9 / t.chips


def spans_named(t: Reduced, name: str) -> list:
    lo, hi = t.window
    return [(a, b) for n, a, b in t.spans if n == name and lo <= a < hi]


def host_ms_per_dispatch(t: Reduced) -> float | None:
    """Mean over dispatches of the dispatch span's length less the
    device-busy time inside it, in ms."""
    ds = spans_named(t, "driver.dispatch")
    if not ds:
        return None
    return float(np.mean([(b - a) - overlap(t.busy, a, b) for a, b in ds])
                 ) * 1e-6


def idle_gaps(t: Reduced, top: int = 10) -> list:
    """Idle device time in the window, summed by the innermost host span
    it fell in, longest first: [[span, seconds], ...]."""
    lo, hi = t.window
    edges = np.concatenate([[lo], t.busy.ravel(), [hi]]).reshape(-1, 2)
    inner = [s for s in t.spans if s[0] in GAP_SPANS]
    inner.sort(key=lambda s: s[1])
    starts = [s[1] for s in inner]
    total: dict = {}
    for a, b in edges:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        mid = (a + b) / 2
        j = bisect.bisect_right(starts, mid) - 1
        name = inner[j][0] if j >= 0 and mid < inner[j][2] else "(no span)"
        total[name] = total.get(name, 0.0) + (b - a) * 1e-9
    return sorted(([k, v] for k, v in total.items()),
                  key=lambda kv: -kv[1])[:top]


def top_ops(t: Reduced, top: int = 10) -> list:
    return sorted(([k, v] for k, v in t.ops.items()),
                  key=lambda kv: -kv[1])[:top]
