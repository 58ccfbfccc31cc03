"""Shared arithmetic of the metric readers in `metrics/`.

Each reader there is `read(run, trace) -> number or None`: `run` is the
harness's record of the window (`harness.Run`), `trace` the reduced
profiler trace (`trace.Reduced`) of a `--trace 1` run, else None.  A
reader that finds nothing to read returns None and the metric is left
out of the result."""
from __future__ import annotations

import numpy as np

from fvsbench import roofline, trace as tr

GRAPH_PROGRAMS = ("search_batch",)


def traced(run) -> list:
    """The dispatches of the profiled part of the window."""
    return [d for d in run.dispatches if d.traced]


def device_ms_per_query(run, t, programs: tuple):
    n = sum(len(d.pairs) for d in traced(run))
    if t is None or n == 0:
        return None
    s = tr.module_seconds(t, programs)
    return s * 1e3 / n if s > 0 else None


def roofline_pct(run, t, programs: tuple, batch_fn):
    """Least time the chip could take for the traced batches over the
    device time of their search programs, in %."""
    if t is None or not run.peaks or not run.counters \
            or run.counters[0] is None:
        return None
    dev = tr.module_seconds(t, programs)
    if dev <= 0:
        return None
    dim = run.cell.config["data"]["dim"]
    least = sum(roofline.least_seconds(*batch_fn(d.pairs, c, run.shape, dim),
                                       run.peaks)[0]
                for d, c in zip(run.dispatches, run.counters) if d.traced)
    return 100.0 * least / dev


def counter_per_query(run, field: str):
    if not run.counters or run.counters[0] is None or run.completed == 0:
        return None
    return float(sum(np.sum(c[field]) for c in run.counters)
                 / run.completed)
