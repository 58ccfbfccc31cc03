"""The trace reduction on a synthetic timeline and on a trace recorded
on a v5e chip."""
import glob
import gzip
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from fvsbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def planes(device_ops, modules, spans):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev(*e) for e in device_ops]),
        NS(name="XLA Modules", events=[ev(*e) for e in modules])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[ev(*e) for e in spans])])
    return [NS(name="/host:metadata", lines=[]), host, dev]


# window [0, 1000); two dispatches: [100, 500) and [600, 950)
SPANS = [("driver.window", 0, 1000),
         ("driver.dispatch", 100, 400), ("driver.form_batch", 100, 50),
         ("executor.search", 150, 300), ("driver.collect", 450, 50),
         ("driver.dispatch", 600, 350), ("driver.form_batch", 600, 50),
         ("executor.search", 650, 250), ("driver.collect", 900, 50),
         ("python_noise", 0, 1000)]
# overlapping ops: busy = [200, 400) u [700, 850) = 350 ns, plus an op
# outside the window that must not count
OPS = [("fusion.1", 200, 150), ("fusion.2", 300, 100), ("fusion.1", 700, 150),
       ("fusion.9", 1200, 100)]
MODULES = [("jit_search_batch(7)", 200, 200), ("jit_search_batch(7)", 700, 150),
           ("jit__gather(3)", 1200, 100)]


def reduced():
    return trace.reduce_planes(planes(OPS, MODULES, SPANS))


def test_busy_and_idle_share():
    t = reduced()
    assert t.window == (0.0, 1000.0)
    assert t.busy_ns == 350.0
    assert trace.idle_share(t) == pytest.approx(0.65)


def test_module_seconds_and_names():
    t = reduced()
    assert trace.module_name("jit_search_batch(12)") == "search_batch"
    assert trace.module_name("search_batch") == "search_batch"
    assert trace.module_seconds(t, ("search_batch",)) == \
        pytest.approx(350e-9)
    assert trace.module_seconds(t, ("gather",)) == 0.0


def test_host_ms_per_dispatch():
    # dispatch 1: 400 ns less 200 busy; dispatch 2: 350 less 150
    t = reduced()
    assert trace.host_ms_per_dispatch(t) == pytest.approx(200e-6)


def test_idle_gaps_named_by_host_span():
    gaps = dict(trace.idle_gaps(reduced()))
    # [0,200): 0-100 no span, 100-150 form_batch, 150-200 search; the
    # gap is named by its midpoint (100) -> form_batch.  [400,700): mid
    # 550 lies between dispatches.  [850,1000): mid 925 -> collect.
    assert gaps == pytest.approx({"driver.form_batch": 200e-9,
                                  "(no span)": 300e-9,
                                  "driver.collect": 150e-9})
    assert sum(gaps.values()) == pytest.approx(650e-9)


def test_top_ops_by_module():
    ops = dict(trace.top_ops(reduced()))
    assert ops == pytest.approx({"search_batch/fusion.1": 300e-9,
                                 "search_batch/fusion.2": 100e-9})


def test_merge():
    m = trace.merge(np.array([[5, 7], [0, 2], [1, 3], [6, 9]], float))
    assert m.tolist() == [[0, 3], [5, 9]]
    assert trace.overlap(m, 2, 6) == 2.0


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_planes(planes(OPS, MODULES, SPANS[1:]))


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "*.xplane.pb.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace(path):
    """A trace recorded on one v5e chip (a short window of a cell at
    rehearsal size): the reduction finds the device, the window, the
    engine's program and the dispatch spans."""
    from jax.profiler import ProfileData
    with gzip.open(path) as f:
        t = trace.reduce_planes(
            ProfileData.from_serialized_xspace(f.read()).planes)
    assert t.chips == 1
    assert 0.0 < t.busy_ns < t.window_ns
    names = {m[0] for m in t.modules}
    assert "search_batch" in names
    assert trace.host_ms_per_dispatch(t) > 0.0
    assert sum(v for _, v in trace.idle_gaps(t, top=100)) == pytest.approx(
        (t.window_ns - t.busy_ns) * 1e-9, rel=1e-6)
