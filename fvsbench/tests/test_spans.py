"""Readings of the program's spans: idle gaps named by the innermost span,
and the build and compile readers on a run that carries a recorder."""
import gzip
import os
from types import SimpleNamespace as NS

import pytest

from fvsbench import harness, spans, trace
from repro import obs

HERE = os.path.dirname(os.path.abspath(__file__))


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def reduced(ops, span_list):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev(n, a, b - a) for n, a, b in ops]),
        NS(name="XLA Modules", events=[])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[ev(n, a, b - a) for n, a, b in span_list])])
    return trace.reduce_planes([host, dev])


# window [0, 1000); one dispatch [100, 600) whose executor.search holds the
# program's plan, execute and anytime; then the wait for the next arrival
DISPATCH = [("driver.window", 0, 1000), ("driver.dispatch", 100, 600),
            ("driver.form_batch", 100, 150), ("executor.search", 150, 550),
            ("executor.plan", 150, 170), ("executor.execute", 170, 540),
            ("executor.anytime", 405, 530), ("driver.collect", 550, 600),
            ("driver.wait", 600, 1000)]
OPS = [("fusion.1", 120, 130), ("fusion.2", 175, 380),
       ("fusion.3", 420, 500), ("fusion.4", 532, 535),
       ("fusion.5", 548, 560)]


def test_gaps_are_named_by_the_innermost_span():
    t = reduced(OPS, DISPATCH)
    gaps = dict(spans.idle_gaps(t, top=100))
    # [130,175) mid 152.5 plan; [380,420) mid 400 execute (anytime starts
    # at 405); [500,532) anytime; [535,548) mid 541.5 lies after execute
    # ended, still inside executor.search
    assert gaps == pytest.approx({"(no span)": 120e-9,
                                  "executor.plan": 45e-9,
                                  "executor.execute": 40e-9,
                                  "executor.anytime": 32e-9,
                                  "executor.search": 13e-9,
                                  "driver.wait": 440e-9})
    assert sum(gaps.values()) == pytest.approx(
        (t.window_ns - t.busy_ns) * 1e-9, rel=1e-6)


def test_without_program_spans_the_breakdown_is_the_benchmarks():
    parent_only = [s for s in DISPATCH if not s[0].startswith(
        ("executor.plan", "executor.execute", "executor.anytime"))]
    t = reduced(OPS, parent_only)
    assert spans.idle_gaps(t, top=100) == trace.idle_gaps(t, top=100)
    assert dict(spans.idle_gaps(t))["executor.search"] == pytest.approx(
        130e-9)


def recorded(path):
    from jax.profiler import ProfileData
    with gzip.open(path) as f:
        return trace.reduce_planes(
            ProfileData.from_serialized_xspace(f.read()).planes)


def test_recorded_trace_without_program_spans_reads_as_before():
    t = recorded(os.path.join(HERE, "data", "navix_v5e.xplane.pb.gz"))
    assert not any(s[0] in spans.GAP_SPANS[len(trace.GAP_SPANS):]
                   for s in t.spans)
    assert spans.idle_gaps(t, top=100) == trace.idle_gaps(t, top=100)


# The profiler aligns the device's clock with the host's to about a
# millisecond: in this trace each batch's `_gather` shows up to 0.5 ms
# before the `driver.form_batch` span that launched it.
CLOCK_SKEW_NS = 2e6


def test_recorded_trace_puts_program_spans_on_the_device_clock():
    """A trace recorded on one v5e chip with the program's spans (a short
    window of the rate cell at rehearsal size): each dispatch's search
    program starts inside its `executor.execute` span and ends inside its
    `executor.anytime`, the host sync that waits for it, to within the
    clocks' alignment."""
    t = recorded(os.path.join(HERE, "data", "navix_spans_v5e.xplane.pb.gz"))
    lo, hi = t.window
    execute = trace.spans_named(t, "executor.execute")
    anytime = trace.spans_named(t, "executor.anytime")
    runs = sorted((a, b) for n, a, b in t.modules
                  if n == "search_batch" and lo <= a < hi)
    assert len(execute) == len(anytime) == len(runs) == len(
        trace.spans_named(t, "driver.dispatch")) > 0
    for (e0, e1), (a0, a1), (m0, m1) in zip(execute, anytime, runs):
        assert e0 <= a0 < a1 <= e1
        assert e0 - CLOCK_SKEW_NS <= m0 < m1 <= a1 + CLOCK_SKEW_NS
        assert a0 - CLOCK_SKEW_NS <= m1
    # the program's spans split the benchmark's `executor.search` share
    # and leave every other share as it was
    gaps = dict(spans.idle_gaps(t, top=100))
    before = dict(trace.idle_gaps(t, top=100))
    inner = ("executor.search",) + spans.GAP_SPANS[len(trace.GAP_SPANS):]
    assert "executor.anytime" in gaps
    assert sum(gaps.get(n, 0.0) for n in inner) == pytest.approx(
        before.pop("executor.search"), rel=1e-9)
    assert before == {k: v for k, v in gaps.items() if k not in inner}
    assert sum(gaps.values()) == pytest.approx(
        (t.window_ns - t.busy_ns) * 1e-9, rel=1e-6)


# -- readers of a run's recorder ------------------------------------------

def span(name, parent, a, b, **args):
    return obs.Span(name, args, parent, a, b)


def fake_run(with_recorder=True):
    rec = obs.Recorder()
    rec.spans = [span("hnsw.build", None, 0, 1000),
                 span("hnsw.fetch", 0, 0, 50),
                 span("hnsw.knn", 0, 50, 400, level=0, members=9),
                 span("child", 2, 100, 150),
                 span("hnsw.prune", 0, 400, 600, level=0, members=9),
                 span("hnsw.link", 0, 600, 800, level=0, members=9),
                 span("hnsw.knn", 0, 800, 850, level=1, members=2),
                 span("hnsw.prune", 0, 850, 900, level=1, members=2),
                 span("hnsw.link", 0, 900, 950, level=1, members=2),
                 span("hnsw.upload", 0, 950, 1000)]
    rec.compiles = [obs.CompileEvent(obs.TRACE, 100e-9, None, 1100),
                    obs.CompileEvent(obs.COMPILE, 300e-9, None, 1500),
                    obs.CompileEvent(obs.CACHE_LOAD, 100e-9, None, 1450),
                    obs.CompileEvent(obs.COMPILE, 20e-9, 4, 3000),
                    obs.CompileEvent(obs.COMPILE, 50e-9, None, 5000)]
    run = NS(dispatches=[NS(start=2000e-9, done=2500e-9),
                         NS(start=4000e-9, done=4500e-9)])
    if with_recorder:
        run.recorder = rec
    return run


READERS = {"hnsw_knn_s": 350e-9, "hnsw_prune_s": 250e-9,
           "hnsw_link_s": 250e-9, "hnsw_transfer_s": 100e-9,
           "warmup_compile_s": 400e-9}


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_of_the_recorder(name):
    mod = harness.load_module(harness.metric_file(name))
    assert mod.read(fake_run(), None) == pytest.approx(READERS[name])
    assert mod.read(fake_run(with_recorder=False), None) is None


def test_readers_without_the_spans_read_nothing():
    run = fake_run()
    run.recorder.spans = []
    assert spans.span_seconds(run, ("hnsw.knn",)) is None
    assert spans.setup_compile_seconds(run) == pytest.approx(400e-9)


def test_compiles_in_the_window():
    # the compile at 5000 ns comes after the last answer (the check's)
    n, s = spans.window_compiles(fake_run())
    assert (n, s) == (1, pytest.approx(20e-9))
    assert spans.window_compiles(fake_run(with_recorder=False)) is None

