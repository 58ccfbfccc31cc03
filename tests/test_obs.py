"""repro.obs: spans, their parents and self times, compile accounting,
and the spans of the HNSW build and the executor's host path."""
import contextvars
import os
import sys
import threading
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import SearchParams, VectorStore, make_executor
from repro.core import hnsw
from repro.core.hnsw import build_graph


@pytest.fixture
def clock(monkeypatch):
    """A fake `time.monotonic_ns` for obs that reads `clock.now`."""
    c = NS(now=0)
    monkeypatch.setattr(obs, "time", NS(monotonic_ns=lambda: c.now))
    return c


def test_nested_spans_give_parents_and_self_times(clock):
    with obs.record() as rec:
        with obs.span("a", k=1):
            clock.now = 100
            with obs.span("b"):
                clock.now = 300
            with obs.span("c"):
                with obs.span("b"):
                    clock.now = 350
                clock.now = 400
            clock.now = 1000
    names = [(s.name, s.parent, s.start_ns, s.end_ns) for s in rec.spans]
    assert names == [("a", None, 0, 1000), ("b", 0, 100, 300),
                     ("c", 0, 300, 400), ("b", 2, 300, 350)]
    assert rec.spans[0].args == {"k": 1}
    assert rec.total_seconds("a") == pytest.approx(1000e-9)
    assert rec.self_seconds("a") == pytest.approx(700e-9)
    assert rec.total_seconds("b") == pytest.approx(250e-9)
    assert rec.self_seconds("c") == pytest.approx(50e-9)
    assert rec.total_seconds("missing") == 0.0


def test_no_recorder_keeps_nothing():
    with obs.record() as rec:
        pass
    s = obs.span("x", level=0)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass
    assert rec.spans == [] and obs._RECORDER.get() is None


def test_span_closes_when_its_body_raises(clock):
    with obs.record() as rec:
        with pytest.raises(KeyError):
            with obs.span("outer"):
                clock.now = 5
                with obs.span("inner"):
                    raise KeyError("x")
        with obs.span("after"):
            pass
    assert [(s.name, s.parent, s.end_ns) for s in rec.spans] == [
        ("outer", None, 5), ("inner", 0, 5), ("after", None, 5)]


def test_record_restores_the_previous_recorder():
    with obs.record() as outer:
        with obs.record() as inner:
            with obs.span("x"):
                pass
        with obs.span("y"):
            pass
    assert [s.name for s in inner.spans] == ["x"]
    assert [s.name for s in outer.spans] == ["y"]


def test_fresh_compile_goes_under_the_innermost_span():
    f = jax.jit(lambda x: jnp.cos(x) * 3.0 + 0.25)
    x = jnp.arange(13, dtype=jnp.float32)
    with obs.record() as rec:
        with obs.span("outer"):
            with obs.span("inner"):
                f(x).block_until_ready()
        n_first = len(rec.compiles)
        with obs.span("again"):
            f(x).block_until_ready()
    stages = {e.stage for e in rec.compiles}
    assert obs.TRACE in stages and obs.COMPILE in stages
    assert stages <= set(obs.COMPILE_STAGES)
    inner = [i for i, s in enumerate(rec.spans) if s.name == "inner"]
    assert {e.span for e in rec.compiles} == set(inner)
    assert len(rec.compiles) == n_first          # the second call: none
    start, end = rec.spans[inner[0]].start_ns, rec.spans[inner[0]].end_ns
    assert all(start <= e.end_ns <= end for e in rec.compiles)
    assert 0.0 < rec.compile_seconds() <= (end - start) * 1e-9


def test_compile_seconds_count_nested_stages_once():
    rec = obs.Recorder()
    # a cache load inside the backend stage, and a trace inside a trace
    rec.compiles = [obs.CompileEvent(obs.CACHE_LOAD, 2e-9, None, 10),
                    obs.CompileEvent(obs.COMPILE, 5e-9, None, 11),
                    obs.CompileEvent(obs.TRACE, 1e-9, None, 30),
                    obs.CompileEvent(obs.TRACE, 4e-9, None, 31)]
    assert rec.compile_seconds() == pytest.approx(9e-9)
    assert rec.compile_seconds(lo_ns=20) == pytest.approx(4e-9)
    assert len(rec.compile_events(hi_ns=20)) == 2


@pytest.fixture(scope="module")
def tiny_store():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((1500, 16)).astype(np.float32)
    return VectorStore.build(x, metric="l2")


def test_build_graph_blocked_spans_every_level(tiny_store, monkeypatch):
    monkeypatch.setattr(hnsw, "_EXACT_THRESHOLD", 400)
    with obs.record() as rec:
        g = build_graph(tiny_store, m=8, ef_construction=24, seed=1)
    build = [i for i, s in enumerate(rec.spans) if s.name == "hnsw.build"]
    assert len(build) == 1
    children = [s for s in rec.spans if s.parent == build[0]]
    names = [s.name for s in children]
    assert names[0] == "hnsw.fetch" and names[-1] == "hnsw.upload"
    node_level = np.asarray(g.node_level)
    linked = [lvl for lvl in range(g.num_levels)
              if (node_level >= lvl).sum() > 1]     # a lone node links none
    for stage in ("hnsw.knn", "hnsw.prune", "hnsw.link"):
        got = [s.args for s in children if s.name == stage]
        assert [a["level"] for a in got] == linked
    members = [s.args["members"] for s in children if s.name == "hnsw.knn"]
    assert members[0] == tiny_store.n > 400      # the routed path
    assert members == sorted(members, reverse=True)
    for s in children:
        if s.name == "hnsw.knn":
            assert s.args["on_device"] is True and s.args["tiles"] > 0
            assert 0.0 <= s.args["pad_share"] < 1.0
        if s.name == "hnsw.prune":
            kept, fill = s.args["kept_share"], s.args["fill_share"]
            assert s.args["on_device"] is True
            assert 0.0 < kept <= 1.0 and 0.0 <= fill <= 1.0
            assert kept + fill <= 1.0 + 1e-9
        if s.name == "hnsw.link":
            placed = s.args["reverse_placed"]
            dropped = s.args["reverse_dropped"]
            assert placed >= 0 and dropped >= 0
            if s.args["level"] == 0:              # the device fill
                assert placed + dropped > 0
    covered = sum(s.end_ns - s.start_ns for s in children) * 1e-9
    assert covered >= 0.95 * rec.total_seconds("hnsw.build")
    assert rec.self_seconds("hnsw.knn") == pytest.approx(
        rec.total_seconds("hnsw.knn"))
    # more rows in the same row class, another seed: the kNN, the prune
    # and the link compile nothing that the first build did not
    rng = np.random.default_rng(8)
    more = VectorStore.build(np.concatenate([
        np.asarray(tiny_store.vectors),
        rng.standard_normal((37, 16)).astype(np.float32)]), metric="l2")
    with obs.record() as again:
        build_graph(more, m=8, ef_construction=24, seed=2)
    for stage in ("hnsw.knn", "hnsw.prune", "hnsw.link"):
        at = {i for i, s in enumerate(again.spans) if s.name == stage}
        assert len(at) >= 2
        assert not [e for e in again.compiles
                    if e.stage == obs.COMPILE and e.span in at], stage


def test_graph_search_records_plan_execute_and_anytime(tiny_store):
    g = build_graph(tiny_store, m=8, ef_construction=24, seed=1)
    ex = make_executor("navix", tiny_store, graph=g)
    q = jnp.asarray(np.asarray(tiny_store.vectors)[:2] + 0.01)
    bm = jnp.full((2, (tiny_store.n + 31) // 32), 0xFFFFFFFF, jnp.uint32)
    params = SearchParams(k=5, ef_search=32)
    ex.search(q, bm, params)                     # compile outside
    with obs.record() as rec:
        res = ex.search(q, bm, params)
    names = [s.name for s in rec.spans]
    assert sorted(names) == ["executor.anytime", "executor.execute",
                             "executor.plan"]
    parent = {s.name: s.parent for s in rec.spans}
    assert parent["executor.plan"] is None
    assert rec.spans[parent["executor.anytime"]].name == "executor.execute"
    assert rec.compiles == []
    assert res.ids.shape == (2, 5)


def test_spans_on_many_threads_keep_their_own_parents():
    n_threads, depth = (os.cpu_count() or 4) + 4, 50

    def work(t):
        for i in range(depth):
            with obs.span("outer", t=t):
                with obs.span("inner", t=t, i=i):
                    pass

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with obs.record() as rec:
            threads = [threading.Thread(
                target=contextvars.copy_context().run, args=(work, t))
                for t in range(n_threads)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=30)
    finally:
        sys.setswitchinterval(switch)
    assert not any(th.is_alive() for th in threads)
    assert len(rec.spans) == 2 * n_threads * depth
    for s in rec.spans:
        assert s.end_ns is not None
        if s.name == "inner":
            p = rec.spans[s.parent]
            assert p.name == "outer" and p.args["t"] == s.args["t"]
        else:
            assert s.parent is None
