"""One run of one cell: set-up, the measured window, the check.

Everything that belongs to one configuration, traffic mix, index kind or
metric is found by name under `fvsbench/`:

  configs/<config>.json    the deployment: data, index, method, search
  indexes/<kind>.py        build(store, spec, seed), describe(built)
  traffic/<traffic>.json   the mix: loop, max_batch, rate, pool, predicates
  cells/<cell>.json        the limits `correct` is judged by
  metrics/<metric>.py      read(run, trace) -> number or None; a metric
                           split by the end-to-end metric it moves
                           (`<quantity>.<part>`) falls back to
                           metrics/<quantity>.py

The window drives `make_executor(method, store, **index).search(queries,
bitmaps, SearchParams(**search))`, the one-shot batched search that every
executor, the planner and the serving layer share.  One host thread
replays a schedule fixed by the seed.  Each dispatch takes the requests
due by now (at most `max_batch`), pads the batch to the next power of two
by repeating its last request (padding lanes are discarded and not
counted), calls `search`, waits for the answer and records each request's
completion.  Latency runs from each request's scheduled time.
"""
from __future__ import annotations

import bisect
import dataclasses
import gc
import importlib.util
import json
import os
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fvsbench import check, data, reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DRAIN_S = 60.0      # an open-loop backlog may take this long past the window
# A traced run profiles the window's first TRACE_S seconds (to the end of
# the dispatch that crosses it): a trace of a whole window of short
# dispatches takes minutes to write and read.
TRACE_S = 10.0


def configure_jax() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program kept, nothing evicted; returns the path."""
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file by path (metric names carry dots, so not by name)."""
    spec = importlib.util.spec_from_file_location(
        "fvsbench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_file(name: str) -> str:
    """The reader of metric `name`: its own file, else that of the
    quantity it splits (`host_ms_per_batch.rate` ->
    `host_ms_per_batch.py`)."""
    own = os.path.join(HERE, "metrics", name + ".py")
    if os.path.exists(own) or "." not in name:
        return own
    return os.path.join(HERE, "metrics", name.split(".", 1)[0] + ".py")


def _for_cell(entries: list, cell: str) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    chips: int


def load_cell(name: str, bench_path: str, rehearse: bool = False) -> Cell:
    """The cell's entry in `bench_path` with its config, traffic and
    limits files.  With `rehearse`, a file of the same name under
    `fvsbench/rehearsal/` takes the place of each (tiny sizes for the
    CPU)."""
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in {bench_path}; have "
                         f"{sorted(cells)}")
    w = cells[name]

    def find(kind: str, stem: str) -> str:
        path = os.path.join(HERE, kind, stem + ".json")
        alt = os.path.join(HERE, "rehearsal", kind, stem + ".json")
        return alt if rehearse and os.path.exists(alt) else path

    return Cell(name=name,
                config=load_json(find("configs", w["config"])),
                traffic=load_json(find("traffic", w["traffic"])),
                limits=load_json(find("cells", name)),
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name),
                chips=int(w["chips"]))


def sub_seed(seed: int, stream: int) -> int:
    """A 32-bit seed for host-side generators that take no larger one."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


def pool_kinds(traffic: dict) -> list:
    """(selectivity, correlation, count) per predicate kind, the counts
    summing to the pool size in proportion to the weights (largest
    remainder)."""
    preds = traffic["predicates"]
    pool = int(traffic["pool"])
    w = np.array([p["weight"] for p in preds], np.float64)
    share = pool * w / w.sum()
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts), kind="stable")[:pool - counts.sum()]:
        counts[i] += 1
    return [(float(p["selectivity"]), p["correlation"], int(c))
            for p, c in zip(preds, counts) if c > 0]


def request_order(seed: int, pool: int, count: int) -> np.ndarray:
    """Pool indices of `count` requests: the pool in a seeded order, again
    and again, so every seed sends the same mix."""
    rng = np.random.default_rng(sub_seed(seed, 2))
    reps = -(-count // pool)
    return np.concatenate([rng.permutation(pool) for _ in range(reps)])[
        :count].astype(np.int32)


def arrivals(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Open-loop arrival times in (0, seconds]: round(rate * seconds)
    exponential gaps drawn once from a fixed stream and scaled to fill
    the window, then put in an order drawn from the seed.  Every seed
    offers the same gaps; only their order differs."""
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(0x5EED).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng(sub_seed(seed, 3))
    return np.cumsum(rng.permutation(gaps))


def next_pow2(b: int) -> int:
    return 1 << (b - 1).bit_length()


@jax.jit
def _gather(pool_q, pool_bm, idx):
    return pool_q[idx], pool_bm[idx]


@dataclasses.dataclass
class Dispatch:
    pairs: np.ndarray            # pool index of each real request
    start: float                 # host clock, dispatch start
    done: float                  # host clock, answer back
    result: Any                  # SearchResult (device arrays)
    traced: bool = False         # inside the profiled part of the window


@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    peaks: Optional[dict]        # the chip's row of peaks.json; None off it
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    completed: int = 0
    latencies_ms: Optional[np.ndarray] = None
    dispatches: list = dataclasses.field(default_factory=list)
    phases: dict = dataclasses.field(default_factory=dict)
    shape: dict = dataclasses.field(default_factory=dict)
    counters: list = dataclasses.field(default_factory=list)
    checks: dict = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int = 0
    truncated: int = 0           # answers the program flags truncated


class Harness:
    """Builds a cell once; `window` may then run as often as wanted (the
    knee sweep reuses one set-up).  `with_program=False` makes only the
    data and the predicate pool (the control readings need no index)."""

    def __init__(self, cell: Cell, seed: int, t_start: float,
                 peaks: Optional[dict] = None, with_program: bool = True):
        from repro.core import SearchParams, make_executor
        from repro.core.types import VectorStore

        self.run = Run(cell, seed, peaks)
        self._span = None            # the open `driver.window` span, traced
        cfg, tr = cell.config, cell.traffic
        d = cfg["data"]
        phase = time.monotonic()

        def lap(name: str) -> None:
            nonlocal phase
            now = time.monotonic()
            self.run.phases[name] = now - phase
            phase = now

        self.kinds = pool_kinds(tr)
        self.pool = sum(c for _, _, c in self.kinds)
        vecs, norms, queries = jax.block_until_ready(data.make_vectors(
            data.key(seed, 0), d["rows"], d["dim"], d["clusters"],
            float(d["cluster_spread"]), d["metric"], self.pool))
        self.vectors, self.metric = vecs, d["metric"]
        store = VectorStore(vectors=vecs, norms_sq=norms, metric=d["metric"])
        lap("data")
        self.pool_q = queries
        self.pool_bm = jax.block_until_ready(data.make_bitmaps(
            data.key(seed, 1), vecs, norms, queries, self.kinds,
            d["metric"]))
        lap("pool")
        if not with_program:
            return
        kind = cfg["index"]["kind"]
        mod = load_module(os.path.join(HERE, "indexes", kind + ".py"))
        self.built = jax.block_until_ready(
            mod.build(store, cfg["index"], sub_seed(seed, 1)))
        self.run.shape = mod.describe(self.built)
        self.executor = make_executor(cfg["method"], store, **self.built)
        self.params = SearchParams(**cfg["search"])
        lap("index")
        self.max_batch = int(tr["max_batch"])
        shapes = ([self.max_batch] if tr["loop"] == "closed" else
                  [1 << i for i in range(self.max_batch.bit_length())
                   if 1 << i <= self.max_batch])
        for p in shapes:                 # compile (or load) every shape
            self._search(np.arange(p, dtype=np.int32) % self.pool)
            lap(f"warmup{p}")
        self.run.setup_s = time.monotonic() - t_start

    def _search(self, pairs: np.ndarray):
        b = len(pairs)
        with jax.profiler.TraceAnnotation("driver.form_batch"):
            idx = np.concatenate([pairs, np.full(next_pow2(b) - b,
                                                 pairs[-1], np.int32)])
            q, bm = _gather(self.pool_q, self.pool_bm, jnp.asarray(idx))
        with jax.profiler.TraceAnnotation("executor.search"):
            res = self.executor.search(q, bm, self.params)
            jax.block_until_ready((res.ids, res.dists))
        return res

    def _dispatch(self, pairs: np.ndarray) -> Dispatch:
        with jax.profiler.TraceAnnotation("driver.dispatch"):
            t = time.monotonic()
            res = self._search(pairs)
            with jax.profiler.TraceAnnotation("driver.collect"):
                d = Dispatch(pairs, t, time.monotonic(), res,
                             self._span is not None)
        if self._span is not None and d.done >= self._trace_end:
            self._stop_trace()
        return d

    def _stop_trace(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()

    def window(self, seconds: float, trace_dir: Optional[str] = None,
               rate: Optional[float] = None) -> Run:
        """The measured window; with `trace_dir`, the profiler records its
        first TRACE_S seconds under the span `driver.window`.  `rate`
        overrides the traffic file's (the knee sweep)."""
        run, tr = self.run, self.run.cell.traffic
        run.dispatches = []
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
            self._span = jax.profiler.TraceAnnotation("driver.window")
            self._span.__enter__()
            self._trace_end = time.monotonic() + TRACE_S
        try:
            if tr["loop"] == "closed":
                self._closed(seconds)
            else:
                self._open(seconds, float(rate or tr["rate"]))
        finally:
            self._stop_trace()
        return run

    def _closed(self, seconds: float) -> None:
        """Always `max_batch` requests queued: a new batch as soon as the
        last one is back.  The window ends with the first answer after
        `seconds`, so the rate counts all work and all time."""
        run, mb = self.run, self.max_batch
        order = request_order(run.seed, self.pool,
                              mb * max(1, int(seconds * 20000 // mb)))
        t0 = time.monotonic()
        i = 0
        while time.monotonic() - t0 < seconds:
            if i + mb > len(order):
                order = np.concatenate([order, order])
            run.dispatches.append(self._dispatch(order[i:i + mb]))
            i += mb
        run.window_s = run.dispatches[-1].done - t0
        run.attempted = run.completed = i
        run.latencies_ms = None

    def _open(self, seconds: float, rate: float) -> None:
        """Poisson arrivals at `rate` through (0, seconds]; the backlog is
        served to the end (at most DRAIN_S past the window) and every
        request's latency runs from its scheduled time."""
        run, mb = self.run, self.max_batch
        arr = arrivals(run.seed, rate, seconds)
        order = request_order(run.seed, self.pool, len(arr))
        done = np.full(len(arr), np.nan)
        t0 = time.monotonic()
        i = 0
        while i < len(arr):
            now = time.monotonic() - t0
            if now > seconds + DRAIN_S:
                break
            due = bisect.bisect_right(arr, now, lo=i) - i
            if due == 0:
                with jax.profiler.TraceAnnotation("driver.wait"):
                    time.sleep(max(0.0, arr[i] - now))
                continue
            b = min(due, mb)
            d = self._dispatch(order[i:i + b])
            run.dispatches.append(d)
            done[i:i + b] = d.done - t0
            i += b
        ok = ~np.isnan(done)
        run.attempted, run.completed = len(arr), int(ok.sum())
        run.window_s = max(seconds, float(np.nanmax(done, initial=0.0)))
        run.latencies_ms = (done[ok] - arr[ok]) * 1e3

    # -- after the window ---------------------------------------------------

    def collect(self) -> dict:
        """The window's answers and counters, on the host: pool index,
        ids, dists of every real request, and the SearchStats counters of
        every dispatch's real lanes."""
        run = self.run
        stats = [None if d.result.stats is None else
                 {f.name: getattr(d.result.stats, f.name)
                  for f in dataclasses.fields(d.result.stats)}
                 for d in run.dispatches]
        got = jax.device_get([(d.result.ids, d.result.dists, s)
                              for d, s in zip(run.dispatches, stats)])
        pairs, ids, dists = [], [], []
        run.counters = []
        run.truncated = sum(
            int(np.sum(d.result.anytime.truncated[:len(d.pairs)]))
            for d in run.dispatches if d.result.anytime is not None)
        for d, (i, dd, st) in zip(run.dispatches, got):
            b = len(d.pairs)
            pairs.append(d.pairs)
            ids.append(np.asarray(i)[:b])
            dists.append(np.asarray(dd)[:b])
            run.counters.append(None if st is None else
                                {k: np.asarray(v)[:b] for k, v in st.items()})
        return {"pairs": np.concatenate(pairs), "ids": np.concatenate(ids),
                "dists": np.concatenate(dists)}

    def free_program(self) -> None:
        """Drop the program's state (index, executor) before the
        reference runs, so the reference does not set the memory peak."""
        for d in self.run.dispatches:
            d.result = None
        self.executor = self.built = None
        gc.collect()

    def judge(self, answers: dict) -> dict:
        """`correct`'s numbers for the window's answers against the
        reference over the same store and bitmaps."""
        k = int(self.run.cell.config["search"].get("k", 10))
        uniq = np.unique(answers["pairs"])
        ref_d, ref_i = reference.filtered_topk(
            self.vectors, self.pool_q[jnp.asarray(uniq)],
            self.pool_bm[jnp.asarray(uniq)], k, self.metric)
        return check.judge(answers, uniq, ref_i, self.vectors,
                           np.asarray(self.pool_q), np.asarray(self.pool_bm),
                           self.metric, self.run.cell.limits)
