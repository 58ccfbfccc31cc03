"""`correct` comes out false where it should: for the control (the
reference in a lower precision in the program's place) and for the
timed path broken underneath, in every cell, at rehearsal size on the
CPU (the harness's look for a chip is skipped)."""
import dataclasses
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from fvsbench import control, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = [w["name"] for w in harness.load_json(
    os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]


def make(name, with_program=True, seed=101):
    cell = harness.load_cell(name, os.path.join(ROOT, "BENCHMARK.json"),
                             rehearse=True)
    return harness.Harness(cell, seed, time.monotonic(),
                           with_program=with_program)


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_reference_passes(name):
    h = make(name, with_program=False)
    assert all(c["ok"] for c in control.control_numbers(h, "highest")
               .values())
    high = control.control_numbers(h, "high")
    assert not all(c["ok"] for c in high.values())


def altered(search):
    """An answer altered where it is produced: each first id moved to
    the next row, its distance kept."""
    def f(q, bm, params):
        res = search(q, bm, params)
        ids = jnp.where(res.ids[:, :1] >= 0, res.ids[:, :1] + 1, -1)
        return dataclasses.replace(res, ids=res.ids.at[:, :1].set(ids))
    return f


def half_left_out(search):
    """Half of the batch left out: the second half of the lanes answer
    nothing."""
    def f(q, bm, params):
        res = search(q, bm, params)
        drop = jnp.arange(res.ids.shape[0])[:, None] >= res.ids.shape[0] // 2
        return dataclasses.replace(
            res, ids=jnp.where(drop, -1, res.ids),
            dists=jnp.where(drop, jnp.inf, res.dists))
    return f


def unchanged(search):
    """A step that returns its state unchanged: every batch gets the first
    answer this batch shape ever got."""
    first = {}

    def f(q, bm, params):
        res = search(q, bm, params)
        return first.setdefault(q.shape[0], res)
    return f


@pytest.fixture(scope="module", params=CELLS)
def built(request):
    return make(request.param)


def window_checks(h):
    h.window(0.5)
    return h.judge(h.collect())


def test_sound_run_is_correct(built):
    checks = window_checks(built)
    assert all(c["ok"] for c in checks.values()), checks


@pytest.mark.parametrize("fault", [altered, half_left_out, unchanged])
def test_fault_is_not_correct(built, fault):
    search = built.executor.search
    built.executor.search = fault(search)
    try:
        checks = window_checks(built)
    finally:
        built.executor.search = search
    assert not all(c["ok"] for c in checks.values()), checks
