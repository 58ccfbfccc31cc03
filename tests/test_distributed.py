"""Distributed FVS + sharding rules. Multi-device cases run in a
subprocess with 8 forced host devices (XLA locks the device count at
first init, so the main test process stays single-device)."""
import json
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import jax
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.launch.sharding import fit_spec, param_specs
from jax.sharding import PartitionSpec as P

REPO = pathlib.Path(__file__).resolve().parents[1]


class FakeMesh:
    axis_names = ("data", "model")

    class devices:
        shape = (4, 8)


def test_fit_spec_divisibility():
    m = FakeMesh()
    assert fit_spec(P("model", None), (16, 4), m) == P("model", None)
    assert fit_spec(P("model", None), (17, 4), m) == P(None, None)
    assert fit_spec(P(("data", "model")), (32,), m) == P(("data", "model"))
    assert fit_spec(P(("data", "model")), (4,), m) == P("data")
    assert fit_spec(P("bogus"), (8,), m) == P(None)


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_param_specs_cover_all_leaves(arch_id):
    """Every param leaf gets a spec, and every named axis divides its dim."""
    import jax.numpy as jnp
    from repro.models import build_model
    cfg = get_config(arch_id)
    bundle = build_model(cfg)
    pshape = jax.eval_shape(bundle.init, jax.ShapeDtypeStruct((2,),
                                                              jnp.uint32))
    m = FakeMesh()
    m.devices.shape = (16, 16)
    specs = param_specs(cfg, pshape, m)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    flat_p = jax.tree.leaves(pshape)
    assert len(flat_s) == len(flat_p)
    sizes = {"data": 16, "model": 16}
    for spec, leaf in zip(flat_s, flat_p):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            prod = int(np.prod([sizes[a] for a in axes]))
            assert dim % prod == 0, (arch_id, spec, leaf.shape)


_SUBPROCESS_SRC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import json
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import (SearchParams, WorkloadSpec, filtered_knn,
                            generate_bitmaps, recall_at_k)
    from repro.core.distributed import (build_sharded_scann,
                                        distributed_search_fn,
                                        distributed_kmeans_fn)
    from repro.data import DatasetSpec, make_dataset

    spec = DatasetSpec("t-dist", 4000, 32, "l2", clusters=16)
    store, queries = make_dataset(spec, num_queries=6, seed=0)
    queries = jnp.asarray(queries)
    mesh = jax.make_mesh((8,), ("data",))
    sh = build_sharded_scann(store, mesh, "data", num_leaves=64, levels=1,
                             seed=0)
    params = SearchParams(k=10, num_leaves_to_search=48, reorder_factor=6)
    fn = distributed_search_fn(sh, params)
    bm = generate_bitmaps(store, queries, WorkloadSpec(0.3, "none"), seed=1)
    d, ids = fn(queries, bm)
    td, tid = filtered_knn(store, queries, bm, 10)
    rec = float(np.mean(np.asarray(jax.vmap(
        lambda f, t: recall_at_k(f, t, 10))(ids, tid))))

    # executor path WITH per-query stats riding the all-gather: identical
    # ids/dists to the stats-free fn, counters per-query and sane
    from repro.core.distributed import DistributedScannExecutor
    from repro.core.scann import _quant_pages_per_leaf
    ex = DistributedScannExecutor(sh)
    res = ex.search(queries, bm, params)
    ids_eq = bool(np.array_equal(np.asarray(res.ids), np.asarray(ids)))
    st = res.stats
    nd = 8
    nsel = min(max(1, -(-params.num_leaves_to_search // nd)),
               sh.index.num_leaves // nd)
    hops_ok = bool((np.asarray(st.hops) == nd * nsel).all())
    qppl = _quant_pages_per_leaf(sh.index)
    pages_ok = bool((np.asarray(st.page_accesses_index)
                     == nd * nsel * qppl).all())
    stats_pos = bool((np.asarray(st.filter_checks) > 0).all()
                     and (np.asarray(st.distance_comps) > 0).all()
                     and (np.asarray(st.reorder_rows)
                          == np.asarray(st.page_accesses_heap)).all())
    # (ppv == 1 at dim=32, so heap pages == reorder rows)

    # distributed kmeans == single-device kmeans (same init, fori semantics)
    km = distributed_kmeans_fn(mesh, "data", k=8, iters=5)
    x = np.asarray(store.vectors)
    init = x[np.random.RandomState(0).choice(len(x), 8, False)]
    c_dist = np.asarray(km(jnp.asarray(x), jnp.asarray(init)))
    mesh1 = jax.make_mesh((1,), ("data",))
    km1 = distributed_kmeans_fn(mesh1, "data", k=8, iters=5)
    c_one = np.asarray(km1(jnp.asarray(x), jnp.asarray(init)))
    err = float(np.abs(c_dist - c_one).max())
    print(json.dumps({"recall": rec, "kmeans_err": err,
                      "devices": jax.device_count(), "ids_eq": ids_eq,
                      "hops_ok": hops_ok, "pages_ok": pages_ok,
                      "stats_pos": stats_pos}))
""")


@pytest.mark.slow
def test_distributed_search_8dev():
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_SRC],
                          capture_output=True, text=True, cwd=REPO,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.splitlines()[-1])
    assert rec["devices"] == 8
    assert rec["recall"] >= 0.9
    assert rec["kmeans_err"] < 1e-3
    # the executor's per-query SearchStats (satellite: stats across the
    # mesh) must not perturb results and must carry the mesh semantics
    assert rec["ids_eq"] and rec["hops_ok"] and rec["pages_ok"] \
        and rec["stats_pos"]
