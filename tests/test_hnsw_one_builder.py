"""`build_graph`, the one HNSW builder, against the host recipe it replaced
(`hnsw_host_recipe.host_build_graph`) at the shape of the tests' shared
graph: the same levels and entry point, and the same adjacency up to
near-ties in the candidates' distances."""
import numpy as np
import pytest

from hnsw_host_recipe import host_build_graph
from repro.core import build_graph
from repro.data import DatasetSpec, make_dataset


@pytest.mark.parametrize("metric", ["l2", "ip", "cos"])
def test_build_graph_matches_the_host_recipe(metric):
    spec = DatasetSpec("t-one-builder", 4000, 48, metric, clusters=16)
    store, _ = make_dataset(spec, num_queries=1, seed=0)
    got = build_graph(store, m=12, ef_construction=48, seed=0)
    want = host_build_graph(store, m=12, ef_construction=48, seed=0)
    np.testing.assert_array_equal(np.asarray(got.node_level),
                                  np.asarray(want.node_level))
    assert int(got.entry_point) == int(want.entry_point)
    got_nb, want_nb = np.asarray(got.neighbors), np.asarray(want.neighbors)
    assert got_nb.shape == want_nb.shape
    assert (got_nb == want_nb).mean() >= 0.99
