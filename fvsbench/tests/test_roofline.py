"""Least operations and bytes against a hand count."""
import numpy as np
import pytest

from fvsbench import roofline

PEAKS = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 394e12,
         "hbm_bytes_per_s": 819e9}


def test_graph_batch_hand_count():
    pairs = np.array([1, 2, 2])
    counters = {"distance_comps": np.array([300, 700, 700]),
                "filter_checks": np.array([1000, 2000, 2000])}
    ops, nbytes = roofline.graph_batch(pairs, counters, {}, dim=64)
    assert ops == 2 * 64 * 1000
    assert nbytes == 700 * 64 * 4 + 3000 / 8


def test_least_seconds_picks_the_binding_bound():
    t, bound = roofline.least_seconds(394e12, 819e9 / 2, PEAKS)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = roofline.least_seconds(1.0, 819e9, PEAKS)
    assert (t, bound) == (pytest.approx(1.0), "memory")
