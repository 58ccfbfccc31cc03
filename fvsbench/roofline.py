"""Least operations and bytes a batch's work needs, from the program's
SearchStats counters and the cell's shapes.

Each count is a lower bound on what ANY implementation of the same
search has to compute or move, so `roofline time / device time` cannot
pass 100%:

  operations  every distance the counters report (`distance_comps`)
              costs 2 * dim operations; nothing else is counted.  The
              least time divides by the chip's highest peak (int8),
              whatever the precision the program uses.
  bytes       the largest single query's scored rows at 4 * dim bytes
              (other queries' rows may be the same ones), plus one bit
              per filter check (each query probes its own bitmap).
              Adjacency reads are left out.

Duplicate requests in one batch are counted once.  Returns per batch
(ops, bytes); `least_seconds` applies the peaks.
"""
from __future__ import annotations

import numpy as np


def _distinct(pairs: np.ndarray, counters: dict) -> dict:
    _, first = np.unique(pairs, return_index=True)
    return {k: np.asarray(v, np.float64)[first] for k, v in counters.items()}


def graph_batch(pairs, counters: dict, shape: dict, dim: int) -> tuple:
    c = _distinct(pairs, counters)
    ops = 2.0 * dim * c["distance_comps"].sum()
    nbytes = (4.0 * dim * c["distance_comps"].max(initial=0.0)
              + c["filter_checks"].sum() / 8.0)
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound) with bound "compute" or "memory"."""
    t_ops = ops / max(peaks["int8_ops_per_s"], peaks["bf16_flops_per_s"])
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
