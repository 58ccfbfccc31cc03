"""The comparison that decides `correct`.

Every answer the window returned is judged against the plain reference
(`reference.py`) over the same store and bitmaps, on four numbers:

  filter_violations  returned ids that fail their request's bitmap, lie
                     outside the table, or repeat within one answer
                     (exact: limit 0)
  short_answers      answers with fewer ids than min(k, rows passing)
                     (exact: limit 0)
  dist_gap           the widest gap between a returned distance and the
                     reference's f32 distance of that id, relative to the
                     scale its rounding error has (||q||^2 + ||x||^2 for
                     L2, ||q|| ||x|| for IP); a lower-precision distance
                     fails it
  recall_at_10       mean share of the exact filtered top-k found (a
                     floor: the quality the cell is held to)

The limits come from the cell's file, `cells/<cell>.json`.
"""
from __future__ import annotations

import numpy as np

from fvsbench import reference

NAMES = ("filter_violations", "short_answers", "dist_gap", "recall_at_10")
FLOORS = ("recall_at_10",)       # numbers that must not fall below


def bits_of(bitmaps: np.ndarray, rows: np.ndarray, ids: np.ndarray
            ) -> np.ndarray:
    """Bit ids[a, j] of bitmap rows[a]; ids must be in range."""
    words = bitmaps[rows[:, None], ids >> 5]
    return ((words >> (ids & 31).astype(np.uint32)) & 1).astype(bool)


def numbers(answers: dict, uniq: np.ndarray, ref_ids: np.ndarray,
            vectors, queries: np.ndarray, bitmaps: np.ndarray,
            metric: str) -> dict:
    """The four numbers for `answers` ({pairs (A,), ids (A, k), dists
    (A, k)}), given the reference top-k `ref_ids` of the pool pairs
    `uniq` (sorted)."""
    pairs, ids, dists = answers["pairs"], answers["ids"], answers["dists"]
    n = vectors.shape[0]
    k = ids.shape[1]
    valid = ids >= 0
    inrange = valid & (ids < n)
    safe = np.where(inrange, ids, 0)
    fails = valid & ~(inrange & bits_of(bitmaps, pairs, safe))
    srt = np.sort(np.where(valid, ids, -1 - np.arange(k)), axis=1)
    dup = (srt[:, 1:] == srt[:, :-1]).sum()
    npass = np.bitwise_count(bitmaps[uniq]).sum(axis=1)
    at = np.searchsorted(uniq, pairs)
    want = np.minimum(k, npass[at])
    short = int((valid.sum(axis=1) < want).sum())
    d_ref, scale = reference.distances_of(vectors, queries[pairs], safe,
                                          metric)
    ok = inrange & ~fails
    gap = np.abs(dists.astype(np.float64) - d_ref) / np.maximum(scale, 1e-30)
    gap = float(np.max(np.where(ok, gap, 0.0), initial=0.0))
    truth = ref_ids[at]
    hit = ((ids[:, :, None] == truth[:, None, :]) & (truth[:, None, :] >= 0)
           & valid[:, :, None]).any(axis=2).sum(axis=1)
    recall = float(np.mean(hit / np.maximum((truth >= 0).sum(axis=1), 1)))
    return {"filter_violations": int(fails.sum() + dup),
            "short_answers": short, "dist_gap": gap,
            "recall_at_10": recall}


def judge(answers, uniq, ref_ids, vectors, queries, bitmaps, metric,
          limits: dict) -> dict:
    """{name: {"value", "limit", "ok"}} for every number; a limit of
    null in the cell's file means the cell does not compare that number
    (it is reported, and always ok)."""
    got = numbers(answers, uniq, ref_ids, vectors, queries, bitmaps, metric)
    out = {}
    for name in NAMES:
        lim = limits[name]
        ok = lim is None or (got[name] >= lim if name in FLOORS
                             else got[name] <= lim)
        out[name] = {"value": got[name], "limit": lim, "ok": bool(ok)}
    return out
