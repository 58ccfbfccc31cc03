"""Filtered ScaNN, built by the program's own `build_scann`.

Config keys (`index` in the config file): num_leaves, levels, pca_dims
(null for none).

The build runs inside a `repro.obs` recorder.  `describe` hands the leaf
shape and the seconds of the program's `scann.*` spans on to `run.shape`
(`built` itself is splatted into `make_executor`, so it carries only the
index), where `metrics/scann_kmeans_s.py` and `metrics/scann_pack_s.py`
read them; the build also prints its seconds and how much of
`scann.build` its child spans cover.

The import of `kmeans` is a requirement, not a use: a program without
that on-device k-means builds from a copy of the whole table on the host,
which a cell of millions of rows cannot afford, so such a program fails
here, at once, rather than after minutes.
"""
import sys

from repro import obs
from repro.core.scann import build_scann, kmeans  # noqa: F401

CHILDREN = ("scann.pca", "scann.kmeans", "scann.pack", "scann.upload")
_seconds: dict = {}     # id(index) -> the build's span seconds


def build(store, spec: dict, seed: int) -> dict:
    with obs.record() as rec:
        index = build_scann(store, num_leaves=spec["num_leaves"],
                            levels=spec["levels"],
                            pca_dims=spec.get("pca_dims"), seed=seed)
    total = rec.total_seconds("scann.build")
    parts = {name: rec.total_seconds(name) for name in CHILDREN}
    print(f"info scann.build {total:.3f} s, children cover "
          f"{100.0 * sum(parts.values()) / max(total, 1e-9):.2f}%: "
          + ", ".join(f"{k} {v:.3f} s" for k, v in parts.items()),
          file=sys.stderr, flush=True)
    _seconds[id(index)] = {"scann_build_s": total,
                           "scann_kmeans_s": parts["scann.kmeans"],
                           "scann_pack_s": parts["scann.pack"],
                           "scann_upload_s": parts["scann.upload"]}
    return {"index": index}


def describe(built: dict) -> dict:
    index = built["index"]
    leaves, rows_per_leaf, dims = index.leaf_tiles.shape
    return {"leaves": int(leaves), "rows_per_leaf": int(rows_per_leaf),
            "dims": int(dims), "levels": int(index.levels),
            **_seconds.pop(id(index), {})}
