"""Readings of the control: the plain reference put in the program's
place, computed in a lower precision, and judged by the same check.

    python fvsbench/control.py --workload <cell> --seeds 1,2,3 [--rehearse]

For each seed it makes the cell's data and predicate pool (no index: the
control replaces the program), answers every pool request with the
reference top-k at precision "high" (three bf16 passes, the nearest
below the reference's f32) and "default" (one bf16 pass), and prints one
JSON line with each precision's numbers beside the cell's limits.  A
sound check fails the control on at least one number.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the TPU runtime would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def control_numbers(h, precision: str) -> dict:
    """The check's numbers for the lower-precision reference's answers to
    every request of the pool."""
    import numpy as np
    from fvsbench import reference
    k = int(h.run.cell.config["search"].get("k", 10))
    d, i = reference.filtered_topk(h.vectors, h.pool_q, h.pool_bm, k,
                                   h.metric, precision=precision)
    answers = {"pairs": np.arange(h.pool, dtype=np.int32), "ids": i,
               "dists": d}
    return h.judge(answers)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from fvsbench import harness
    harness.configure_jax()
    cell = harness.load_cell(args.workload,
                             os.path.join(ROOT, "BENCHMARK.json"),
                             args.rehearse)
    devs = jax.devices()
    if not args.rehearse and devs[0].platform != "tpu":
        sys.exit("fvsbench control: needs a TPU")
    for seed in (int(s) for s in args.seeds.split(",")):
        h = harness.Harness(cell, seed, time.monotonic(),
                            with_program=False)
        out = {"workload": cell.name, "seed": seed}
        for p in ("high", "default"):
            checks = control_numbers(h, p)
            out[p] = {k: v["value"] for k, v in checks.items()}
            out[p + "_fails"] = [k for k, v in checks.items() if not v["ok"]]
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
