"""Find the knee of an open-loop cell: one set-up, a closed-loop window
for the capacity, then open-loop windows at fractions of it.

    python fvsbench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --fractions 0.5,0.7,0.9,1.0,1.1 [--rehearse]

For each rate it prints one JSON line: requests, p50/p95/max latency, the
backlog at the window's close (requests due but not answered), the time
the backlog took to drain, and the mean latency of the window's first and
last quarter of requests.  The knee is the highest rate at which the
backlog does not grow over the window.  On a TPU only, as run.py.
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


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fractions", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import numpy as np
    from fvsbench import harness
    harness.configure_jax()
    cell = harness.load_cell(args.workload,
                             os.path.join(ROOT, "BENCHMARK.json"),
                             args.rehearse)
    devs = jax.devices()
    if not args.rehearse and devs[0].platform != "tpu":
        sys.exit("fvsbench sweep: needs a TPU")
    if cell.traffic["loop"] != "open":
        sys.exit(f"{cell.name}: not an open-loop cell")
    h = harness.Harness(cell, args.seed, T_START)
    print(json.dumps({"setup_s": h.run.setup_s, "phases": h.run.phases}),
          flush=True)
    cell.traffic["loop"] = "closed"
    run = h.window(args.seconds)
    cap = run.completed / run.window_s
    print(json.dumps({"closed_loop_qps": cap, "batches":
                      len(run.dispatches)}), flush=True)
    cell.traffic["loop"] = "open"
    for frac in (float(f) for f in args.fractions.split(",")):
        rate = frac * cap
        arr = harness.arrivals(args.seed, rate, args.seconds)
        run = h.window(args.seconds, rate=rate)
        lat = run.latencies_ms
        done = arr[:len(lat)] + lat / 1e3
        backlog = int(np.sum(arr <= args.seconds) - np.sum(
            done <= args.seconds))
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "fraction": frac, "rate": rate, "attempted": run.attempted,
            "completed": run.completed,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "max_ms": float(lat.max()), "backlog_at_close": backlog,
            "drain_s": float(max(0.0, done.max() - args.seconds)),
            "first_quarter_ms": float(lat[:q].mean()),
            "last_quarter_ms": float(lat[-q:].mean()),
            "batches": len(run.dispatches),
            "mean_batch": run.completed / max(1, len(run.dispatches))}),
            flush=True)


if __name__ == "__main__":
    main()
