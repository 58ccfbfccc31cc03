"""Run one benchmark cell once and print its result as one JSON line.

    python fvsbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--rehearse] [--bench FILE]

From the root of a checkout.  The cell (a `workloads` entry of
BENCHMARK.json, or of FILE) names a configuration and a traffic mix; the
harness (`fvsbench/harness.py`) builds the deployment from the seed,
warms up every batch shape the traffic uses, measures for `--seconds`,
then checks every answer of the window against the plain reference.
With `--trace 0` the result carries the cell's end-to-end metrics, with
`--trace 1` (the profiler on through the window) its per-layer metrics,
`busy_s`/`window_s` and a breakdown.  The last lines on standard error
and the result's last key give each number `correct` compared, beside
its limit.

Off a TPU it exits non-zero before any work and prints no result, unless
`--rehearse` is given: then it runs anywhere (Pallas kernels interpreted
off the chip), takes the tiny files under `fvsbench/rehearsal/` in place
of the cell's config, traffic and limits where they exist, and reports
no device-trace metric off the chip.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the TPU runtime would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def fail(msg: str, code: int = 1):
    print(f"fvsbench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow any platform; use fvsbench/rehearsal/ sizes")
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"the program (src/repro) is not in {ROOT}")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax
    from fvsbench import check, harness, trace
    harness.configure_jax()
    cell = harness.load_cell(args.workload, args.bench, args.rehearse)
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if not args.rehearse and (platform != "tpu" or len(devs) < cell.chips):
        fail(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX sees "
             f"{len(devs)} {platform!r} device(s)")
    peaks = None
    if platform == "tpu":
        table = harness.load_json(os.path.join(harness.HERE, "peaks.json"))
        if kind not in table["devices"]:
            fail(f"no peaks for device kind {kind!r} in fvsbench/peaks.json")
        peaks = table["devices"][kind]

    h = harness.Harness(cell, args.seed, T_START, peaks)
    print("setup " + " ".join(f"{k} {v:.3f} s" for k, v in
                              h.run.phases.items()), file=sys.stderr)
    trace_dir = tempfile.mkdtemp(prefix="fvsbench-trace-") \
        if args.trace else None
    run = h.window(args.seconds, trace_dir)
    stats = devs[0].memory_stats() or {}
    run.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    reduced = None
    if trace_dir is not None:
        reduced = trace.reduce(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    answers = h.collect()
    h.free_program()
    run.checks = h.judge(answers)

    on_chip = platform == "tpu"
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        if m["source"] == "device_trace" and not on_chip:
            continue
        mod = harness.load_module(harness.metric_file(m["name"]))
        value = mod.read(run, reduced)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": all(c["ok"] for c in run.checks.values()),
           "attempted": run.attempted,
           "failed": run.attempted - run.completed,
           "metrics": metrics, "device": device}
    if reduced is not None and on_chip:
        device["busy_s"] = reduced.busy_ns * 1e-9
        device["window_s"] = reduced.window_ns * 1e-9
        out["breakdown"] = {"device_ops": trace.top_ops(reduced),
                            "idle_gaps": trace.idle_gaps(reduced)}
    compared = {k: c for k, c in run.checks.items()
                if c["limit"] is not None}
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in compared.items()}
    took = [d.done - d.start for d in run.dispatches]
    print(f"info dispatches {len(took)}, longest {max(took):.3f} s, "
          f"largest batch {max(len(d.pairs) for d in run.dispatches)}, "
          f"window {run.window_s:.3f} s", file=sys.stderr)
    print(", ".join([f"info answers {len(answers['pairs'])}",
                     f"flagged truncated by the program {run.truncated}"]
                    + [f"{k} {c['value']!r} (not compared)" for k, c in
                       run.checks.items() if c["limit"] is None]),
          file=sys.stderr)
    for k, c in compared.items():
        rel = ">=" if k in check.FLOORS else "<="
        print(f"check {k} {c['value']!r} {rel} {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
