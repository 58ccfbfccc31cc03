"""run.py end to end on the CPU at rehearsal size, and its refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run(args, cwd=ROOT, timeout=600):
    return subprocess.run([sys.executable, "fvsbench/run.py", *args],
                          cwd=cwd, env=ENV, capture_output=True, text=True,
                          timeout=timeout)


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal(cell, trace):
    p = run(["--workload", cell, "--seed", "4294967311", "--seconds", "1",
             "--trace", trace, "--rehearse"])
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] and out["failed"] == 0, out
    assert out["device"]["platform"] == "cpu"
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    kinds = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in out["metrics"]:
        assert kinds[name]["source"] != "device_trace"
    if trace == "0":
        assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2
    assert "busy_s" not in out["device"] and "breakdown" not in out
    last = p.stderr.strip().splitlines()[-len(out["checks"]):]
    assert all(line.startswith("check ") for line in last)


def test_refuses_off_the_chip():
    p = run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert p.returncode != 0 and p.stdout == ""


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "fvsbench"), tmp_path / "fvsbench")
    p = run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
             "--rehearse"], cwd=tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_a_cell_added_by_data_files_alone():
    """fvsbench/rehearsal/example.json names a configuration, a traffic
    mix and limits that exist only as files: no code names them."""
    p = run(["--workload", "sift16k-hnsw-sweeping.sel10pos-sat", "--seed",
             "9", "--seconds", "1", "--rehearse", "--bench",
             "fvsbench/rehearsal/example.json"])
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] and set(out["metrics"]) == {
        "qps", "recall_at_10", "setup_s"}
