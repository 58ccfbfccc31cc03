"""`chip_smoke.py` at a tiny size on the CPU: its refusal to run without a
TPU, every one-chip phase (kernel parity, all executors on both paths,
continuous serving, mutable index), and the four-device mesh phase on
forced host devices.  The script's own checks are the assertions."""
import os
import pathlib
import subprocess
import sys
import textwrap

REPO = pathlib.Path(__file__).resolve().parents[1]


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "src"), os.environ.get("PYTHONPATH", "")]))
    env.update(extra)
    return env


def test_refuses_to_run_without_a_tpu():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=_env(), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "needs 1 TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_one_chip_phases_tiny():
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    chip_smoke.smoke(0, rows=3000, queries=8, num_leaves=16)


_FOUR = textwrap.dedent("""
    import sys
    sys.path.insert(0, ".")
    import chip_smoke
    chip_smoke.four_chips(0, rows=3000, queries=8, num_leaves=16)
    print("FOUR_OK")
""")


def test_four_chip_phase_on_host_devices():
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR], cwd=REPO, capture_output=True,
        text=True, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "lockstep S=4 ids, dists and 7 counters equal" in proc.stdout
    assert proc.stdout.splitlines()[-1] == "FOUR_OK"
