"""The harness end to end on the CPU at a tiny size: it refuses a CPU run
and a checkout without the program, and a sound run comes out correct."""
import subprocess
import sys

import pytest

from conftest import NAMES, ROOT, run_tiny


def test_refuses_a_cpu():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", NAMES[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("name", NAMES)
def test_sound_run_is_correct(name):
    r = run_tiny(name)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"client_updates_per_s", "peak_hbm_gb",
                                 "setup_s"}


def test_fails_with_only_the_benchmark_files(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", NAMES[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_traced_run_reports_the_per_layer_metrics():
    r = run_tiny("mlp-int8-xdevice", trace=True)
    assert r["correct"], r["checks"]
    # no TPU in the trace: only the span-based metrics have something to
    # read
    assert set(r["metrics"]) == {"boundary_ms", "cohort_plan_ms",
                                 "slab_stage_ms", "metrics_pull_ms"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
