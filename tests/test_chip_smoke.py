"""chip_smoke.py's phases on the CPU at a tiny size.

The script runs on a TPU only; here its phases run with the Pallas kernel
in interpret mode, which checks their wiring and checks without a chip.
"""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
SIZE = dict(n_items=512, n_clients=4, rounds=4, chunk=2)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    monkeypatch.delenv("REPRO_QUANT_AGG", raising=False)


def test_device_phase_refuses_cpu_and_kernel_overrides(smoke, monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_IMPL", raising=False)
    with pytest.raises(SystemExit, match="no TPU"):
        smoke.phase_device(1)
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "jnp")
    with pytest.raises(SystemExit, match="REPRO_KERNEL_IMPL"):
        smoke.phase_device(1)


def test_kernel_phase(smoke, interpret, capsys):
    smoke.phase_kernel(impl="interpret", n_big=1 << 14)
    lines = capsys.readouterr().out.splitlines()
    assert [ln.count('"phase": "kernel"') for ln in lines] == [1, 1]


def test_sync_and_campaign_phases(smoke, interpret):
    single = smoke.phase_sync(impl="interpret", **SIZE)
    with pytest.warns(UserWarning, match="vmapped"):
        smoke.phase_campaign(single, seeds=2, **SIZE)


def test_async_phase(smoke, interpret):
    smoke.phase_async(impl="interpret", **SIZE)


def test_lane_mesh_phase_on_four_host_devices():
    """The ``--chips 4`` phase on four fake CPU devices, in a subprocess
    (the device count is fixed when jax starts): the lane planes span the
    four devices and every sharded lane is bitwise its one-device twin."""
    code = (f"import importlib.util as u; s = u.spec_from_file_location("
            f"'chip_smoke', {str(SCRIPT)!r}); m = u.module_from_spec(s); "
            "s.loader.exec_module(m); m.phase_lane_mesh(4, seeds=8, "
            "n_items=128, n_clients=2, rounds=1, chunk=1)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_KERNEL_IMPL="interpret",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-3000:]}"
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["phase"] == "lane_mesh"
    assert line["plane_devices"] == {"idx": 4, "params": 4}
    assert line["split_lanes_bitwise"] == 8
    assert line["wrong_seed_min_rel_diff"] > 0.1
