"""A configuration brings its model as new files: a family module under
bench/families/ that the harness finds by the name the configuration
gives, and that the reference, the costs and the readers call."""
import hashlib
import json
import shutil
import subprocess
import sys

import pytest

from bench import cells, families
from conftest import ROOT

STUB_FAMILY = '''"""A stand-in family: softmax regression on Gaussian features, its
parameters nested one level deep."""
import jax
import jax.numpy as jnp
import numpy as np


def data(conf, seed):
    rng = np.random.RandomState(seed)
    n, pub = conf["n_items"], conf["published"]
    y = rng.randint(0, pub["n_classes"], n)
    x = rng.randn(n, pub["features"]).astype(np.float32)
    parts = np.array_split(rng.permutation(n), conf["n_clients"])
    return {"x": x, "y": y}, [np.sort(p) for p in parts]


def init_params(conf, seed):
    d, k = conf["published"]["features"], conf["published"]["n_classes"]
    w = jax.random.normal(jax.random.PRNGKey(seed), (d, k)) / np.sqrt(d)
    return {"head": {"w": w, "b": jnp.zeros((k,))}}


def make_local_train(conf, total_steps, n_steps, half_batch):
    def loss(p, b):
        lp = jax.nn.log_softmax(b["x"] @ p["head"]["w"] + p["head"]["b"])
        return -jnp.take_along_axis(lp, b["y"][:, None], 1).mean()

    def train(p, batch, lr):
        if half_batch:
            batch = jax.tree.map(lambda a: a[:, :a.shape[1] // 2], batch)

        def step(p, i):
            v, g = jax.value_and_grad(loss)(
                p, jax.tree.map(lambda a: a[i % n_steps], batch))
            return jax.tree.map(lambda a, b: a - lr * b, p, g), v

        p, losses = jax.lax.scan(step, p, jnp.arange(total_steps))
        return p, losses.mean()

    return jax.jit(train)


def train_flops_per_item(conf):
    return 6 * conf["published"]["features"] * conf["published"]["n_classes"]


def leaf_sizes(conf):
    d, k = conf["published"]["features"], conf["published"]["n_classes"]
    return [d * k, k]


def tiny(conf):
    return dict(conf)
'''

STUB_CONFIG = {
    "name": "stub-logreg", "source": "a stand-in for the tests",
    "family": "stub", "model": "flsim-logreg",
    "published": {"features": 784, "n_classes": 10},
    "deployment": "12 clients of 20 items", "dataset": "synthetic_vision",
    "n_items": 240, "n_clients": 12, "partition": "iid",
    "job": {"model": {"reduced": False},
            "dataset": {"items_per_client": 20}},
    "reduced": []}

STUB_TRAFFIC = {
    "strategy": "compressed",
    "train_params": {"mode": "sync", "placement": "spatial",
                     "compression": "int8", "error_feedback": False,
                     "cohort": 4, "max_cohort": 4, "local_epochs": 1,
                     "local_steps": 2, "batch_size": 4, "client_lr": 0.05,
                     "rounds_per_launch": 2},
    "runtime": {}, "horizon_rounds": 100}

CELL = "stub-logreg.stub-sync"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# Runs in the copy: every entry of the harness reaches the stub.
PROBE = f'''
import json, sys
import jax.numpy as jnp
from bench import cells, compare, families, reference
from bench.metrics import quant_aggregate_roofline, step_mfu
from repro.core.jobs import load_job

cell = cells.load_cell({CELL!r})
raw = cells.job_dict(cell, 2**31 + 7)
job = load_job(raw)
shape = cells.shape(cell)
trace = {{"window_s": 2.0, "busy_s": 1.0, "chunks": 3,
          "kernels": {{"quant_aggregate": [6, 0.006]}}}}
ctx = {{"trace": trace, "spans": [], "window_calls": 3, "cell": cell,
        "shape": shape, "peaks": {PEAKS!r}}}
ref = reference.run(cell, 2**31 + 7, steps=3)
low = reference.run(cell, 2**31 + 7, steps=3, dtype=jnp.bfloat16)
print(json.dumps({{
    "family_file": families.of(cell["config"]).__file__,
    "model": raw["model"], "dataset": raw["dataset"], "arch": job.arch,
    "shape": shape, "step_mfu": step_mfu.read(ctx),
    "roofline": quant_aggregate_roofline.read(ctx),
    "losses": ref["losses"].tolist(),
    "leaves": [sorted(p) for p in ref["params"][0]],
    "same": compare.numbers(ref, ref), "control": compare.numbers(low, ref),
}}))
'''


def digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_family_is_new_files_only(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "stub-logreg", "source": "https://arxiv.org/abs/2507.11430",
        "file": "bench/configs/stub-logreg.json", "reduced": [],
        "why": "a stand-in"})
    bench["workloads"].append({"name": CELL, "config": "stub-logreg",
                               "traffic": "stub-sync", "chips": 1,
                               "why": "a stand-in"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    before = digest(tmp_path / "bench")
    (tmp_path / "bench/families/stub.py").write_text(STUB_FAMILY)
    (tmp_path / "bench/configs/stub-logreg.json").write_text(
        json.dumps(STUB_CONFIG))
    (tmp_path / "bench/traffic/stub-sync.json").write_text(
        json.dumps(STUB_TRAFFIC))
    proc = subprocess.run(
        [sys.executable, "-c", PROBE], cwd=tmp_path, capture_output=True,
        text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": str(ROOT / "src")})
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    after = digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {"families/stub.py",
                                        "configs/stub-logreg.json",
                                        "traffic/stub-sync.json"}

    assert got["family_file"] == str(tmp_path / "bench/families/stub.py")
    assert got["arch"] == "flsim-logreg"
    assert got["model"] == {"arch": "flsim-logreg", "reduced": False}
    assert got["dataset"] == {"dataset": "synthetic_vision", "n_items": 240,
                              "items_per_client": 20}
    assert got["shape"]["items_per_update"] == 8
    # 3 calls x 2 rounds x 4 clients x 8 items x 6 x 784 x 10 FLOPs in 2 s
    flops = 3 * 2 * 4 * 8 * 6 * 784 * 10
    assert got["step_mfu"] == pytest.approx(100 * flops / 2.0 / 197e12)
    # one client's int8 deltas: 7,840 + 10 values in 31 + 1 blocks of 256
    cost = 4 * 8192 + 4 * 4 * 8192 // 256 + 4 * 8192 + 4 * 4
    assert got["roofline"] == pytest.approx(
        100 * 6 * cost / 819e9 / 0.006)
    assert len(got["losses"][0]) == 6
    assert got["leaves"] == [["head/b", "head/w"]] * 3
    assert all(v == 0.0 for v in got["same"].values())
    assert got["control"]["loss_gap"] > 0


def test_a_configuration_without_a_family_is_refused():
    conf = cells.read_json(cells.BENCH / "configs"
                           / "flsim-mlp-cifar10-xdevice.json")
    assert families.of(conf).__name__ == "bench.families.vision"
    for bad in (None, "", "../vision", "vision.x"):
        with pytest.raises(ValueError, match="names no family"):
            families.of(dict(conf, family=bad))
