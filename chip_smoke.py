"""Chip smoke test: the FL drivers and the int8 aggregation kernel on a TPU.

Drives the public entry points (``load_job`` -> ``Executor`` /
``CampaignExecutor``) with ``flsim-cnn`` at its published widths and int8
compressed aggregation, in one process, phase by phase:

- ``device``   the backend is a TPU (never a CPU fallback);
- ``kernel``   the compiled ``ops.quant_aggregate`` Pallas kernel against
               the fused jnp path, at C=16 for N=2^20 and for flsim-cnn's
               packed N (which takes the pad path);
- ``sync``     a spatial int8 run with error feedback on the Pallas kernel,
               then the same job on the dequant-first reference;
- ``async``    the same job as FedBuff (packed int8 buffers in the ring),
               on the kernel and on the reference;
- ``campaign`` 8 seeds vmapped as lanes, lane 0 against the single run.

With ``--chips 4`` only the lane-mesh phase runs: 16 seeds (8 clients
each) sharded over a 4-device lane mesh against the same 16 lanes on one
device, run as four campaigns of 4 lanes (bitwise) and as one of 16.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``. Any failed check ends the run with a
non-zero exit and no such line.

  python chip_smoke.py               # one chip
  python chip_smoke.py --chips 4     # lane mesh over four chips
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import packing
from repro.core.jobs import load_job
from repro.kernels import ops
from repro.kernels.quant_aggregate import tile_shape
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_zoo
from repro.runtime.campaign import CampaignExecutor
from repro.runtime.executor import Executor

ARCH = "flsim-cnn"
# Pallas kernel against the fused jnp path on random int8 inputs.
KERNEL_TOL = dict(rtol=1e-5, atol=1e-6)
# Whole trajectories on two aggregation paths. Both compute the same
# (q*scale)*w sums; where a backend rounds them differently by an ulp, the
# next round's int8 quantization can flip a value by one step (~1% of its
# block's max), so trajectories agree to this tolerance, not to the ulp.
TRAJ_TOL = dict(rtol=1e-3, atol=1e-3)


def job(*, mode: str = "sync", seeds=None, n_items: int = 8192,
        n_clients: int = 32, rounds: int = 6, chunk: int = 3):
    """The smoke job: int8 compressed FedAvg with error feedback over a
    Dirichlet(0.5) split of CIFAR-shaped synthetic data."""
    tp = {"n_clients": n_clients, "local_epochs": 1, "client_lr": 0.05,
          "rounds": rounds, "seed": 0, "rounds_per_launch": chunk,
          "placement": "spatial", "compression": "int8",
          "error_feedback": True}
    runtime = {}
    if mode == "async":
        tp.update({"mode": "async", "async_buffer": 8, "max_staleness": 4,
                   "staleness_exponent": 0.5})
        runtime = {"straggler_prob": 0.2, "duration_sigma": 0.25}
    raw = {"name": f"chip-smoke-{mode}", "model": {"arch": ARCH},
           "dataset": {"dataset": "synthetic_vision", "n_items": n_items,
                       "distribution": {"partition": "dirichlet",
                                        "dirichlet_alpha": 0.5}},
           "strategy": {"strategy": "compressed", "train_params": tp},
           "runtime": runtime}
    if seeds is not None:
        raw["sweep"] = {"seeds": list(seeds)}
    return load_job(raw)


def emit(phase: str, t0: float, **fields) -> None:
    print(json.dumps({"phase": phase,
                      "wall_s": time.perf_counter() - t0, **fields}),
          flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def compare(a, b, tol) -> tuple[bool, float, bool]:
    """(bitwise equal, largest |a-b|, allclose at ``tol``) over pytrees."""
    la = [np.asarray(x) for x in jax.tree.leaves(a)]
    lb = [np.asarray(x) for x in jax.tree.leaves(b)]
    bitwise = all(np.array_equal(x, y) for x, y in zip(la, lb))
    diff = max(float(np.max(np.abs(x - y))) for x, y in zip(la, lb))
    close = all(np.allclose(x, y, **tol) for x, y in zip(la, lb))
    return bitwise, diff, close


def launch_compile_s(round_s, chunk: int) -> float:
    """The first launch's time minus a warm launch's (rows carry each
    launch's time split evenly over its rounds)."""
    return float(sum(round_s[:chunk]) - sum(round_s[chunk:2 * chunk]))


def phase_device(chips: int) -> dict:
    t0 = time.perf_counter()
    for var in ("REPRO_KERNEL_IMPL", "REPRO_QUANT_AGG"):
        check(var not in os.environ,
              f"{var} is set; it would swap the Pallas kernel out")
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    check(dev["platform"] == "tpu", f"no TPU: JAX found {dev['platform']}")
    check(dev["count"] >= chips, f"{chips} chips wanted, {dev['count']} seen")
    check(ops.backend() == "pallas", "kernel backend is not pallas")
    emit("device", t0, **dev, backend=ops.backend())
    return dev


def phase_kernel(impl: str = "pallas", n_big: int = 1 << 20) -> None:
    model = model_zoo.build(get_config(ARCH))
    model_n = packing.packed_size(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))))[0]
    for C, N in ((16, n_big), (16, model_n)):
        t0 = time.perf_counter()
        ks = jax.random.split(jax.random.PRNGKey(C + N), 3)
        q = jax.random.randint(ks[0], (C, N), -127, 128, jnp.int8)
        s = jax.random.uniform(ks[1], (C, N // packing.QBLOCK), jnp.float32,
                               1e-4, 1e-2)
        w = jax.random.uniform(ks[2], (C,), jnp.float32)
        w = w / w.sum()
        fn = jax.jit(ops.quant_aggregate)
        with ops.quant_agg_scope() as frame:
            t1 = time.perf_counter()
            got = jax.block_until_ready(fn(q, s, w))
            first = time.perf_counter() - t1
            t1 = time.perf_counter()
            jax.block_until_ready(fn(q, s, w))
            warm = time.perf_counter() - t1
            hlo = fn.lower(q, s, w).compile().as_text()
        want = jax.jit(ops._quant_agg_fused)(q, s, w)
        bitwise, diff, close = compare(got, want, KERNEL_TOL)
        nblocks = N // packing.QBLOCK
        cb, rows = tile_shape(C, packing.QBLOCK, nblocks)
        emit("kernel", t0, C=C, N=N, tile=[cb, rows],
             pad_blocks=-nblocks % rows,
             compile_s=first - warm, warm_s=warm,
             tpu_custom_call="tpu_custom_call" in hlo,
             bitwise_vs_fused=bitwise, max_abs_diff=diff,
             quant_agg=dict(frame))
        check(frame["last_impl"] == impl, f"kernel ran {frame['last_impl']}")
        check(impl != "pallas" or "tpu_custom_call" in hlo,
              "no tpu_custom_call in the compiled HLO")
        check(got.shape == (N,) and bool(jnp.isfinite(got).all()),
              "kernel output shape or finiteness")
        check(close, f"kernel vs fused: max |diff| {diff}")


def run_single(j, chunk: int):
    """One Executor run -> (params, losses, compile_s, counters)."""
    with ops.quant_agg_scope() as frame:
        state, logger = Executor(j).scaffold().run()
    params = jax.tree.map(np.asarray, state["params"])
    losses = [r["loss"] for r in logger.rows]
    comp = launch_compile_s([r["round_s"] for r in logger.rows], chunk)
    return params, losses, comp, dict(frame)


def run_against_reference(phase: str, mk_job, chunk: int, impl: str,
                          t0: float, **fields):
    """Run the job on the Pallas path, then a fresh copy on the
    dequant-first reference; emit the phase line and check both."""
    params, losses, comp, stats = run_single(mk_job(), chunk)
    os.environ["REPRO_QUANT_AGG"] = "dequant"
    try:
        ref_params, ref_losses, _, ref_stats = run_single(mk_job(), chunk)
    finally:
        del os.environ["REPRO_QUANT_AGG"]
    bitwise, diff, close = compare(params, ref_params, TRAJ_TOL)
    loss_diff = float(np.max(np.abs(np.subtract(losses, ref_losses))))
    emit(phase, t0, **fields, compile_s=comp, loss_first=losses[0],
         loss_last=losses[-1], quant_agg=stats, ref_quant_agg=ref_stats,
         bitwise_vs_dequant=bitwise, max_abs_param_diff=diff,
         max_abs_loss_diff=loss_diff, tol=TRAJ_TOL)
    check(stats["last_impl"] == impl, f"{phase} ran {stats['last_impl']}")
    check(stats["calls"] > 0 and stats["batched_fallbacks"] == 0,
          f"{phase} routing {stats}")
    check(ref_stats["last_impl"] == "dequant-first", "reference not selected")
    check(all(math.isfinite(x) for x in losses), f"non-finite {phase} loss")
    check(close and np.allclose(losses, ref_losses, **TRAJ_TOL),
          f"{phase} vs dequant-first: max |diff| {diff}")
    return params, losses


def phase_sync(impl: str = "pallas", **size):
    t0 = time.perf_counter()
    params, losses = run_against_reference(
        "sync", lambda: job(**size), size.get("chunk", 3), impl, t0)
    check(losses[-1] < losses[0], f"sync loss did not fall: {losses}")
    return params


def phase_async(impl: str = "pallas", **size):
    t0 = time.perf_counter()
    chunk = size.get("chunk", 3)
    size = dict(size, rounds=3 * chunk)
    run_against_reference("async", lambda: job(mode="async", **size), chunk,
                          impl, t0, launches=3)


def run_campaign(j, chunk: int, lane_devices: int = 0):
    """-> (campaign, lane-0 losses, compile_s, counters, start params)."""
    with ops.quant_agg_scope() as frame:
        camp = CampaignExecutor(j, lane_devices=lane_devices).scaffold()
        start = jax.tree.map(np.asarray, camp.state["params"])
        camp.run()
    lane0 = [r for r in camp.results if r["traj"] == 0]
    return camp, [r["loss"] for r in lane0], launch_compile_s(
        [r["round_s"] for r in lane0], chunk), dict(frame), start


def rel_diff(a, b, start) -> float:
    """||a - b|| / ||b - start|| over all leaves."""
    def norm(x, y):
        return math.sqrt(sum(float(np.sum((np.asarray(u, np.float64) - v) ** 2))
                             for u, v in zip(jax.tree.leaves(x),
                                             jax.tree.leaves(y))))
    return norm(a, b) / norm(b, start)


def phase_campaign(single_params, seeds: int = 8, **size):
    t0 = time.perf_counter()
    camp, losses, comp, stats, _ = run_campaign(
        job(seeds=range(seeds), **size), size.get("chunk", 3))
    bitwise, diff, close = compare(camp.trajectory_params(0), single_params,
                                   TRAJ_TOL)
    emit("campaign", t0, lanes=camp.S, compile_s=comp, loss_first=losses[0],
         loss_last=losses[-1], quant_agg=stats,
         lane0_bitwise_vs_single=bitwise, max_abs_param_diff=diff,
         tol=TRAJ_TOL)
    check(stats["calls"] > 0, f"campaign routing {stats}")
    check(all(math.isfinite(r["loss"]) for r in camp.results),
          "non-finite campaign loss")
    check(close, f"lane 0 vs single run: max |diff| {diff}")


def lane_params(camp) -> list:
    return [camp.trajectory_params(s) for s in range(camp.S)]


def phase_lane_mesh(chips: int, seeds: int = 16, **size):
    """16 seeds on a lane mesh against the same 16 lanes on one device.

    Each device of the mesh runs the vmapped program over seeds/chips
    lanes, so the reference that runs the same program is ``chips``
    one-device campaigns of seeds/chips lanes each: the sharded lanes must
    equal those bitwise. The one-device campaign of all 16 lanes is a
    differently shaped program; it is compared and printed, not required
    to agree bitwise. Eight clients per lane, so that 16 lanes fit one
    chip's HBM (16 lanes of 32 clients would need about 24 GB)."""
    t0 = time.perf_counter()
    size = {"n_clients": 8, **size}
    chunk = size.get("chunk", 3)
    per = seeds // chips
    mesh, lm, cm, sm, start = run_campaign(job(seeds=range(seeds), **size),
                                           chunk, lane_devices=chips)
    spans = {
        "idx": len(mesh.staged["idx"].sharding.device_set),
        "params": len(jax.tree.leaves(
            mesh.state["params"])[0].sharding.device_set)}
    replicated = jax.tree.leaves(mesh.state["params"])[0] \
        .sharding.is_fully_replicated
    got, mesh_rows = lane_params(mesh), mesh.results
    del mesh
    split, cs = [], None
    for k in range(chips):
        camp, _, c, _, _ = run_campaign(
            job(seeds=range(k * per, (k + 1) * per), **size), chunk)
        split += lane_params(camp)
        cs = c if cs is None else cs
    whole, _, c1, _, _ = run_campaign(job(seeds=range(seeds), **size), chunk)
    whole = lane_params(whole)
    starts = [jax.tree.map(lambda t: t[s], start) for s in range(seeds)]
    vs_split = [compare(g, o, TRAJ_TOL) for g, o in zip(got, split)]
    vs_whole = [compare(g, o, TRAJ_TOL) for g, o in zip(got, whole)]
    # A lane against its neighbour seed's: what a lane fed the wrong seed
    # or data parts by, for scale against the diffs above.
    rel_whole = [rel_diff(g, o, s0) for g, o, s0 in zip(got, whole, starts)]
    rel_wrong = [rel_diff(got[s], split[(s + 1) % seeds], starts[s])
                 for s in range(seeds)]
    emit("lane_mesh", t0, lanes=seeds, lane_devices=chips,
         lanes_per_device=per, compile_s_mesh=cm,
         compile_s_split=cs, compile_s_whole=c1,
         loss_first=lm[0], loss_last=lm[-1], quant_agg=sm,
         plane_devices=spans,
         split_lanes_bitwise=sum(b for b, _, _ in vs_split),
         split_max_abs_diff=max(d for _, d, _ in vs_split),
         whole_lanes_bitwise=sum(b for b, _, _ in vs_whole),
         whole_lanes_allclose=sum(c for _, _, c in vs_whole),
         whole_max_abs_diff=max(d for _, d, _ in vs_whole), tol=TRAJ_TOL,
         whole_max_rel_diff=max(rel_whole),
         wrong_seed_min_rel_diff=min(rel_wrong))
    check(spans["idx"] == chips and spans["params"] == chips,
          f"lane planes span {spans} devices, not {chips}")
    check(not replicated, "lane params are replicated")
    check(all(math.isfinite(r["loss"]) for r in mesh_rows),
          "non-finite lane-mesh loss")
    check(all(b for b, _, _ in vs_split),
          f"sharded lanes vs {chips} one-device campaigns of {per} lanes: "
          f"{sum(b for b, _, _ in vs_split)} of {seeds} bitwise")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the lane-mesh phase over four chips")
    args = ap.parse_args()
    enable_compile_cache()
    dev = phase_device(args.chips)
    if args.chips == 4:
        phase_lane_mesh(args.chips)
    else:
        phase_kernel()
        single = phase_sync()
        phase_async()
        phase_campaign(single)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
