"""Shared driver for the paper-replication benchmarks (Figs. 8-12, Tabs 1-2).

Paper settings (CIFAR-10, 3-conv CNN, 10 clients, Dirichlet alpha=0.5,
batch 64, lr 1e-3, 30 rounds) are scaled to CPU-minutes: synthetic
CIFAR-shaped data, reduced channel counts, fewer rounds — the *relative*
comparisons the figures make are preserved. Every run reports accuracy,
loss, wall time, and simulated communication bytes per round.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig, get_config
from repro.core import determinism
from repro.core.rounds import build_spatial_round, init_state
from repro.core.strategies import get_strategy
from repro.data.pipeline import SyntheticVision
from repro.models import model_zoo
from repro.metrics.logger import PerformanceLogger
from repro.sharding.axes import AxisCtx


def comm_bytes_per_round(params, fl: FLConfig) -> float:
    """Simulated network bytes/round — delegates to the comms observatory's
    closed-form byte model (``core/netmodel.round_nbytes``): exact
    dense/int8/topk payload sizes, gossip neighbour exchanges, consensus
    sharing + digest votes, ledger block records. Full participation; the
    mask-gated per-round accounting lives in ``netmodel.LaneComms``."""
    from repro.core.netmodel import round_nbytes
    return float(round_nbytes(params, fl))


def bench_driver(arch: str = "flsim-mlp", n_clients: int = 16,
                 rounds: int = 20, chunks=(1, 10), n_items: int = 512,
                 seed: int = 0, out_path: str = "BENCH_driver.json"):
    """Rounds/sec for the device-resident multi-round driver, chunked vs
    unchunked, on a paper-scale (flsim_small) CPU config.

    For each chunk size the same Executor path runs ``rounds`` rounds after a
    warm-up launch (compile excluded). Because chunked and unchunked runs are
    bitwise-identical by the driver contract, the delta is pure host+dispatch
    overhead; ``host_overhead_frac`` = the fraction of the unchunked
    per-round wall time that chunking eliminates. Writes ``out_path`` and
    prints one CSV row per chunk size.
    """
    import json

    from repro.core.jobs import load_job
    from repro.runtime.executor import Executor

    assert chunks[0] == 1, \
        "chunks must start with 1 (the speedup/overhead baselines are " \
        "defined vs unchunked execution)"
    assert all(rounds % c == 0 for c in chunks), \
        "rounds must be a multiple of every chunk size (keeps the timed " \
        "region free of remainder-length compiles)"

    results = {"config": {"arch": arch, "n_clients": n_clients,
                          "rounds": rounds, "n_items": n_items,
                          "seed": seed, "backend": jax.default_backend()},
               "runs": {}}
    for chunk in chunks:
        job = load_job({
            "name": f"bench-driver-c{chunk}",
            "model": {"arch": arch},
            "dataset": {"dataset": "synthetic_vision", "n_items": n_items,
                        "distribution": {"partition": "dirichlet",
                                         "dirichlet_alpha": 0.5}},
            "strategy": {"strategy": "fedavg",
                         "train_params": {"n_clients": n_clients,
                                          "local_epochs": 1,
                                          "client_lr": 0.1,
                                          "rounds": rounds + chunk,
                                          "seed": seed,
                                          "rounds_per_launch": chunk}},
        })
        ex = Executor(job).scaffold()
        ex.run(rounds=chunk)                      # warm-up: compile + stage
        t0 = time.time()
        ex.run(rounds=chunk + rounds)
        dt = time.time() - t0
        results["runs"][str(chunk)] = {"rounds": rounds, "wall_s": dt,
                                       "rounds_per_s": rounds / dt,
                                       "s_per_round": dt / rounds}
    runs = results["runs"]
    base = runs[str(chunks[0])]
    for chunk in chunks:
        r = runs[str(chunk)]
        r["speedup_vs_chunk1"] = r["rounds_per_s"] / base["rounds_per_s"]
        r["host_overhead_frac"] = max(
            0.0, 1.0 - r["s_per_round"] / base["s_per_round"])
        print(f"driver_chunk{chunk},{r['s_per_round']*1e6:.0f},"
              f"rounds_per_s={r['rounds_per_s']:.2f};"
              f"speedup={r['speedup_vs_chunk1']:.2f};"
              f"host_overhead={r['host_overhead_frac']:.2f}")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
    return results


def bench_async(arch: str = "flsim-mlp", n_clients: int = 16,
                events: int = 256, chunk_events: int = 64,
                n_items: int = 512, seed: int = 0,
                out_path: str = "BENCH_async.json"):
    """Events/sec for the event-driven async subsystem, chunked vs
    per-event, on a paper-scale (flsim_small) CPU config.

    The same compiled event-scan body runs the same ``events`` server
    events two ways: one launch per event (the host-loop rendering of an
    async server) and ``chunk_events`` events fused per launch (the
    device-resident rendering). By the async determinism contract both
    trajectories are bitwise-identical, so the delta is pure host+dispatch
    overhead. Writes ``out_path`` and prints one CSV row per granularity.
    """
    import json

    from repro.core.async_rounds import async_init_state, build_async_multi
    from repro.core.jobs import load_job
    from repro.core.rounds import init_state
    from repro.data.pipeline import stage_partitions
    from repro.runtime.clock import build_schedule
    from repro.sharding.axes import AxisCtx

    assert events % chunk_events == 0, \
        "events must be a multiple of chunk_events (keeps the timed " \
        "region free of remainder-length compiles)"
    job = load_job({
        "name": "bench-async",
        "model": {"arch": arch},
        "dataset": {"dataset": "synthetic_vision", "n_items": n_items,
                    "distribution": {"partition": "dirichlet",
                                     "dirichlet_alpha": 0.5}},
        "strategy": {"strategy": "fedavg",
                     "train_params": {"n_clients": n_clients,
                                      "client_lr": 0.1, "seed": seed,
                                      "mode": "async", "async_buffer": 8,
                                      "staleness_exponent": 0.5,
                                      "max_staleness": 8}},
        "runtime": {"straggler_prob": 0.1, "duration_sigma": 0.25},
    })
    fl = job.fl
    x, y, parts = job.dataset.distribute_into_chunks(
        fl.partition, fl.n_clients, fl.dirichlet_alpha)
    staged = stage_partitions(x, y, parts)
    warm = chunk_events
    sched = build_schedule(job.fault, fl.n_clients, warm + events,
                           np.asarray(staged["len"], np.float32),
                           buffer_size=fl.async_buffer,
                           staleness_exponent=fl.staleness_exponent,
                           max_staleness=fl.max_staleness)
    sched_dev = sched.device_arrays()
    multi = build_async_multi(job.model, job.strategy, fl)
    root = determinism.root_key(fl.seed)
    state0 = async_init_state(
        init_state(job.model, job.strategy, fl, root), sched.ring)

    def timed(n_per_launch: int) -> float:
        prog = jax.jit(lambda s, start, n=n_per_launch:
                       multi(AxisCtx(), s, staged, sched_dev, root, start, n))
        state = state0
        for e0 in range(0, warm, n_per_launch):   # warm-up: compile + stage
            state, _ = prog(state, e0)
        state = jax.block_until_ready(state)
        t0 = time.time()
        for e0 in range(warm, warm + events, n_per_launch):
            state, _ = prog(state, e0)
        jax.block_until_ready(state)
        return time.time() - t0

    results = {"config": {"arch": arch, "n_clients": n_clients,
                          "events": events, "chunk_events": chunk_events,
                          "n_items": n_items, "seed": seed,
                          "async_buffer": fl.async_buffer,
                          "backend": jax.default_backend()},
               "runs": {}}
    for n in (1, chunk_events):
        dt = timed(n)
        results["runs"][str(n)] = {"events": events, "wall_s": dt,
                                   "events_per_s": events / dt,
                                   "s_per_event": dt / events}
    base = results["runs"]["1"]
    for n in (1, chunk_events):
        r = results["runs"][str(n)]
        r["speedup_vs_per_event"] = r["events_per_s"] / base["events_per_s"]
        print(f"async_chunk{n},{r['s_per_event']*1e6:.0f},"
              f"events_per_s={r['events_per_s']:.2f};"
              f"speedup={r['speedup_vs_per_event']:.2f}")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
    return results


def bench_sweep(arch: str = "flsim-logreg", n_traj: int = 8,
                n_clients: int = 8, rounds: int = 16, chunk: int = 1,
                n_items: int = 512, seed: int = 0,
                out_path: str = "BENCH_sweep.json"):
    """Trajectory-rounds/sec for a multi-seed campaign, vmapped vs
    sequential, on a paper-scale (flsim_small) CPU config.

    The same S-seed sweep runs two ways: S independent Executor runs (the
    pre-campaign cost of a multi-seed comparison) and one CampaignExecutor
    whose S trajectories share a single vmapped compiled program. Each
    executor gets a warm-up chunk first (compile excluded), so the speedup
    is steady-state throughput: dispatch amortization + batched lane math.
    By the campaign determinism contract the two produce bitwise-identical
    per-lane params, so the delta is pure execution efficiency. Writes
    ``out_path`` and prints one CSV row per mode.

    The default is the paper's scale-experiment model (logreg, Fig. 12):
    vmapping the trajectory axis pays where per-launch overhead dominates —
    at paper scale that is every model; a model whose per-lane working set
    overflows CPU cache (e.g. the 1M-param MLP at S=8) can instead go
    memory-bound, which is the documented trade-off, not a bug.
    """
    import json

    from repro.core.jobs import load_job
    from repro.runtime.campaign import CampaignExecutor
    from repro.runtime.executor import Executor

    assert rounds % chunk == 0, \
        "rounds must be a multiple of chunk (keeps the timed region free " \
        "of remainder-length compiles)"

    def raw(seed_s=seed, sweep=None):
        r = {
            "name": "bench-sweep",
            "model": {"arch": arch},
            "dataset": {"dataset": "synthetic_vision", "n_items": n_items,
                        "distribution": {"partition": "dirichlet",
                                         "dirichlet_alpha": 0.5}},
            "strategy": {"strategy": "fedavg",
                         "train_params": {"n_clients": n_clients,
                                          "local_epochs": 1,
                                          "client_lr": 0.1,
                                          "rounds": rounds + chunk,
                                          "seed": seed_s,
                                          "rounds_per_launch": chunk}},
        }
        if sweep:
            r["sweep"] = sweep
        return r

    seeds = [seed + s for s in range(n_traj)]
    results = {"config": {"arch": arch, "n_traj": n_traj,
                          "n_clients": n_clients, "rounds": rounds,
                          "chunk": chunk, "n_items": n_items, "seed": seed,
                          "backend": jax.default_backend()},
               "runs": {}}

    # sequential: S independent single runs (warm-up chunk each, excluded)
    execs = [Executor(load_job(raw(seed_s=s))).scaffold() for s in seeds]
    for ex in execs:
        ex.run(rounds=chunk)
    t0 = time.time()
    for ex in execs:
        ex.run(rounds=chunk + rounds)
    dt_seq = time.time() - t0

    # vmapped: one campaign, S trajectories per launch
    camp = CampaignExecutor(
        load_job(raw(sweep={"seeds": seeds}))).scaffold()
    camp.run(rounds=chunk)
    t0 = time.time()
    camp.run(rounds=chunk + rounds)
    dt_vm = time.time() - t0

    traj_rounds = n_traj * rounds        # trajectory-rounds moved per mode
    for name, dt in (("sequential", dt_seq), ("vmapped", dt_vm)):
        results["runs"][name] = {
            "trajectories": n_traj, "rounds": rounds, "wall_s": dt,
            "traj_rounds_per_s": traj_rounds / dt,
            "s_per_traj_round": dt / traj_rounds}
    speedup = dt_seq / dt_vm
    results["speedup_vmapped_vs_sequential"] = speedup
    for name in ("sequential", "vmapped"):
        r = results["runs"][name]
        print(f"sweep_{name},{r['s_per_traj_round']*1e6:.0f},"
              f"traj_rounds_per_s={r['traj_rounds_per_s']:.2f};"
              f"speedup={speedup if name == 'vmapped' else 1.0:.2f}")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
    return results


def bench_plan(arch: str = "flsim-logreg", strategies=("fedavg", "fedprox"),
               n_seeds: int = 8, n_clients: int = 4, rounds: int = 16,
               chunk: int = 4, n_items: int = 256, batch_size: int = 16,
               seed: int = 0, reps: int = 6,
               out_path: str = "BENCH_plan.json"):
    """Trajectory-rounds/sec for a heterogeneous strategy x seed campaign,
    bucketed-vmap (planner) vs sequential, on a paper-scale CPU config.

    The same grid runs two ways: one independent Executor per (strategy,
    seed) point — the pre-planner cost of a cross-strategy comparison — and
    one PlanExecutor that buckets the grid by program signature (one bucket
    per strategy here) and vmaps the seeds within each bucket. Each path
    gets a warm-up chunk first (compile excluded), so the speedup is
    steady-state throughput. By the planner determinism contract the two
    produce bitwise-identical per-lane params, so the delta is pure
    execution efficiency. Also reports the compile counts: the bucketed
    path compiles one program per signature, the sequential path one per
    point. Writes ``out_path`` and prints one CSV row per mode.

    Both paths use the same ``rounds_per_launch`` chunking, so the speedup
    isolates bucketing; the per-bucket lane count is what pays (S=8 seeds
    per strategy here, same scale as ``bench_sweep``) — two buckets also
    means two dispatches per chunk, so the bucketed ratio sits slightly
    under the single-bucket sweep ratio by construction. The two modes'
    timed regions *interleave* over ``reps`` repetitions and each reports
    its best — on small shared CPU runners the noise floor moves on the
    scale of one region, so back-to-back phases would charge one mode for
    the other's unlucky window.
    """
    import json

    from repro.core.jobs import load_job
    from repro.runtime.executor import Executor
    from repro.runtime.scheduler import PlanExecutor

    assert rounds % chunk == 0, \
        "rounds must be a multiple of chunk (keeps the timed region free " \
        "of remainder-length compiles)"

    def raw(strategy="fedavg", seed_s=seed, sweep=None):
        r = {
            "name": "bench-plan",
            "model": {"arch": arch},
            "dataset": {"dataset": "synthetic_vision", "n_items": n_items,
                        "distribution": {"partition": "dirichlet",
                                         "dirichlet_alpha": 0.5}},
            "strategy": {"strategy": strategy,
                         "train_params": {"n_clients": n_clients,
                                          "local_epochs": 1,
                                          "client_lr": 0.1,
                                          "batch_size": batch_size,
                                          "rounds": chunk + reps * rounds,
                                          "seed": seed_s,
                                          "rounds_per_launch": chunk}},
        }
        if sweep:
            r["sweep"] = sweep
        return r

    seeds = [seed + s for s in range(n_seeds)]
    grid = [(st, sd) for st in strategies for sd in seeds]
    results = {"config": {"arch": arch, "strategies": list(strategies),
                          "n_seeds": n_seeds, "n_clients": n_clients,
                          "rounds": rounds, "chunk": chunk, "reps": reps,
                          "n_items": n_items, "batch_size": batch_size,
                          "seed": seed,
                          "backend": jax.default_backend()},
               "runs": {}}

    # sequential: one Executor per grid point; bucketed: one PlanExecutor,
    # one vmapped launch per signature bucket. Warm-up chunk each
    # (compile excluded), then interleaved timed reps.
    execs = [Executor(load_job(raw(st, sd))).scaffold() for st, sd in grid]
    pe = PlanExecutor(load_job(raw(
        sweep={"strategy": list(strategies), "seeds": seeds}))).scaffold()
    for ex in execs:
        ex.run(rounds=chunk)
    pe.run(rounds=chunk)
    dt_seq = dt_plan = float("inf")
    for rep in range(reps):
        upto = chunk + (rep + 1) * rounds
        t0 = time.time()
        for ex in execs:
            ex.run(rounds=upto)
        dt_seq = min(dt_seq, time.time() - t0)
        t0 = time.time()
        pe.run(rounds=upto)
        dt_plan = min(dt_plan, time.time() - t0)
    seq_programs = sum(ex.compiled_programs() for ex in execs)

    traj_rounds = len(grid) * rounds
    for name, dt in (("sequential", dt_seq), ("bucketed", dt_plan)):
        results["runs"][name] = {
            "trajectories": len(grid), "rounds": rounds, "wall_s": dt,
            "traj_rounds_per_s": traj_rounds / dt,
            "s_per_traj_round": dt / traj_rounds}
    results["runs"]["sequential"]["compiled_programs"] = seq_programs
    results["runs"]["bucketed"]["compiled_programs"] = pe.compiled_programs()
    results["n_buckets"] = len(pe.plan.buckets)
    speedup = dt_seq / dt_plan
    results["speedup_bucketed_vs_sequential"] = speedup
    for name in ("sequential", "bucketed"):
        r = results["runs"][name]
        print(f"plan_{name},{r['s_per_traj_round']*1e6:.0f},"
              f"traj_rounds_per_s={r['traj_rounds_per_s']:.2f};"
              f"programs={r['compiled_programs']};"
              f"speedup={speedup if name == 'bucketed' else 1.0:.2f}")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
    return results


def bench_shard(arch: str = "flsim-logreg", n_traj: int = 16,
                n_devices: int = 4, n_clients: int = 8, rounds: int = 16,
                chunk: int = 4, n_items: int = 512, seed: int = 0,
                reps: int = 4, out_path: str = "BENCH_shard.json"):
    """Trajectory-rounds/sec for a device-parallel campaign: the S=16 seed
    grid sharded over a ``n_devices``-lane mesh vs the same campaign's
    1-device vmap, on a 4-device host (a CPU rehearsal fakes one with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4
    JAX_PLATFORMS=cpu``).

    Both paths run the *same* compiled vmap program over the same S lanes —
    the sharded one just places the leading sweep dim of every plane under a
    ``NamedSharding`` over ``lanes``, so each device advances S/n lanes with
    zero collectives. By the sharding determinism contract
    (tests/test_shard_sweep.py) the two produce bitwise-identical per-lane
    params, so the delta is pure device parallelism. The default grid is the
    paper's scale-experiment model (logreg, Fig. 12) under the **async**
    event scan: a long chain of small serial ops is exactly the program
    shape one CPU device cannot thread (no big batched gemms for intra-op
    parallelism to chew on), so concurrent per-device lane shards show the
    cleanest win — while a model whose stacked working set is memory-bound
    (the 1M-param MLP caveat bench_sweep documents) gains little on a
    bandwidth-starved 2-core runner, since fake devices share one memory
    bus. Timed regions interleave over ``reps`` repetitions and report each
    mode's best (same noisy-runner rationale as bench_plan). Writes
    ``out_path`` and prints one CSV row per mode.
    """
    import json

    from repro.core.jobs import load_job
    from repro.runtime.campaign import CampaignExecutor

    if jax.device_count() < n_devices:
        raise RuntimeError(
            f"bench_shard wants {n_devices} devices but only "
            f"{jax.device_count()} are visible; on CPU, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices} "
            "JAX_PLATFORMS=cpu before jax initializes")
    assert rounds % chunk == 0, \
        "rounds must be a multiple of chunk (keeps the timed region free " \
        "of remainder-length compiles)"

    raw = {
        "name": "bench-shard",
        "model": {"arch": arch},
        "dataset": {"dataset": "synthetic_vision", "n_items": n_items,
                    "distribution": {"partition": "dirichlet",
                                     "dirichlet_alpha": 0.5}},
        "strategy": {"strategy": "fedavg",
                     "train_params": {"n_clients": n_clients,
                                      "local_epochs": 1,
                                      "client_lr": 0.1,
                                      "mode": "async", "async_buffer": 8,
                                      "max_staleness": 8,
                                      "staleness_exponent": 0.5,
                                      "rounds": chunk + reps * rounds,
                                      "seed": seed,
                                      "rounds_per_launch": chunk}},
        "runtime": {"straggler_prob": 0.1, "duration_sigma": 0.25},
        "sweep": {"seeds": [seed + s for s in range(n_traj)]},
    }
    results = {"config": {"arch": arch, "n_traj": n_traj,
                          "n_devices": n_devices, "n_clients": n_clients,
                          "rounds": rounds, "chunk": chunk, "reps": reps,
                          "n_items": n_items, "seed": seed,
                          "backend": jax.default_backend(),
                          "device_count": jax.device_count()},
               "runs": {}}

    vm = CampaignExecutor(load_job(raw)).scaffold()
    sh = CampaignExecutor(load_job(raw), lane_devices=n_devices).scaffold()
    vm.run(rounds=chunk)                     # warm-up: compile + stage
    sh.run(rounds=chunk)
    dt_vm = dt_sh = float("inf")
    for rep in range(reps):
        upto = chunk + (rep + 1) * rounds
        t0 = time.time()
        vm.run(rounds=upto)
        dt_vm = min(dt_vm, time.time() - t0)
        t0 = time.time()
        sh.run(rounds=upto)
        dt_sh = min(dt_sh, time.time() - t0)

    traj_rounds = n_traj * rounds
    for name, dt in (("vmapped_1dev", dt_vm), ("sharded", dt_sh)):
        results["runs"][name] = {
            "trajectories": n_traj, "rounds": rounds, "wall_s": dt,
            "traj_rounds_per_s": traj_rounds / dt,
            "s_per_traj_round": dt / traj_rounds}
    speedup = dt_vm / dt_sh
    results["speedup_sharded_vs_vmapped"] = speedup
    for name in ("vmapped_1dev", "sharded"):
        r = results["runs"][name]
        print(f"shard_{name},{r['s_per_traj_round']*1e6:.0f},"
              f"traj_rounds_per_s={r['traj_rounds_per_s']:.2f};"
              f"speedup={speedup if name == 'sharded' else 1.0:.2f}")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
    return results


def bench_agg(n_params: int = 1 << 20, n_clients: int = 16,
              qblock: int = 256, reps: int = 30, seed: int = 0,
              out_path: str = "BENCH_agg.json"):
    """Fused int8 aggregation vs dequant-first, at the memory-bound
    1M-param MLP scale the sweep bench flagged (bench_sweep's docstring
    caveat: at that size a round is HBM-traffic-, not compute-, dominated —
    exactly the regime where reading each client byte once matters).

    One server reduce over C client sends in the kernel's packed layout
    ((C, N) int8 + (C, N/qblock) f32 scales — what ``compression: int8``
    runs actually aggregate every round/flush):

    - ``fused``         — ``ops._quant_agg_fused``: the unrolled
      dequant+weighted-sum XLA compiles to one pass; the (C, N) f32
      dequant never exists in memory.
    - ``dequant_first`` — ``ops._quant_agg_dequant_first``: materializes
      the full f32 dequant behind an ``optimization_barrier`` (identity on
      values, so the two are asserted bitwise equal here) before the same
      accumulation — the naive path's 4x write + 4x read-back traffic.

    Timed regions interleave over ``reps`` and report best-of (same noisy
    shared-runner rationale as bench_plan). Writes ``out_path`` with
    ``speedup_fused_vs_dequant`` — the bench gate's BENCH_agg contract
    (>= 1.5x) reads it.
    """
    import json

    from repro.kernels import ops

    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    n = n_params + (-n_params) % qblock
    qd = jax.random.randint(ks[0], (n_clients, n), -127, 128, jnp.int8)
    sc = jax.random.uniform(ks[1], (n_clients, n // qblock), jnp.float32,
                            1e-4, 1e-2)
    w = jax.random.uniform(ks[2], (n_clients,), jnp.float32)
    w = w / w.sum()

    fused = jax.jit(ops._quant_agg_fused)
    dequant = jax.jit(ops._quant_agg_dequant_first)
    a = jax.block_until_ready(fused(qd, sc, w))        # warm-up + compile
    b = jax.block_until_ready(dequant(qd, sc, w))
    assert (np.asarray(a) == np.asarray(b)).all(), \
        "fused and dequant-first paths diverged (bitwise contract)"

    dt = {"fused": float("inf"), "dequant_first": float("inf")}
    for _ in range(reps):
        for name, fn in (("fused", fused), ("dequant_first", dequant)):
            t0 = time.time()
            jax.block_until_ready(fn(qd, sc, w))
            dt[name] = min(dt[name], time.time() - t0)

    int8_mb = qd.size * 1 / 2**20
    results = {"config": {"n_params": n_params, "n_clients": n_clients,
                          "qblock": qblock, "reps": reps, "seed": seed,
                          "backend": jax.default_backend(),
                          "kernel_impl": ops.backend()},
               "runs": {}, "bitwise_equal": True}
    for name in ("fused", "dequant_first"):
        results["runs"][name] = {
            "best_s": dt[name],
            "agg_per_s": 1.0 / dt[name],
            "int8_GiBps": int8_mb / 1024 / dt[name]}
    speedup = dt["dequant_first"] / dt["fused"]
    results["speedup_fused_vs_dequant"] = speedup
    for name in ("fused", "dequant_first"):
        r = results["runs"][name]
        print(f"agg_{name},{r['best_s']*1e6:.0f},"
              f"int8_GiBps={r['int8_GiBps']:.2f};"
              f"speedup={speedup if name == 'fused' else 1.0:.2f}")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
    return results


def bench_telemetry(arch: str = "flsim-logreg", n_traj: int = 8,
                    n_clients: int = 8, rounds: int = 16, chunk: int = 1,
                    n_items: int = 512, seed: int = 0, reps: int = 4,
                    artifact_dir: str = "telemetry_smoke",
                    out_path: str = "BENCH_telemetry.json"):
    """Flight-recorder overhead on the S=8 seed sweep grid (bench_sweep's
    vmapped campaign shape) at chunk=1 — the recorder's worst case: every
    round is a chunk boundary, so the span/counter plumbing fires at its
    maximum rate relative to useful work.

    The same campaign runs twice — telemetry off (no ``telemetry:``
    section: the no-op recorder) and on (streaming ``telemetry.jsonl`` to
    ``artifact_dir``) — with a warm-up chunk each (compile excluded) and
    timed regions interleaved over ``reps`` repetitions, reporting each
    mode's best (noisy-runner rationale as bench_plan/bench_shard). The
    recorder is host-side only, so the two runs share compiled programs
    bitwise; the gate (benchmarks/report.py: ``speedup_on_vs_off >= 0.95``)
    is the ISSUE's <=5% overhead budget. Also exports ``artifact_dir``'s
    Chrome trace + prints the breakdown report, so the bench doubles as
    the telemetry smoke artifact for CI upload. Writes ``out_path``."""
    import json

    from repro.core.jobs import load_job
    from repro.runtime.campaign import CampaignExecutor
    from repro.telemetry import trace as trace_mod

    assert rounds % chunk == 0, \
        "rounds must be a multiple of chunk (keeps the timed region free " \
        "of remainder-length compiles)"

    def raw(telemetry=False):
        r = {
            "name": "bench-telemetry",
            "model": {"arch": arch},
            "dataset": {"dataset": "synthetic_vision", "n_items": n_items,
                        "distribution": {"partition": "dirichlet",
                                         "dirichlet_alpha": 0.5}},
            "strategy": {"strategy": "fedavg",
                         "train_params": {"n_clients": n_clients,
                                          "local_epochs": 1,
                                          "client_lr": 0.1,
                                          "rounds": chunk + reps * rounds,
                                          "seed": seed,
                                          "rounds_per_launch": chunk}},
            "sweep": {"seeds": [seed + s for s in range(n_traj)]},
        }
        if telemetry:
            r["telemetry"] = {"out_dir": artifact_dir}
        return r

    results = {"config": {"arch": arch, "n_traj": n_traj,
                          "n_clients": n_clients, "rounds": rounds,
                          "chunk": chunk, "reps": reps, "n_items": n_items,
                          "seed": seed, "backend": jax.default_backend()},
               "runs": {}}

    off = CampaignExecutor(load_job(raw())).scaffold()
    on = CampaignExecutor(load_job(raw(telemetry=True))).scaffold()
    off.run(rounds=chunk)                    # warm-up: compile + stage
    on.run(rounds=chunk)
    dt_off = dt_on = float("inf")
    for rep in range(reps):
        upto = chunk + (rep + 1) * rounds
        t0 = time.time()
        off.run(rounds=upto)
        dt_off = min(dt_off, time.time() - t0)
        t0 = time.time()
        on.run(rounds=upto)
        dt_on = min(dt_on, time.time() - t0)
    on.recorder.close()

    traj_rounds = n_traj * rounds
    for name, dt in (("telemetry_off", dt_off), ("telemetry_on", dt_on)):
        results["runs"][name] = {
            "trajectories": n_traj, "rounds": rounds, "wall_s": dt,
            "traj_rounds_per_s": traj_rounds / dt,
            "s_per_traj_round": dt / traj_rounds}
    speedup = dt_off / dt_on
    results["speedup_on_vs_off"] = speedup
    results["events"] = len(on.recorder.events)
    for name in ("telemetry_off", "telemetry_on"):
        r = results["runs"][name]
        print(f"telemetry_{name},{r['s_per_traj_round']*1e6:.0f},"
              f"traj_rounds_per_s={r['traj_rounds_per_s']:.2f};"
              f"speedup={speedup if name == 'telemetry_on' else 1.0:.2f}")
    if artifact_dir:
        trace_path = trace_mod.export(artifact_dir)
        print(f"trace: {trace_path}")
        print(trace_mod.report(artifact_dir))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
    return results


def bench_probes(arch: str = "flsim-logreg", n_traj: int = 8,
                 n_clients: int = 8, rounds: int = 16, chunk: int = 1,
                 local_epochs: int = 4, n_items: int = 1024, seed: int = 0,
                 reps: int = 4, artifact_dir: str = "probes_smoke",
                 out_path: str = "BENCH_probes.json"):
    """Round-probe overhead on the S=8 seed sweep grid at chunk=1 — the
    probe plane's worst case: probes ride the scan as extra outputs, and
    every round is a chunk boundary, so the drain (counter back-dating +
    probes.csv flush) fires at its maximum rate relative to useful work.

    ``local_epochs=4`` keeps the per-round *useful* work representative: a
    federated round canonically runs several local epochs per client
    (FedAvg's E), and the probe reductions are a fixed per-round cost —
    one extra pass over the already-materialized deltas regardless of how
    much training produced them. Benching against a one-batch round would
    measure the probes against a round that does almost nothing, which is
    the one configuration no real campaign uses.

    The same campaign runs twice — probes+telemetry off and on (probes are
    an observability feature: the realistic "on" cost includes the flight
    recorder that receives them) — with a warm-up chunk each (compile
    excluded) and timed regions interleaved over ``reps`` repetitions,
    reporting each mode's best. The two runs are bitwise-identical in
    params by the probe plane's contract; the gate (benchmarks/report.py:
    ``speedup_on_vs_off >= 0.9``) is the ISSUE's <=10% overhead budget.
    Also exports ``artifact_dir``'s Chrome trace (per-lane probe counter
    tracks) + probes.csv, the CI smoke artifacts. Writes ``out_path``."""
    import json

    from repro.core.jobs import load_job
    from repro.runtime.campaign import CampaignExecutor
    from repro.telemetry import trace as trace_mod

    assert rounds % chunk == 0, \
        "rounds must be a multiple of chunk (keeps the timed region free " \
        "of remainder-length compiles)"

    def raw(probes=False):
        r = {
            "name": "bench-probes",
            "model": {"arch": arch},
            "dataset": {"dataset": "synthetic_vision", "n_items": n_items,
                        "distribution": {"partition": "dirichlet",
                                         "dirichlet_alpha": 0.5}},
            "strategy": {"strategy": "fedavg",
                         "train_params": {"n_clients": n_clients,
                                          "local_epochs": local_epochs,
                                          "client_lr": 0.1,
                                          "rounds": chunk + reps * rounds,
                                          "seed": seed,
                                          "rounds_per_launch": chunk}},
            "sweep": {"seeds": [seed + s for s in range(n_traj)]},
        }
        if probes:
            r["probes"] = {"enabled": True, "out_dir": artifact_dir}
            r["telemetry"] = {"out_dir": artifact_dir}
        return r

    results = {"config": {"arch": arch, "n_traj": n_traj,
                          "n_clients": n_clients, "rounds": rounds,
                          "chunk": chunk, "reps": reps, "n_items": n_items,
                          "seed": seed, "backend": jax.default_backend()},
               "runs": {}}

    off = CampaignExecutor(load_job(raw())).scaffold()
    on = CampaignExecutor(load_job(raw(probes=True))).scaffold()
    off.run(rounds=chunk)                    # warm-up: compile + stage
    on.run(rounds=chunk)
    dt_off = dt_on = float("inf")
    for rep in range(reps):
        upto = chunk + (rep + 1) * rounds
        t0 = time.time()
        off.run(rounds=upto)
        dt_off = min(dt_off, time.time() - t0)
        t0 = time.time()
        on.run(rounds=upto)
        dt_on = min(dt_on, time.time() - t0)
    on.recorder.close()

    traj_rounds = n_traj * rounds
    for name, dt in (("probes_off", dt_off), ("probes_on", dt_on)):
        results["runs"][name] = {
            "trajectories": n_traj, "rounds": rounds, "wall_s": dt,
            "traj_rounds_per_s": traj_rounds / dt,
            "s_per_traj_round": dt / traj_rounds}
    speedup = dt_off / dt_on
    results["speedup_on_vs_off"] = speedup
    results["probe_rows"] = len(on.probe_rows)
    for name in ("probes_off", "probes_on"):
        r = results["runs"][name]
        print(f"probes_{name},{r['s_per_traj_round']*1e6:.0f},"
              f"traj_rounds_per_s={r['traj_rounds_per_s']:.2f};"
              f"speedup={speedup if name == 'probes_on' else 1.0:.2f}")
    if artifact_dir:
        trace_path = trace_mod.export(artifact_dir)
        print(f"trace: {trace_path}")
        print(trace_mod.report(artifact_dir))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
    return results


def bench_comms(arch: str = "flsim-logreg", n_traj: int = 8,
                n_clients: int = 8, rounds: int = 16, chunk: int = 1,
                local_epochs: int = 4, n_items: int = 1024, seed: int = 0,
                reps: int = 4, artifact_dir: str = "comms_smoke",
                out_path: str = "BENCH_comms.json"):
    """Comms-observatory overhead on the S=8 seed sweep grid at chunk=1 —
    the accounting plane's worst case: every round is a chunk boundary, so
    the per-lane host accountants, the counter drain, and the comms.csv
    flush all fire at their maximum rate relative to useful work
    (``local_epochs=4`` keeps the per-round useful work representative,
    same rationale as ``bench_probes``).

    The same campaign runs twice — comms+telemetry off and on (comms is an
    observability feature: the realistic "on" cost includes the flight
    recorder its counters stream into) — with a warm-up chunk each
    (compile excluded) and timed regions interleaved over ``reps``
    repetitions, reporting each mode's best. The two runs are bitwise
    identical in params by the comms plane's zero-device-code contract;
    the gate (benchmarks/report.py: ``speedup_on_vs_off >= 0.95``) is the
    ISSUE's <=5% host-accounting budget. Also exports ``artifact_dir``'s
    Chrome trace (per-lane ``comms:*`` counter tracks) + comms.csv, the CI
    smoke artifacts. Writes ``out_path``."""
    import json

    from repro.core.jobs import load_job
    from repro.runtime.campaign import CampaignExecutor
    from repro.telemetry import trace as trace_mod

    assert rounds % chunk == 0, \
        "rounds must be a multiple of chunk (keeps the timed region free " \
        "of remainder-length compiles)"

    def raw(comms=False):
        r = {
            "name": "bench-comms",
            "model": {"arch": arch},
            "dataset": {"dataset": "synthetic_vision", "n_items": n_items,
                        "distribution": {"partition": "dirichlet",
                                         "dirichlet_alpha": 0.5}},
            "strategy": {"strategy": "fedavg",
                         "train_params": {"n_clients": n_clients,
                                          "local_epochs": local_epochs,
                                          "client_lr": 0.1,
                                          "rounds": chunk + reps * rounds,
                                          "seed": seed,
                                          "rounds_per_launch": chunk}},
            "sweep": {"seeds": [seed + s for s in range(n_traj)]},
        }
        if comms:
            r["comms"] = {"enabled": True, "out_dir": artifact_dir}
            r["telemetry"] = {"out_dir": artifact_dir}
        return r

    results = {"config": {"arch": arch, "n_traj": n_traj,
                          "n_clients": n_clients, "rounds": rounds,
                          "chunk": chunk, "reps": reps, "n_items": n_items,
                          "seed": seed, "backend": jax.default_backend()},
               "runs": {}}

    off = CampaignExecutor(load_job(raw())).scaffold()
    on = CampaignExecutor(load_job(raw(comms=True))).scaffold()
    off.run(rounds=chunk)                    # warm-up: compile + stage
    on.run(rounds=chunk)
    dt_off = dt_on = float("inf")
    for rep in range(reps):
        upto = chunk + (rep + 1) * rounds
        t0 = time.time()
        off.run(rounds=upto)
        dt_off = min(dt_off, time.time() - t0)
        t0 = time.time()
        on.run(rounds=upto)
        dt_on = min(dt_on, time.time() - t0)
    on.recorder.close()

    traj_rounds = n_traj * rounds
    for name, dt in (("comms_off", dt_off), ("comms_on", dt_on)):
        results["runs"][name] = {
            "trajectories": n_traj, "rounds": rounds, "wall_s": dt,
            "traj_rounds_per_s": traj_rounds / dt,
            "s_per_traj_round": dt / traj_rounds}
    speedup = dt_off / dt_on
    results["speedup_on_vs_off"] = speedup
    results["comms_rows"] = len(on.comms_rows)
    for name in ("comms_off", "comms_on"):
        r = results["runs"][name]
        print(f"comms_{name},{r['s_per_traj_round']*1e6:.0f},"
              f"traj_rounds_per_s={r['traj_rounds_per_s']:.2f};"
              f"speedup={speedup if name == 'comms_on' else 1.0:.2f}")
    if artifact_dir:
        trace_path = trace_mod.export(artifact_dir)
        print(f"trace: {trace_path}")
        print(trace_mod.report(artifact_dir))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
    return results


def bench_stream(arch: str = "flsim-logreg", n_clients: int = 256,
                 cohort: int = 16, max_cohort: int = 20, rounds: int = 16,
                 chunk: int = 4, reps: int = 3, n_items: int = 2048,
                 local_epochs: int = 2, seed: int = 0,
                 population: int = 100_000, pop_rounds: int = 4,
                 out_path: str = "BENCH_stream.json"):
    """The streaming client plane: (a) double-buffered per-chunk staging
    vs the resident device gather on a config that fits in memory — same
    compiled program, same bytes, so the runs are bitwise identical and
    the only question is throughput (gated >= 0.9x in
    benchmarks/report.py: the prefetch thread must hide the host
    assembly); (b) a synthetic population too large to stage resident
    (``population`` clients) training through the sync driver, reporting
    the peak staged working set against the resident-equivalent bytes off
    the ``staged_bytes`` telemetry counters. Writes ``out_path``."""
    import json
    import tempfile

    from repro.core.jobs import load_job
    from repro.runtime.executor import Executor
    from repro.telemetry.recorder import read_events

    assert rounds % chunk == 0, \
        "rounds must be a multiple of chunk (keeps the timed region free " \
        "of remainder-length compiles)"

    def raw(streaming):
        return {
            "name": "bench-stream",
            "model": {"arch": arch},
            "dataset": {"dataset": "synthetic_vision", "n_items": n_items,
                        "distribution": {"partition": "dirichlet",
                                         "dirichlet_alpha": 0.5}},
            "strategy": {"strategy": "fedavg",
                         "train_params": {"n_clients": n_clients,
                                          "cohort": cohort,
                                          "max_cohort": max_cohort,
                                          "streaming": streaming,
                                          "local_epochs": local_epochs,
                                          "client_lr": 0.1,
                                          "rounds": chunk + reps * rounds,
                                          "seed": seed,
                                          "rounds_per_launch": chunk}},
            "runtime": {"straggler_prob": 0.1,
                        "straggler_overprovision": 1.25},
        }

    results = {"config": {"arch": arch, "n_clients": n_clients,
                          "cohort": cohort, "max_cohort": max_cohort,
                          "rounds": rounds, "chunk": chunk, "reps": reps,
                          "n_items": n_items, "population": population,
                          "backend": jax.default_backend()},
               "runs": {}}

    res = Executor(load_job(raw(False))).scaffold()
    stm = Executor(load_job(raw(True))).scaffold()
    res.run(rounds=chunk)                    # warm-up: compile + stage
    stm.run(rounds=chunk)
    dt_res = dt_stm = float("inf")
    for rep in range(reps):
        upto = chunk + (rep + 1) * rounds
        t0 = time.time()
        res.run(rounds=upto)
        dt_res = min(dt_res, time.time() - t0)
        t0 = time.time()
        stm.run(rounds=upto)
        dt_stm = min(dt_stm, time.time() - t0)
    for a, b in zip(jax.tree.leaves(res.state["params"]),
                    jax.tree.leaves(stm.state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name, dt in (("resident", dt_res), ("streaming", dt_stm)):
        results["runs"][name] = {
            "rounds": rounds, "wall_s": dt, "rounds_per_s": rounds / dt,
            "s_per_round": dt / rounds}
    speedup = dt_res / dt_stm
    results["speedup_streaming_vs_resident"] = speedup
    for name in ("resident", "streaming"):
        r = results["runs"][name]
        print(f"stream_{name},{r['s_per_round']*1e6:.0f},"
              f"rounds_per_s={r['rounds_per_s']:.2f};"
              f"speedup={speedup if name == 'streaming' else 1.0:.2f}")

    # (b) the population that cannot be staged resident
    tdir = tempfile.mkdtemp(prefix="bench-stream-")
    pop_job = load_job({
        "name": "bench-stream-pop",
        "model": {"arch": arch},
        "dataset": {"dataset": "synthetic_population",
                    "n_items": population, "items_per_client": 8},
        "strategy": {"strategy": "fedavg",
                     "train_params": {"n_clients": population,
                                      "cohort": cohort,
                                      "max_cohort": max_cohort,
                                      "streaming": True,
                                      "client_lr": 0.1,
                                      "rounds": chunk + pop_rounds,
                                      "seed": seed,
                                      "rounds_per_launch": chunk}},
        "runtime": {"straggler_prob": 0.1,
                    "straggler_overprovision": 1.25},
        "telemetry": {"enabled": True, "out_dir": tdir},
    })
    ex = Executor(pop_job).scaffold()
    ex.run(rounds=chunk)                     # warm-up chunk
    t0 = time.time()
    ex.run(rounds=chunk + pop_rounds)
    dt_pop = time.time() - t0
    ex.recorder.close()
    slabs = [e["values"] for e in read_events(tdir)
             if e.get("kind") == "counter"
             and e.get("name") == "staged_bytes"
             and "slab" in e.get("values", {})]
    peak = max(v["peak_slab"] for v in slabs)
    resident_equiv = max(v["resident_equiv"] for v in slabs)
    results["population_run"] = {
        "n_clients": population, "rounds": pop_rounds, "wall_s": dt_pop,
        "rounds_per_s": pop_rounds / dt_pop,
        "peak_slab_bytes": peak, "resident_equiv_bytes": resident_equiv,
        "working_set_ratio": peak / resident_equiv}
    print(f"stream_population,{dt_pop/pop_rounds*1e6:.0f},"
          f"clients={population};peak_slab_MiB={peak/2**20:.1f};"
          f"resident_equiv_MiB={resident_equiv/2**20:.1f};"
          f"working_set_ratio={peak/resident_equiv:.6f}")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
    return results


def run_fl(fl: FLConfig, arch: str = "flsim-cnn", n_items: int = 768,
           rounds: int = 8, batch: int = 16, steps: int = 1,
           eval_n: int = 256, arch_cfg=None, run_name: str = "run"):
    cfg = arch_cfg or get_config(arch)
    if cfg.name == "flsim-cnn":
        cfg = cfg.replace(d_model=32, d_ff=64)      # CPU-scale channels
    model = model_zoo.build(cfg)
    strategy = get_strategy(fl)
    decentralized = fl.topology == "decentralized"
    round_fn = jax.jit(lambda s, b, w, r: build_spatial_round(
        model, strategy, fl)(AxisCtx(), s, b, w, r))

    from repro.models.small import input_shape
    data = SyntheticVision(n_items=n_items, shape=input_shape(cfg),
                           seed=fl.seed)
    x, y, parts = data.distribute_into_chunks(fl.partition, fl.n_clients,
                                              fl.dirichlet_alpha)
    state = init_state(model, strategy, fl, determinism.root_key(fl.seed),
                       n_clients_local=fl.n_clients,
                       decentralized=decentralized)
    logger = PerformanceLogger(run_name=run_name)
    test = {"x": jnp.asarray(x[:eval_n]), "y": jnp.asarray(y[:eval_n])}
    root = determinism.root_key(fl.seed)
    comm = comm_bytes_per_round(state["params"], fl)
    batch = min(batch, max(min(len(p) for p in parts), 1))  # uniform shapes
    for r in range(rounds):
        bs = [SyntheticVision.client_batches(
            x, y, parts[c], batch, steps,
            seed=fl.seed * 7919 + c + r * 104729)[0]
            for c in range(fl.n_clients)]
        b = jax.tree.map(lambda *t: np.stack(t), *bs)
        w = jnp.asarray([len(p) for p in parts], jnp.float32)
        t0 = time.time()
        state, m = round_fn(state, b, w, determinism.round_key(root, r))
        dt = time.time() - t0
        params_eval = state["params"]
        if decentralized:
            params_eval = jax.tree.map(lambda t: t.mean(0), params_eval)
        acc = float(model.accuracy(params_eval, test))
        logger.log_round(r, loss=float(m["loss"]), accuracy=acc,
                         round_s=dt, comm_mb=comm / 2**20)
    return state, logger
