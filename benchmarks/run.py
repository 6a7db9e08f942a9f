"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. The roofline table (our §Perf
artifact) is appended from cached dry-run results when present.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig8,...]
"""
import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def roofline_table():
    res = pathlib.Path(__file__).resolve().parents[1] / "results" / "roofline"
    rows = []
    if not res.exists():
        print("roofline,0,run `python -m benchmarks.roofline` first")
        return rows
    for f in sorted(res.glob("*.json")):
        r = json.loads(f.read_text())
        name = f"roofline_{r['arch']}_{r['shape']}"
        bound = max(r["compute_s"], r["memory_s"], r["collective_s"])
        print(f"{name},{bound*1e6:.0f},"
              f"dom={r['dominant']};useful={r['useful_ratio']};"
              f"roof={r['roofline_fraction']};mem_GiB={r['memory_peak_GiB']}")
        rows.append(r)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer rounds / smaller sizes")
    ap.add_argument("--only", default="all")
    args = ap.parse_args()
    selected = (args.only != "all") and args.only.split(",")
    from benchmarks import figures, flbench
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    q = args.quick
    jobs = {
        # --quick keeps the flsim_small config shape (the host-overhead
        # share depends on it) and only cuts the timed rounds
        "driver": lambda: flbench.bench_driver(rounds=10 if q else 20),
        "async": lambda: flbench.bench_async(
            events=64 if q else 256, chunk_events=16 if q else 64),
        # S=8 seeds vmapped vs sequential; --quick keeps S (the speedup is
        # the claim) and only cuts the timed rounds
        "sweep": lambda: flbench.bench_sweep(rounds=8 if q else 16),
        # heterogeneous strategy x seed grid, bucketed-vmap vs sequential;
        # --quick keeps the grid (bucketing is the claim), cuts the rounds
        "plan": lambda: flbench.bench_plan(rounds=8 if q else 16),
        # S=16 seed grid sharded over a 4-lane device mesh vs 1-device
        # vmap; --quick keeps S and the mesh (the speedup is the claim).
        # Selecting it explicitly fails hard without 4 devices; only under
        # the implicit "all" does a short host skip it, so the other
        # benches still run. A CPU rehearsal brings its own fake devices:
        # XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu
        "shard": lambda: (
            flbench.bench_shard(rounds=8 if q else 16, reps=3 if q else 4)
            if selected or jax.device_count() >= 4 else
            print("shard,0,skipped: needs 4 devices")),
        # fused int8 dequant+weighted-sum vs dequant-first materialize at
        # the memory-bound 1M-param scale; --quick keeps the shape (the
        # traffic ratio is the claim) and only cuts the timed reps
        "agg": lambda: flbench.bench_agg(reps=10 if q else 30),
        # flight-recorder overhead at chunk=1 (worst case: a boundary per
        # round); --quick keeps the S=8 grid and cuts rounds/reps. Also
        # writes the telemetry_smoke/ trace artifacts CI uploads
        "telemetry": lambda: flbench.bench_telemetry(
            rounds=8 if q else 16, reps=3 if q else 4),
        # round-probe + recorder overhead at chunk=1 (worst case: drain at
        # every boundary); --quick keeps the S=8 grid and cuts rounds/reps.
        # Also writes the probes_smoke/ trace + probes.csv CI uploads
        "probes": lambda: flbench.bench_probes(
            rounds=8 if q else 16, reps=3 if q else 4),
        # comms-observatory + recorder overhead at chunk=1 (worst case: the
        # host accountants + drain run at every boundary); --quick keeps
        # the S=8 grid and cuts rounds/reps. Also writes the comms_smoke/
        # trace + comms.csv CI uploads
        "comms": lambda: flbench.bench_comms(
            rounds=8 if q else 16, reps=3 if q else 4),
        # streaming vs resident slab staging throughput (the double
        # buffer must hide the host assembly), plus the 10^5-client
        # population working-set demo; --quick keeps the cohort geometry
        # (the overlap is the claim) and cuts rounds + the population
        "stream": lambda: flbench.bench_stream(
            rounds=8 if q else 16, reps=2 if q else 3,
            population=20_000 if q else 100_000),
        "fig8": lambda: figures.fig8_frameworks(rounds=4 if q else 8),
        "fig9": lambda: figures.fig9_agnosticism(rounds=4 if q else 8),
        "fig10": lambda: figures.fig10_multiworker(rounds=3 if q else 6),
        "fig11": lambda: figures.fig11_topologies(rounds=4 if q else 8),
        "tab12": lambda: figures.tab12_reproducibility(rounds=3 if q else 5),
        "fig12": lambda: figures.fig12_scale(
            rounds=2 if q else 3, sizes=(100, 250) if q else
            (100, 250, 500, 1000)),
        "roofline": roofline_table,
    }
    only = selected or list(jobs)
    print("name,us_per_call,derived")
    for name in only:
        jobs[name]()


if __name__ == "__main__":
    main()
