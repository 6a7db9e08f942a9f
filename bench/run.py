"""One run of one benchmark cell on the chip.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's job through the public entry (``load_job``, then
``Executor`` or ``CampaignExecutor`` and ``scaffold``) and drives it from
the seed through its first three calls of ``run`` (one call = one chunk of
``rounds_per_launch`` rounds); the first compiles, the others warm. The
window then keeps calling ``run`` on the same executor for ``--seconds``,
timing each call on the host clock (a call returns after the state is
ready and the chunk-boundary work is done).

After the window the peak device memory is read, the program is freed and
the plain reference (bench/reference.py, with the configuration's family in
bench/families/) recomputes the first three calls; bench/compare.py decides
``correct``. ``--trace 1`` turns the flight recorder on, traces the first
seconds of the window with the JAX profiler, reduces the trace by op, by
``fl.*`` scope and by span (bench/trace.py, bench/scopes.py) and reports
the cell's per-layer metrics (bench/metrics/<name>.py) instead of the
end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit.
A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

STEPS = 3                 # calls of set-up that the reference follows
TRACE_SECONDS = 4.0       # profiled part of a --trace 1 window
SWAP_VARS = ("REPRO_KERNEL_IMPL", "REPRO_QUANT_AGG")


def device_info(chips: int, on_chip: bool) -> dict:
    """The device as JAX reports it; refuses anything but enough TPUs."""
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if on_chip:
        if info["platform"] != "tpu":
            raise SystemExit(f"bench: no TPU, JAX found {info['platform']}")
        if info["count"] < chips:
            raise SystemExit(f"bench: the cell needs {chips} chips, JAX "
                             f"found {info['count']}")
        for var in SWAP_VARS:
            if var in os.environ:
                raise SystemExit(f"bench: {var} is set; it swaps the "
                                 "aggregation path out")
    return info


def host_params(ex, lanes: int) -> list:
    """Per lane, the parameter tree's leaves on the host by path."""
    from bench.reference import flat
    params = flat(ex.state["params"])
    if lanes == 1 and not getattr(ex, "S", 0):
        return [params]
    return [{k: v[s] for k, v in params.items()} for s in range(lanes)]


def round_losses(ex, lanes: int, since: int) -> list:
    """Per-lane losses of the rounds logged since row ``since``."""
    if getattr(ex, "S", 0):
        rows = ex.results[since:]
        out = [[] for _ in range(lanes)]
        for r in sorted(rows, key=lambda r: (r["traj"], r["round"])):
            out[r["traj"]].append(r["loss"])
        return out
    return [[r["loss"] for r in ex.logger.rows[since:]]]


def rows_logged(ex) -> int:
    return len(ex.results) if getattr(ex, "S", 0) else len(ex.logger.rows)


def path_checks(ex, shape: dict, qframe: dict, backend: str) -> dict:
    """What the cell's path must be, from its files, against what ran."""
    want_impl = {"pallas": "pallas", "interpret": "interpret",
                 "jnp": "jnp-fused"}[backend]
    got = {"quant_agg_calls": qframe["calls"],
           "batched_fallbacks": qframe["batched_fallbacks"],
           "last_impl": qframe["last_impl"]}
    if shape["int8"] and shape["lanes"] == 1:
        ok = (qframe["calls"] > 0 and qframe["batched_fallbacks"] == 0
              and qframe["last_impl"] == want_impl)
        want = {"last_impl": want_impl, "batched_fallbacks": 0}
    elif shape["int8"]:
        ok = qframe["batched_fallbacks"] > 0
        want = {"batched_fallbacks": ">0"}
    else:
        ok = qframe["calls"] == 0
        want = {"quant_agg_calls": 0}
    if shape["ragged_slots"]:
        stager = ex.stager
        slots = [lane.k_slots for lane in getattr(stager, "lanes",
                                                  [stager])]
        got["ragged_slots"] = slots
        want["ragged_slots"] = [shape["ragged_slots"]] * shape["lanes"]
        ok = ok and slots == want["ragged_slots"]
    return {"path": got, "want": want, "path_ok": bool(ok)}


class CompileCount:
    """Counts JAX's traces and backend compiles (cache hits included)
    while ``on``: the window should hold none. One listener per process
    serves the newest counter."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")
    live = None

    def __init__(self):
        import jax
        if CompileCount.live is None:
            jax.monitoring.register_event_duration_secs_listener(
                CompileCount._event)
        self.on, self.n = False, 0
        CompileCount.live = self

    @staticmethod
    def _event(event, duration, **kwargs):
        c = CompileCount.live
        if c is not None and c.on and event in CompileCount.EVENTS:
            c.n += 1


def call_stats(times: list) -> dict:
    """The spread of the window's call times, to tell a stall from a
    uniformly slower run."""
    import numpy as np
    if not times:
        return {}
    t = np.asarray(times)
    med = float(np.median(t))
    return {"call_s_median": med, "call_s_max": float(t.max()),
            "calls_over_2x_median": int((t > 2 * med).sum()),
            "call_s_thirds": [float(np.sum(c)) / len(c)
                              for c in np.array_split(t, 3) if len(c)]}


def reduce_trace(compact: dict):
    """The traced window's reductions (``bench/trace.py`` and, by scope and
    span, ``bench/scopes.py``) as one dict, and the result's breakdown;
    (None, None) when the trace holds nothing to read."""
    from bench import scopes
    from bench import trace as tracelib
    reduced = tracelib.reduce(compact)
    if reduced is None:
        return None, None
    reduced.update(scopes.reduce(compact))
    return reduced, {k: reduced[k] for k in ("device_ops", "idle_gaps",
                                             "scopes", "idle_spans")}


def per_layer(cell, ctx) -> dict:
    out = {}
    for m in cell["per_layer"]:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def first_steps(cell: dict, seed: int, trace: bool = False):
    """Build the cell's executor from the seed and drive it through its
    first ``STEPS`` calls: -> (executor, {"losses", "params"}) with the
    parameters before step 1, after step 1 and after the last."""
    from bench import cells
    from repro.core.jobs import load_job
    from repro.runtime.campaign import CampaignExecutor
    from repro.runtime.executor import Executor

    shape = cells.shape(cell)
    rpl, lanes = shape["rounds_per_call"], shape["lanes"]
    job = load_job(cells.job_dict(cell, seed, telemetry=trace))
    if job.sweep is not None:
        ex = CampaignExecutor(job, lane_devices=cell["chips"]
                              if cell["chips"] > 1 else 0)
    else:
        ex = Executor(job)
    ex.scaffold()
    kept = [[p] for p in host_params(ex, lanes)]
    for step in range(1, STEPS + 1):
        ex.run(rounds=ex.round_idx + rpl)
        if step in (1, STEPS):
            for lane, p in zip(kept, host_params(ex, lanes)):
                lane.append(p)
    return ex, {"losses": round_losses(ex, lanes, 0), "params": kept}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float | None = None, on_chip: bool = True,
             out=None, err=None) -> dict:
    """One run of ``cell``; prints the path line and the result line to
    ``out`` and the compared numbers to ``err``; returns the result."""
    out, err = out or sys.stdout, err or sys.stderr
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    import numpy as np

    from bench import cells, compare, costs, reference, scopes
    from bench import trace as tracelib

    dev = device_info(cell["chips"], on_chip)
    if on_chip:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        if trace:
            # op metadata (the fl.* scopes) is left out of the cache key,
            # so a cached program would show another build's scopes
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key", True)
    from repro.kernels import ops

    shape = cells.shape(cell)
    rpl, lanes = shape["rounds_per_call"], shape["lanes"]
    horizon = int(cell["traffic"]["horizon_rounds"])
    compiles = CompileCount()
    with ops.quant_agg_scope() as qframe:
        ex, prog = first_steps(cell, seed, trace)

        since = rows_logged(ex)
        profile_dir = tempfile.mkdtemp(prefix="bench-trace-") \
            if trace else None
        tracing = False
        times = []
        compiles.on = True
        t_window = time.perf_counter()
        setup_s = t_window - t_start
        while ex.round_idx + rpl <= horizon:
            t0 = time.perf_counter()
            if trace and not times:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0   # TraceMe spans only
                jax.profiler.start_trace(profile_dir, profiler_options=opts)
                tracing = True
            with jax.profiler.TraceAnnotation(tracelib.CHUNK):
                ex.run(rounds=ex.round_idx + rpl)
            t1 = time.perf_counter()
            times.append(t1 - t0)
            if tracing and t1 - t_window >= min(TRACE_SECONDS, seconds):
                jax.profiler.stop_trace()
                tracing = False
            if t1 - t_window >= seconds:
                break
        if tracing:
            jax.profiler.stop_trace()
        window_s = time.perf_counter() - t_window
        compiles.on = False
        losses = np.asarray(round_losses(ex, lanes, since), np.float64)
        spans = list(ex.recorder.events) if trace else []
        path = path_checks(ex, shape, dict(qframe), ops.backend())

    stats = [d.memory_stats() or {} for d in jax.devices()[:cell["chips"]]]
    dev["memory_peak_bytes"] = max(int(s.get("peak_bytes_in_use", 0))
                                   for s in stats)
    calls = len(times)
    attempted = calls * shape["updates_per_call"]
    failed = int((~np.isfinite(losses)).sum()) * shape["updates_per_round"] \
        // max(lanes, 1)
    print(json.dumps(_plain({"path": path["path"], "want": path["want"],
                             "path_ok": path["path_ok"],
                             "window_calls": calls, "window_s": window_s,
                             "window_compiles": compiles.n,
                             **call_stats(times)})),
          file=out, flush=True)

    breakdown = None
    if trace:
        reduced, breakdown = reduce_trace(
            scopes.from_xplane(profile_dir, cell["chips"]))
        shutil.rmtree(profile_dir, ignore_errors=True)
        ctx = {"trace": reduced, "spans": spans, "window_calls": calls,
               "cell": cell, "shape": shape,
               "peaks": costs.peaks(dev["kind"]) if on_chip
               else {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
        metrics = per_layer(cell, ctx)
        if reduced is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
    else:
        metrics = {
            "client_updates_per_s": {"value": attempted / sum(times),
                                     "unit": "updates/s"},
            "peak_hbm_gb": {"value": dev["memory_peak_bytes"] / 1e9,
                            "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        wanted = {m["name"] for m in cell["end_to_end"]}
        metrics = {k: v for k, v in metrics.items() if k in wanted}

    del ex
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference.run(cell, seed, steps=STEPS,
                        precision=cells.reference_precision(cell))
    print(f"reference_s {time.perf_counter() - t_ref}", file=err, flush=True)
    checks = compare.judge(compare.numbers(prog, ref), cell["limits"])
    correct = path["path_ok"] and all(c["ok"] for c in checks) and \
        failed == 0
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = [{k: c[k] for k in ("name", "value", "limit")}
                        for c in checks] + [
        {"name": "path_ok", "value": int(path["path_ok"]), "limit": 1}]
    for c in result["checks"]:
        print(f"check {c['name']} {c['value']} limit {c['limit']}",
              file=err, flush=True)
    print(json.dumps(_plain(result), allow_nan=False), file=out,
          flush=True)
    return result


def _plain(x):
    """JSON-safe copy: numpy scalars as numbers, non-finite floats as None."""
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if hasattr(x, "item") and not isinstance(x, (str, bytes)):
        x = x.item()
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench.cells import load_cell
    run_cell(load_cell(args.workload), args.seed, args.seconds,
             bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    main()
