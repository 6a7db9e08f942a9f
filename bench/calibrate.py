"""Readings that set the limits of ``correct`` (bench/limits/<cell>.json).

  python3 bench/calibrate.py --workload <cell> --seeds 12 --first-seed <n>

For each seed, the program's first three calls (as ``bench/run.py``'s
set-up makes them) against the plain reference: the lower readings. For
the first ``--control`` seeds also the control, the reference computed in
bfloat16, and a planted fault, the reference trained on the first half of
each batch, each against the float32 reference: the upper readings. A step
that leaves the state unchanged reads 1 on the change numbers by their
definition and needs no run. The float32 reference, and the fault with
it, compute their matrix products at ``--precision`` (the cell's
``reference_precision``, see bench/cells.py); with ``--witness 1`` the
reference at the other precision (``default`` or ``highest``) is read on
the same seeds too. One process, so the program compiles once.

Prints one JSON line per reading, then a summary line: per number, the
largest program and witness readings and the smallest control and fault
readings.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--precision", choices=("highest", "default"),
                    default=None)
    ap.add_argument("--witness", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench import compare, reference, run
    from bench.cells import load_cell, reference_precision
    from repro.launch.compile_cache import enable_compile_cache

    cell = load_cell(args.workload)
    prec = args.precision or reference_precision(cell)
    other = "default" if prec == "highest" else "highest"
    witness = f"ref_{other}"
    kinds = [("control", {"dtype": jnp.bfloat16}),
             ("half_batch", {"half_batch": True, "precision": prec})]
    if args.witness:
        kinds.append((witness, {"precision": other}))
    run.device_info(cell["chips"], True)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = open(args.out, "a") if args.out else None
    readings = {"program": [], "control": [], "half_batch": [],
                witness: []}

    def by_lane(a, b):
        return [compare.numbers({"losses": [la], "params": [pa]},
                                {"losses": [lb], "params": [pb]})
                for la, pa, lb, pb in zip(a["losses"], a["params"],
                                          b["losses"], b["params"])]

    def emit(kind, seed, nums, lanes=None):
        row = {"cell": cell["name"], "kind": kind, "seed": seed, **nums}
        if lanes and len(lanes) > 1:
            row["lanes"] = lanes
        readings[kind].append(nums)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        ex, prog = run.first_steps(cell, seed)
        del ex
        gc.collect()
        ref = reference.run(cell, seed, steps=run.STEPS, precision=prec)
        emit("program", seed, compare.numbers(prog, ref),
             by_lane(prog, ref))
        if i < args.control:
            for kind, kw in kinds:
                got = reference.run(cell, seed, steps=run.STEPS, **kw)
                emit(kind, seed, compare.numbers(got, ref),
                     by_lane(got, ref))
    summary = {"cell": cell["name"], "kind": "summary", "precision": prec}
    for name in compare.NUMBERS:
        summary[name] = {
            "lower": max(r[name] for r in readings["program"]),
            **{kind: min(r[name] for r in readings[kind])
               for kind in ("control", "half_batch") if readings[kind]}}
        if readings[witness]:
            summary[name][witness] = max(r[name] for r in readings[witness])
    line = json.dumps(summary)
    print(line, flush=True)
    if out:
        out.write(line + "\n")
        out.close()


if __name__ == "__main__":
    main()
