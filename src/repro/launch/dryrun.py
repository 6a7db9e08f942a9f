import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces:
- proof of compilation on the production meshes (16x16 and 2x16x16),
- memory_analysis (fits-on-chip evidence),
- cost_analysis flops/bytes,
- the collective schedule parsed from the compiled HLO.

``--layers k`` compiles with a truncated layer stack; the roofline harness
compiles two small depths and extrapolates per-layer costs (XLA's CPU cost
analysis counts while-loop bodies once — see benchmarks/roofline.py).

Results are cached as JSON under results/dryrun/.
"""
import argparse
import dataclasses
import json
import pathlib
import re
import sys
import time
from collections import Counter

import jax

from repro.configs.base import (ARCHS, SHAPES, get_config, shapes_for)
from repro.launch import mesh as mesh_mod
from repro.launch import steps as steps_mod

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}


_SHAPE_PAT = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(segment: str) -> int:
    nbytes = 0
    for dt, dims in _SHAPE_PAT.findall(segment):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _DTYPE_BYTES[dt]
    return nbytes


def _group_size(line: str) -> int:
    i = line.find("replica_groups=")
    if i < 0:
        return 2
    seg = line[i:i + 4000]
    # forms: {{0,1,2,...},{...}} or [16,32]<=[...] (iota groups)
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", seg)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9, ]*)\}", seg)
    if m:
        return m.group(1).count(",") + 1
    return 2


def collective_bytes(hlo_text: str) -> dict:
    """Per-chip collective traffic from the (post-SPMD) compiled HLO.

    Shapes in the compiled module are PER-DEVICE. For each collective op the
    RESULT shape bytes and replica-group size g give the estimated per-chip
    link traffic: AG/A2A ~ result*(g-1)/g, AR ~ 2*result*(g-1)/g,
    RS ~ result*(g-1), permute ~ result. while-loop bodies appear once (the
    roofline harness scales by trip count via depth extrapolation)."""
    out = {c: 0.0 for c in COLLECTIVES}
    raw = {c: 0 for c in COLLECTIVES}
    counts = Counter()
    for line in hlo_text.splitlines():
        ls = line.lstrip()
        if not (ls.startswith("%") or ls.startswith("ROOT")):
            continue
        eq = ls.find(" = ")
        if eq < 0:
            continue
        rhs = ls[eq + 3:]
        kind = None
        for c in COLLECTIVES:
            j = rhs.find(c + "(")
            if j < 0:
                j = rhs.find(c + "-start(")
            if j >= 0:
                kind = c
                type_seg = rhs[:j]
                break
        if kind is None:
            continue
        counts[kind] += 1
        nbytes = _shape_bytes(type_seg)
        g = _group_size(line)
        raw[kind] += nbytes
        if kind in ("all-gather", "all-to-all"):
            out[kind] += nbytes * (g - 1) / g
        elif kind == "all-reduce":
            out[kind] += 2 * nbytes * (g - 1) / g
        elif kind == "reduce-scatter":
            out[kind] += nbytes * (g - 1)
        else:
            out[kind] += nbytes
    return {"traffic_bytes": out, "result_bytes": raw, "counts": dict(counts)}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             layers: int | None = None, verbose: bool = True) -> dict:
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    if layers:
        kw = {"n_layers": layers}
        if cfg.family == "encdec":
            kw["n_enc_layers"] = layers
        cfg = cfg.replace(**kw)
    shape = SHAPES[shape_name]
    t0 = time.time()
    built = steps_mod.make_step_from_cfg(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(built.fn,
                          donate_argnums=built.donate).lower(*built.inputs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    coll = collective_bytes(txt)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "layers": layers or cfg.n_layers,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "args_GiB": ma.argument_size_in_bytes / 2**30,
            "output_GiB": ma.output_size_in_bytes / 2**30,
            "temp_GiB": ma.temp_size_in_bytes / 2**30,
            "peak_GiB": (ma.argument_size_in_bytes
                         + ma.temp_size_in_bytes) / 2**30,
        },
        "cost": {"flops": ca.get("flops", 0.0),
                 "bytes_accessed": ca.get("bytes accessed", 0.0)},
        "collectives": coll,
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {rec['mesh']} L={rec['layers']}] "
              f"compile {t_compile:.1f}s  args {rec['memory']['args_GiB']:.2f}G "
              f"temp {rec['memory']['temp_GiB']:.2f}G  "
              f"flops {rec['cost']['flops']:.3e}  "
              f"coll {coll['counts']}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="truncate layer stacks (roofline extrapolation)")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    RESULTS.mkdir(parents=True, exist_ok=True)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch in archs:
        names = shapes_for(arch) if args.shape == "all" else args.shape.split(",")
        for shape_name in names:
            if shape_name not in shapes_for(arch):
                continue
            for mp in meshes:
                key = f"{arch}__{shape_name}__{'mp' if mp else 'sp'}"
                if args.layers:
                    key += f"__L{args.layers}"
                if args.tag:
                    key += f"__{args.tag}"
                out = RESULTS / f"{key}.json"
                try:
                    rec = run_cell(arch, shape_name, mp,
                                   layers=args.layers or None)
                    out.write_text(json.dumps(rec, indent=1))
                except Exception as e:  # noqa
                    failures.append((key, repr(e)[:400]))
                    print(f"FAIL {key}: {e!r}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for k, e in failures:
            print(" ", k, e)
        sys.exit(1)
    print("\nAll requested dry-run cells compiled.")


if __name__ == "__main__":
    main()
