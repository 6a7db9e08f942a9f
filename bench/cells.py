"""The benchmark's cells: the entries of ``BENCHMARK.json`` and their files.

A cell names a configuration (``bench/configs/<config>.json``: the model, its
family in ``bench/families/`` and the deployment it runs in) and a traffic
mix (``bench/traffic/<traffic>.json``: the FL job run over that deployment).
``job_dict`` is the one generator that turns the pair and a seed into the
job dict ``repro.core.jobs.load_job`` takes; nothing in it names a
particular cell or model.
"""
from __future__ import annotations

import json
import pathlib

from bench import families

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

# Seeds reach 2**31 and beyond; the program keys numpy and JAX generators
# with a signed 32-bit seed, so a run's job seed is the seed folded into it.
SEED_MOD = 2**31 - 1


def read_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_benchmark(root=ROOT) -> dict:
    return read_json(pathlib.Path(root) / "BENCHMARK.json")


def load_cell(name: str, root=ROOT) -> dict:
    """The cell ``name`` with its configuration and traffic files read."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = read_json(pathlib.Path(root) / configs[entry["config"]]["file"])
    families.of(conf)
    traffic = read_json(pathlib.Path(root) / "bench" / "traffic"
                        / f"{entry['traffic']}.json")
    limits_path = pathlib.Path(root) / "bench" / "limits" / f"{name}.json"
    limits = read_json(limits_path) if limits_path.exists() else None
    return {"name": name, "chips": int(entry["chips"]), "config": conf,
            "traffic": traffic, "limits": limits,
            "per_layer": [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])],
            "end_to_end": [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]}


def reference_precision(cell: dict) -> str:
    """The matrix-product precision of the float32 reference the cell's
    limits were read against (``bench/limits/<cell>.json``), ``highest``
    unless the file names ``default``, the precision the configurations
    state."""
    return (cell.get("limits") or {}).get("reference_precision", "highest")


def job_seed(seed: int) -> int:
    return int(seed) % SEED_MOD


def train_params(cell: dict) -> dict:
    """The FLConfig fields of the cell: deployment first, then traffic."""
    conf, tr = cell["config"], cell["traffic"]
    tp = {"n_clients": conf["n_clients"], "partition": conf["partition"]}
    tp.update(tr["train_params"])
    return tp


def job_dict(cell: dict, seed: int, telemetry: bool = False) -> dict:
    """The job dict for ``load_job``: one cell at one seed. The
    configuration's optional ``job`` object adds to the ``model`` and
    ``dataset`` sections (a sequence length, the program's cut)."""
    conf, tr = cell["config"], cell["traffic"]
    extra = conf.get("job", {})
    tp = dict(train_params(cell), seed=job_seed(seed),
              rounds=int(tr["horizon_rounds"]))
    raw = {"name": cell["name"],
           "model": {"arch": conf["model"], **extra.get("model", {})},
           "dataset": {"dataset": conf["dataset"],
                       "n_items": conf["n_items"],
                       **extra.get("dataset", {})},
           "strategy": {"strategy": tr["strategy"], "train_params": tp},
           "runtime": dict(tr.get("runtime", {}))}
    if tr.get("sweep"):
        raw["sweep"] = dict(tr["sweep"])
    if telemetry:
        raw["telemetry"] = {"enabled": True, "cost_analysis": False}
    return raw


def lanes(cell: dict) -> list:
    """Per-lane client learning rates (one entry for a single run)."""
    tr = cell["traffic"]
    sweep = tr.get("sweep") or {}
    if set(sweep) - {"client_lr"}:
        raise ValueError(f"the generator sweeps client_lr only, not "
                         f"{sorted(set(sweep) - {'client_lr'})}")
    return [float(v) for v in sweep.get(
        "client_lr", [train_params(cell).get("client_lr", 0.1)])]


def shape(cell: dict) -> dict:
    """The work of one chunk (one call of the window), from the files."""
    tp = train_params(cell)
    rpl = int(tp.get("rounds_per_launch", 1))
    asynchronous = tp.get("mode", "sync") == "async"
    if asynchronous:
        buf = int(tp.get("async_buffer", 0))
        per_round = buf if buf > 1 else int(tp["n_clients"])
    else:
        per_round = int(tp.get("cohort") or tp["n_clients"])
    n_lanes = len(lanes(cell))
    items = (int(tp.get("local_epochs", 1)) * int(tp.get("local_steps", 1))
             * int(tp.get("batch_size", 32)))
    return {"rounds_per_call": rpl, "async": asynchronous,
            "updates_per_round": per_round * n_lanes,
            "updates_per_call": rpl * per_round * n_lanes,
            "items_per_update": items, "lanes": n_lanes,
            "int8": tp.get("compression", "none") == "int8",
            "ragged_slots": int(tp.get("max_cohort", 0))}
