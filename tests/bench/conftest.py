"""Shared helpers for the benchmark's tests: the repository root on the
path, and cells of BENCHMARK.json cut to a size the CPU runs in seconds."""
import io
import json
import pathlib
import sys


ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def files_cell(config: str, traffic: str) -> dict:
    """A cell built straight from a configuration and a traffic file, for
    mixes that no entry of BENCHMARK.json runs yet (no limits)."""
    from bench.cells import BENCH, read_json
    return {"name": f"{config}.{traffic}", "chips": 1,
            "config": read_json(BENCH / "configs" / f"{config}.json"),
            "traffic": read_json(BENCH / "traffic" / f"{traffic}.json"),
            "limits": None, "per_layer": [], "end_to_end": []}


def shrink(cell: dict, keep_steps: bool = False) -> dict:
    """``cell`` over its family's ``tiny`` cut of its configuration, with
    its cohort, batch and (unless ``keep_steps``) local steps cut down; the
    traffic's algorithm stays."""
    from bench import families
    cell["config"] = families.of(cell["config"]).tiny(cell["config"])
    return cut_traffic(cell, keep_steps)


def cut_traffic(cell: dict, keep_steps: bool = False) -> dict:
    """``cell`` with its cohort, batch and (unless ``keep_steps``) local
    steps cut down."""
    tr = cell["traffic"]
    tp = tr["train_params"]
    tp["batch_size"] = 4
    if not keep_steps:
        tp["local_steps"] = min(tp.get("local_steps", 1), 2)
        tp["local_epochs"] = min(tp.get("local_epochs", 1), 2)
    tp["rounds_per_launch"] = min(tp.get("rounds_per_launch", 1), 2)
    if tp.get("max_cohort"):
        tp["cohort"] = tp["max_cohort"] = 4
    if tp.get("mode") == "async":
        tp["async_buffer"] = 3
        tr["horizon_rounds"] = 40
    if tr.get("sweep"):
        tr["sweep"] = {"client_lr": tr["sweep"]["client_lr"][-2:]}
    return cell


def tiny_cell(name: str, keep_steps: bool = False) -> dict:
    """Cell ``name`` of BENCHMARK.json cut down by ``shrink``."""
    from bench.cells import load_cell
    return shrink(load_cell(name), keep_steps)


def names() -> list:
    from bench.cells import load_benchmark
    return [w["name"] for w in load_benchmark()["workloads"]]


NAMES = names()
SEED = 2**31 + 4242


def run_tiny(name, trace=False):
    """One run of cell ``name`` at a tiny size on the CPU; checks the shape
    of the printed lines and returns the result."""
    from bench import run
    cell = tiny_cell(name)
    out, err = io.StringIO(), io.StringIO()
    result = run.run_cell(cell, SEED, 0.3, trace, on_chip=False, out=out,
                          err=err)
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(last)[-1] == "checks"
    assert err.getvalue().strip().splitlines()[-1].startswith(
        "check path_ok")
    return result
