"""Every cell of BENCHMARK.json is whole: its files load, its job passes
``load_job``, and every metric it reports has its reader."""
import importlib
import re

import pytest

from bench import cells

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert all(unit.match(m["unit"]) for m in metrics)
    lines = [x["why"] for k in ("configs", "workloads") for x in BENCH[k]]
    lines += [m["layer"] for m in BENCH["per_layer"]]
    assert all(0 < len(s) <= 200 and "\n" not in s and "\t" not in s
               for s in lines)
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_load_and_pass_load_job(name):
    from repro.core.jobs import load_job
    cell = cells.load_cell(name)
    entry = {w["name"]: w for w in BENCH["workloads"]}[name]
    assert cell["config"]["name"] == entry["config"]
    assert cell["chips"] in (1, 4)
    job = load_job(cells.job_dict(cell, 2**31 + 5))
    assert job.arch == cell["config"]["model"]
    assert job.fl.n_clients == cell["config"]["n_clients"]
    assert job.fl.seed == (2**31 + 5) % cells.SEED_MOD
    assert (job.sweep is not None) == (cells.shape(cell)["lanes"] > 1)
    assert cell["limits"] is not None, f"no bench/limits/{name}.json"
    from bench.compare import NUMBERS
    for number in NUMBERS:
        entry = cell["limits"][number]
        assert entry.get("compared") is False or entry["limit"] > 0


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    mod = importlib.import_module(f"bench.metrics.{metric}")
    assert callable(mod.read)


def cut_faults(entry: dict, conf: dict) -> list:
    """What a configuration file and its entry in BENCHMARK.json leave
    unstated of their cuts: each key cut gives its published value beside
    the cut (under ``published``), and a cut file states the deployment
    whose share of a chip it is."""
    faults = []
    if conf.get("reduced") != entry["reduced"]:
        faults.append("reduced differs between the file and its entry")
    faults += [f"{k}: no published value" for k in entry["reduced"]
               if k not in conf.get("published", {})]
    if entry["reduced"] and not conf.get("deployment"):
        faults.append("no deployment")
    return faults


def test_config_files_state_their_cuts():
    for c in BENCH["configs"]:
        conf = cells.read_json(cells.ROOT / c["file"])
        assert conf["name"] == c["name"]
        assert c["file"].startswith("bench/configs/")
        assert cut_faults(c, conf) == []


@pytest.mark.parametrize("conf,faults", [
    ({"reduced": ["num_hidden_layers"], "num_hidden_layers": 6,
      "published": {"num_hidden_layers": 27},
      "deployment": "8-way expert parallel"}, 0),
    ({"reduced": ["num_hidden_layers"], "num_hidden_layers": 6,
      "published": {}, "deployment": "8-way expert parallel"}, 1),
    ({"reduced": ["num_hidden_layers"], "num_hidden_layers": 6,
      "published": {"num_hidden_layers": 27}}, 1),
    ({"reduced": [], "published": {"num_hidden_layers": 27},
      "deployment": "8-way expert parallel"}, 1)],
    ids=["stated", "no-published-value", "no-deployment", "entry-differs"])
def test_a_cut_is_accepted_with_its_published_values(conf, faults):
    entry = {"reduced": ["num_hidden_layers"]}
    assert len(cut_faults(entry, conf)) == faults
