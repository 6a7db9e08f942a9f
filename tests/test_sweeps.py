"""Campaign subsystem tests (vmapped multi-trajectory sweeps).

The campaign determinism contract extends the driver/async contracts
(tests/test_driver.py, tests/test_async.py) along the sweep axis: lane ``s``
of a vmapped S-trajectory campaign is bitwise identical to an independent
single run of the s-th expanded config — for sync and async modes, across a
seeds x alpha x lr grid — and chunked == unchunked still holds under the
sweep axis. Plus the job-loader satellite: unknown config keys fail loudly
with a near-miss suggestion instead of silently running with defaults.
"""
import os

os.environ.setdefault("REPRO_KERNEL_IMPL", "jnp")

import jax
import numpy as np
import pytest

from repro.core import sweeps
from repro.core.jobs import load_job
from repro.runtime.campaign import CampaignExecutor
from repro.runtime.executor import Executor


def _raw(coord=None, sweep=None, *, mode="sync", strategy="fedavg",
         rounds=3, chunk=3, n_clients=4):
    """One job dict; ``coord`` overrides land in their proper sections (the
    single-run references for each campaign lane are built this way)."""
    coord = coord or {}
    tp = {"n_clients": n_clients, "local_epochs": 1,
          "client_lr": coord.get("client_lr", 0.1),
          "rounds": rounds, "seed": coord.get("seed", 3),
          "rounds_per_launch": chunk,
          "prox_mu": coord.get("prox_mu", 0.0)}
    runtime = {"straggler_prob": 0.2, "straggler_overprovision": 1.25}
    if mode == "async":
        tp.update({"mode": "async", "async_buffer": 3, "max_staleness": 4,
                   "staleness_exponent": coord.get("staleness_exponent",
                                                   0.5)})
        runtime = {"straggler_prob": 0.2, "duration_sigma": 0.25}
    raw = {
        "name": "sweep-test",
        "model": {"arch": "flsim-mlp"},
        "dataset": {"dataset": "synthetic_vision", "n_items": 128,
                    "distribution": {
                        "partition": "dirichlet",
                        "dirichlet_alpha": coord.get("dirichlet_alpha",
                                                     0.5)}},
        "strategy": {"strategy": strategy, "train_params": tp},
        "runtime": runtime,
    }
    if sweep:
        raw["sweep"] = sweep
    return raw


def _assert_bitwise_equal(p1, p2):
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _assert_lanes_match_singles(camp, mk_raw):
    for s, coord in enumerate(camp.spec.coords()):
        state, _ = Executor(load_job(mk_raw(coord))).scaffold().run()
        _assert_bitwise_equal(jax.tree.map(np.asarray, state["params"]),
                              camp.trajectory_params(s))


# ---------------------------------------------------------------------------
# the campaign determinism contract
# ---------------------------------------------------------------------------

def test_sync_campaign_bitwise_equals_single_runs():
    """S=8 seeds x alpha x lr grid, one vmapped launch == 8 independent
    Executor runs, bitwise (data plane + scalar plane together)."""
    sweep = {"seeds": [3, 5], "dirichlet_alpha": [0.3, 3.0],
             "client_lr": [0.05, 0.1]}
    camp = CampaignExecutor(load_job(_raw(sweep=sweep))).scaffold()
    camp.run()
    assert camp.S == 8
    _assert_lanes_match_singles(camp, lambda c: _raw(c))


def test_async_campaign_bitwise_equals_single_runs():
    """Async (FedBuff) campaign: seeds x staleness_exponent x lr — per-lane
    schedules (seed + staleness discount are host-plane) and traced lr."""
    sweep = {"seeds": [7, 9], "staleness_exponent": [0.0, 1.0],
             "client_lr": [0.05, 0.1]}
    camp = CampaignExecutor(
        load_job(_raw({"seed": 7}, sweep=sweep, mode="async",
                      chunk=2))).scaffold()
    camp.run()
    assert camp.S == 8
    _assert_lanes_match_singles(
        camp, lambda c: _raw(c, mode="async", chunk=2))


def test_compressed_campaign_bitwise_equals_single_runs():
    """The packed int8 path under the campaign vmap: lanes must stay
    bitwise their single runs (quantize -> quant_aggregate -> server
    update per round), and the aggregation must actually route through
    the kernels/ops dispatcher inside the vmapped trace."""
    from repro.kernels import ops

    def mk(coord=None):
        raw = _raw(coord, strategy="compressed")
        raw["strategy"]["train_params"].update(
            {"compression": "int8", "error_feedback": True})
        return raw

    sweep = {"seeds": [3, 5], "client_lr": [0.05, 0.1]}
    raw = mk()
    raw["sweep"] = sweep
    jax.clear_caches()
    ops.reset_quant_agg_stats()
    camp = CampaignExecutor(load_job(raw)).scaffold()
    camp.run()
    assert camp.S == 4
    assert ops.quant_agg_stats()["calls"] > 0, \
        "campaign aggregation bypassed the kernel dispatcher"
    _assert_lanes_match_singles(camp, mk)


@pytest.mark.xfail(strict=True, raises=AssertionError, reason=(
    "known breach: on flsim-cnn the compressed spatial round program under "
    "the campaign vmap differs from the single run by ulps in the conv "
    "biases b2/b3 after one round (fedavg, temporal placement and flsim-mlp "
    "stay bitwise); source not isolated"))
def test_compressed_cnn_lane_bitwise_equals_single_run():
    """The lane == single run contract on the conv model: spatial int8, one
    round, four clients, lane 0 against the seed-0 single run."""
    def mk(seeds=None):
        raw = _raw({"seed": 0}, strategy="compressed", rounds=1, chunk=1)
        raw["model"]["arch"] = "flsim-cnn"
        raw["dataset"]["n_items"] = 256
        raw["strategy"]["train_params"].update(
            {"placement": "spatial", "compression": "int8"})
        raw.pop("runtime")
        if seeds:
            raw["sweep"] = {"seeds": seeds}
        return raw

    state, _ = Executor(load_job(mk())).scaffold().run()
    camp = CampaignExecutor(load_job(mk([0, 1]))).scaffold()
    camp.run()
    _assert_bitwise_equal(jax.tree.map(np.asarray, state["params"]),
                          camp.trajectory_params(0))


def test_compression_is_a_categorical_sweep_axis():
    """A compression axis buckets by program signature (dense vs packed
    aggregation are different traced programs) — it must parse, expand,
    and land in the categorical plane, with typos caught."""
    spec = sweeps.parse_sweep({"compression": ["none", "int8", "topk"]})
    assert spec.size == 3 and spec.categorical_names == ("compression",)
    from repro.configs.base import FLConfig
    assert [f.compression for f in sweeps.expand(FLConfig(), spec)] == \
        ["none", "int8", "topk"]
    with pytest.raises(KeyError, match="int8"):
        sweeps.parse_sweep({"compression": ["int9"]})


def test_fedprox_mu_sweep_bitwise():
    """The scalar plane reaches strategy hooks: swept prox_mu through
    FedProx's local_loss, bitwise vs single runs."""
    sweep = {"prox_mu": [0.0, 0.1]}
    camp = CampaignExecutor(
        load_job(_raw(sweep=sweep, strategy="fedprox"))).scaffold()
    camp.run()
    _assert_lanes_match_singles(
        camp, lambda c: _raw(c, strategy="fedprox"))


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_chunked_equals_unchunked_under_sweep(mode):
    """rounds_per_launch chunking must stay bitwise-invariant with the
    sweep axis vmapped on top (uneven 2+1 chunking included)."""
    sweep = {"seeds": [3, 5], "client_lr": [0.05, 0.1]}
    runs = {}
    for chunk in (1, 3, 2):
        camp = CampaignExecutor(
            load_job(_raw(sweep=sweep, mode=mode, chunk=chunk))).scaffold()
        camp.run()
        runs[chunk] = jax.tree.map(np.asarray, camp.state["params"])
    _assert_bitwise_equal(runs[1], runs[3])
    _assert_bitwise_equal(runs[1], runs[2])


def test_async_schedule_plane_dedup_bitwise():
    """Scalar-only async sweeps used to duplicate the (E,) event schedule S
    times the way data used to be duplicated (the ROADMAP schedule-plane
    item): lanes sharing (seed, partition, alpha, staleness_exponent) must
    share ONE schedule on device — and stay bitwise their single runs (the
    strongest form of "dedup changed nothing")."""
    sweep = {"client_lr": [0.05, 0.1, 0.2]}
    camp = CampaignExecutor(
        load_job(_raw(sweep=sweep, mode="async", chunk=2))).scaffold()
    assert camp.S == 3
    # one unique schedule serves all three lanes
    assert camp.sched_dev["client"].shape[0] == 1
    np.testing.assert_array_equal(camp.lane_sched, [0, 0, 0])
    assert camp.schedules[0] is camp.schedules[2]
    camp.run()
    _assert_lanes_match_singles(
        camp, lambda c: _raw(c, mode="async", chunk=2))


def test_async_schedule_plane_dedup_keys():
    """Mixed sweep: the schedule dedups per distinct (seed,
    staleness_exponent) while the swept lr rides along — U=4 schedules for
    S=8 lanes, keyed row-major like the data plane."""
    sweep = {"seeds": [7, 9], "staleness_exponent": [0.0, 1.0],
             "client_lr": [0.05, 0.1]}
    camp = CampaignExecutor(
        load_job(_raw({"seed": 7}, sweep=sweep, mode="async",
                      chunk=2))).scaffold()
    assert camp.S == 8
    assert camp.sched_dev["client"].shape[0] == 4
    np.testing.assert_array_equal(camp.lane_sched, [0, 0, 1, 1, 2, 2, 3, 3])


# ---------------------------------------------------------------------------
# sweep expansion / config surface
# ---------------------------------------------------------------------------

def test_sweep_grid_expansion_row_major():
    spec = sweeps.parse_sweep({"seeds": [0, 1], "client_lr": [0.1, 0.2]})
    assert spec.size == 4 and spec.names == ("seed", "client_lr")
    assert spec.coords() == [
        {"seed": 0, "client_lr": 0.1}, {"seed": 0, "client_lr": 0.2},
        {"seed": 1, "client_lr": 0.1}, {"seed": 1, "client_lr": 0.2}]
    from repro.configs.base import FLConfig
    fls = sweeps.expand(FLConfig(), spec)
    assert [f.seed for f in fls] == [0, 0, 1, 1]
    hyper = sweeps.scalar_plane(fls)
    np.testing.assert_array_equal(np.asarray(hyper["seed"]), [0, 0, 1, 1])
    np.testing.assert_allclose(np.asarray(hyper["client_lr"]),
                               [0.1, 0.2, 0.1, 0.2])
    # unswept sweepable scalars broadcast the base value
    np.testing.assert_allclose(np.asarray(hyper["server_lr"]), [1.0] * 4)


def test_sweep_unknown_axis_near_miss():
    with pytest.raises(KeyError, match="client_lr"):
        sweeps.parse_sweep({"cleint_lr": [0.1]})
    with pytest.raises(ValueError, match="non-empty"):
        sweeps.parse_sweep({"seeds": []})
    with pytest.raises(ValueError, match="duplicates"):
        sweeps.parse_sweep({"seeds": [0, 1], "seed": [2, 3]})
    assert sweeps.parse_sweep(None) is None


def test_campaign_resume_keeps_full_results_table(tmp_path):
    """Checkpoint + resume must not truncate campaign.csv: the table is
    rewritten at chunk boundaries and re-adopted on restore, so the resumed
    run's table covers every round."""
    sweep = {"seeds": [3, 5]}

    def mk(out):
        raw = _raw(sweep=sweep, chunk=2)
        raw["strategy"]["train_params"]["rounds"] = 4
        raw["strategy"]["train_params"]["checkpoint_every"] = 2
        return CampaignExecutor(load_job(raw), out_dir=str(out),
                                ckpt_dir=str(tmp_path / "ckpt"))

    full = CampaignExecutor(
        load_job({**_raw(sweep=sweep, chunk=2),
                  "strategy": {"strategy": "fedavg", "train_params": {
                      **_raw(sweep=sweep)["strategy"]["train_params"],
                      "rounds": 4, "rounds_per_launch": 2}}})).scaffold()
    full.run()

    ex = mk(tmp_path / "a").scaffold()
    ex.run(rounds=2)                     # crash after the first chunk
    ex2 = mk(tmp_path / "a").scaffold()  # resumes at round 2
    assert ex2.round_idx == 2 and len(ex2.results) == 2 * 2
    ex2.run()
    assert sorted({r["round"] for r in ex2.results}) == [0, 1, 2, 3]
    assert len(ex2.results) == 2 * 4
    _assert_bitwise_equal(jax.tree.map(np.asarray, full.state["params"]),
                          jax.tree.map(np.asarray, ex2.state["params"]))


def test_campaign_resume_rejects_changed_grid(tmp_path):
    """A checkpoint records the campaign's real lane count: resuming with a
    different sweep grid must fail loudly instead of silently adopting
    lane states whose coordinates belong to the old grid (only the device
    padding is elastic)."""

    def mk(sweep):
        raw = _raw(sweep=sweep, chunk=2)
        raw["strategy"]["train_params"]["rounds"] = 4
        raw["strategy"]["train_params"]["checkpoint_every"] = 2
        return CampaignExecutor(load_job(raw),
                                ckpt_dir=str(tmp_path / "ckpt"))

    mk({"seeds": [3, 5, 7, 9]}).scaffold().run(rounds=2)
    with pytest.raises(ValueError, match="different sweep grid"):
        mk({"seeds": [3, 5]}).scaffold()          # fewer lanes
    with pytest.raises(ValueError, match="different sweep grid"):
        mk({"seeds": [11, 13, 17, 19]}).scaffold()  # same S, other coords


def test_campaign_curves_grouping_immune_to_eval_columns():
    """rounds_per_launch=1 puts eval metrics on every row; the curve
    grouping must still key on sweep axes only (one curve per lr)."""
    from benchmarks.figures import campaign_curves
    sweep = {"seeds": [3, 5], "client_lr": [0.05, 0.1]}
    camp = CampaignExecutor(load_job(_raw(sweep=sweep, chunk=1))).scaffold()
    camp.eval_fn = lambda params: {
        "acc": float(sum(np.abs(np.asarray(t)).sum()
                         for t in jax.tree.leaves(params)))}
    camp.run()
    out = campaign_curves(camp.results)
    assert len(out) == 2
    assert all(len(c["rounds"]) == 3 for c in out)


def test_load_job_rejects_unknown_top_level_section():
    raw = _raw()
    raw["runtim"] = raw.pop("runtime")
    with pytest.raises(KeyError, match="runtime"):
        load_job(raw)


def test_campaign_ledger_records_per_lane_digests():
    """Blockchain-enabled campaigns must keep per-run provenance: each
    lane's params digest (== the single run's, by the bitwise contract)
    must be findable in the chain."""
    from repro.core.blockchain import param_digest
    raw = _raw(sweep={"seeds": [3, 5]})
    raw["strategy"]["train_params"]["blockchain"] = "hashchain"
    camp = CampaignExecutor(load_job(raw)).scaffold()
    camp.run()
    assert camp.job.ledger.verify()
    for s in range(camp.S):
        dig = param_digest(camp.trajectory_params(s))
        assert camp.job.ledger.provenance(dig), f"lane {s} not in ledger"


def test_campaign_results_table(tmp_path):
    """Tidy table: one row per (trajectory, round) keyed by the sweep
    coordinates; per-lane eval merges into each trajectory's last row."""
    sweep = {"seeds": [3, 5], "client_lr": [0.05, 0.1]}
    camp = CampaignExecutor(load_job(_raw(sweep=sweep)),
                            out_dir=str(tmp_path)).scaffold()
    camp.eval_fn = lambda params: {
        "pnorm": float(sum(np.abs(np.asarray(t)).sum()
                           for t in jax.tree.leaves(params)))}
    camp.run()
    assert len(camp.results) == camp.S * 3
    row = camp.results[0]
    assert {"seed", "client_lr", "traj", "round", "loss"} <= set(row)
    # eval lands on each lane's final-round row, with per-lane values
    tails = [r for r in camp.results if r["round"] == 2]
    assert len(tails) == camp.S and all("pnorm" in r for r in tails)
    assert len({r["pnorm"] for r in tails}) > 1
    csv_path = camp.write_results()
    assert csv_path.exists()
    header = csv_path.read_text().splitlines()[0].split(",")
    assert header[:4] == ["seed", "client_lr", "traj", "round"]


# ---------------------------------------------------------------------------
# job loader validation (no silent key drops)
# ---------------------------------------------------------------------------

def test_load_job_rejects_unknown_keys_with_near_miss():
    raw = _raw()
    raw["strategy"]["train_params"]["cleint_lr"] = 0.5
    del raw["strategy"]["train_params"]["client_lr"]
    with pytest.raises(KeyError, match="client_lr"):
        load_job(raw)

    raw = _raw()
    raw["runtime"]["stragler_prob"] = 0.5
    with pytest.raises(KeyError, match="straggler_prob"):
        load_job(raw)

    raw = _raw()
    raw["dataset"]["distribution"]["dirichlet_alpa"] = 1.0
    with pytest.raises(KeyError, match="dirichlet_alpha"):
        load_job(raw)
