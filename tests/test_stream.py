"""Streaming client plane tests: ragged cohorts + slab staging.

The contracts (ISSUE 10):

- streaming == resident **bitwise**: both stagers feed identical slab bytes
  into ONE compiled program, so swapping the staging backend can never move
  a trajectory (sync and async).
- chunked == unchunked under the ragged plane (the driver contract extends).
- checkpoint save/resume mid-stream is bitwise the uninterrupted run.
- ``n_clients``/``cohort`` become sweepable axes: a ragged campaign lane is
  bitwise its independent single run AND the whole grid compiles ONE
  program (``Executor.compiled_programs``).
- a population far larger than device memory trains at a working set
  bounded by the cohort slab — asserted off the ``staged_bytes`` telemetry
  counters.
- bad cohort geometry fails loudly at load time (``jobs.validate_cohort``).
"""
import os

os.environ.setdefault("REPRO_KERNEL_IMPL", "jnp")

import jax
import numpy as np
import pytest

from repro.core.jobs import load_job
from repro.runtime.campaign import CampaignExecutor
from repro.runtime.executor import Executor
from repro.runtime.faults import select_cohort
from repro.telemetry.recorder import read_events


def _job(sweep=None, telemetry=None, strategy="fedavg", **tp):
    params = {"n_clients": 8, "cohort": 4, "max_cohort": 6,
              "local_epochs": 1, "client_lr": 0.1, "rounds": 4, "seed": 11,
              "rounds_per_launch": 2, "batch_size": 4, "local_steps": 2}
    params.update(tp)
    cfg = {
        "name": "stream",
        "model": {"arch": "flsim-mlp"},
        "dataset": {"dataset": "synthetic_vision", "n_items": 128,
                    "distribution": {"partition": "dirichlet",
                                     "dirichlet_alpha": 0.5}},
        "strategy": {"strategy": strategy, "train_params": params},
        "runtime": {"straggler_prob": 0.2,
                    "straggler_overprovision": 1.25},
    }
    if sweep:
        cfg["sweep"] = sweep
    if telemetry:
        cfg["telemetry"] = telemetry
    return load_job(cfg)


def _run(job, **kw):
    ex = Executor(job, **kw).scaffold()
    state, logger = ex.run()
    return (jax.tree.map(np.asarray, state["params"]),
            logger.series("loss"), ex)


def _assert_bitwise_equal(p1, p2):
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_streaming_equals_resident_bitwise():
    """The tentpole contract: per-chunk host staging of only the sampled
    cohorts' shards feeds the SAME compiled program the resident gather
    feeds — identical slab bytes, identical trajectory, bitwise."""
    p_res, l_res, ex = _run(_job())
    p_str, l_str, _ = _run(_job(streaming=True))
    assert l_res == l_str, "streaming moved the loss trajectory"
    _assert_bitwise_equal(p_res, p_str)
    assert ex.stager is not None and ex.stager.peak_slab_bytes > 0


def test_ragged_chunked_equals_unchunked():
    """The driver's fusion contract extends to the ragged plane: the slab
    is addressed by absolute round index, so chunk boundaries are
    unobservable (streaming included)."""
    p1, l1, _ = _run(_job(streaming=True, rounds_per_launch=1))
    p4, l4, _ = _run(_job(streaming=True, rounds_per_launch=4))
    assert l1 == l4
    _assert_bitwise_equal(p1, p4)


@pytest.mark.parametrize("start,n", [(0, 2), (3, 5)])
def test_plan_equals_per_round_select_cohort(start, n):
    """The stager's one batched plan of a chunk is the slab plan rebuilt
    round by round from ``select_cohort``: kept ids ascending, pad slots
    repeating the first kept id, ``real`` on the kept slots only."""
    stager = Executor(_job()).scaffold().stager
    fl, k = stager.fl, stager.k_slots
    slots, real = stager.plan(start, n)
    want_slots = np.zeros((n, k), np.int32)
    want_real = np.zeros((n, k), np.float32)
    for i in range(n):
        kept = select_cohort(stager.fault, start + i, np.arange(fl.n_clients),
                             fl.cohort, fl.straggler_overprovision)
        assert 0 < len(kept) <= k
        want_slots[i] = kept[0]
        want_slots[i, :len(kept)] = kept
        want_real[i, :len(kept)] = 1.0
    assert slots.dtype == np.int32 and real.dtype == np.float32
    np.testing.assert_array_equal(slots, want_slots)
    np.testing.assert_array_equal(real, want_real)


def test_seed_lanes_share_one_plan_program():
    """Campaign lanes that differ only in ``seed`` plan with one compiled
    program: the first chunk's first lane compiles it, and every later
    plan call reports ``compiled=0``."""
    jax.clear_caches()
    camp = CampaignExecutor(_job(sweep={"seed": [11, 12]},
                                 telemetry={"cost_analysis": False}))
    camp.scaffold()
    camp.run()
    plans = [e["values"] for e in camp.recorder.events
             if e["kind"] == "counter" and e["name"] == "cohort_plan_batch"]
    assert len(plans) >= 2 * 2, plans          # two lanes, two chunks
    assert [v["compiled"] for v in plans] == [1] + [0] * (len(plans) - 1)
    assert all(v["rounds"] == 2 for v in plans)


def test_async_streaming_equals_resident():
    """Async ragged: the per-event slab row is gathered by the real client
    id off the schedule, so the event stream is bitwise invariant to the
    staging backend — and to the ragged plane itself (same draw as
    ``gather_one_client_batch``)."""
    kw = dict(mode="async", async_buffer=3, max_staleness=2,
              rounds_per_launch=1, rounds=3, n_clients=6, cohort=0,
              max_cohort=6)
    p_dense, l_dense, _ = _run(_job(**dict(kw, max_cohort=0)))
    p_res, l_res, _ = _run(_job(**kw))
    p_str, l_str, _ = _run(_job(**dict(kw, streaming=True)))
    assert l_res == l_str
    _assert_bitwise_equal(p_res, p_str)
    assert l_dense == l_res, "ragged changed the async event stream"
    _assert_bitwise_equal(p_dense, p_res)


def test_checkpoint_resume_mid_stream(tmp_path):
    """Interrupting a streaming run at a chunk boundary and resuming from
    the checkpoint is bitwise the uninterrupted run (the stager addresses
    absolute rounds, so a resumed chunk re-stages exactly what the
    uninterrupted run staged)."""
    mk = lambda: _job(streaming=True, rounds=6, checkpoint_every=2)
    p_full, _, _ = _run(mk())
    ex1 = Executor(mk(), ckpt_dir=str(tmp_path)).scaffold()
    ex1.run(rounds=4)
    p_res, _, _ = _run(mk(), ckpt_dir=str(tmp_path))
    _assert_bitwise_equal(p_full, p_res)


def test_cohort_sweep_one_program_bitwise():
    """The sweepable-axes contract: a ragged campaign over n_clients x
    cohort compiles ONE program (the sizes are host-side slab-plan values,
    not trace shapes), and every lane is bitwise its independent single
    run."""
    camp = CampaignExecutor(
        _job(sweep={"n_clients": [6, 8], "cohort": [2, 4]}))
    camp.scaffold()
    camp.run()
    assert camp.compiled_programs() == 1
    for s, coord in enumerate(camp.coords):
        p_single, _, _ = _run(_job(**coord))
        _assert_bitwise_equal(camp.trajectory_params(s), p_single)


def test_population_bounded_working_set(tmp_path):
    """A synthetic population too large to stage resident trains through
    the sync driver with a per-chunk working set bounded by the cohort
    slab — the ``staged_bytes`` counters report slab vs resident-equivalent
    bytes, and the ratio must be tiny."""
    job = load_job({
        "name": "pop", "model": {"arch": "flsim-logreg"},
        "dataset": {"dataset": "synthetic_population", "n_items": 20_000,
                    "items_per_client": 8},
        "strategy": {"strategy": "fedavg",
                     "train_params": {"n_clients": 20_000, "cohort": 8,
                                      "max_cohort": 10, "streaming": True,
                                      "client_lr": 0.1, "rounds": 2,
                                      "seed": 1, "rounds_per_launch": 2,
                                      "batch_size": 4, "local_steps": 1}},
        "telemetry": {"enabled": True, "out_dir": str(tmp_path)},
    })
    state, logger = Executor(job).scaffold().run()
    assert np.isfinite(logger.series("loss")).all()
    evs = [e["values"] for e in read_events(str(tmp_path))
           if e.get("kind") == "counter" and e.get("name") == "staged_bytes"
           and "slab" in e.get("values", {})]
    assert evs, "no per-chunk staged_bytes counters recorded"
    for v in evs:
        assert v["peak_slab"] <= v["slab"] * 2
        assert v["peak_slab"] < 0.01 * v["resident_equiv"], v


def test_cohort_validation_errors():
    """Bad cohort geometry fails at load, not mid-campaign: an oversized
    cohort must not silently clamp, an undersized slab must not silently
    truncate, and streaming requires the ragged plane."""
    with pytest.raises(ValueError, match="cohort"):
        load_job({"name": "bad", "model": {"arch": "flsim-logreg"},
                  "dataset": {"dataset": "synthetic_vision", "n_items": 32},
                  "strategy": {"strategy": "fedavg",
                               "train_params": {"n_clients": 4,
                                                "cohort": 8}}})
    with pytest.raises(ValueError, match="max_cohort"):
        load_job({"name": "bad", "model": {"arch": "flsim-logreg"},
                  "dataset": {"dataset": "synthetic_vision", "n_items": 32},
                  "strategy": {"strategy": "fedavg",
                               "train_params": {"n_clients": 8, "cohort": 4,
                                                "max_cohort": 2}}})
    with pytest.raises(ValueError, match="streaming"):
        load_job({"name": "bad", "model": {"arch": "flsim-logreg"},
                  "dataset": {"dataset": "synthetic_vision", "n_items": 32},
                  "strategy": {"strategy": "fedavg",
                               "train_params": {"n_clients": 8, "cohort": 4,
                                                "streaming": True}}})


def test_population_requires_streaming():
    """A shard-factory population cannot be staged resident."""
    with pytest.raises(ValueError, match="streaming"):
        job = load_job({
            "name": "pop", "model": {"arch": "flsim-logreg"},
            "dataset": {"dataset": "synthetic_population", "n_items": 100,
                        "items_per_client": 4},
            "strategy": {"strategy": "fedavg",
                         "train_params": {"n_clients": 100, "cohort": 4,
                                          "max_cohort": 6,
                                          "client_lr": 0.1, "rounds": 1}}})
        Executor(job).scaffold()


def test_ragged_rejects_client_state_strategies():
    """SCAFFOLD-style per-client carried state indexes a dense (C, ...)
    plane; the ragged plane must refuse it loudly instead of training with
    silently wrong control variates."""
    with pytest.raises((ValueError, NotImplementedError),
                       match="(?i)client.state|scaffold|ragged"):
        Executor(_job(strategy="scaffold")).scaffold()
