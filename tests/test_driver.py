"""Device-resident multi-round driver tests.

The driver's determinism contract: fusing rounds into one compiled launch
(``rounds_per_launch``) must not change the trajectory — chunked and
unchunked execution are bitwise-identical for the same seed, for both client
placements. Plus the cohort regression: the in-program weight mask and the
host-side ``select_cohort`` are the same function.
"""
import os

os.environ.setdefault("REPRO_KERNEL_IMPL", "jnp")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.jobs import load_job
from repro.runtime.executor import Executor
from repro.runtime.faults import (FaultModel, cohort_mask, cohort_masks,
                                  select_cohort)


def _job(rounds_per_launch: int, placement: str = "spatial",
         rounds: int = 5, strategy: str = "fedavg"):
    return load_job({
        "name": f"driver-{placement}-{rounds_per_launch}",
        "model": {"arch": "flsim-mlp"},
        "dataset": {"dataset": "synthetic_vision", "n_items": 256,
                    "distribution": {"partition": "dirichlet",
                                     "dirichlet_alpha": 0.5}},
        "strategy": {"strategy": strategy,
                     "train_params": {"n_clients": 4, "local_epochs": 1,
                                      "client_lr": 0.1, "rounds": rounds,
                                      "seed": 11, "placement": placement,
                                      "rounds_per_launch": rounds_per_launch}},
        "runtime": {"straggler_prob": 0.2, "straggler_overprovision": 1.25},
    })


def _run(rounds_per_launch, placement):
    ex = Executor(_job(rounds_per_launch, placement)).scaffold()
    state, logger = ex.run()
    return (jax.tree.map(np.asarray, state["params"]),
            logger.series("loss"))


def _assert_bitwise_equal(p1, p2):
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("placement", ["spatial", "temporal"])
def test_chunked_equals_unchunked(placement):
    """rounds_per_launch=10 (one fused launch) == 1 (per-round launches),
    bitwise, over 5 rounds; an uneven chunking (3+2) must also agree."""
    p1, l1 = _run(1, placement)
    p10, l10 = _run(10, placement)
    assert l1 == l10, f"{placement}: per-round losses diverged"
    _assert_bitwise_equal(p1, p10)
    p3, _ = _run(3, placement)
    _assert_bitwise_equal(p1, p3)


def test_chunked_equals_unchunked_with_server_momentum():
    """The carried server state (FedAvgM momentum) must also survive fusion."""
    for chunk in (1, 5):
        ex = Executor(_job(chunk, "spatial", strategy="fedavgm")).scaffold()
        state, _ = ex.run()
        if chunk == 1:
            ref = jax.tree.map(np.asarray, state["params"])
        else:
            _assert_bitwise_equal(ref, jax.tree.map(np.asarray,
                                                    state["params"]))


def test_cohort_mask_matches_select_cohort():
    """The jittable in-program mask and the host kept-set are one function."""
    fault = FaultModel(drop_prob=0.2, straggler_prob=0.3,
                       straggler_slowdown=8.0, seed=5)
    ids = np.arange(50)
    for r in range(6):
        mask = np.asarray(cohort_mask(fault, r, 50, 20, 1.5))
        kept = select_cohort(fault, r, ids, target=20, overprovision=1.5)
        np.testing.assert_array_equal(np.where(mask > 0)[0], kept)
        assert mask.sum() <= 20


@pytest.mark.parametrize("faulty", [True, False], ids=["faults", "clean"])
@pytest.mark.parametrize("start,n", [(0, 10), (7, 10), (30, 3)],
                         ids=["first", "later", "remainder"])
def test_cohort_masks_equal_per_round_mask(faulty, start, n):
    """The chunk's batched plan is the per-round mask, bitwise, for any
    chunk start and length (a remainder chunk included)."""
    fault = (FaultModel(drop_prob=0.1, straggler_prob=0.2, seed=7)
             if faulty else FaultModel(seed=7))
    over = 1.5 if faulty else 1.0
    got = np.asarray(cohort_masks(fault, np.arange(start, start + n), 50,
                                  20, over))
    want = np.stack([np.asarray(cohort_mask(fault, r, 50, 20, over))
                     for r in range(start, start + n)])
    assert got.shape == (n, 50) and got.dtype == np.float32
    np.testing.assert_array_equal(got, want)


def test_cohort_mask_traced_round_idx():
    """Mask must be identical when round_idx is a traced scalar (as inside
    the multi-round scan) vs a Python int."""
    fault = FaultModel(drop_prob=0.1, straggler_prob=0.2, seed=3)
    jitted = jax.jit(lambda r: cohort_mask(fault, r, 32, 8, 1.25))
    for r in range(4):
        np.testing.assert_array_equal(
            np.asarray(jitted(jnp.int32(r))),
            np.asarray(cohort_mask(fault, r, 32, 8, 1.25)))


def test_checkpoint_cadence_survives_chunking(tmp_path):
    """checkpoint_every not divisible by rounds_per_launch must still save
    whenever a chunk crosses a multiple (not only on exact-divisor rounds)."""
    from repro.checkpoint import ckpt as ckpt_mod
    from repro.data.pipeline import SyntheticVision

    def mk():
        job = load_job({
            "name": "ckpt-cadence",
            "model": {"arch": "flsim-logreg"},
            "dataset": {"dataset": "synthetic_vision", "n_items": 64},
            "strategy": {"strategy": "fedavg",
                         "train_params": {"n_clients": 2, "client_lr": 0.1,
                                          "rounds": 6, "seed": 0,
                                          "rounds_per_launch": 3,
                                          "checkpoint_every": 2}}})
        job.dataset = SyntheticVision(n_items=64, shape=(28, 28, 1), seed=0)
        return job

    ex = Executor(mk(), ckpt_dir=str(tmp_path)).scaffold()
    ex.run(rounds=3)
    # chunk [0,3) crossed the multiple 2 -> a checkpoint must exist
    assert ckpt_mod.latest_round(str(tmp_path)) == 3
    ex.run()
    assert ckpt_mod.latest_round(str(tmp_path)) == 6
    # and resume lands on the saved boundary
    ex2 = Executor(mk(), ckpt_dir=str(tmp_path)).scaffold()
    assert ex2.round_idx == 6


def test_cohort_mask_keeps_target_without_faults():
    mask = np.asarray(cohort_mask(FaultModel(seed=0), 0, 16, 8, 2.0))
    assert mask.sum() == 8
    assert set(np.unique(mask)) <= {0.0, 1.0}
