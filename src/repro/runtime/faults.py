"""Fault injection + straggler simulation (paper Alg. 1 timeout() semantics,
scaled to 1000+-node thinking).

The deadline-drop semantics live in ``cohort_mask`` — a *jittable* weight
mask, so the device-resident multi-round driver (core/rounds.py
``build_multi_round``) can select cohorts inside the compiled program with
no host round-trips. The host-side ``select_cohort`` (one round) and
``cohort_masks`` (a chunk of rounds, in one compiled call) are thin wrappers
over the same function and therefore agree with the in-program mask
bit-for-bit (regression-tested in tests/test_driver.py). Deterministic given
the seed — so fault-tolerance tests can assert bitwise-reproducible recovery.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import determinism


@dataclasses.dataclass(frozen=True)
class FaultModel:
    drop_prob: float = 0.0        # client fails mid-round
    straggler_prob: float = 0.0   # client exceeds the deadline
    straggler_slowdown: float = 4.0
    worker_fail_prob: float = 0.0
    seed: int = 0

    def round_outcome(self, round_idx: int, client_ids):
        """Returns (alive_mask, sim_durations) as numpy arrays. Durations are
        lognormal with stragglers inflated; the deadline keeps the first-K."""
        _, k_out = jax.random.split(determinism.cohort_key(self.seed,
                                                           round_idx))
        alive, dur = _outcome(self, k_out, len(client_ids))
        return np.asarray(alive), np.asarray(dur)


def _outcome(fault: FaultModel, key, n: int):
    """Jittable (alive, duration) draw for ``n`` clients."""
    k_alive, k_dur, k_strag = jax.random.split(key, 3)
    alive = jax.random.uniform(k_alive, (n,)) >= fault.drop_prob
    dur = jnp.exp(0.25 * jax.random.normal(k_dur, (n,)))
    strag = jax.random.uniform(k_strag, (n,)) < fault.straggler_prob
    dur = jnp.where(strag, dur * fault.straggler_slowdown, dur)
    return alive, dur


def cohort_mask(fault: FaultModel, round_idx, n_clients: int, target: int,
                overprovision: float = 1.0):
    """Over-provisioned cohort with deadline-drop as a float32 weight mask.

    Jittable: ``round_idx`` may be a traced scalar (it is, inside the
    multi-round scan). Samples ceil(target*overprovision) clients without
    replacement, drops the dead, keeps the ``target`` fastest survivors; if
    fewer than target survive, the survivors are kept and the aggregator's
    weight normalization makes the drop unbiased under random failures.
    Returns shape (n_clients,): 1.0 for kept clients, 0.0 otherwise.
    """
    want = int(min(math.ceil(target * overprovision), n_clients))
    key = determinism.cohort_key(fault.seed, round_idx)
    k_pool, k_out = jax.random.split(key)
    perm = jax.random.permutation(k_pool, n_clients)
    in_pool = jnp.zeros((n_clients,), bool).at[perm[:want]].set(True)
    alive, dur = _outcome(fault, k_out, n_clients)
    eligible = in_pool & alive
    dur = jnp.where(eligible, dur, jnp.inf)
    rank = jnp.argsort(jnp.argsort(dur))   # rank of each client by duration
    kept = eligible & (rank < target)
    return kept.astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _cohort_masks(draw, rounds, n_clients, target, overprovision):
    seed, drop_prob, straggler_prob, straggler_slowdown = draw
    fault = FaultModel(drop_prob=drop_prob, straggler_prob=straggler_prob,
                       straggler_slowdown=straggler_slowdown, seed=seed)
    return jax.vmap(lambda r: cohort_mask(fault, r, n_clients, target,
                                          overprovision))(rounds)


def cohort_masks(fault: FaultModel, rounds, n_clients: int, target: int,
                 overprovision: float = 1.0):
    """``cohort_mask`` for each absolute round index in ``rounds``, as one
    compiled call on the default backend: shape (len(rounds), n_clients).

    Only the shapes are static; the fault model's seed and probabilities
    are traced (as the uint32 and float32 values the in-program mask's
    constants become), so fault models that differ in them (a seed sweep's
    lanes) share one executable.
    """
    draw = (np.uint32(fault.seed), np.float32(fault.drop_prob),
            np.float32(fault.straggler_prob),
            np.float32(fault.straggler_slowdown))
    return _cohort_masks(draw, np.asarray(rounds, np.int32), int(n_clients),
                         int(target), float(overprovision))


def cohort_mask_programs() -> int:
    """How many ``cohort_masks`` programs are compiled (the jit cache)."""
    return _cohort_masks._cache_size()


def select_cohort(fault: FaultModel, round_idx: int, client_ids,
                  target: int, overprovision: float = 1.0):
    """Host view of ``cohort_mask``: the sorted kept client ids."""
    client_ids = np.asarray(client_ids)
    mask = np.asarray(cohort_mask(fault, round_idx, len(client_ids),
                                  int(target), overprovision))
    return np.sort(client_ids[mask > 0])
