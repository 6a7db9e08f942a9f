"""Strategy unit + property tests (hypothesis on the aggregation invariants)."""
import os

os.environ.setdefault("REPRO_KERNEL_IMPL", "jnp")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs.base import FLConfig
from repro.core.strategies import REGISTRY, get_strategy
from repro.core.strategy import Strategy, tree_sub
from repro.core.topology import ClientServer, Decentralized, Hierarchical
from repro.sharding.axes import AxisCtx

CTX = AxisCtx()


def toy_params(seed=0, n=64):
    k = jax.random.PRNGKey(seed)
    a, b = jax.random.split(k)
    return {"w": jax.random.normal(a, (n,)), "b": jax.random.normal(b, (4,))}


# ---------------------------------------------------------------------------
# aggregation properties
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(2, 8), st.integers(1, 1000))
def test_weighted_mean_linearity_and_permutation(n_clients, seed):
    rng = np.random.RandomState(seed)
    deltas = {"w": jnp.asarray(rng.randn(n_clients, 16), jnp.float32)}
    w = jnp.asarray(rng.rand(n_clients) + 0.1, jnp.float32)
    topo = ClientServer()
    agg = topo.aggregate(CTX, deltas, w)
    want = np.average(np.asarray(deltas["w"]), axis=0, weights=np.asarray(w))
    np.testing.assert_allclose(np.asarray(agg["w"]), want, rtol=1e-5, atol=1e-6)
    # permutation invariance
    perm = rng.permutation(n_clients)
    agg2 = topo.aggregate(CTX, {"w": deltas["w"][perm]}, w[perm])
    np.testing.assert_allclose(np.asarray(agg2["w"]), want, rtol=1e-5,
                               atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 8), st.integers(1, 100))
def test_hierarchical_equals_flat_for_equal_weights(n_clients, seed):
    rng = np.random.RandomState(seed)
    deltas = {"w": jnp.asarray(rng.randn(n_clients, 8), jnp.float32)}
    w = jnp.ones((n_clients,), jnp.float32)
    flat = ClientServer().aggregate(CTX, deltas, w)
    hier = Hierarchical().aggregate(CTX, deltas, w)
    np.testing.assert_allclose(np.asarray(flat["w"]), np.asarray(hier["w"]),
                               rtol=1e-5, atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(3, 12), st.integers(1, 100), st.integers(1, 4))
def test_gossip_preserves_mean_and_contracts(n_clients, seed, steps):
    """Doubly-stochastic mixing: mean invariant, variance non-increasing."""
    rng = np.random.RandomState(seed)
    state = {"w": jnp.asarray(rng.randn(n_clients, 8), jnp.float32)}
    topo = Decentralized(gossip_steps=steps)
    mixed = topo.mix(CTX, state)
    np.testing.assert_allclose(np.asarray(mixed["w"]).mean(0),
                               np.asarray(state["w"]).mean(0),
                               rtol=1e-4, atol=1e-5)
    assert np.asarray(mixed["w"]).var(0).sum() <= \
        np.asarray(state["w"]).var(0).sum() + 1e-5


# ---------------------------------------------------------------------------
# per-strategy behaviour
# ---------------------------------------------------------------------------

def test_registry_complete():
    fl = FLConfig()
    for name in REGISTRY:
        s = get_strategy(FLConfig(strategy=name))
        assert isinstance(s, Strategy)


def test_fedavgm_momentum_accumulates():
    fl = FLConfig(strategy="fedavgm", server_momentum=0.5, server_lr=1.0)
    s = get_strategy(fl)
    p = toy_params()
    st_ = s.server_state_init(p)
    d = jax.tree.map(jnp.ones_like, p)
    p1, st_ = s.server_update(p, d, st_)
    p2, st_ = s.server_update(p1, d, st_)
    # second step moves further (momentum): dp2 = 1.5, dp1 = 1.0
    dp1 = np.asarray(p1["w"] - p["w"])
    dp2 = np.asarray(p2["w"] - p1["w"])
    np.testing.assert_allclose(dp1, 1.0, rtol=1e-5)
    np.testing.assert_allclose(dp2, 1.5, rtol=1e-5)


def test_fedprox_penalizes_drift():
    fl = FLConfig(strategy="fedprox", prox_mu=10.0)
    s = get_strategy(fl)
    p_far = toy_params(1)
    g = toy_params(0)

    def base(params, batch, rng):
        return jnp.zeros(()), {}

    l_far, _ = s.local_loss(base, p_far, g, None, (), None)
    l_same, _ = s.local_loss(base, g, g, None, (), None)
    assert float(l_far) > float(l_same) + 1e-3
    assert abs(float(l_same)) < 1e-6


def test_scaffold_correction_and_cstate():
    fl = FLConfig(strategy="scaffold", client_lr=0.1)
    s = get_strategy(fl)
    p = toy_params()
    sst = s.server_state_init(p)
    cst = s.client_state_init(p)
    g = jax.tree.map(jnp.ones_like, p)
    # with zero control variates the gradient is unchanged
    g2 = s.grad_transform(g, cst, sst)
    np.testing.assert_allclose(np.asarray(g2["w"]), np.asarray(g["w"]))
    # after an update with drift, c_i changes by -delta/(K*lr)
    delta = jax.tree.map(lambda t: -0.1 * t, g)   # one sgd step of lr .1
    cst2 = s.client_state_update(cst, sst, delta, 1, 0.1)
    np.testing.assert_allclose(np.asarray(cst2["c_i"]["w"]), 1.0, rtol=1e-5)


def test_dp_clipping_bounds_norm():
    fl = FLConfig(strategy="dp_fedavg", dp_clip=1.0, dp_noise=0.0)
    s = get_strategy(fl)
    d = {"w": jnp.full((100,), 10.0)}
    out, _ = s.postprocess(d, (), jax.random.PRNGKey(0))
    nrm = float(jnp.linalg.norm(out["w"]))
    assert nrm <= 1.0 + 1e-4


def test_dp_noise_scales():
    fl = FLConfig(strategy="dp_fedavg", dp_clip=1.0, dp_noise=0.5)
    s = get_strategy(fl)
    d = {"w": jnp.zeros((10_000,))}
    out, _ = s.postprocess(d, (), jax.random.PRNGKey(0))
    std = float(jnp.std(out["w"]))
    assert abs(std - 0.5) < 0.05


@pytest.mark.parametrize("comp", ["int8", "topk"])
def test_compression_error_feedback_recovers(comp):
    """With error feedback, repeated identical deltas converge: residual
    carries the compression error forward.

    int8 sends almost everything each round, so the running total tracks
    the truth closely. Exact-k topk (ratio 0.2) on equal magnitudes sends
    each element about once every 5 rounds, so its total lags by up to a
    round's worth; what error feedback guarantees there is the identity
    ``sent_total + residual == 8 * true_delta``, with nothing starved."""
    fl = FLConfig(strategy="compressed", compression=comp, topk_ratio=0.2,
                  error_feedback=True)
    s = get_strategy(fl)
    p = toy_params()
    cst = s.client_state_init(p)
    true_delta = jax.tree.map(lambda t: 0.01 * jnp.sign(t), p)
    sent_total = jax.tree.map(jnp.zeros_like, p)
    sent_once = jax.tree.map(lambda t: jnp.zeros(t.shape, bool), p)
    for _ in range(8):
        sent, cst = s.postprocess(true_delta, cst, jax.random.PRNGKey(0))
        sent_total = jax.tree.map(lambda a, b: a + b, sent_total, sent)
        sent_once = jax.tree.map(lambda o, b: o | (b != 0), sent_once, sent)
    want = jax.tree.map(lambda t: 8 * t, true_delta)
    if comp == "int8":
        err = max(float(jnp.abs(a - b).max())
                  for a, b in zip(jax.tree.leaves(sent_total),
                                  jax.tree.leaves(want)))
        assert err < 0.015, f"error feedback failed to recover: {err}"
        return
    for tot, res, w in zip(jax.tree.leaves(sent_total),
                           jax.tree.leaves(cst["residual"]),
                           jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(tot + res), np.asarray(w),
                                   rtol=1e-5, atol=1e-7)
    for leaf, once in zip(jax.tree.leaves(true_delta),
                          jax.tree.leaves(sent_once)):
        assert bool(jnp.all(once | (leaf == 0))), \
            "an element was never sent in 8 rounds"


def test_topk_mask_exact_k_under_ties():
    """All-equal magnitudes tie at the k-th value: a threshold compare
    would keep everything; the scatter mask must keep exactly k."""
    from repro.core.strategies.compressed import _topk_mask
    x = jnp.ones((100,))
    mask = _topk_mask(x, 0.2)
    assert int(mask.sum()) == 20
    # blocks of repeated values around the cut: still exactly k survive
    y = jnp.repeat(jnp.asarray([3.0, 2.0, 2.0, 1.0]), 25)
    mask = _topk_mask(y, 0.3)
    assert int(mask.sum()) == 30


def test_topk_postprocess_keeps_exact_budget():
    fl = FLConfig(strategy="compressed", compression="topk", topk_ratio=0.1,
                  error_feedback=False)
    s = get_strategy(fl)
    d = {"w": jnp.ones((200,))}          # every element ties
    sent, _ = s.postprocess(d, {}, jax.random.PRNGKey(0))
    assert int((sent["w"] != 0).sum()) == 20


def test_packed_int8_matches_roundtrip_path():
    """The packed emission (what quant_aggregate consumes) must be the
    same quantization the unpacked ``_roundtrip_int8`` send models:
    per-leaf padding keeps block boundaries identical, so dequantized
    sends AND error-feedback residuals agree bitwise across the two
    representations of the same compression."""
    from repro.core import packing
    fl = FLConfig(strategy="compressed", compression="int8",
                  error_feedback=True)
    s = get_strategy(fl)
    assert s.packs_deltas
    p = toy_params(n=300)                # w: 300 floats -> pads to 512
    delta = jax.tree.map(lambda t: 0.1 * t, p)
    rng = jax.random.PRNGKey(0)

    sent_ref, cst_ref = s.postprocess(delta, s.client_state_init(p), rng)
    pd, cst_pk = s.postprocess_packed(delta, s.client_state_init(p), rng)
    sent_pk = packing.unpack_tree(packing.dequant_flat(pd), delta)
    for a, b in zip(jax.tree.leaves(sent_ref), jax.tree.leaves(sent_pk)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(cst_ref["residual"]),
                    jax.tree.leaves(cst_pk["residual"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packing_roundtrip_identity():
    """pack -> unpack is the identity on any float pytree (padding is
    sliced off per leaf), and packed_size reports the padded layout."""
    from repro.core import packing
    p = toy_params(n=300)
    n, nblocks = packing.packed_size(p)
    assert n == nblocks * packing.QBLOCK
    flat = packing.pack_tree(p)
    assert flat.shape == (n,)
    back = packing.unpack_tree(flat, p)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b))


def test_moon_contrastive_term_positive():
    fl = FLConfig(strategy="moon", moon_mu=1.0, moon_tau=0.5)
    s = get_strategy(fl)
    p = toy_params(2)
    g = toy_params(0)
    cst = {"prev_local": tree_sub(p, g)}

    def base(params, batch, rng):
        return jnp.zeros(()), {}

    l, _ = s.local_loss(base, p, g, None, cst, None)
    assert float(l) > 0.0
