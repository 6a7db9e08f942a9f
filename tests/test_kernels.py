"""Per-kernel interpret-mode validation against the pure-jnp oracles.

Sweeps shapes/dtypes per the deliverable: every Pallas kernel is asserted
allclose against ref.py, plus the differentiable jnp-blockwise path is checked
against plain-softmax autodiff.
"""
import os

os.environ.setdefault("REPRO_KERNEL_IMPL", "jnp")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.quant_aggregate import quant_aggregate as pallas_quant_agg
from repro.kernels.quant_aggregate import tile_shape
from repro.kernels.rmsnorm import rmsnorm as pallas_rmsnorm

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Sq,Sk,H,KV,Dk,Dv", [
    (2, 128, 128, 4, 4, 64, 64),      # MHA
    (1, 256, 256, 8, 2, 64, 64),      # GQA
    (2, 128, 256, 4, 1, 32, 32),      # MQA, Sq != Sk
    (1, 128, 128, 4, 2, 96, 64),      # MLA dims (Dk != Dv)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_pallas_interpret_vs_ref(B, Sq, Sk, H, KV, Dk, Dv, dtype, causal):
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (B, Sq, H, Dk), dtype)
    k = rand(ks[1], (B, Sk, KV, Dk), dtype)
    v = rand(ks[2], (B, Sk, KV, Dv), dtype)
    offset = Sk - Sq
    out = flash_attention_fwd(q, k, v, causal=causal, block_q=64, block_k=64,
                              q_offset=offset, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, q_offset=offset)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_jnp_matches_ref(causal):
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (2, 128, 8, 64), jnp.float32)
    k = rand(ks[1], (2, 128, 2, 64), jnp.float32)
    v = rand(ks[2], (2, 128, 2, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, 0, causal, None, 32, 32)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_backward_matches_autodiff():
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (1, 64, 4, 32), jnp.float32)
    k = rand(ks[1], (1, 64, 2, 32), jnp.float32)
    v = rand(ks[2], (1, 64, 2, 32), jnp.float32)

    def f_flash(q, k, v):
        return (ops.flash_attention(q, k, v, 0, True, None, 32, 32) ** 2).sum()

    def f_ref(q, k, v):
        return (ref.flash_attention_ref(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,D", [(2, 256, 8, 2, 64), (1, 512, 4, 4, 128),
                                        (3, 128, 8, 1, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_pallas_interpret_vs_ref(B, S, H, KV, D, dtype):
    ks = jax.random.split(KEY, 4)
    q = rand(ks[0], (B, H, D), dtype)
    k = rand(ks[1], (B, S, KV, D), dtype)
    v = rand(ks[2], (B, S, KV, D), dtype)
    length = jax.random.randint(ks[3], (B,), 1, S + 1)
    o, m, l = decode_attention_fwd(q, k, v, length, block_k=64, interpret=True)
    got = o / np.maximum(np.asarray(l)[..., None], 1e-30)
    want = ref.decode_attention_ref(q, k, v, length)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_decode_blockwise_jnp_matches_ref():
    ks = jax.random.split(KEY, 4)
    q = rand(ks[0], (2, 8, 64), jnp.float32)
    k = rand(ks[1], (2, 256, 2, 64), jnp.float32)
    v = rand(ks[2], (2, 256, 2, 64), jnp.float32)
    length = jnp.array([100, 256])
    got = ops.decode_attention(q, k, v, length, block_k=64)
    want = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_lse_combine_across_shards():
    """Chunk-parallel decode: combining per-shard (o,m,l) == full attention."""
    ks = jax.random.split(KEY, 4)
    B, S, H, KV, D = 2, 256, 8, 2, 64
    q = rand(ks[0], (B, H, D), jnp.float32)
    k = rand(ks[1], (B, S, KV, D), jnp.float32)
    v = rand(ks[2], (B, S, KV, D), jnp.float32)
    length = jnp.array([200, 256])
    nsh = 4
    chunks = []
    for i in range(nsh):
        ck = k[:, i * (S // nsh):(i + 1) * (S // nsh)]
        cv = v[:, i * (S // nsh):(i + 1) * (S // nsh)]
        clen = jnp.clip(length - i * (S // nsh), 0, S // nsh)
        o, m, l = ops.decode_attention(q, ck, cv, clen, block_k=32,
                                       combine=False)
        chunks.append((o, m, l))
    m_glob = jnp.max(jnp.stack([m for _, m, _ in chunks]), 0)
    l_glob = sum(l * jnp.exp(m - m_glob) for _, m, l in chunks)
    o_glob = sum(o * jnp.exp(m - m_glob)[..., None] for o, m, l in chunks)
    got = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
    want = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# rmsnorm / quant aggregate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(64, 128), (3, 40, 256), (130, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_interpret_vs_ref(shape, dtype):
    ks = jax.random.split(KEY, 2)
    x = rand(ks[0], shape, dtype)
    w = rand(ks[1], shape[-1:], jnp.float32)
    got = pallas_rmsnorm(x, w, block_rows=32, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("C,N,qblock", [(4, 8192, 256), (10, 4096, 128),
                                        (32, 16384, 512), (6, 384 * 256, 256),
                                        (128, 256 * 256, 256)])
def test_quant_aggregate_interpret_vs_ref(C, N, qblock):
    ks = jax.random.split(KEY, 3)
    qd = jax.random.randint(ks[0], (C, N), -127, 128, jnp.int8)
    sc = jax.random.uniform(ks[1], (C, N // qblock), jnp.float32, 1e-4, 1e-2)
    w = jax.random.uniform(ks[2], (C,), jnp.float32)
    w = w / w.sum()
    cb, rows = tile_shape(C, qblock, N // qblock)
    got = pallas_quant_agg(qd, sc, w, cb=cb, rows=rows, interpret=True)
    want = ref.quant_aggregate_ref(qd, sc, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(KEY, (8192,), jnp.float32)
    q, sc = ops.quantize_blockwise(x, block=256)
    deq = ref.quant_aggregate_ref(q[None], sc[None], jnp.ones((1,)))
    err = np.abs(np.asarray(deq - x))
    amax = np.abs(np.asarray(x).reshape(-1, 256)).max(1, keepdims=True)
    bound = np.repeat(amax / 127.0, 256, 1).reshape(-1) / 2 + 1e-7
    assert (err <= bound + 1e-6).all()


# ---------------------------------------------------------------------------
# quant_aggregate dispatcher (ops-level: the path compressed drivers call)
# ---------------------------------------------------------------------------

def _qagg_inputs(C, N, qblock, key=KEY):
    ks = jax.random.split(key, 3)
    qd = jax.random.randint(ks[0], (C, N), -127, 128, jnp.int8)
    sc = jax.random.uniform(ks[1], (C, N // qblock), jnp.float32, 1e-4, 1e-2)
    w = jax.random.uniform(ks[2], (C,), jnp.float32)
    return qd, sc, w / w.sum()


@pytest.mark.parametrize("C,N,qblock", [(4, 8192, 256), (7, 4096, 128),
                                        (1, 2048, 256)])
def test_quant_agg_fused_equals_dequant_first_bitwise(C, N, qblock):
    """The BENCH_agg contract's correctness half: the fused path and the
    dequant-first reference share per-client arithmetic and accumulation
    order, so they must agree bit-for-bit, not just allclose."""
    qd, sc, w = _qagg_inputs(C, N, qblock)
    fused = ops._quant_agg_fused(qd, sc, w)
    dequant = ops._quant_agg_dequant_first(qd, sc, w)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(dequant))


@pytest.mark.parametrize("N,qblock", [(1280, 256), (4096 + 128, 128),
                                      (512, 512)])
def test_quant_aggregate_pad_and_mask_non_divisible(N, qblock, monkeypatch):
    """Pytree packing yields N that rarely divides the kernel tile: the
    interpret-path wrapper must zero-pad up to whole tiles and slice the
    pad back off, matching the unpadded jnp reference."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    qd, sc, w = _qagg_inputs(5, N, qblock)
    got = ops.quant_aggregate(qd, sc, w)
    assert got.shape == (N,)
    want = ref.quant_aggregate_ref(qd, sc, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_quant_aggregate_pads_client_chunks(monkeypatch):
    """A cohort above one client chunk that the chunk does not divide is
    padded with zero-weight clients and reduced chunk by chunk."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    qd, sc, w = _qagg_inputs(200, 1280, 256)
    got = ops.quant_aggregate(qd, sc, w)
    want = ref.quant_aggregate_ref(qd, sc, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_quant_aggregate_vmap_falls_back_to_fused(monkeypatch):
    """Under a campaign lane vmap the Pallas path's custom_vmap rule must
    fall back to the fused jnp path — warning + counter, bitwise per-lane."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    L, C, N, qblock = 3, 4, 2048, 256
    ks = jax.random.split(KEY, 3)
    qd = jax.random.randint(ks[0], (L, C, N), -127, 128, jnp.int8)
    sc = jax.random.uniform(ks[1], (L, C, N // qblock), jnp.float32,
                            1e-4, 1e-2)
    w = jax.random.uniform(ks[2], (L, C), jnp.float32)
    ops.reset_quant_agg_stats()
    with pytest.warns(UserWarning, match="vmapped"):
        got = jax.vmap(ops.quant_aggregate)(qd, sc, w)
    stats = ops.quant_agg_stats()
    assert stats["calls"] == 1 and stats["batched_fallbacks"] == 1
    assert stats["last_impl"] == "jnp-fused(vmap-fallback)"
    for lane in range(L):
        np.testing.assert_array_equal(
            np.asarray(got[lane]),
            np.asarray(ops._quant_agg_fused(qd[lane], sc[lane], w[lane])))


def test_quant_aggregate_vmap_broadcasts_unbatched_args(monkeypatch):
    """Lanes that share the weights (an unbatched operand) take the same
    fallback; the rule broadcasts the shared operand across lanes."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    L, C, N, qblock = 2, 3, 1024, 256
    ks = jax.random.split(KEY, 2)
    qd = jax.random.randint(ks[0], (L, C, N), -127, 128, jnp.int8)
    sc = jax.random.uniform(ks[1], (L, C, N // qblock), jnp.float32,
                            1e-4, 1e-2)
    w = jnp.array([0.5, 0.25, 0.25], jnp.float32)
    with ops.quant_agg_scope() as frame, \
            pytest.warns(UserWarning, match="vmapped"):
        got = jax.vmap(ops.quant_aggregate, in_axes=(0, 0, None))(qd, sc, w)
    assert frame["batched_fallbacks"] == 1
    for lane in range(L):
        np.testing.assert_array_equal(
            np.asarray(got[lane]),
            np.asarray(ops._quant_agg_fused(qd[lane], sc[lane], w)))


def test_quant_aggregate_rejects_unknown_mode(monkeypatch):
    monkeypatch.setenv("REPRO_QUANT_AGG", "fussed")
    qd, sc, w = _qagg_inputs(2, 1024, 256)
    with pytest.raises(ValueError, match="REPRO_QUANT_AGG"):
        ops.quant_aggregate(qd, sc, w)
