"""Public kernel API with backend dispatch.

Backends:
- ``pallas``    — the Pallas TPU kernels (production target);
- ``interpret`` — same kernels executed with ``interpret=True`` (CPU-correct);
- ``jnp``       — blockwise pure-jnp implementations with flash-style memory
                  behaviour. This is what the CPU dry-run compiles, so the
                  lowered HLO never materializes an (S x S) score matrix.

Default: ``pallas`` on TPU, ``jnp`` elsewhere; override with env
``REPRO_KERNEL_IMPL``. Training always differentiates through the jnp
blockwise path (flash-style recomputing backward via ``jax.custom_vjp``).
"""
from __future__ import annotations

import contextlib
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref


def backend() -> str:
    impl = os.environ.get("REPRO_KERNEL_IMPL", "auto")
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention in pure jnp — forward
# ---------------------------------------------------------------------------

def _score_dtype():
    """REPRO_BF16_SCORES=1: materialize attention scores/probs in bf16.

    The Pallas TPU kernel computes f32 scores in VMEM — they never touch
    HBM. The jnp blockwise stand-in (CPU dry-run) materializes them, so the
    roofline harness enables this flag to reproduce the KERNEL's HBM traffic
    profile; numerics-sensitive tests run with it off (f32)."""
    return jnp.bfloat16 if os.environ.get("REPRO_BF16_SCORES") == "1" \
        else jnp.float32


def _blockwise_fwd(q, k, v, causal, q_offset, scale, block_q, block_k):
    """Returns (out (B,Sq,H,Dv), lse (B,H,Sq) f32). Memory O(block) not O(S^2)."""
    B, Sq, H, Dk = q.shape
    _, Sk, KVH, Dv = v.shape
    G = H // KVH
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq, nk = Sq // block_q, Sk // block_k

    qg = jnp.moveaxis(q.reshape(B, nq, block_q, KVH, G, Dk), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, nk, block_k, KVH, Dk), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, block_k, KVH, Dv), 1, 0)

    def per_qblock(qi, qblk):
        q_start = q_offset + qi * block_q

        def kv_step(carry, xs):
            o, m, l = carry
            kb, vb, ks = xs
            s = jnp.einsum("bqkgd,btkd->bkgqt", qblk, kb).astype(_score_dtype())
            s = s * jnp.asarray(scale, s.dtype)
            if causal:
                qpos = q_start + jnp.arange(block_q)
                kpos = ks + jnp.arange(block_k)
                s = jnp.where((qpos[:, None] >= kpos[None, :])[None, None, None],
                              s, jnp.asarray(-1e30, s.dtype))
            s = s.astype(jnp.float32)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None]).astype(_score_dtype())
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.astype(jnp.float32).sum(-1)
            pv = jnp.einsum("bkgqt,btkd->bkgqd", p.astype(vb.dtype), vb)
            o = o * alpha[..., None] + pv.astype(jnp.float32)
            return (o, m_new, l), None

        o0 = jnp.zeros((B, KVH, G, block_q, Dv), jnp.float32)
        m0 = jnp.full((B, KVH, G, block_q), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, block_q), jnp.float32)
        ks = jnp.arange(nk) * block_k
        if causal:
            # only scan kv blocks that can intersect the causal triangle
            pass  # masking handles it; block skipping is a pallas-level win
        (o, m, l), _ = jax.lax.scan(kv_step, (o0, m0, l0), (kc, vc, ks))
        o = o / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        out = jnp.moveaxis(o, 3, 1).reshape(B, block_q, KVH * G, Dv)
        return out.astype(q.dtype), lse.reshape(B, H, block_q)

    _, (outs, lses) = jax.lax.scan(
        lambda c, xs: (c, per_qblock(*xs)), 0, (jnp.arange(nq), qg))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, Dv)
    lse = jnp.moveaxis(lses, 0, 2).reshape(B, H, Sq)   # (nq,B,H,bq)->(B,H,Sq)
    return out, lse


# ---------------------------------------------------------------------------
# Flash backward (recomputes p per block pair; saves only out + lse)
# ---------------------------------------------------------------------------

def _blockwise_bwd(q, k, v, out, lse, dout, causal, q_offset, scale,
                   block_q, block_k):
    """Flash backward, KV-outer / Q-inner loop order.

    dk/dv for a kv block are EMITTED per step (scan ys — written once each)
    while only dq (Sq-sized, the small side under sequence sharding) rides
    the carry. The kv-outer order cuts the dominant HBM term ~(Sk/Sq)x vs
    carrying Sk-sized dk/dv accumulators through a q-outer scan
    (EXPERIMENTS.md §Perf iteration 2).
    """
    B, Sq, H, Dk = q.shape
    _, Sk, KVH, Dv = v.shape
    G = H // KVH
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq, nk = Sq // block_q, Sk // block_k

    # delta_i = rowsum(dout_i * out_i)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), -1)
    delta = jnp.moveaxis(delta, 1, 2)                       # (B, H, Sq)

    qg = q.reshape(B, Sq, KVH, G, Dk)
    dog = dout.reshape(B, Sq, KVH, G, Dv)
    lseg = lse.reshape(B, KVH, G, Sq)
    delg = delta.reshape(B, KVH, G, Sq)
    kc = jnp.moveaxis(k.reshape(B, nk, block_k, KVH, Dk), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, block_k, KVH, Dv), 1, 0)

    def kv_step(dq_acc, kxs):
        kb, vb, ks = kxs
        s = jnp.einsum("bqkgd,btkd->bkgqt", qg, kb).astype(_score_dtype())
        s = s * jnp.asarray(scale, s.dtype)
        if causal:
            qpos = q_offset + jnp.arange(Sq)
            kpos = ks + jnp.arange(block_k)
            s = jnp.where((qpos[:, None] >= kpos[None, :])[None, None, None],
                          s, jnp.asarray(-1e30, s.dtype))
        p = jnp.exp(s.astype(jnp.float32)
                    - lseg[..., None]).astype(_score_dtype())
        dp = jnp.einsum("bqkgd,btkd->bkgqt", dog, vb).astype(_score_dtype())
        ds = (p.astype(jnp.float32) * (dp.astype(jnp.float32)
                                       - delg[..., None])
              * scale).astype(_score_dtype())
        dqb = jnp.einsum("bkgqt,btkd->bqkgd", ds.astype(kb.dtype), kb)
        dkb = jnp.einsum("bkgqt,bqkgd->btkd", ds.astype(qg.dtype), qg)
        dvb = jnp.einsum("bkgqt,bqkgd->btkd", p.astype(dog.dtype), dog)
        return dq_acc + dqb.astype(jnp.float32), (dkb, dvb)

    dq0 = jnp.zeros((B, Sq, KVH, G, Dk), jnp.float32)
    ks = jnp.arange(nk) * block_k
    dq, (dks, dvs) = jax.lax.scan(kv_step, dq0, (kc, vc, ks))
    dq = dq.reshape(B, Sq, H, Dk).astype(q.dtype)
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, Sk, KVH, Dk).astype(k.dtype)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, Sk, KVH, Dv).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public flash attention (differentiable, backend-dispatched)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention(q, k, v, q_offset=0, causal: bool = True,
                    scale: float | None = None, block_q: int = 512,
                    block_k: int = 512):
    """Differentiable flash attention. q:(B,Sq,H,Dk) k:(B,Sk,KV,Dk) v:(B,Sk,KV,Dv).

    ``q_offset`` — global position of q row 0; may be a traced scalar (e.g.
    ``axis_index('model') * S_loc`` for sequence-sharded attention).
    """
    out, _ = _fa_fwd_rule(q, k, v, q_offset, causal, scale, block_q, block_k)
    return out


def _is_static_int(x) -> bool:
    return isinstance(x, (int, np.integer))


def _fa_fwd_rule(q, k, v, q_offset, causal, scale, block_q, block_k):
    scale = float(scale if scale is not None else 1.0 / np.sqrt(q.shape[-1]))
    impl = backend()
    if impl in ("pallas", "interpret") and _is_static_int(q_offset):
        from repro.kernels.flash_attention import flash_attention_fwd
        out = flash_attention_fwd(
            q, k, v, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, q_offset=int(q_offset),
            interpret=(impl == "interpret"))
        # lse is recomputed blockwise in the bwd rule when grads are needed
        return out, (q, k, v, q_offset, out, None)
    out, lse = _blockwise_fwd(q, k, v, causal, q_offset, scale,
                              block_q, block_k)
    return out, (q, k, v, q_offset, out, lse)


def _fa_bwd_rule(causal, scale, block_q, block_k, res, dout):
    q, k, v, q_offset, out, lse = res
    scale = float(scale if scale is not None else 1.0 / np.sqrt(q.shape[-1]))
    if lse is None:  # pallas fwd didn't keep lse: recompute blockwise
        out, lse = _blockwise_fwd(q, k, v, causal, q_offset, scale,
                                  block_q, block_k)
    dq, dk, dv = _blockwise_bwd(q, k, v, out, lse, dout, causal, q_offset,
                                scale, block_q, block_k)
    d_off = None if _is_static_int(q_offset) else jnp.zeros_like(q_offset)
    return dq, dk, dv, d_off


flash_attention.defvjp(_fa_fwd_rule, _fa_bwd_rule)


# ---------------------------------------------------------------------------
# Decode attention (not differentiated — serving only)
# ---------------------------------------------------------------------------

def decode_attention(q, k, v, length, *, scale: float | None = None,
                     block_k: int = 512, combine: bool = True):
    """One-token attention over a KV cache; optionally returns (o, m, l) stats."""
    impl = backend()
    if impl in ("pallas", "interpret"):
        from repro.kernels.decode_attention import decode_attention_fwd
        o, m, l = decode_attention_fwd(
            q, k, v, length, scale=scale, block_k=block_k,
            interpret=(impl == "interpret"))
    else:
        o, m, l = _decode_blockwise(q, k, v, length, scale=scale,
                                    block_k=block_k)
    if combine:
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return o, m, l


def _decode_blockwise(q, k, v, length, *, scale, block_k):
    """jnp blockwise decode: scans kv chunks; never forms (B,H,S) f32 at once
    beyond one chunk. Returns unnormalized (o, m, l)."""
    B, H, Dk = q.shape
    _, S, KVH, Dv = v.shape
    G = H // KVH
    scale = float(scale if scale is not None else 1.0 / np.sqrt(Dk))
    block_k = min(block_k, S)
    nk = S // block_k
    qg = q.reshape(B, KVH, G, Dk)
    kc = jnp.moveaxis(k.reshape(B, nk, block_k, KVH, Dk), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, block_k, KVH, Dv), 1, 0)

    def step(carry, xs):
        o, m, l = carry
        kb, vb, ks = xs
        s = jnp.einsum("bkgd,btkd->bkgt", qg, kb).astype(jnp.float32) * scale
        kpos = ks + jnp.arange(block_k)
        s = jnp.where(kpos[None, None, None] < length[:, None, None, None],
                      s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bkgt,btkd->bkgd", p.astype(vb.dtype), vb).astype(jnp.float32)
        return (o, m_new, l), None

    o0 = jnp.zeros((B, KVH, G, Dv), jnp.float32)
    m0 = jnp.full((B, KVH, G), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KVH, G), jnp.float32)
    (o, m, l), _ = jax.lax.scan(step, (o0, m0, l0),
                                (kc, vc, jnp.arange(nk) * block_k))
    return o.reshape(B, H, Dv), m.reshape(B, H), l.reshape(B, H)


# ---------------------------------------------------------------------------
# RMSNorm / quantized aggregation
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps: float = 1e-6):
    impl = backend()
    if impl in ("pallas", "interpret"):
        from repro.kernels.rmsnorm import rmsnorm as _k
        return _k(x, w, eps=eps, interpret=(impl == "interpret"))
    return _ref.rmsnorm_ref(x, w, eps)


# ---------------------------------------------------------------------------
# Quantized aggregation (the FL compressed-comms hot path)
# ---------------------------------------------------------------------------

# Trace-time dispatch counters. ``calls`` increments every time a program
# containing quant_aggregate is TRACED (cached jit re-executions do not
# retrace), so tests assert the compressed drivers really route through this
# function — instrumentation, not code inspection.
#
# Counters are SCOPED, not process-global: ``quant_agg_scope()`` pushes a
# fresh frame, increments land on every active frame, and
# ``quant_agg_stats()`` snapshots the innermost one — so two runs in one
# process (each executor's chunk loop holds its own scope) never bleed
# routing counts into each other's telemetry, while the bottom frame keeps
# the legacy process-wide view for callers outside any scope.
def _quant_agg_frame() -> dict:
    return {"calls": 0, "batched_fallbacks": 0, "last_impl": None}


_QUANT_AGG_FRAMES = [_quant_agg_frame()]


def quant_agg_stats() -> dict:
    """Snapshot of the innermost active scope's dispatch counters (the
    process-wide frame when no ``quant_agg_scope`` is open)."""
    return dict(_QUANT_AGG_FRAMES[-1])


def reset_quant_agg_stats() -> None:
    """Zero the innermost active scope's counters."""
    _QUANT_AGG_FRAMES[-1].update(_quant_agg_frame())


@contextlib.contextmanager
def quant_agg_scope():
    """A fresh counter frame for one run's telemetry. Yields the live frame
    dict; increments inside the scope also propagate to every enclosing
    frame (outer totals stay complete)."""
    frame = _quant_agg_frame()
    _QUANT_AGG_FRAMES.append(frame)
    try:
        yield frame
    finally:
        _QUANT_AGG_FRAMES.remove(frame)


def _quant_agg_bump(key: str) -> None:
    for frame in _QUANT_AGG_FRAMES:
        frame[key] += 1


def _quant_agg_impl(name) -> None:
    for frame in _QUANT_AGG_FRAMES:
        frame["last_impl"] = name


def _quant_agg_fused(qdeltas, scales, weights):
    """Fused dequant + weighted sum: the client accumulation is unrolled
    (C is a static shape), so XLA fuses the whole chain into ONE pass over
    the output — each int8 byte is converted in-register and feeds the
    accumulator directly; the (C, N) f32 dequant never exists in memory.
    (A ``.sum(axis=0)`` or einsum formulation defeats this on CPU: XLA
    materializes reduce/dot-general operands.)"""
    C, N = qdeltas.shape
    nblocks = scales.shape[-1]
    out = jnp.zeros((nblocks, N // nblocks), jnp.float32)
    for c in range(C):
        deq = qdeltas[c].astype(jnp.float32).reshape(nblocks, -1) \
            * scales[c, :, None]
        out = out + deq * weights[c]
    return out.reshape(N)


def _quant_agg_dequant_first(qdeltas, scales, weights):
    """Reference path: materialize the whole (C, N) f32 dequant, then run
    the same unrolled weighted accumulation over it. ``optimization_barrier``
    is the identity on values — per-client arithmetic is (q*scale)*weight
    with the identical left-to-right accumulation, so the result is
    bit-for-bit the fused path's — but it pins the f32 intermediate in
    memory: 4x the int8 bytes written AND read back. That traffic gap is
    what BENCH_agg measures and the CI bench gate enforces."""
    C, N = qdeltas.shape
    nblocks = scales.shape[-1]
    d = qdeltas.astype(jnp.float32).reshape(C, nblocks, N // nblocks)
    d = d * scales[..., None]
    d = jax.lax.optimization_barrier(d)
    out = jnp.zeros((nblocks, N // nblocks), jnp.float32)
    for c in range(C):
        out = out + d[c] * weights[c]
    return out.reshape(N)


def _quant_agg_pallas(qdeltas, scales, weights, interpret: bool):
    """Pad-and-mask wrapper around the Pallas kernel: the block rows are
    padded up to whole kernel tiles and the clients up to whole client
    chunks, all with zeros (q == 0, scale == 0 and weight == 0 contribute
    exactly +0.0), and the pad lanes are sliced off."""
    from repro.kernels import quant_aggregate as qa
    C, N = qdeltas.shape
    nblocks = scales.shape[-1]
    qblock = N // nblocks
    cb, rows = qa.tile_shape(C, qblock, nblocks)
    pad_b, pad_c = (-nblocks) % rows, (-C) % cb
    if pad_b or pad_c:
        qdeltas = jnp.pad(qdeltas, ((0, pad_c), (0, pad_b * qblock)))
        scales = jnp.pad(scales, ((0, pad_c), (0, pad_b)))
        weights = jnp.pad(weights, (0, pad_c))
    out = qa.quant_aggregate(qdeltas, scales, weights, cb=cb, rows=rows,
                             interpret=interpret)
    return out[:N] if pad_b else out


def _quant_agg_vmap_rule(axis_size, in_batched, qdeltas, scales, weights):
    """custom_vmap rule of the Pallas path: a vmapped lane axis (campaign
    lanes) routes to the fused jnp path, counted and warned."""
    import warnings
    _quant_agg_bump("batched_fallbacks")
    _quant_agg_impl("jnp-fused(vmap-fallback)")
    warnings.warn(
        "quant_aggregate: Pallas kernel requested under a vmapped lane "
        "axis; using the fused jnp path for this trace", stacklevel=2)
    args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, b in zip((qdeltas, scales, weights), in_batched)]
    return jax.vmap(_quant_agg_fused)(*args), True


@functools.cache
def _pallas_entry(interpret: bool):
    """The Pallas path with its vmap rule attached (one per interpret flag,
    so the traced function takes arrays only)."""
    entry = jax.custom_batching.custom_vmap(
        functools.partial(_quant_agg_pallas, interpret=interpret))
    entry.def_vmap(_quant_agg_vmap_rule)
    return entry


@jax.named_scope("fl.aggregate")
def quant_aggregate(qdeltas, scales, weights):
    """-> (N,) f32: ``sum_c weights[c] * dequant(qdeltas[c])``.

    Dispatch (rows: REPRO_KERNEL_IMPL; REPRO_QUANT_AGG=dequant overrides all
    rows with the dequant-first reference path):

    - ``pallas``/``interpret`` — Pallas kernel (compiled / interpret=True),
      via the pad-and-mask wrapper; its ``custom_vmap`` rule sends a
      campaign lane ``vmap`` to the fused jnp path with a logged warning;
    - ``jnp`` (CPU default)   — the fused jnp expression.
    """
    mode = os.environ.get("REPRO_QUANT_AGG", "fused")
    if mode not in ("fused", "dequant"):
        raise ValueError(f"REPRO_QUANT_AGG={mode!r} (want fused|dequant)")
    _quant_agg_bump("calls")
    if mode == "dequant":
        _quant_agg_impl("dequant-first")
        return _quant_agg_dequant_first(qdeltas, scales, weights)
    impl = backend()
    if impl in ("pallas", "interpret"):
        _quant_agg_impl(impl)
        return _pallas_entry(impl == "interpret")(qdeltas, scales, weights)
    _quant_agg_impl("jnp-fused")
    return _quant_agg_fused(qdeltas, scales, weights)


def quantize_blockwise(x, block: int = 256):
    return _ref.quantize_blockwise_ref(x, block=block)
