"""FL rounds as single compiled programs (the TPU rendering of Alg. 1).

Two client placements (DESIGN.md):

- ``spatial``  — each point of the flattened client grid (data x model [x pod])
  hosts one or more whole clients; local epochs run truly in parallel under
  shard_map (vmap over the per-chip client dim), aggregation is a weighted
  psum / gossip ppermute per the topology. The model itself runs *unsharded*
  inside each client (AxisCtx() is passed down).

- ``temporal`` — one client at a time uses the entire mesh (ZeRO-3 + SP
  sharding from sharding/specs.py); the cohort is a lax.scan, deltas are
  accumulated with client weights, then the server update runs. With
  cohort=1 and E=1 a round is mathematically one data-parallel step +
  server optimizer — that identity is a unit test.

Both paths run meshless (AxisCtx()) for CPU-scale tests and benches.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import SWEEPABLE_SCALARS, FLConfig, ModelConfig
from repro.core import determinism, packing
from repro.core import probes as probelib
from repro.core.consensus import MultiWorkerAggregator
from repro.core.strategy import (Strategy, client_sgd_step, tree_add,
                                 tree_scale, tree_sub, tree_zeros_like)
from repro.core.topology import Decentralized, get_topology
from repro.sharding.axes import AxisCtx

PyTree = Any


def bind_hyper(fl: FLConfig, strategy: Strategy, hyper):
    """Rebind swept scalars (possibly traced) onto the (fl, strategy) pair.

    ``hyper`` is a dict mapping SWEEPABLE_SCALARS names to scalars — Python
    floats or traced 0-d arrays (one vmap lane of a campaign's (S,) sweep
    axis). With ``hyper`` empty/None this is the identity, so the
    single-trajectory path is untouched."""
    if not hyper:
        return fl, strategy
    unknown = set(hyper) - set(SWEEPABLE_SCALARS)
    if unknown:
        raise KeyError(f"non-sweepable hyper keys {sorted(unknown)}; "
                       f"sweepable scalars: {SWEEPABLE_SCALARS}")
    fl_h = dataclasses.replace(fl, **hyper)
    return fl_h, dataclasses.replace(strategy, fl=fl_h)


def pop_alive(hyper):
    """Split the lane-scheduler's alive mask off a hyper dict.

    ``alive`` is the one hyper entry that is not a SWEEPABLE scalar: a
    per-lane 0/1 float the campaign threads as a *runtime* value so the
    lane scheduler (runtime/scheduler.py) can zero-weight dropped lanes
    between chunk launches without recompiling. Returns ``(alive, rest)``
    with ``alive`` None when absent (every single-run path)."""
    if not hyper or "alive" not in hyper:
        return None, hyper
    rest = dict(hyper)
    return rest.pop("alive"), rest


def freeze_unless(alive, new_state, old_state):
    """Select ``new_state`` where ``alive`` > 0, else keep ``old_state``.

    A dropped lane's state freezes at its drop round: the select picks
    whole computed tensors, so for alive lanes it is bitwise the identity
    (the load-bearing property for the scheduler-off contract)."""
    keep = alive > 0
    return jax.tree.map(lambda n, o: jnp.where(keep, n, o),
                        new_state, old_state)


# ---------------------------------------------------------------------------
# Per-client local training (pure; no cross-client communication)
# ---------------------------------------------------------------------------

@jax.named_scope("fl.local_train")
def local_train(model, model_ctx: AxisCtx, strategy: Strategy, fl: FLConfig,
                global_params, server_state, client_state, batches, rng,
                gather_fn=lambda b: b, grad_sync=lambda g: g,
                pack_deltas: bool = False):
    """Run E local epochs over ``batches`` (leading dim = steps).

    Returns (delta, new_client_state, mean_loss). With ``pack_deltas`` the
    delta leaves the client as a ``packing.PackedDelta`` (int8 + block
    scales, via ``Strategy.postprocess_packed``) — what actually crosses the
    simulated network on the compressed path, under the ``fl.pack``
    scope."""
    post = (jax.named_scope("fl.pack")(strategy.postprocess_packed)
            if pack_deltas else strategy.postprocess)
    n_steps = jax.tree.leaves(batches)[0].shape[0]
    use_mom = fl.client_optimizer == "sgdm" and fl.client_momentum > 0
    mom0 = tree_zeros_like(global_params) if use_mom else None

    def base_loss(p, b, key):
        return model.loss(model_ctx, p, b, gather_fn)

    if fl.local_epochs * n_steps == 1 and not use_mom:
        # Fast path: one local SGD step => delta == -lr * grad. Elides the
        # params' copy + subtraction buffers (matters at 400B scale).
        batch = jax.tree.map(lambda t: t[0], batches)
        key = determinism.step_key(rng, 0)

        def lfn(p):
            return strategy.local_loss(base_loss, p, global_params, batch,
                                       client_state, key)

        (loss, _), grads = jax.value_and_grad(lfn, has_aux=True)(global_params)
        grads = grad_sync(grads)
        grads = strategy.grad_transform(grads, client_state, server_state)
        delta = jax.tree.map(
            lambda p, g: (-fl.client_lr * g).astype(p.dtype),
            global_params, grads)
        delta, client_state = post(delta, client_state, rng)
        client_state = strategy.client_state_update(
            client_state, server_state, delta, 1, fl.client_lr)
        return delta, client_state, loss

    def one_step(carry, xs):
        params, mom = carry
        step_idx, key = xs
        batch = jax.tree.map(lambda t: t[step_idx % n_steps], batches)

        def lfn(p):
            return strategy.local_loss(base_loss, p, global_params, batch,
                                       client_state, key)

        (loss, _), grads = jax.value_and_grad(lfn, has_aux=True)(params)
        grads = grad_sync(grads)
        grads = strategy.grad_transform(grads, client_state, server_state)
        params, new_mom = client_sgd_step(params, grads, fl.client_lr, mom,
                                          fl.client_momentum)
        return (params, new_mom), loss

    total = fl.local_epochs * n_steps
    keys = jax.vmap(lambda i: determinism.step_key(rng, i))(jnp.arange(total))
    (params, _), losses = jax.lax.scan(
        one_step, (global_params, mom0), (jnp.arange(total), keys))
    delta = tree_sub(params, global_params)
    delta, client_state = post(delta, client_state, rng)
    client_state = strategy.client_state_update(
        client_state, server_state, delta, total, fl.client_lr)
    return delta, client_state, losses.mean()


# ---------------------------------------------------------------------------
# Packed (int8) server-side aggregation
# ---------------------------------------------------------------------------

@jax.named_scope("fl.aggregate")
def packed_aggregate(topo, ctx: AxisCtx, pd, weights):
    """Weighted mean of stacked ``PackedDelta``s ((C, N) int8 + (C, N/b)
    scales) through the fused dequant+weighted-sum kernel, following the
    topology's reduction plan: each int8 byte is read once and only the
    (N,) f32 numerator crosses the mesh. Returns the flat f32 aggregate."""
    from repro.kernels import ops
    from repro.core.topology import Hierarchical
    num = ops.quant_aggregate(pd.q, pd.scale, weights)
    den = weights.sum()
    if isinstance(topo, Hierarchical):
        intra = tuple(a for a in (ctx.data, ctx.model) if a)
        if intra:      # edge tier
            num = jax.lax.psum(num, intra)
            den = jax.lax.psum(den, intra)
        agg = num / jnp.maximum(den, 1e-12)
        if ctx.pod:    # cloud tier
            agg = jax.lax.pmean(agg, ctx.pod)
        return agg
    axes = tuple(a for a in (ctx.pod, ctx.data, ctx.model) if a)
    if axes:
        num = jax.lax.psum(num, axes)
        den = jax.lax.psum(den, axes)
    return num / jnp.maximum(den, 1e-12)


# ---------------------------------------------------------------------------
# Spatial round
# ---------------------------------------------------------------------------

def build_spatial_round(model, strategy: Strategy, fl: FLConfig,
                        probes: bool = False):
    """Returns round_fn(ctx, state, batch, weights, rng) -> (state, metrics).

    state: {"params", "server", "clients"}; for decentralized topology
    ``params`` carries the per-client leading dim (diverged models).

    ``probes`` (a trace-time flag: off compiles the exact pre-probe program)
    adds a ``metrics["probes"]`` dict of read-only per-round diagnostics
    (core/probes.py) — pure extra consumers of the round's intermediates,
    so probes-on trajectories stay bitwise probes-off."""
    topo = get_topology(fl.topology, fl.gossip_steps)
    decentralized = isinstance(topo, Decentralized)
    mw = (MultiWorkerAggregator(fl.n_workers, fl.byzantine_workers,
                                fl.consensus)
          if (fl.n_workers > 1 or fl.byzantine_workers > 0) else None)
    inner = AxisCtx()   # the model runs unsharded inside each client
    # gossip mixing has no server-side reduce to fuse into — the packed
    # path is the client->server topologies' (ROADMAP: gossip follow-on)
    packed = strategy.packs_deltas and not decentralized

    def round_fn(ctx: AxisCtx, state, batch, weights, rng, hyper=None):
        """batch: (C_loc, steps, B_c, ...); weights: (C_loc,)."""
        fl_h, strategy_h = bind_hyper(fl, strategy, hyper)
        params = state["params"]
        server_state = state["server"]
        C_loc = jax.tree.leaves(batch)[0].shape[0]
        chip = ctx.index(ctx.model)
        for axis in (ctx.data, ctx.pod):
            if axis is not None:
                chip = chip * 0 + ctx.index(axis) * _grid_below(ctx, axis) + chip
        client_ids = chip * C_loc + jnp.arange(C_loc)
        keys = jax.vmap(lambda c: determinism.client_key(rng, c))(client_ids)
        axes = tuple(a for a in (ctx.pod, ctx.data, ctx.model) if a)
        psum_ = (lambda x: jax.lax.psum(x, axes)) if axes else (lambda x: x)
        pmean_ = (lambda x: jax.lax.pmean(x, axes)) if axes else (lambda x: x)
        pr = {}

        def per_client(cbatch, cstate, key, start_params):
            delta, cst, loss = local_train(
                model, inner, strategy_h, fl_h, start_params, server_state,
                cstate, cbatch, key, pack_deltas=packed)
            if not probes or decentralized:
                return delta, cst, loss
            # probe moments computed where the delta/residual are written
            # (cache-hot, fusable with the producing ops) — a separate
            # post-vmap pass would re-read every client's full parameter
            # volume at memory speed, which dwarfs the training compute
            # on small models
            ex = {"sq": (probelib.packed_sq_norm(delta.q, delta.scale)
                         if packed else probelib.tree_sq_norm(delta))}
            if packed:
                ex["sat"] = probelib.sat_frac(delta.q)
            if isinstance(cst, dict) and "residual" in cst:
                ex["rsq"] = probelib.tree_sq_norm(cst["residual"])
            return delta, cst, loss, ex

        if decentralized:
            deltas, cstates, losses = jax.vmap(per_client)(
                batch, state["clients"], keys, params)
            updated = tree_add(params, deltas)
            mixed = topo.mix(ctx, updated)
            new_params = mixed
            new_server = server_state
            if probes:
                # drift for gossip = param spread across the client models:
                # sqrt(mean_c ||p_c - mean_c' p_c'||^2)
                mean_p = jax.tree.map(lambda t: pmean_(t.mean(0)), new_params)
                spread = probelib.per_client_sq_norms(jax.tree.map(
                    lambda t, m: t - m[None], new_params, mean_p))
                pr["drift_norm"] = jnp.sqrt(pmean_(spread.mean()))
                pr["sat_frac"] = jnp.zeros((), jnp.float32)
                pr["ef_residual_norm"] = jnp.zeros((), jnp.float32)
        else:
            out = jax.vmap(per_client, in_axes=(0, 0, 0, None))(
                batch, state["clients"], keys, params)
            if probes:
                deltas, cstates, losses, pex = out
            else:
                deltas, cstates, losses = out
            if packed:
                agg_flat = packed_aggregate(topo, ctx, deltas, weights)
                agg = packing.unpack_tree(agg_flat, params)
            else:
                agg = topo.aggregate(ctx, deltas, weights)
            if mw is not None:
                agg = mw.run(agg, rng)
            agg = jax.tree.map(lambda a, p: a.astype(p.dtype), agg, params)
            new_params, new_server = strategy_h.server_update(
                params, agg, server_state)
            # SCAFFOLD: the server control variate is the cohort mean of the
            # client variates (communicated alongside the deltas, per the
            # paper's "additional states" requirement (5)).
            if isinstance(new_server, dict) and "c" in new_server \
                    and isinstance(cstates, dict) and "c_i" in cstates:
                new_server = dict(new_server,
                                  c=topo.aggregate(ctx, cstates["c_i"],
                                                   weights))
            if probes:
                pr["sat_frac"] = (pmean_(pex["sat"].mean()) if packed
                                  else jnp.zeros((), jnp.float32))
                pr["drift_norm"] = probelib.drift_from_moments(
                    weights, pex["sq"], probelib.tree_sq_norm(agg), psum_)
                if "rsq" in pex:
                    pr["ef_residual_norm"] = jnp.sqrt(
                        psum_(pex["rsq"].sum()) / jnp.maximum(
                            psum_(jnp.asarray(C_loc, jnp.float32)), 1.0))
                else:
                    pr["ef_residual_norm"] = jnp.zeros((), jnp.float32)
        loss = losses.mean()
        if axes:
            loss = jax.lax.pmean(loss, axes)
        new_state = {"params": new_params, "server": new_server,
                     "clients": cstates}
        metrics = {"loss": loss}
        if probes:
            pr["update_norm"] = probelib.tree_norm(
                tree_sub(new_params, params))
            pr["nonfinite"] = probelib.norm_nonfinite(pr["update_norm"])
            metrics["probes"] = pr
        return new_state, metrics

    return round_fn


def _grid_below(ctx: AxisCtx, axis: str) -> int:
    """Flattened grid stride for client-id computation."""
    if axis == ctx.data:
        return ctx.size(ctx.model)
    if axis == ctx.pod:
        return ctx.size(ctx.model) * ctx.size(ctx.data)
    return 1


# ---------------------------------------------------------------------------
# Temporal round
# ---------------------------------------------------------------------------

def build_temporal_round(model, strategy: Strategy, fl: FLConfig,
                         cfg: ModelConfig, probes: bool = False):
    """Returns round_fn(ctx, state, batch, weights, rng) -> (state, metrics).

    batch: (C_t, steps, B_loc, ...) — cohort clients scanned in time, each
    using the whole mesh. For C_t == 1 the delta buffer is elided.
    ``probes`` as in ``build_spatial_round`` (for the scanned-client path
    the drift moments accumulate in the fori carry — only weighted sums are
    needed, never the stacked deltas)."""
    from repro.sharding import specs as sspecs
    topo = get_topology(fl.topology, fl.gossip_steps)
    mw = (MultiWorkerAggregator(fl.n_workers, fl.byzantine_workers,
                                fl.consensus)
          if (fl.n_workers > 1 or fl.byzantine_workers > 0) else None)
    packed = strategy.packs_deltas

    def round_fn(ctx: AxisCtx, state, batch, weights, rng, hyper=None):
        fl_h, strategy_h = bind_hyper(fl, strategy, hyper)
        params = state["params"]
        server_state = state["server"]
        gather_fn = sspecs.make_gather_fn(cfg, ctx)
        grad_sync = sspecs.make_grad_sync(cfg, ctx)
        C_t = jax.tree.leaves(batch)[0].shape[0]

        def client(i, carry):
            acc, loss_acc, *rest = carry
            cbatch = jax.tree.map(lambda t: t[i], batch)
            key = determinism.client_key(rng, i)
            delta, _, loss = local_train(
                model, ctx, strategy_h, fl_h, params, server_state, (),
                cbatch, key, gather_fn, grad_sync)
            w = weights[i]
            acc = tree_add(acc, tree_scale(
                delta, w / jnp.maximum(weights.sum(), 1e-12)))
            out = (acc, loss_acc + loss / C_t)
            if probes:
                # weighted second moment of the deltas for the drift probe
                out += (rest[0] + w / jnp.maximum(weights.sum(), 1e-12)
                        * probelib.tree_sq_norm(delta),)
            return out

        def client_packed(i):
            cbatch = jax.tree.map(lambda t: t[i], batch)
            key = determinism.client_key(rng, i)
            pd, _, loss = local_train(
                model, ctx, strategy_h, fl_h, params, server_state, (),
                cbatch, key, gather_fn, grad_sync, pack_deltas=True)
            return pd, loss

        pr = {"sat_frac": jnp.zeros((), jnp.float32),
              "ef_residual_norm": jnp.zeros((), jnp.float32),
              "drift_norm": jnp.zeros((), jnp.float32)} if probes else {}
        if packed:
            # clients still run one at a time (lax.map scans), but their
            # int8 sends are stacked to the kernel's (C_t, N) layout and
            # reduced in ONE fused dequant+weighted-sum
            if C_t == 1:
                pd, loss = client_packed(0)
                pds = jax.tree.map(lambda t: t[None], pd)
                w = jnp.ones((1,), jnp.float32)   # C_t==1 applies raw delta
            else:
                pds, losses = jax.lax.map(client_packed, jnp.arange(C_t))
                loss = losses.sum() / C_t
                w = weights / jnp.maximum(weights.sum(), 1e-12)
            from repro.kernels import ops
            agg_flat = ops.quant_aggregate(pds.q, pds.scale, w)
            agg = jax.tree.map(
                lambda a, p: a.astype(p.dtype),
                packing.unpack_tree(agg_flat, params), params)
            if probes:
                pr["sat_frac"] = probelib.sat_frac(pds.q)
                pr["drift_norm"] = probelib.drift_from_moments(
                    w, probelib.packed_sq_norms(pds.q, pds.scale),
                    jnp.sum(jnp.square(agg_flat)))
        elif C_t == 1:
            cbatch = jax.tree.map(lambda t: t[0], batch)
            key = determinism.client_key(rng, 0)
            agg, _, loss = local_train(
                model, ctx, strategy_h, fl_h, params, server_state, (),
                cbatch, key, gather_fn, grad_sync)
        else:
            acc0 = tree_zeros_like(params)
            if probes:
                agg, loss, msq = jax.lax.fori_loop(
                    0, C_t, lambda i, c: client(i, c),
                    (acc0, 0.0, jnp.zeros((), jnp.float32)))
                # msq is already the weighted mean (weights normalized in
                # the carry), so the variance identity needs no psum here
                pr["drift_norm"] = jnp.sqrt(jnp.maximum(
                    msq - probelib.tree_sq_norm(agg), 0.0))
            else:
                agg, loss = jax.lax.fori_loop(
                    0, C_t, lambda i, c: client(i, c), (acc0, 0.0))

        # hierarchical/cross-pod tier: average edge aggregates over pods
        if ctx.pod is not None:
            agg = jax.tree.map(lambda t: jax.lax.pmean(t, ctx.pod), agg)
        if mw is not None:
            agg = mw.run(agg, rng)
        new_params, new_server = strategy_h.server_update(params, agg,
                                                          server_state)
        new_state = {"params": new_params, "server": new_server,
                     "clients": state.get("clients", ())}
        axes = tuple(a for a in (ctx.pod, ctx.data, ctx.model) if a)
        if axes:
            loss = jax.lax.pmean(loss, axes)
        metrics = {"loss": loss}
        if probes:
            pr["update_norm"] = probelib.tree_norm(
                tree_sub(new_params, params))
            pr["nonfinite"] = probelib.norm_nonfinite(pr["update_norm"])
            if axes:
                # the temporal model is sharded; probe scalars are computed
                # identically per device (grad_sync replicates), so pmean is
                # the replication-safe fold
                pr = {k: jax.lax.pmean(v, axes) for k, v in pr.items()}
            metrics["probes"] = pr
        return new_state, metrics

    return round_fn


# ---------------------------------------------------------------------------
# Device-resident multi-round driver
# ---------------------------------------------------------------------------

def build_multi_round(model, strategy: Strategy, fl: FLConfig, cfg=None,
                      placement: str = "spatial", fault=None,
                      batch_size: Optional[int] = None,
                      probes: bool = False, on_divergence: str = "report"):
    """Fuse ``rounds_per_launch`` FL rounds into one compiled program.

    Wraps a single-round program (spatial or temporal) in a ``jax.lax.scan``
    whose body does, *inside* the compiled program, everything the host loop
    used to do per round:

    - per-round batch gather from the partition tensors staged on device once
      (``data.pipeline.stage_partitions``), indices derived from
      ``determinism.round_key`` so chunking cannot change the data stream;
    - cohort selection with deadline-drop straggler semantics as a weight
      mask (``runtime.faults.cohort_mask``) — dropped clients get zero weight
      with no host round-trip.

    Returns ``multi_fn(ctx, state, staged, root, start_round, n_rounds)``
    -> ``(state, metrics)`` where ``n_rounds`` must be a Python int (it is
    the scan length; jit with it closed over or static) and every metric
    comes back stacked with a leading ``n_rounds`` dim.

    Determinism contract: because each round's randomness is keyed only by
    ``(root, absolute round index)``, a run chunked as e.g. 10+10 rounds is
    bitwise-identical to 20 launches of 1 round (asserted by
    tests/test_driver.py).
    """
    from repro.data.pipeline import gather_client_batches
    from repro.runtime.faults import FaultModel, cohort_mask

    if placement == "temporal":
        if cfg is None:
            raise ValueError("temporal placement needs the ModelConfig "
                             "(sharding specs are derived from it)")
        single = build_temporal_round(model, strategy, fl, cfg, probes=probes)
    elif placement == "spatial":
        single = build_spatial_round(model, strategy, fl, probes=probes)
    else:
        raise ValueError(f"unknown placement {placement!r} "
                         "(want 'spatial' or 'temporal')")
    freeze_div = probes and on_divergence == "freeze"
    fault = fault if fault is not None else FaultModel(seed=fl.seed)
    batch_size = batch_size or fl.batch_size
    steps = max(fl.local_steps, 1)
    target = int(fl.cohort or fl.n_clients)

    def multi_fn(ctx: AxisCtx, state, staged, root, start_round,
                 n_rounds: int, hyper=None):
        alive, hyper = pop_alive(hyper)
        # a swept seed must also steer the in-program cohort draw
        fault_h = (dataclasses.replace(fault, seed=hyper["seed"])
                   if hyper and "seed" in hyper else fault)
        base_w = staged["len"].astype(jnp.float32)

        def body(st, r):
            rkey = determinism.round_key(root, r)
            batch = gather_client_batches(staged, rkey, batch_size, steps)
            mask = cohort_mask(fault_h, r, fl.n_clients, target,
                               fl.straggler_overprovision)
            eff_w = base_w * mask
            new_st, metrics = single(ctx, st, batch, eff_w, rkey, hyper)
            if probes:
                # engine probes live here, where the cohort/straggler mask
                # and the staged weight mass both exist
                pr = metrics.pop("probes")
                pr["participation"] = (eff_w > 0).sum().astype(jnp.float32)
                pr["masked_frac"] = 1.0 - eff_w.sum() / jnp.maximum(
                    base_w.sum(), 1e-12)
                if freeze_div:
                    # hold a diverged lane at its last finite state — the
                    # same runtime select the lane scheduler uses, compiled
                    # in from launch 1 (a divergence never recompiles)
                    new_st = freeze_unless(1.0 - pr["nonfinite"], new_st, st)
            if alive is not None:
                new_st = freeze_unless(alive, new_st, st)
            if probes:
                if alive is not None:
                    pr = probelib.mask_probes(alive, pr)
                # one stacked (P,) vector, not 7 scalars: the scan emits a
                # single (R, P) probe plane per launch (one output buffer,
                # one host transfer), (S, R, P) under the campaign vmap
                metrics = dict(metrics, probes=probelib.stack_probes(pr))
            return new_st, metrics

        rounds = start_round + jnp.arange(n_rounds)
        return jax.lax.scan(body, state, rounds)

    return multi_fn


def check_ragged_support(fl: FLConfig, strategy: Strategy,
                         placement: str = "spatial") -> None:
    """Reject configs the ragged client plane cannot honor.

    Ragged mode trains only the sampled cohort, so anything that keeps
    per-client state across rounds (SCAFFOLD/MOON variates, error-feedback
    residuals) or per-client parameters (decentralized topology) would
    silently skip updates for unsampled clients — refuse loudly instead.
    """
    topo = get_topology(fl.topology, fl.gossip_steps)
    if isinstance(topo, Decentralized):
        raise ValueError(
            "ragged cohorts (max_cohort > 0) need client-anonymous state, "
            "but the decentralized topology keeps per-client parameters — "
            "use a client_server/hierarchical topology or max_cohort: 0")
    if _has_client_state(strategy):
        raise ValueError(
            f"ragged cohorts (max_cohort > 0) cannot carry per-client "
            f"strategy state (strategy {fl.strategy!r}"
            + (", error_feedback" if fl.error_feedback else "")
            + ") — unsampled clients would never update it; use a "
            "stateless strategy or max_cohort: 0")
    if placement != "spatial":
        raise ValueError(
            f"ragged cohorts support the spatial placement only, got "
            f"{placement!r} — the cohort slab is a per-slot client grid")


def build_ragged_multi(model, strategy: Strategy, fl: FLConfig,
                       placement: str = "spatial",
                       batch_size: Optional[int] = None,
                       probes: bool = False, on_divergence: str = "report"):
    """The ragged-cohort rendering of ``build_multi_round``.

    Instead of gathering batches for all ``n_clients`` from a resident
    root, each round of the scan consumes one *cohort slab row* (see
    ``data.pipeline.SlabStager``): the sampled cohort's shards padded to
    K = max_cohort slots with the tail zero-weighted. The population size
    and cohort draw live entirely on the host, so ``n_clients``/``cohort``
    drop out of the program signature — any population trains through one
    compiled program per (K, Lmax, scan length).

    Returns ``multi_fn(ctx, state, slab, root, start_round, n_rounds,
    hyper)`` with the slab in the resident driver's ``staged`` slot (the
    executors launch both through the same call shape). Randomness is keyed
    by (root, absolute round) and, per slot, by the *real* client id the
    slab carries — so chunking and slab pad width are unobservable, and
    streaming vs resident staging is bitwise the same program on the same
    bytes.
    """
    from repro.data.pipeline import gather_slab_batches

    check_ragged_support(fl, strategy, placement)
    single = build_spatial_round(model, strategy, fl, probes=probes)
    freeze_div = probes and on_divergence == "freeze"
    batch_size = batch_size or fl.batch_size
    steps = max(fl.local_steps, 1)
    k_slots = int(fl.max_cohort)

    def multi_fn(ctx: AxisCtx, state, slab, root, start_round,
                 n_rounds: int, hyper=None):
        alive, hyper = pop_alive(hyper)

        def body(st, xs):
            r, row = xs
            rkey = determinism.round_key(root, r)
            batch = gather_slab_batches(row, rkey, batch_size, steps)
            eff_w = row["w"]
            new_st, metrics = single(ctx, st, batch, eff_w, rkey, hyper)
            if probes:
                # participation counts real (non-pad) slots; masked_frac is
                # the pad fraction of the slab — the population weight mass
                # is a host-side quantity in ragged mode
                pr = metrics.pop("probes")
                real = (eff_w > 0).astype(jnp.float32)
                pr["participation"] = real.sum()
                pr["masked_frac"] = 1.0 - real.sum() / k_slots
                if freeze_div:
                    new_st = freeze_unless(1.0 - pr["nonfinite"], new_st, st)
            if alive is not None:
                new_st = freeze_unless(alive, new_st, st)
            if probes:
                if alive is not None:
                    pr = probelib.mask_probes(alive, pr)
                metrics = dict(metrics, probes=probelib.stack_probes(pr))
            return new_st, metrics

        rounds = start_round + jnp.arange(n_rounds)
        return jax.lax.scan(body, state, (rounds, slab))

    return multi_fn


def init_state(model, strategy: Strategy, fl: FLConfig, key,
               n_clients_local: int = 1, dtype=jnp.float32,
               decentralized: bool = False):
    """Initial FL state (meshless path; sharded init goes via launch/)."""
    params = model.init(key, dtype)
    cstate = ()
    if _has_client_state(strategy):
        # probe the client state off the params we already initialized —
        # a second model.init here would double the init cost at scale
        cstate = jax.tree.map(
            lambda t: jnp.broadcast_to(t, (n_clients_local,) + t.shape),
            strategy.client_state_init(params))
    if decentralized:
        params = jax.tree.map(
            lambda t: jnp.broadcast_to(t, (n_clients_local,) + t.shape),
            params)
    return {
        "params": params,
        "server": strategy.server_state_init(params),
        "clients": cstate,
    }


def _has_client_state(strategy) -> bool:
    probe = strategy.client_state_init({"x": jnp.zeros(())})
    return bool(jax.tree.leaves(probe))
