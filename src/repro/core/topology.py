"""Network topologies as mesh-axis reduction plans (paper Fig. 4 / RQ5).

- client-server: one weighted mean over the client grid.
- hierarchical: two-tier reduction — intra-pod mean (edge aggregator) then
  cross-pod mean (cloud). On the production mesh the ``pod`` axis IS the
  hierarchy; single-pod runs emulate tiers with (data -> model) stages.
- decentralized: no global reduction — torus gossip via ppermute rings over
  the client grid (doubly stochastic mixing), Fedstellar-style.

All plans also run meshless over a leading client dim (vmap path for the
paper-scale CPU benches).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.sharding.axes import AxisCtx


def _wmean_local(deltas, weights):
    """deltas: (C, ...) leading client dim; weights: (C,)."""
    wsum = weights.sum()
    return jax.tree.map(
        lambda d: jnp.tensordot(weights, d.astype(jnp.float32), axes=1)
        / jnp.maximum(wsum, 1e-12), deltas)


@dataclasses.dataclass(frozen=True)
class ClientServer:
    """Star topology: weighted mean of client deltas at the server."""
    name: str = "client_server"

    @jax.named_scope("fl.aggregate")
    def aggregate(self, ctx: AxisCtx, deltas, weights):
        """deltas: (C_loc, ...) per-chip clients; weighted psum over the grid."""
        num = jax.tree.map(
            lambda d: jnp.tensordot(weights, d.astype(jnp.float32), axes=1),
            deltas)
        den = weights.sum()
        axes = tuple(a for a in (ctx.pod, ctx.data, ctx.model) if a)
        if axes:
            num = jax.tree.map(lambda t: jax.lax.psum(t, axes), num)
            den = jax.lax.psum(den, axes)
        return jax.tree.map(lambda t: t / jnp.maximum(den, 1e-12), num)


@dataclasses.dataclass(frozen=True)
class Hierarchical:
    """Edge aggregators first (within pod: data+model axes), then cloud (pod).
    Matches [26]-style hierarchical FL; with cluster weighting the edge tiers
    can aggregate heterogeneous cohort sizes without bias."""
    name: str = "hierarchical"

    @jax.named_scope("fl.aggregate")
    def aggregate(self, ctx: AxisCtx, deltas, weights):
        """Two-tier aggregation: pod-local means, then the cross-pod mean."""
        num = jax.tree.map(
            lambda d: jnp.tensordot(weights, d.astype(jnp.float32), axes=1),
            deltas)
        den = weights.sum()
        intra = tuple(a for a in (ctx.data, ctx.model) if a)
        if intra:  # edge tier
            num = jax.tree.map(lambda t: jax.lax.psum(t, intra), num)
            den = jax.lax.psum(den, intra)
        edge = jax.tree.map(lambda t: t / jnp.maximum(den, 1e-12), num)
        if ctx.pod:  # cloud tier over pod aggregates
            edge = jax.tree.map(lambda t: jax.lax.pmean(t, ctx.pod), edge)
        return edge


@dataclasses.dataclass(frozen=True)
class Decentralized:
    """k steps of torus gossip; returns per-client mixed deltas (no global)."""
    name: str = "decentralized"
    gossip_steps: int = 1

    def mix(self, ctx: AxisCtx, state):
        """state: per-client pytree (C_loc leading dim). One gossip step mixes
        each client with its ring neighbours along both grid axes."""
        def step(t):
            mixed = t.astype(jnp.float32)
            n = 1
            for axis in (ctx.model, ctx.data):
                if axis is not None:
                    sz = ctx.size(axis)
                    right = jax.lax.ppermute(
                        mixed, axis, [(i, (i + 1) % sz) for i in range(sz)])
                    left = jax.lax.ppermute(
                        mixed, axis, [(i, (i - 1) % sz) for i in range(sz)])
                    mixed = mixed + right + left
                    n += 2
            if ctx.model is None and ctx.data is None and t.shape[0] > 1:
                # roll the f32-cast accumulator, not the raw t: the meshless
                # ring must feed the same dtype into the accumulator as the
                # ppermute path (which exchanges the cast ``mixed``)
                mixed = mixed + jnp.roll(mixed, 1, 0) + jnp.roll(mixed, -1, 0)
                n += 2
            return (mixed / n).astype(t.dtype)

        for _ in range(self.gossip_steps):
            state = jax.tree.map(step, state)
        return state

    @jax.named_scope("fl.aggregate")
    def aggregate(self, ctx: AxisCtx, deltas, weights):
        """Gossip-average deltas over the ring for ``gossip_steps``."""
        return self.mix(ctx, deltas)


# neighbours each client exchanges with per gossip step (the meshless ring
# rolls ±1; the comms byte model in core/netmodel.py counts sends off it)
GOSSIP_NEIGHBORS = 2

_TOPOLOGIES = ("client_server", "hierarchical", "decentralized")


def get_topology(name: str, gossip_steps: int = 1):
    """Resolve a topology implementation by name."""
    if name == "client_server":
        return ClientServer()
    if name == "hierarchical":
        return Hierarchical()
    if name == "decentralized":
        return Decentralized(gossip_steps=gossip_steps)
    import difflib
    hint = difflib.get_close_matches(name, _TOPOLOGIES, n=1)
    suffix = (f" — did you mean {hint[0]!r}?" if hint
              else f"; known topologies: {list(_TOPOLOGIES)}")
    raise ValueError(f"unknown topology {name!r}{suffix}")
