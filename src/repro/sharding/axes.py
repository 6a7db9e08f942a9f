"""Mesh-axis context: collectives that degrade to no-ops off-mesh.

Model code is written once against an ``AxisCtx``. With ``AxisCtx()`` (all axes
None) every collective is the identity and the code runs on one device — that
is the oracle used by tests. Inside ``shard_map`` over the production mesh the
same code emits real collectives.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp


_FOLLOW_MODEL = "__follow_model__"


@dataclass(frozen=True)
class AxisCtx:
    data: Optional[str] = None    # FL-client / batch axis
    model: Optional[str] = None   # TP / FSDP / EP axis
    pod: Optional[str] = None     # hierarchical / replica axis
    # vocab-sharding axis for embeddings/logits/loss; defaults to `model`.
    # Spatial archs keep full (replicated) embeddings while still using the
    # model axis for sequence-sharded caches — there vocab=None.
    vocab: Optional[str] = _FOLLOW_MODEL

    @property
    def vaxis(self) -> Optional[str]:
        return self.model if self.vocab == _FOLLOW_MODEL else self.vocab

    # -- axis sizes (1 when absent) -----------------------------------
    def size(self, name: Optional[str]) -> int:
        if name is None:
            return 1
        return jax.lax.axis_size(name)

    def index(self, name: Optional[str]):
        if name is None:
            return 0
        return jax.lax.axis_index(name)

    @property
    def data_axes(self):
        """Axes that jointly act as the batch/client grid (data [+ pod])."""
        axes = tuple(a for a in (self.pod, self.data) if a is not None)
        return axes if axes else None

    # -- collectives ---------------------------------------------------
    def all_gather(self, x, name: Optional[str], axis: int):
        if name is None:
            return x
        return jax.lax.all_gather(x, name, axis=axis, tiled=True)

    def psum(self, x, name):
        if name is None or (isinstance(name, tuple) and not name):
            return x
        return jax.lax.psum(x, name)

    def pmean(self, x, name):
        if name is None or (isinstance(name, tuple) and not name):
            return x
        return jax.lax.pmean(x, name)

    def psum_scatter(self, x, name: Optional[str], axis: int):
        if name is None:
            return x
        return jax.lax.psum_scatter(x, name, scatter_dimension=axis, tiled=True)

    def all_to_all(self, x, name: Optional[str], split_axis: int, concat_axis: int):
        if name is None:
            return x
        return jax.lax.all_to_all(x, name, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)

    def ppermute(self, x, name: Optional[str], perm):
        if name is None:
            return x
        return jax.lax.ppermute(x, name, perm=perm)


# Convenience contexts
SINGLE = AxisCtx()


def gather_on_spec(ctx: AxisCtx, tensor: jnp.ndarray, spec, axis_name: str):
    """All-gather ``tensor`` along whichever dim ``spec`` shards over ``axis_name``.

    ``spec`` is a PartitionSpec-like tuple; entries may be None, a name, or a
    tuple of names. Returns the tensor with that dim unsharded.
    """
    if axis_name is None:
        return tensor
    for dim, entry in enumerate(spec):
        names = entry if isinstance(entry, tuple) else (entry,)
        if axis_name in names:
            return ctx.all_gather(tensor, axis_name, axis=dim)
    return tensor


def gather_params(ctx: AxisCtx, params, specs, axis_name: str):
    """ZeRO-3 style: all-gather every tensor on its ``axis_name``-sharded dim."""
    return jax.tree.map(
        lambda t, s: gather_on_spec(ctx, t, s, axis_name), params, specs,
        is_leaf=lambda x: x is None)
