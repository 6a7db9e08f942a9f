"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: (16, 16) = 256 chips, axes
("data", "model"); multi-pod: (2, 16, 16) = 512 chips with a leading "pod"
axis (hierarchical-FL tier / DP replica; collectives over it model the
cross-pod DCN hop).
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    """jax.make_mesh with every axis Auto (sharding propagated by XLA)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over forced host devices for unit tests."""
    return _mesh(shape, axes)


def lane_mesh(n=0):
    """1-D device mesh over the campaign sweep axis (``"lanes"``).

    Sweep trajectories are embarrassingly parallel, so the leading (S,) dim
    of every campaign plane (data idx/len, schedules, scalars, alive mask,
    stacked model state) shards cleanly over devices — each device advances
    S/n lanes of the same compiled program with zero collectives.
    ``n`` is a device count — or a ``configs.base.MeshConfig``, whose
    ``lanes`` axis is that count. ``n = 0`` takes every local device. On
    CPU, ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` fakes a
    multi-device host for tests and benches (see README "Device-parallel
    campaigns").
    """
    n = int(getattr(n, "lanes", n)) or jax.local_device_count()
    if n > jax.device_count():
        raise ValueError(
            f"lane_mesh({n}) wants {n} devices but only "
            f"{jax.device_count()} are visible; on CPU, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            "jax initializes to fake a multi-device host")
    return _mesh((n,), ("lanes",))


def lane_sharding(mesh, replicated: bool = False):
    """NamedSharding placing the leading dim over ``lanes`` (or replicating:
    the campaign's concatenated data roots and unique schedules serve every
    lane from one logical copy per device)."""
    spec = (jax.sharding.PartitionSpec() if replicated
            else jax.sharding.PartitionSpec("lanes"))
    return jax.sharding.NamedSharding(mesh, spec)


def shard_lanes(tree, mesh, axes=None):
    """Place a campaign plane pytree on a lane mesh.

    With ``axes`` (a dict like ``data/pipeline.DEDUP_STAGED_AXES``), leaves
    mapped over the sweep axis (entry ``0``) shard their leading dim over
    ``lanes`` and unmapped leaves (entry ``None``) replicate; without it
    every leaf lane-shards. Identity when ``mesh`` is None, so single-device
    campaigns never touch placement."""
    if mesh is None:
        return tree
    lane = lane_sharding(mesh)
    repl = lane_sharding(mesh, replicated=True)
    if axes is None:
        return jax.tree.map(lambda t: jax.device_put(t, lane), tree)
    return {k: jax.device_put(v, repl if axes.get(k) is None else lane)
            for k, v in tree.items()}


# TPU v5e hardware constants (roofline):
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
