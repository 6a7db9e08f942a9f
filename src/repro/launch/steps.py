"""Step builders: shard_map-wrapped train / prefill / decode programs plus
ShapeDtypeStruct input factories for every (arch x shape x mesh) cell.

This is the single source of truth used by dryrun.py, train.py and serve.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import (FLConfig, ModelConfig, ShapeConfig, get_config)
from repro.core.rounds import build_spatial_round, build_temporal_round
from repro.core.strategies import get_strategy
from repro.models import model_zoo, transformer
from repro.models.attention import KVCache, LatentCache
from repro.models.ssm import MLSTMState, MambaState, SLSTMState
from repro.sharding import specs as sspecs
from repro.sharding.axes import AxisCtx


def mesh_ctx(mesh) -> AxisCtx:
    names = mesh.axis_names
    return AxisCtx(data="data" if "data" in names else None,
                   model="model" if "model" in names else None,
                   pod="pod" if "pod" in names else None)


def _axis_sizes(mesh):
    return list(zip(mesh.axis_names, mesh.devices.shape))


def _batch_axes(mesh, global_batch: int, spatial: bool = False,
                order=("pod", "data")):
    """Axes over which the leading batch dim shards (divisibility-checked)."""
    if spatial:
        order = ("data", "model")
    sizes = dict(_axis_sizes(mesh))
    axes, n = [], 1
    for a in order:
        if a in sizes and global_batch % (n * sizes[a]) == 0:
            axes.append(a)
            n *= sizes[a]
    return tuple(axes)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------

def _sds(mesh, shape, spec, dtype):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def batch_struct(cfg: ModelConfig, shape: ShapeConfig, mesh,
                 lead: tuple = (), spatial: bool = False):
    """Token/label (or frame) stand-ins for one step. ``lead`` prepends
    (cohort, steps) dims replicated/client-sharded by the caller.

    The sequence dim shards over ``model`` (SP) except: the pure-SSM family
    (sLSTM/mLSTM recurrences cross shard boundaries — full sequences, batch
    over data) and hybrid TRAINING (the mamba cross-shard state handoff is
    AD-hostile, so batch shards over data x model instead; prefill keeps SP
    with the forward-only handoff). See transformer.seq_sharded_in."""
    from repro.models.transformer import seq_sharded_in
    B, S = shape.global_batch, shape.seq_len
    sharded_seq = seq_sharded_in(cfg, shape.kind)
    order = ("data", "model", "pod") if (
        shape.kind == "train" and not sharded_seq
        and cfg.family != "ssm") else ("pod", "data")
    baxes = _batch_axes(mesh, B, spatial, order=order)
    bspec = baxes if baxes else None
    seq = "model" if sharded_seq and "model" not in baxes else None
    nlead = len(lead)
    pad = (None,) * nlead

    def tok(shp, spec, dt=jnp.int32):
        return _sds(mesh, lead + shp, P(*pad, *spec), dt)

    if cfg.family == "encdec":
        S_dec = S // cfg.dec_len_ratio
        return {
            "frames": tok((B, S, cfg.d_model), (bspec, seq, None),
                          jnp.bfloat16),
            "tokens": tok((B, S_dec), (bspec, seq)),
            "labels": tok((B, S_dec), (bspec, seq)),
        }
    return {
        "tokens": tok((B, S), (bspec, seq)),
        "labels": tok((B, S), (bspec, seq)),
    }


def param_structs(cfg: ModelConfig, mesh, phase: str, dtype=jnp.bfloat16):
    shapes = transformer.param_shapes(cfg)
    specs = sspecs.param_specs(cfg, phase)
    return jax.tree.map(
        lambda sh, sp: _sds(mesh, sh, sp, dtype), shapes, specs,
        is_leaf=lambda x: isinstance(x, tuple))


# -- decode caches -----------------------------------------------------------

def cache_tree(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """(structs, specs) for a full decode cache at context length S."""
    model = model_zoo.build(cfg)
    B, S = shape.global_batch, shape.seq_len
    ctx0 = AxisCtx()
    if cfg.family == "encdec":
        S_dec = S // cfg.dec_len_ratio
        batch = {
            "frames": jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16),
            "tokens": jax.ShapeDtypeStruct((B, S_dec), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S_dec), jnp.int32),
        }
    else:
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
    params = jax.tree.map(
        lambda sh: jax.ShapeDtypeStruct(sh, jnp.bfloat16),
        transformer.param_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple))
    caches, _, _ = jax.eval_shape(
        lambda p, b: model.prefill(ctx0, p, b), params, batch)
    baxes = _batch_axes(mesh, B)
    bspec = baxes if baxes else None
    tp = sspecs.placement_for(cfg) == "temporal"

    def spec_for(path, leaf):
        # leaf shapes: (L, B, ...) stacked; classify by enclosing cache type
        names = [getattr(k, "name", getattr(k, "key", "")) for k in path]
        nd = len(leaf.shape)
        sp = [None] * nd
        # find batch dim: the dim whose size == B right after stack dims
        bdim = 1
        sp[bdim] = bspec
        if any(n in ("k", "v", "ckv", "krope") for n in names):
            sp[2] = "model"                      # sequence-sharded cache
        elif "h" in names or any(n == "conv" for n in names):
            # mamba state: channels dim model-sharded in tp decode
            cdim = 2 if "h" in names else 3
            if tp and leaf.shape[cdim] % 16 == 0:
                sp[cdim] = "model"
        # mlstm / slstm states stay replicated over model
        return P(*sp)

    flat = jax.tree_util.tree_flatten_with_path(caches)
    specs = jax.tree.unflatten(flat[1], [spec_for(p, l) for p, l in flat[0]])
    structs = jax.tree.map(
        lambda l, sp: _sds(mesh, l.shape, sp, l.dtype), caches, specs)
    return structs, specs


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BuiltStep:
    fn: Any                   # jit-able callable over GLOBAL arrays
    inputs: tuple             # ShapeDtypeStructs (global, with shardings)
    kind: str
    donate: tuple = ()        # argnums whose buffers the step may reuse


def make_train_step(cfg: ModelConfig, shape: ShapeConfig, mesh,
                    fl: Optional[FLConfig] = None) -> BuiltStep:
    fl = fl or FLConfig(strategy="fedavg", local_epochs=1, client_lr=1e-2)
    model = model_zoo.build(cfg)
    strategy = get_strategy(fl)
    ctx = mesh_ctx(mesh)
    spatial = sspecs.placement_for(cfg) == "spatial"
    sizes = dict(_axis_sizes(mesh))

    if spatial:
        round_fn = build_spatial_round(model, strategy, fl)
        n_clients = sizes.get("data", 1) * sizes.get("model", 1)
        pspec = sspecs.param_specs(cfg, "spatial")
        state_specs = {"params": pspec, "server":
                       jax.tree.map(lambda _: P(), strategy.server_state_init(
                           transformer.param_shapes(cfg))),
                       "clients": ()}
        # batch: (C, steps, B_c, ...) with C over the client grid
        B, S = shape.global_batch, shape.seq_len
        B_c = max(B // n_clients, 1)
        lead = (n_clients, 1, B_c)
        cspec = ("data", "model")
        if cfg.family == "encdec":
            S_dec = S // cfg.dec_len_ratio
            batch = {
                "frames": _sds(mesh, lead + (S, cfg.d_model),
                               P(cspec, None, None, None, None), jnp.bfloat16),
                "tokens": _sds(mesh, lead + (S_dec,),
                               P(cspec, None, None, None), jnp.int32),
                "labels": _sds(mesh, lead + (S_dec,),
                               P(cspec, None, None, None), jnp.int32),
            }
        else:
            batch = {
                "tokens": _sds(mesh, lead + (S,),
                               P(cspec, None, None, None), jnp.int32),
                "labels": _sds(mesh, lead + (S,),
                               P(cspec, None, None, None), jnp.int32),
            }
        bspecs = jax.tree.map(lambda s: P(cspec, *([None] * (len(s.shape) - 1))),
                              batch)
        weights = _sds(mesh, (n_clients,), P(cspec), jnp.float32)
        wspec = P(cspec)
    else:
        round_fn = build_temporal_round(model, strategy, fl, cfg)
        pspec = sspecs.param_specs(cfg, "fsdp")
        state_specs = {"params": pspec, "server":
                       jax.tree.map(lambda _: P(),
                                    strategy.server_state_init(
                                        transformer.param_shapes(cfg))),
                       "clients": ()}
        bs = batch_struct(cfg, shape, mesh, lead=(1, 1))
        batch = bs
        bspecs = jax.tree.map(lambda s: s.sharding.spec, batch)
        weights = _sds(mesh, (1,), P(None), jnp.float32)
        wspec = P(None)

    params = param_structs(cfg, mesh, "spatial" if spatial else "fsdp")
    # server-state structs mirror params (momenta shard like their params);
    # stateless servers (plain FedAvg) give ().
    if strategy.server_state_init({"_": jnp.zeros(())}):
        server = jax.tree.map(lambda s: s, {"momentum": params}) \
            if strategy.name == "fedavgm" else \
            {"m": params, "v": params, "t": _sds(mesh, (), P(), jnp.int32)}
    else:
        server = ()
    state = {"params": params, "server": server, "clients": ()}
    rng = _sds(mesh, (2,), P(None), jnp.uint32)
    sstate_specs = jax.tree.map(lambda s: s.sharding.spec, state)

    fn = shard_map(
        functools.partial(round_fn, ctx),
        mesh=mesh,
        in_specs=(sstate_specs, bspecs, wspec, P(None)),
        out_specs=(sstate_specs, {"loss": P()}),
        check_vma=False)
    return BuiltStep(fn, (state, batch, weights, rng), "train", donate=(0,))


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, shape: ShapeConfig, mesh) -> BuiltStep:
    model = model_zoo.build(cfg)
    ctx = mesh_ctx(mesh)
    # spatial archs keep replicated weights (tiny); big archs ZeRO-3-gather
    spatial = sspecs.placement_for(cfg) == "spatial"
    phase = "spatial" if spatial else "fsdp"
    if spatial:
        ctx = dataclasses.replace(ctx, vocab=None)
    params = param_structs(cfg, mesh, phase)
    batch = batch_struct(cfg, shape, mesh)
    cache_structs, cache_specs = cache_tree(cfg, shape, mesh)
    baxes = _batch_axes(mesh, shape.global_batch)
    bspec = baxes if baxes else None

    def step(p, b):
        gather = sspecs.make_gather_fn(cfg, ctx)
        caches, logits, _ = model.prefill(ctx, p, b, gather)
        return caches, logits

    fn = shard_map(
        step, mesh=mesh,
        in_specs=(jax.tree.map(lambda s: s.sharding.spec, params),
                  jax.tree.map(lambda s: s.sharding.spec, batch)),
        out_specs=(cache_specs, P(bspec, None)),
        check_vma=False)
    return BuiltStep(fn, (params, batch), "prefill")


def make_decode_step(cfg: ModelConfig, shape: ShapeConfig, mesh) -> BuiltStep:
    model = model_zoo.build(cfg)
    ctx = mesh_ctx(mesh)
    tp = sspecs.placement_for(cfg) == "temporal"
    phase = "tp" if tp else "spatial"
    if not tp:
        ctx = dataclasses.replace(ctx, vocab=None)
    params = param_structs(cfg, mesh, phase)
    cache_structs, cache_specs = cache_tree(cfg, shape, mesh)
    B = shape.global_batch
    baxes = _batch_axes(mesh, B)
    bspec = baxes if baxes else None
    tokens = _sds(mesh, (B,), P(bspec), jnp.int32)
    length = _sds(mesh, (B,), P(bspec), jnp.int32)

    def step(p, t, c, ln):
        logits, new_c = model.decode_step(ctx, p, t, c, ln, tp=tp)
        return logits, new_c

    logits_spec = P(bspec, "model" if tp else None)
    fn = shard_map(
        step, mesh=mesh,
        in_specs=(jax.tree.map(lambda s: s.sharding.spec, params),
                  P(bspec), cache_specs, P(bspec)),
        out_specs=(logits_spec, cache_specs),
        check_vma=False)
    return BuiltStep(fn, (params, tokens, cache_structs, length), "decode",
                     donate=(2,))


def make_step_from_cfg(cfg: ModelConfig, shape_cfg: ShapeConfig, mesh,
                       fl: Optional[FLConfig] = None) -> BuiltStep:
    if shape_cfg.kind == "train":
        return make_train_step(cfg, shape_cfg, mesh, fl)
    if shape_cfg.kind == "prefill":
        return make_prefill_step(cfg, shape_cfg, mesh)
    return make_decode_step(cfg, shape_cfg, mesh)


def make_step(arch: str, shape_cfg: ShapeConfig, mesh,
              fl: Optional[FLConfig] = None) -> BuiltStep:
    return make_step_from_cfg(get_config(arch), shape_cfg, mesh, fl)
