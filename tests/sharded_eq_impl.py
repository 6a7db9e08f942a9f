"""Sharded-vs-single-device equivalence — executed in a subprocess with
forced host devices (imported by test_sharded_equivalence.py).

For each reduced arch: the shard_map'd train step (loss value) and decode
step (logits) must match the meshless oracle to fp tolerance. This validates
the gather tables, sequence-sharded attention offsets, EP dispatch + ring,
the embedding layouts, the distributed softmax and the LSE decode combine.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("REPRO_KERNEL_IMPL", "jnp")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ShapeConfig, get_config
from repro.configs.reduce import reduced_config
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_test_mesh
from repro.models import model_zoo
from repro.sharding.axes import AxisCtx

MESHES = {
    "dm": make_test_mesh((2, 2), ("data", "model")),
    "pdm": make_test_mesh((2, 2, 2), ("pod", "data", "model")),
}


def reduced(arch):
    cfg = reduced_config(get_config(arch))
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=4.0))
    return cfg


def materialize(structs, seed=0):
    """Random global arrays matching the ShapeDtypeStruct tree (+sharding)."""
    leaves, treedef = jax.tree_util.tree_flatten(structs)
    rng = np.random.RandomState(seed)
    out = []
    for i, s in enumerate(leaves):
        if np.issubdtype(s.dtype, np.integer):
            a = rng.randint(0, 2, size=s.shape).astype(s.dtype)
        else:
            a = (rng.randn(*s.shape) * 0.02).astype(s.dtype)
        out.append(jax.device_put(jnp.asarray(a), s.sharding))
    return jax.tree_util.tree_unflatten(treedef, out)


def check_train(arch, mesh_name, B=8, S=32):
    cfg = reduced(arch)
    mesh = MESHES[mesh_name]
    shape = ShapeConfig("t", S, B, "train")
    built = steps_mod.make_train_step(cfg, shape, mesh)
    # materialize inputs; tokens within vocab
    state, batch, weights, rng = materialize(built.inputs)
    batch = jax.tree.map(
        lambda t: (t % cfg.vocab_size) if t.dtype == jnp.int32 else t, batch)
    weights = jnp.ones_like(weights)
    rng = jnp.zeros((2,), jnp.uint32)
    with jax.set_mesh(mesh):
        new_state, metrics = jax.jit(built.fn)(state, batch, weights, rng)
        sharded_loss = float(metrics["loss"])
        sharded_params = jax.tree.map(np.asarray, new_state["params"])

    # oracle: same semantics meshless
    from repro.core.rounds import build_spatial_round, build_temporal_round
    from repro.core.strategies import get_strategy
    from repro.configs.base import FLConfig
    from repro.sharding import specs as sspecs
    fl = FLConfig(strategy="fedavg", local_epochs=1, client_lr=1e-2)
    model = model_zoo.build(cfg)
    strategy = get_strategy(fl)
    ctx0 = AxisCtx()
    params_full = jax.tree.map(np.asarray, state["params"])
    state0 = {"params": jax.tree.map(jnp.asarray, params_full),
              "server": (), "clients": ()}
    spatial = sspecs.placement_for(cfg) == "spatial"
    if spatial:
        rf = build_spatial_round(model, strategy, fl)
        # flatten client grid into leading dim
        b0 = jax.tree.map(lambda t: jnp.asarray(np.asarray(t)), batch)
        w0 = jnp.asarray(np.asarray(weights))
        st, m = jax.jit(lambda s, b, w, r: rf(ctx0, s, b, w, r))(
            state0, b0, w0, rng)
    else:
        rf = build_temporal_round(model, strategy, fl, cfg)
        b0 = jax.tree.map(lambda t: jnp.asarray(np.asarray(t)), batch)
        st, m = jax.jit(lambda s, b, w, r: rf(ctx0, s, b, w, r))(
            state0, b0, jnp.asarray(np.asarray(weights)), rng)
    oracle_loss = float(m["loss"])
    ok_loss = abs(sharded_loss - oracle_loss) < 5e-2 * max(1, abs(oracle_loss))
    # parameter agreement (sampled leaves)
    o_params = jax.tree.map(np.asarray, st["params"])
    errs = []
    for a, b in zip(jax.tree.leaves(sharded_params),
                    jax.tree.leaves(o_params)):
        d = np.max(np.abs(a.astype(np.float32) - b.astype(np.float32)))
        errs.append(d)
    ok_params = max(errs) < 5e-2
    status = "OK" if (ok_loss and ok_params) else "MISMATCH"
    print(f"TRAIN {arch:24s} {mesh_name:3s} loss {sharded_loss:+.5f} vs "
          f"{oracle_loss:+.5f}  max_param_err {max(errs):.2e}  {status}")
    return ok_loss and ok_params


def check_decode(arch, mesh_name, B=8, S=32):
    cfg = reduced(arch)
    mesh = MESHES[mesh_name]
    shape = ShapeConfig("d", S, B, "decode")
    built = steps_mod.make_decode_step(cfg, shape, mesh)
    params, tokens, caches, length = materialize(built.inputs)
    tokens = tokens % cfg.vocab_size
    length = jnp.full_like(length, S - 1)
    with jax.set_mesh(mesh):
        logits, _ = jax.jit(built.fn)(params, tokens, caches, length)
        logits_sh = np.asarray(logits).astype(np.float32)

    model = model_zoo.build(cfg)
    ctx0 = AxisCtx()
    p0 = jax.tree.map(lambda t: jnp.asarray(np.asarray(t)), params)
    c0 = jax.tree.map(lambda t: jnp.asarray(np.asarray(t)), caches)
    t0 = jnp.asarray(np.asarray(tokens))
    l0 = jnp.asarray(np.asarray(length))
    lo, _ = jax.jit(lambda p, t, c, ln: model.decode_step(
        ctx0, p, t, c, ln, tp=False))(p0, t0, c0, l0)
    logits_or = np.asarray(lo).astype(np.float32)
    err = np.max(np.abs(logits_sh - logits_or))
    scale = np.maximum(np.max(np.abs(logits_or)), 1e-3)
    ok = err < 5e-2 * scale
    print(f"DECODE {arch:23s} {mesh_name:3s} max_err {err:.2e} "
          f"(scale {scale:.2e})  {'OK' if ok else 'MISMATCH'}")
    return ok


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    archs_train = ["yi-34b", "minicpm3-4b", "qwen3-moe-30b-a3b",
                   "arctic-480b", "jamba-1.5-large-398b", "whisper-base",
                   "xlstm-125m"]
    archs_decode = ["yi-34b", "minicpm3-4b", "qwen3-moe-30b-a3b",
                    "jamba-1.5-large-398b", "whisper-base", "xlstm-125m"]
    ok = True
    for arch in archs_train:
        if which in ("all", "train", arch):
            for mesh_name in ("dm", "pdm"):
                ok &= check_train(arch, mesh_name)
    for arch in archs_decode:
        if which in ("all", "decode", arch):
            ok &= check_decode(arch, "dm")
    print("ALL OK" if ok else "FAILURES PRESENT")
    sys.exit(0 if ok else 1)
