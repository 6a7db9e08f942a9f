"""End-to-end driver: federated training of an LM architecture.

Runs temporal FL rounds (cohort scanned over the mesh — the same round
program the multi-pod dry-run compiles) on a synthetic Markov token stream,
with checkpointing and restart. Default is a CPU-sized model; --scale 100m
selects a ~100M-parameter config (the deliverable-(b) setting — budget a few
hours of CPU, or minutes on a real pod).

  PYTHONPATH=src python examples/train_fl_lm.py --arch yi-34b --rounds 30
"""
import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt as ckpt_mod
from repro.configs.base import FLConfig, get_config
from repro.configs.reduce import reduced_config
from repro.core import determinism
from repro.core.rounds import build_temporal_round, init_state
from repro.core.strategies import get_strategy
from repro.data.pipeline import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.metrics.logger import PerformanceLogger
from repro.models import model_zoo
from repro.sharding.axes import AxisCtx

SCALES = {
    # (d_model, n_layers, d_ff, vocab) — heads stay at the reduced config's
    "tiny": (64, 2, 128, 512),
    "10m": (256, 4, 1024, 2048),
    "100m": (640, 10, 2560, 8192),
}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-34b")
    ap.add_argument("--scale", default="tiny", choices=sorted(SCALES))
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--cohort", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--local-epochs", type=int, default=2)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--strategy", default="fedavgm")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    d, L, f, v = SCALES[args.scale]
    cfg = reduced_config(get_config(args.arch)).replace(
        d_model=d, d_ff=f, vocab_size=v)
    if cfg.family not in ("hybrid", "ssm"):
        cfg = cfg.replace(n_layers=L)
    model = model_zoo.build(cfg)
    n_params = sum(int(np.prod(s)) for s in jax.tree.leaves(
        model.shapes(), is_leaf=lambda x: isinstance(x, tuple)))
    print(f"arch={cfg.name} scale={args.scale}: {n_params/1e6:.1f}M params")

    fl = FLConfig(strategy=args.strategy, n_clients=args.clients,
                  local_epochs=args.local_epochs, client_lr=0.05,
                  server_momentum=0.9, seed=0)
    strategy = get_strategy(fl)
    ctx = AxisCtx()
    round_fn = jax.jit(lambda s, b, w, r: build_temporal_round(
        model, strategy, fl, cfg)(ctx, s, b, w, r))
    state = init_state(model, strategy, fl, determinism.root_key(0))
    start_round = 0
    if args.ckpt_dir:
        last = ckpt_mod.latest_round(args.ckpt_dir)
        if last is not None:
            state, extra = ckpt_mod.restore(args.ckpt_dir, last, state)
            start_round = extra["next_round"]
            print(f"resumed from round {start_round}")

    lm = SyntheticLM(vocab=cfg.vocab_size, seed=0)
    logger = PerformanceLogger(run_name=f"fl-lm-{args.arch}-{args.scale}")
    root = determinism.root_key(0)
    for r in range(start_round, args.rounds):
        cohort = [(r * 13 + i) % args.clients for i in range(args.cohort)]
        batches = [lm.client_batches(c, args.local_steps, args.batch,
                                     args.seq, round_idx=r)
                   for c in cohort]
        batch = jax.tree.map(lambda *t: np.stack(t), *batches)
        w = jnp.ones((len(cohort),), jnp.float32)
        t0 = time.time()
        state, m = round_fn(state, batch, w, determinism.round_key(root, r))
        logger.log_round(r, loss=float(m["loss"]),
                         round_s=time.time() - t0)
        if r % 5 == 0 or r == args.rounds - 1:
            print(f"round {r:4d} loss {float(m['loss']):.4f} "
                  f"({time.time()-t0:.1f}s)", flush=True)
        if args.ckpt_dir and (r + 1) % 10 == 0:
            ckpt_mod.save(args.ckpt_dir, r + 1, state,
                          extra={"next_round": r + 1}, async_write=False)
    print(logger.dashboard())
    first, last = logger.rows[0]["loss"], logger.rows[-1]["loss"]
    print(f"loss {first:.4f} -> {last:.4f}")
    assert last < first, "FL training must reduce loss"


if __name__ == "__main__":
    main()
