"""Decentralized (Fedstellar-style) FL: no server, torus gossip mixing.

Shows per-client models diverging during local training and re-contracting
through gossip; reports the consensus distance ||theta_i - mean|| per round.

  PYTHONPATH=src python examples/decentralized_gossip.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig, get_config
from repro.core import determinism
from repro.core.rounds import build_spatial_round, init_state
from repro.core.strategies import get_strategy
from repro.data.pipeline import SyntheticVision
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_zoo
from repro.sharding.axes import AxisCtx


def divergence(params):
    tot, n = 0.0, 0
    for leaf in jax.tree.leaves(params):
        mean = leaf.mean(0, keepdims=True)
        tot += float(jnp.sum((leaf - mean) ** 2))
        n += leaf[0].size
    return (tot / max(n, 1)) ** 0.5


def main():
    enable_compile_cache()
    fl = FLConfig(strategy="gossip", topology="decentralized", n_clients=8,
                  local_epochs=2, client_lr=0.05, gossip_steps=1, seed=0)
    cfg = get_config("flsim-mlp")
    model = model_zoo.build(cfg)
    strategy = get_strategy(fl)
    round_fn = jax.jit(lambda s, b, w, r: build_spatial_round(
        model, strategy, fl)(AxisCtx(), s, b, w, r))
    data = SyntheticVision(n_items=512, seed=0)
    x, y, parts = data.distribute_into_chunks("dirichlet", fl.n_clients, 0.5)
    state = init_state(model, strategy, fl, determinism.root_key(0),
                       n_clients_local=fl.n_clients, decentralized=True)
    test = {"x": jnp.asarray(x[:256]), "y": jnp.asarray(y[:256])}
    root = determinism.root_key(0)
    for r in range(6):
        bs = [SyntheticVision.client_batches(x, y, parts[c], 16, 1,
                                             seed=c + 31 * r)[0]
              for c in range(fl.n_clients)]
        batch = jax.tree.map(lambda *t: np.stack(t), *bs)
        w = jnp.ones((fl.n_clients,), jnp.float32)
        state, m = round_fn(state, batch, w, determinism.round_key(root, r))
        mean_params = jax.tree.map(lambda t: t.mean(0), state["params"])
        acc = float(model.accuracy(mean_params, test))
        print(f"round {r}: loss {float(m['loss']):.4f}  "
              f"mean-model acc {acc:.3f}  divergence {divergence(state['params']):.2e}")
    print("gossip OK")


if __name__ == "__main__":
    main()
