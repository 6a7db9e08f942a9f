"""Multi-worker aggregation with a byzantine worker + blockchain audit trail.

Replicates the paper's RQ3/RQ4 story end to end: three redundant workers
(one malicious), majority-digest consensus (the "smart contract"), and a
hash-chain ledger recording aggregate digests, consensus decisions, worker
reputations and global-model provenance.

  PYTHONPATH=src python examples/byzantine_consensus.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig, get_config
from repro.core import determinism
from repro.core.blockchain import HashChainLedger, param_digest
from repro.core.consensus import MultiWorkerAggregator, poison
from repro.core.rounds import build_spatial_round, init_state
from repro.core.strategies import get_strategy
from repro.data.pipeline import SyntheticVision
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_zoo
from repro.sharding.axes import AxisCtx


def main():
    enable_compile_cache()
    fl = FLConfig(strategy="fedavg", n_clients=6, local_epochs=1,
                  client_lr=0.1, n_workers=3, byzantine_workers=1,
                  consensus="majority_digest", blockchain="hashchain",
                  seed=0)
    cfg = get_config("flsim-mlp")
    model = model_zoo.build(cfg)
    strategy = get_strategy(fl)
    ledger = HashChainLedger()
    round_fn = jax.jit(lambda s, b, w, r: build_spatial_round(
        model, strategy, fl)(AxisCtx(), s, b, w, r))
    data = SyntheticVision(n_items=384, seed=0)
    x, y, parts = data.distribute_into_chunks("dirichlet", fl.n_clients, 0.5)
    state = init_state(model, strategy, fl, determinism.root_key(0),
                       n_clients_local=fl.n_clients)
    root = determinism.root_key(0)
    for r in range(4):
        bs = [SyntheticVision.client_batches(x, y, parts[c], 16, 1,
                                             seed=c + 101 * r)[0]
              for c in range(fl.n_clients)]
        batch = jax.tree.map(lambda *t: np.stack(t), *bs)
        w = jnp.ones((fl.n_clients,), jnp.float32)
        state, m = round_fn(state, batch, w, determinism.round_key(root, r))
        # ledger: record each worker's (possibly poisoned) digest + decision
        good = param_digest(state["params"])
        digests = {}
        for wk in range(fl.n_workers):
            if wk < fl.byzantine_workers:
                digests[f"worker_{wk}"] = param_digest(
                    poison(state["params"], 3.0))
            else:
                digests[f"worker_{wk}"] = good
            ledger.record_aggregate(r, f"worker_{wk}", state["params"])
        ledger.record_consensus(r, "majority_digest", good, digests)
        ledger.record_global(r, state["params"])
        print(f"round {r}: loss {float(m['loss']):.4f} "
              f"global digest {good[:12]}…")
    assert ledger.verify(), "chain must verify"
    print("\nworker reputations:", {k: round(v, 2)
                                    for k, v in ledger.reputation.items()})
    prov = ledger.provenance(param_digest(state["params"]))
    print(f"provenance of final model: {len(prov)} block(s); "
          f"chain length {len(ledger.blocks())}; verified=True")


if __name__ == "__main__":
    main()
