"""Quickstart: the paper's core loop in ~30 lines of public API.

Defines an FL job (paper Fig. 2 sections as a dict), scaffolds it through
the Job Orchestrator, runs FedAvg over Dirichlet-partitioned clients with
the Logic-Controller executor, and prints the FL dashboard.

  PYTHONPATH=src python examples/quickstart.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.core.jobs import load_job
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.executor import Executor

JOB = {
    "name": "quickstart",
    "model": {"arch": "flsim-cnn"},
    "dataset": {
        "dataset": "synthetic_vision",
        "n_items": 512,
        "distribution": {"partition": "dirichlet", "dirichlet_alpha": 0.5},
    },
    "strategy": {
        "strategy": "fedavg",
        # rounds_per_launch=5 fuses all 5 rounds into ONE compiled launch
        # (lax.scan); batches + cohorts are derived on device, the host only
        # sees the chunk boundary. placement can be "temporal" to run one
        # client at a time over the whole mesh instead.
        "train_params": {"n_clients": 8, "local_epochs": 2,
                         "client_lr": 0.05, "rounds": 5, "seed": 0,
                         "rounds_per_launch": 5, "placement": "spatial"},
    },
    "runtime": {"straggler_prob": 0.1, "straggler_overprovision": 1.25},
}


def main():
    enable_compile_cache()
    job = load_job(JOB)
    # scale the CNN for CPU quickness (same as the benches)
    job.model = job.model.__class__(job.model.cfg.replace(d_model=32, d_ff=64),
                                    job.model.kind)
    ex = Executor(job).scaffold()

    def eval_fn(params):
        x, y, _ = ex.data
        import jax.numpy as jnp
        return {"accuracy": job.model.accuracy(
            params, {"x": jnp.asarray(x[:256]), "y": jnp.asarray(y[:256])})}

    ex.eval_fn = eval_fn
    state, logger = ex.run()
    print(logger.dashboard())
    assert logger.rows[-1]["loss"] < logger.rows[0]["loss"]
    print("quickstart OK")


if __name__ == "__main__":
    main()
