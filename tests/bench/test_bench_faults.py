"""A run with the timed path broken underneath comes out not correct,
once per fault the cells can have: a step that returns its state
unchanged, and half of each batch left out with the mean taken over the
rest. (One chip: there is no exchange between chips to leave out.)"""
import pytest

from conftest import NAMES, run_tiny


def state_unchanged(monkeypatch):
    from repro.core.strategy import Strategy
    monkeypatch.setattr(Strategy, "server_update",
                        lambda self, params, agg, server: (params, server))


def half_batch(monkeypatch):
    from repro.models.small import SmallModel
    loss = SmallModel.loss

    def half(self, ctx, params, batch, gather_fn=lambda b: b):
        n = batch["y"].shape[0] // 2
        return loss(self, ctx, params, {k: v[:n] for k, v in batch.items()},
                    gather_fn)
    monkeypatch.setattr(SmallModel, "loss", half)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
@pytest.mark.parametrize("name", NAMES)
def test_broken_path_is_not_correct(name, fault, monkeypatch):
    import jax
    fault(monkeypatch)
    jax.clear_caches()
    try:
        r = run_tiny(name)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not r["correct"], r["checks"]
