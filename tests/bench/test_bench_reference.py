"""The plain reference (bench/reference.py) against the program at a tiny
size on the CPU, one test per traffic kind, and its control."""
import json
import pathlib

import numpy as np
import pytest

from bench import cells, compare, reference, run
from bench.families import vision
from conftest import cut_traffic, files_cell, shrink, tiny_cell

SEED = 2**31 + 99

# CPU float32 on both sides: equal to rounding, except that int8 turns a
# one-ulp difference into a whole quantization step now and then.
CLOSE = {"loss_gap": 1e-3, "step1_change_gap": 1e-3, "last_change_gap": 2e-2,
         "step1_change_diff": 2e-3, "last_change_diff": 2e-3}


MLP = "flsim-mlp-cifar10-xdevice"
PINNED = json.loads((pathlib.Path(__file__).resolve().parent / "data"
                     / "reference_tiny_pinned.json").read_text())


@pytest.mark.parametrize("traffic", ["sweep8-fedavg", "int8-xdevice",
                                     "int8-fedbuff"])
def test_reference_agrees_with_the_program(traffic):
    """A campaign of lanes, a sync int8 run and an async FedBuff run."""
    cell = shrink(files_cell(MLP, traffic))
    ex, prog = run.first_steps(cell, SEED)
    del ex
    ref = reference.run(cell, SEED, steps=run.STEPS)
    np.testing.assert_allclose(np.asarray(prog["losses"])[:, 0],
                               ref["losses"][:, 0], rtol=1e-5)
    for lane_p, lane_r in zip(prog["params"], ref["params"]):
        for k in lane_r[0]:
            np.testing.assert_array_equal(lane_p[0][k], lane_r[0][k])
    nums = compare.numbers(prog, ref)
    assert all(nums[k] <= CLOSE[k] for k in CLOSE), nums


def test_reference_cnn_matches_the_program_model():
    import jax
    from repro.configs.base import get_config
    from repro.models import model_zoo
    from repro.sharding.axes import AxisCtx
    conf = cells.read_json(cells.BENCH / "configs" / "flsim-cnn-cifar10.json")
    model = model_zoo.build(get_config("flsim-cnn"))
    p = model.init(jax.random.PRNGKey(7))
    r = vision.init_params(conf, 7)
    assert set(p) == set(r)
    for k in p:
        np.testing.assert_array_equal(np.asarray(p[k]), np.asarray(r[k]))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 32, 32, 3))
    np.testing.assert_allclose(np.asarray(model.logits(p, x)),
                               np.asarray(vision.logits(conf, r, x)),
                               rtol=1e-5, atol=1e-5)
    y = jax.numpy.asarray([0, 3, 9])
    loss, _ = model.loss(AxisCtx(), p, {"x": x, "y": y})
    lp = jax.nn.log_softmax(vision.logits(conf, r, x))
    assert float(loss) == pytest.approx(
        float(-lp[np.arange(3), y].mean()), rel=1e-6)
    assert float(vision.loss(conf, r, {"x": x, "y": y})) == pytest.approx(
        float(loss), rel=1e-6)


def test_reference_schedule_is_the_program_schedule():
    from repro.runtime.clock import ClientSystemModel, build_schedule
    cell = cells.load_cell("cnn-int8-fedbuff")
    fl, rt = cells.train_params(cell), cell["traffic"]["runtime"]
    w = np.random.RandomState(0).randint(1, 900, 100).astype(np.float32)
    got = reference.schedule(12345, 100, 3000, w, fl, rt)
    want = build_schedule(
        ClientSystemModel(seed=12345, **rt), 100, 3000, w,
        buffer_size=fl["async_buffer"],
        staleness_exponent=fl["staleness_exponent"],
        max_staleness=fl["max_staleness"])
    for k in ("client", "task", "staleness", "accept", "apply", "coeff"):
        np.testing.assert_array_equal(got[k], getattr(want, k))
    np.testing.assert_array_equal(got["start"] % got["ring"], want.read_slot)


@pytest.mark.parametrize("name", [w["name"] for w in
                                  cells.load_benchmark()["workloads"]])
def test_control_in_bfloat16_fails_the_cell_limits(name):
    cell = tiny_cell(name, keep_steps=True)
    ref = reference.run(cell, SEED, steps=run.STEPS,
                        precision=cells.reference_precision(cell))
    low = reference.run(cell, SEED, steps=run.STEPS,
                        dtype=reference.jnp.bfloat16)
    checks = compare.judge(compare.numbers(low, ref),
                           cells.load_cell(name)["limits"])
    assert not all(c["ok"] for c in checks), checks


@pytest.mark.parametrize("case", sorted(PINNED["cases"]))
def test_vision_reference_reads_as_pinned(case):
    """The vision family's reference, to the bit, as it read before the
    model moved out of bench/reference.py: a campaign of lanes, sync int8,
    and FedBuff on the MLP and on the CNN."""
    config, traffic = case.split(".")
    cell = files_cell(config, traffic)
    cell["config"].update(n_items=240, n_clients=12)
    r = reference.run(cut_traffic(cell), PINNED["seed"], steps=run.STEPS)
    got = {"losses": np.asarray(r["losses"]).tolist(),
           "norms": [[{k: float(np.linalg.norm(np.asarray(v, np.float64)))
                       for k, v in sorted(p.items())} for p in lane]
                     for lane in r["params"]]}
    assert got == PINNED["cases"][case]
