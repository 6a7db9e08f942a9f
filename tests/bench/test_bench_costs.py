"""bench/costs.py and the vision family's counts against counts made by
hand."""
import pytest

from bench import costs
from bench.cells import BENCH, read_json
from bench.families import vision


def config(name):
    return read_json(BENCH / "configs" / f"{name}.json")


@pytest.mark.parametrize("name,by_hand,total", [
    # conv 3->32 at 32x32, 32->64 at 16x16, 64->64 at 8x8, FC 1024->128->10
    ("flsim-cnn-cifar10", 2 * (32 * 32 * 32 * 27 + 16 * 16 * 64 * 288
                               + 8 * 8 * 64 * 576 + 1024 * 128 + 128 * 10),
     16_189_952),
    ("flsim-mlp-cifar10-xdevice", 2 * (3072 * 256 + 3 * 256 * 256 + 256 * 10),
     1_971_200)], ids=["cnn", "mlp"])
def test_forward_flops_by_hand(name, by_hand, total):
    assert by_hand == total
    assert vision.forward_flops_per_item(config(name)) == by_hand


def test_training_flops_leave_out_the_images_gradient():
    p = config("flsim-mlp-cifar10-xdevice")
    assert vision.train_flops_per_item(p) == 3 * 1_971_200 - 2 * 3072 * 256


def test_params_and_packed_sizes_match_the_model():
    import jax
    from repro.configs.base import get_config
    from repro.core import packing
    from repro.models import model_zoo
    for name, arch in (("flsim-cnn-cifar10", "flsim-cnn"),
                       ("flsim-mlp-cifar10-xdevice", "flsim-mlp")):
        conf = config(name)
        tree = jax.eval_shape(lambda: model_zoo.build(
            get_config(arch)).init(jax.random.PRNGKey(0)))
        n = sum(leaf.size for leaf in jax.tree.leaves(tree))
        assert vision.n_params(conf) == n == conf["published"]["n_params"]
        assert costs.packed_size(vision.leaf_sizes(conf)) \
            == packing.packed_size(tree)[0]


def test_kernel_bytes_at_c100_n986880():
    c, n = 100, 986_880
    cost = costs.quant_aggregate_cost(c, n)
    assert cost["bytes"] == c * n + 4 * c * n // 256 + 4 * n + 4 * c \
        == 104_177_920
    assert cost["flops"] == 3 * c * n


def test_peaks_refuse_an_unknown_device():
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("cpu")
