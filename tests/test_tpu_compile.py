"""Compile-only checks of the int8 aggregation kernel for a v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip's compiler
would refuse (tile alignment, scoped VMEM, Mosaic layouts). Nothing runs,
so these tests say nothing about results or times; ``chip_smoke.py`` runs
the kernel on the chip. The topology is described inside a fixture, so
importing this module never loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.core import packing
from repro.kernels import ops
from repro.models import model_zoo


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _cnn_packed_n() -> int:
    model = model_zoo.build(get_config("flsim-cnn"))
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    return packing.packed_size(params)[0]


@pytest.mark.parametrize("C,N", [(16, 1 << 20), (16, "cnn"), (1, "cnn"),
                                 (200, "cnn")])
def test_quant_aggregate_compiles_for_v5e(topo, C, N):
    """The Pallas path through the pad-and-mask wrapper: 2^20 divides the
    tile; flsim-cnn's packed N (742 blocks) pads; C=1 is FedAsync's
    per-event call; C=200 pads to two client chunks."""
    if N == "cnn":
        N = _cnn_packed_n()
        assert N == 189_952
    one_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    args = (jax.ShapeDtypeStruct((C, N), jnp.int8, sharding=one_chip),
            jax.ShapeDtypeStruct((C, N // packing.QBLOCK), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((C,), jnp.float32, sharding=one_chip))
    fn = jax.jit(lambda q, s, w: ops._quant_agg_pallas(q, s, w,
                                                       interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (N,)
