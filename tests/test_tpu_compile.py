"""The Pallas GR-MAC kernel compiles for a TPU v5e at qwen2-1.5b's widths.

Compiled against a described (not attached) ``v5e:2x2`` topology, through
``kernels.dispatch``'s zero-padding, for every normalisation granularity and
analog column depth: a lowering the chip's compiler refuses fails here, with
no chip. Nothing runs, so this says nothing about results or times.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.formats import FP4_E2M1, FP6_E3M2
from repro.kernels.dispatch import grmac_matmul

_ARCH = get_config("qwen2-1.5b")
_D, _FF = _ARCH.d_model, _ARCH.d_ff


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n_r", [16, 32, 64])
@pytest.mark.parametrize("granularity", ["conv", "row", "unit"])
def test_pallas_grmac_compiles_for_v5e(one_chip, granularity, n_r):
    kw = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=n_r, enob=8.0,
              granularity=granularity, backend="pallas")
    fn = jax.jit(lambda x, w: grmac_matmul(x, w, **kw))
    # decode rows, unaligned to the 128-row block: dispatch pads them
    for m, k, n in ((4, _D, _FF), (4, _FF, _D)):
        x = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
        w = jax.ShapeDtypeStruct((k, n), jnp.float32, sharding=one_chip)
        compiled = fn.lower(x, w).compile()
        assert "tpu_custom_call" in compiled.as_text(), (m, k, n)
        assert compiled.memory_analysis().output_size_in_bytes == m * n * 4
