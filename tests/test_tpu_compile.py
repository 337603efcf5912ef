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
    # decode rows, unaligned to the 8-row block: dispatch pads them
    for m, k, n in ((4, _D, _FF), (4, _FF, _D)):
        x = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
        w = jax.ShapeDtypeStruct((k, n), jnp.float32, sharding=one_chip)
        compiled = fn.lower(x, w).compile()
        assert "tpu_custom_call" in compiled.as_text(), (m, k, n)
        assert compiled.memory_analysis().output_size_in_bytes == m * n * 4


@pytest.mark.parametrize("granularity", ["conv", "row", "unit"])
@pytest.mark.parametrize("m,k,n", [(8, _D, _ARCH.padded_vocab), (8, _FF, _D),
                                   (1024, _D, _FF),
                                   (1024, _D, _ARCH.padded_vocab)])
def test_pallas_planned_blocks_compile_for_v5e(one_chip, granularity, m, k,
                                               n):
    """Decode rows at the head and the MLP's down projection, and a
    bucket-128 prefill chunk's rows at the MLP and at the head (the widest
    planned block, 128 x 3456), under the blocks ``pallas_blocks`` plans
    for them: they fit the default scoped VMEM."""
    kw = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=32, enob=8.0,
              granularity=granularity, backend="pallas")
    x = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda x, w: grmac_matmul(x, w, **kw)).lower(
        x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes == m * n * 4


@pytest.mark.parametrize("site", ["head", "attn_qkv"])
def test_pallas_launch_is_named_after_its_site(one_chip, site):
    """On the TPU an op's name is its HLO instruction's, which a launch
    takes from the innermost jitted function around its pallas_call: each
    model call site launches as ``%grmac_<site>``, and so the device trace
    tells the sites apart."""
    from repro.core.cim_config import CIMConfig
    from repro.kernels.ops import cim_matmul
    cim = CIMConfig(mode="grmac", granularity="row", fmt_x=FP6_E3M2,
                    fmt_w=FP4_E2M1, n_r=32, backend="pallas")
    x = jax.ShapeDtypeStruct((8, _D), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((_D, 256), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(lambda x, w: cim_matmul(x, w, cim, site=site)).lower(
        x, w).compile().as_text()
    launches = [ln.strip() for ln in text.split("\n")
                if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(launches) == 1
    assert launches[0].startswith(f"%grmac_{site}")
