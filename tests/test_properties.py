"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.cim_config import CIMConfig
from repro.core import formats as F
from repro.kernels.ops import cim_matmul
from repro.kernels.ref import grmac_matmul_ref
from repro.kernels.tiled import grmac_matmul_tiled


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1 << 30), scale=st.floats(0.1, 100.0),
       mode=st.sampled_from(["fakequant", "grmac"]))
def test_cim_matmul_scale_equivariance(seed, scale, mode):
    """Dynamic pre-scale makes the op exactly scale-equivariant: the
    normalized inputs are identical, so out(c·x) == c·out(x)."""
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (8, 64))
    w = jax.random.normal(kw, (64, 16))
    cfg = CIMConfig(mode=mode)
    o1 = cim_matmul(x, w, cfg, use_kernel=False)
    o2 = cim_matmul(x * scale, w, cfg, use_kernel=False)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1) * scale,
                               rtol=1e-4, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1 << 30),
       gran=st.sampled_from(["row", "unit", "conv"]))
def test_grmac_ideal_adc_equals_exact_quantized_product(seed, gran):
    """With a near-ideal ADC the GR-MAC block simulation reduces to the
    exact quantized matmul (the paper's reconstruction identity)."""
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.uniform(kx, (16, 64), minval=-1, maxval=1)
    w = F.quantize(jax.random.uniform(kw, (64, 8), minval=-1, maxval=1),
                   F.FP4_E2M1)
    out = grmac_matmul_ref(x, w, fmt_x=F.FP6_E3M2, fmt_w=F.FP4_E2M1,
                           n_r=32, enob=28.0, granularity=gran)
    ref = F.quantize(x, F.FP6_E3M2) @ w
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 1 << 30),
       gran=st.sampled_from(["row", "unit", "conv"]),
       m=st.integers(1, 70),
       n=st.integers(1, 50),
       blocks=st.integers(1, 4),
       n_r=st.sampled_from([4, 8, 32, 96]),   # one element shy .. whole K
       tile_m=st.sampled_from([1, 8, 13, 32, 256]),
       tile_n=st.sampled_from([0, 8, 13]))
def test_tiled_bit_identical_to_ref(seed, gran, m, n, blocks, n_r,
                                    tile_m, tile_n):
    """The fused tiled backend is the oracle, bit for bit (0 ulp), across
    granularities, tile sizes that do and don't divide M/N, and n_r edge
    values (one block per row through many narrow columns)."""
    k = blocks * n_r
    kx, kw_ = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.uniform(kx, (m, k), minval=-1, maxval=1)
    w = F.quantize(jax.random.uniform(kw_, (k, n), minval=-1, maxval=1),
                   F.FP4_E2M1)
    kw = dict(fmt_x=F.FP6_E3M2, fmt_w=F.FP4_E2M1, n_r=n_r, enob=8.0,
              granularity=gran)
    ref = grmac_matmul_ref(x, w, **kw)
    out = grmac_matmul_tiled(x, w, tile_m=tile_m, tile_n=tile_n, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1 << 30), ne=st.integers(1, 4),
       nm=st.integers(1, 4))
def test_quantize_monotone(seed, ne, nm):
    """Quantization preserves order (monotone non-decreasing map)."""
    fmt = F.FPFormat(ne, nm)
    x = jnp.sort(jax.random.uniform(jax.random.PRNGKey(seed), (256,),
                                    minval=-1, maxval=1))
    xq = F.quantize(x, fmt)
    assert bool(jnp.all(jnp.diff(xq) >= -1e-9))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1 << 30), enob=st.floats(2.0, 12.0))
def test_adc_noise_bound(seed, enob):
    """|Q_ADC(v) - v| <= Δ/2 for v in [-1, 1]."""
    from repro.core.mac import adc_quantize
    v = jax.random.uniform(jax.random.PRNGKey(seed), (512,),
                           minval=-1, maxval=1)
    vq = adc_quantize(v, enob)
    delta = 2.0 / 2 ** enob
    assert float(jnp.max(jnp.abs(vq - v))) <= delta / 2 + 1e-7
