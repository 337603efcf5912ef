"""GR-MAC backend cross-validation: fast XLA path and fused tiled path vs
the jnp oracle (exact), Pallas-interpret vs oracle (slow debug cross-check),
plan-based dispatch (heuristic + autotune cache), and the model-facing
cim_matmul op."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cim_config import CIMConfig
from repro.core.formats import FP4_E2M1, FP6_E3M2, FPFormat, quantize
from repro.kernels.dispatch import (
    BACKENDS,
    Plan,
    clear_plan_cache,
    grmac_matmul,
    pallas_blocks,
    plan_for,
    resolve_backend,
)
from repro.kernels.grmac_matmul import grmac_matmul_pallas
from repro.kernels.ops import cim_matmul
from repro.kernels.ref import grmac_matmul_ref
from repro.kernels.tiled import default_tile_m, grmac_matmul_tiled
from repro.kernels.xla import grmac_matmul_xla


def _data(key, m, k, n):
    kx, kw = jax.random.split(key)
    x = jax.random.uniform(kx, (m, k), minval=-1.0, maxval=1.0)
    w = quantize(jax.random.uniform(kw, (k, n), minval=-1.0, maxval=1.0), FP4_E2M1)
    return x, w


# ------------------------------------------------------------- fast XLA path
@pytest.mark.parametrize("granularity", ["conv", "row", "unit"])
@pytest.mark.parametrize(
    "m,k,n", [(128, 128, 128), (256, 384, 128), (128, 256, 256)]
)
def test_xla_backend_matches_ref(granularity, m, k, n):
    x, w = _data(jax.random.PRNGKey(0), m, k, n)
    kw = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=32, enob=8.0,
              granularity=granularity)
    ref = grmac_matmul_ref(x, w, **kw)
    out = grmac_matmul_xla(x, w, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("granularity", ["conv", "row", "unit"])
def test_xla_backend_unpadded_shapes(granularity):
    # no 128-alignment requirement: dispatch pads K to n_r only
    x, w = _data(jax.random.PRNGKey(7), 7, 100, 13)
    kw = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=32, enob=8.0,
              granularity=granularity)
    ref = grmac_matmul(x, w, backend="ref", **kw)
    out = grmac_matmul(x, w, backend="xla", **kw)
    assert out.shape == (7, 13)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_xla_backend_vmap_grad_safe():
    x, w = _data(jax.random.PRNGKey(8), 32, 128, 16)
    kw = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=32, enob=8.0,
              granularity="row")
    vm = jax.vmap(lambda a: grmac_matmul_xla(a, w, **kw))(
        jnp.stack([x, x * 0.5, -x]))
    assert vm.shape == (3, 32, 16)
    g = jax.grad(lambda a: jnp.sum(grmac_matmul_xla(a, w, **kw) ** 2))(x)
    assert g.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(g)))


# ------------------------------------------------------------- tiled path
@pytest.mark.parametrize("granularity", ["conv", "row", "unit"])
@pytest.mark.parametrize(
    "m,k,n,tile_m,tile_n",
    [
        (128, 128, 128, 32, 0),      # tiles divide M, no N tiling
        (100, 128, 96, 32, 0),       # tile_m does not divide M
        (64, 128, 80, 16, 32),       # N tiling, divides
        (64, 128, 80, 16, 24),       # N tiling, does not divide
        (24, 128, 48, 256, 0),       # single tile larger than M
    ],
)
def test_tiled_backend_matches_ref_exactly(granularity, m, k, n,
                                           tile_m, tile_n):
    """The fused tiled backend is bit-identical to the oracle at 0 ulp for
    every granularity and for tile sizes that do and don't divide M/N."""
    x, w = _data(jax.random.PRNGKey(21), m, k, n)
    kw = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=32, enob=8.0,
              granularity=granularity)
    ref = grmac_matmul_ref(x, w, **kw)
    out = grmac_matmul_tiled(x, w, tile_m=tile_m, tile_n=tile_n, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("n_r", [8, 32, 128])
def test_tiled_backend_n_r_edges(n_r):
    """n_r from one block per row (n_r == K) down to many tiny columns."""
    x, w = _data(jax.random.PRNGKey(22), 48, 128, 40)
    for gran in ["conv", "row", "unit"]:
        kw = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=n_r, enob=8.0,
                  granularity=gran)
        ref = grmac_matmul_ref(x, w, **kw)
        out = grmac_matmul_tiled(x, w, tile_m=16, **kw)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_tiled_backend_vmap_grad_safe():
    x, w = _data(jax.random.PRNGKey(23), 32, 128, 16)
    kw = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=32, enob=8.0,
              granularity="row")
    vm = jax.vmap(lambda a: grmac_matmul_tiled(a, w, tile_m=8, **kw))(
        jnp.stack([x, x * 0.5, -x]))
    assert vm.shape == (3, 32, 16)
    g = jax.grad(
        lambda a: jnp.sum(grmac_matmul_tiled(a, w, tile_m=8, **kw) ** 2))(x)
    assert g.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(g)))


def test_tiled_through_dispatch_unpadded():
    """dispatch pads K to n_r for the tiled backend exactly like xla/ref."""
    x, w = _data(jax.random.PRNGKey(24), 70, 100, 13)
    kw = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=32, enob=8.0,
              granularity="row")
    ref = grmac_matmul(x, w, backend="ref", **kw)
    out = grmac_matmul(x, w, backend="tiled", **kw)
    tiny = grmac_matmul(x, w, backend="tiled", tile_m=16, tile_n=8, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(tiny), np.asarray(ref))


# ---------------------------------------------------------------- dispatch
_FMT_KW = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1)


def test_dispatch_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_GRMAC_BACKEND", raising=False)
    # "auto" stays symbolic at the name level; plan_for decides per shape
    assert resolve_backend(None) == "auto"
    assert resolve_backend("ref") == "ref"
    monkeypatch.setenv("REPRO_GRMAC_BACKEND", "ref")
    assert resolve_backend(None) == "ref"
    assert resolve_backend("auto") == "ref"
    assert resolve_backend("xla") == "xla"  # explicit beats env
    with pytest.raises(ValueError):
        resolve_backend("cuda")
    assert set(BACKENDS) == {"auto", "xla", "tiled", "pallas",
                             "pallas_interpret", "ref"}


def test_plan_heuristic_small_vs_large_m(monkeypatch):
    """The static heuristic routes the deployment regimes: edge_decode
    (16x768x3072) to the batched-einsum xla path, train_large_m
    (2048x768x3072) to the fused tiled path (off-TPU)."""
    if jax.default_backend() == "tpu":
        pytest.skip("heuristic plans pallas on TPU")
    monkeypatch.delenv("REPRO_GRMAC_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_GRMAC_AUTOTUNE", raising=False)
    monkeypatch.setenv("REPRO_GRMAC_PLAN_CACHE", "/nonexistent/plans.json")
    clear_plan_cache()
    edge = plan_for(16, 768, 3072, granularity="row", **_FMT_KW)
    train = plan_for(2048, 768, 3072, granularity="row", **_FMT_KW)
    assert edge.backend == "xla"
    assert train.backend == "tiled"
    assert train.tile_m == default_tile_m(768, 3072, 32)
    # explicit names always short-circuit the planner
    assert plan_for(2048, 768, 3072, granularity="row", backend="ref",
                    **_FMT_KW) == Plan("ref", source="fixed")
    clear_plan_cache()


def test_autotune_cache_round_trip(tmp_path, monkeypatch):
    """REPRO_GRMAC_AUTOTUNE=1 probes an unknown shape once, persists the
    winning plan to the JSON cache, and a fresh lookup (new in-memory
    state, autotune off) serves the persisted plan instead of re-probing
    or falling back to the heuristic."""
    cache = tmp_path / "plans.json"
    monkeypatch.setenv("REPRO_GRMAC_PLAN_CACHE", str(cache))
    monkeypatch.setenv("REPRO_GRMAC_AUTOTUNE", "1")
    monkeypatch.delenv("REPRO_GRMAC_BACKEND", raising=False)
    clear_plan_cache()
    probed = plan_for(96, 96, 64, granularity="row", **_FMT_KW)
    assert probed.source == "autotune"
    assert cache.exists()

    clear_plan_cache()                      # drop memory, keep the file
    monkeypatch.setenv("REPRO_GRMAC_AUTOTUNE", "0")
    reloaded = plan_for(96, 96, 64, granularity="row", **_FMT_KW)
    assert reloaded.source == "cache"
    assert (reloaded.backend, reloaded.tile_m, reloaded.tile_n) == \
        (probed.backend, probed.tile_m, probed.tile_n)
    # a different shape/granularity is a different key -> heuristic again
    other = plan_for(96, 96, 64, granularity="unit", **_FMT_KW)
    assert other.source == "heuristic"
    clear_plan_cache()


def test_plan_cache_version_mismatch_ignored(tmp_path, monkeypatch):
    """A plan cache written under a different schema version — including a
    pre-versioned flat dict — is ignored with a warning, not served: its
    plans may have been measured under different rules. The next persisted
    plan rewrites the file under the current version."""
    import json
    import warnings

    from repro.kernels.dispatch import PLAN_CACHE_VERSION, _plan_key

    if jax.default_backend() == "tpu":
        pytest.skip("heuristic plans pallas on TPU; test pins xla/tiled")
    cache = tmp_path / "plans.json"
    key = _plan_key(16, 96, 64, "row", _FMT_KW["fmt_x"], _FMT_KW["fmt_w"], 32)
    stale = {"backend": "ref", "tile_m": 0, "tile_n": 0, "warm_us": 1.0}
    monkeypatch.setenv("REPRO_GRMAC_PLAN_CACHE", str(cache))
    monkeypatch.delenv("REPRO_GRMAC_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_GRMAC_AUTOTUNE", raising=False)

    for payload in (
        {key: stale},                                     # pre-versioned
        {"version": PLAN_CACHE_VERSION + 1, "plans": {key: stale}},
    ):
        cache.write_text(json.dumps(payload))
        clear_plan_cache()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            plan = plan_for(16, 96, 64, granularity="row", **_FMT_KW)
        assert plan.source == "heuristic"      # stale "ref" plan NOT served
        assert plan.backend == "xla"
        assert any("plan cache" in str(w.message) for w in caught)

    # a current-version cache IS served
    cache.write_text(json.dumps(
        {"version": PLAN_CACHE_VERSION, "plans": {key: stale}}))
    clear_plan_cache()
    assert plan_for(16, 96, 64, granularity="row", **_FMT_KW).source == "cache"
    clear_plan_cache()


def test_auto_dispatch_matches_ref_under_jit(monkeypatch):
    """backend="auto" plans inside jit traces (no probing) and the planned
    backend keeps the 0-ulp contract."""
    monkeypatch.delenv("REPRO_GRMAC_BACKEND", raising=False)
    monkeypatch.setenv("REPRO_GRMAC_AUTOTUNE", "1")  # must not probe in-trace
    monkeypatch.setenv("REPRO_GRMAC_PLAN_CACHE", "/nonexistent/plans.json")
    clear_plan_cache()
    x, w = _data(jax.random.PRNGKey(25), 96, 96, 48)
    kw = dict(n_r=32, enob=8.0, granularity="row", **_FMT_KW)
    ref = grmac_matmul(x, w, backend="ref", **kw)
    out = jax.jit(lambda a, b: grmac_matmul(a, b, backend="auto", **kw))(x, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    clear_plan_cache()


def test_cim_matmul_backend_kwarg():
    x, w = _data(jax.random.PRNGKey(9), 16, 96, 24)
    cfg = CIMConfig(mode="grmac", granularity="row", n_r=32)
    a = cim_matmul(x, w, cfg, backend="xla")
    b = cim_matmul(x, w, cfg, backend="ref")
    c = cim_matmul(x, w, cfg.with_backend("xla"))
    d = cim_matmul(x, w, cfg, use_kernel=False)  # legacy knob -> xla
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(d))


@pytest.mark.parametrize("backend", BACKENDS)
def test_grmac_with_intformat_raises_not_implemented(backend):
    """grmac execution has no INT signal chain (the gr_int ladder is priced
    analytically by core.dse only): every backend must refuse an IntFormat
    input with the same actionable error through the model-facing op, not
    trace into a wrong-numerics kernel."""
    from repro.core.formats import IntFormat
    x, w = _data(jax.random.PRNGKey(11), 16, 64, 8)
    cfg = CIMConfig(mode="grmac", granularity="row", n_r=32,
                    fmt_x=IntFormat(8))
    with pytest.raises(NotImplementedError, match="IntFormat"):
        cim_matmul(x, w, cfg, backend=backend)
    # fakequant, by contrast, supports the INT ladder: same config must run
    out = cim_matmul(x, w, CIMConfig(mode="fakequant", granularity="row",
                                     n_r=32, fmt_x=IntFormat(8)))
    assert out.shape == (16, 8)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_pallas_never_falls_back_to_the_interpreter():
    """Off-TPU the Pallas kernel runs only when the interpreter is asked
    for by name (``pallas_interpret``): the default is an error."""
    if jax.default_backend() == "tpu":
        pytest.skip("the kernel compiles natively on TPU")
    x, w = _data(jax.random.PRNGKey(4), 128, 128, 128)
    with pytest.raises(RuntimeError, match="pallas_interpret"):
        grmac_matmul_pallas(x, w, fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=32,
                            enob=8.0, granularity="row")


# -------------------------------------------------- Pallas block planning
@pytest.mark.parametrize("m", [8, 64, 1024])
@pytest.mark.parametrize("k,n", [(1536, 2048), (1536, 1536), (1536, 8960),
                                 (8960, 1536), (1536, 152064)])
def test_pallas_blocks_from_call_shape(m, k, n):
    """qwen2-1.5b's projection and head widths at decode, bucket-8 and
    bucket-128 prefill rows: rows rounded up to 8 up to a 128-row block,
    N blocks that tile N exactly (no weight padded), block_k unchanged;
    explicit tiles still pin the blocks."""
    bm, bn, bk = pallas_blocks(m, n, 32)
    assert bm == min(128, -(-m // 8) * 8)
    assert bk == math.lcm(128, 32)
    # these widths are multiples of 128, so no weight is padded
    assert bn % 128 == 0 and n % bn == 0 and k % bk == 0
    assert pallas_blocks(m, n, 32, tile_m=128, tile_n=128) == (128, 128, 128)
    assert pallas_blocks(m, n, 32, tile_m=20, tile_n=300) == (24, 384, 128)


# ----------------------------------------- Pallas interpret-mode cross-check
@pytest.mark.parametrize("granularity", ["conv", "row", "unit"])
@pytest.mark.parametrize("m", [8, 64])
def test_planned_pallas_blocks_match_128_blocks_bit_for_bit(granularity, m):
    """The planned blocks (m rows, wide N) give the same bits as the kernel
    at 128 x 128 x 128 blocks on the same operands: block shapes reorder no
    output element's arithmetic."""
    k, n = 256, 768
    x, w = _data(jax.random.PRNGKey(7), m, k, n)
    kw = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=32, enob=8.0,
              granularity=granularity)
    bm, bn, _ = pallas_blocks(m, n, 32)
    assert bm == m and bn > 128
    planned = grmac_matmul(x, w, backend="pallas_interpret", **kw)
    fixed = grmac_matmul_pallas(
        jnp.pad(x, ((0, 128 - m), (0, 0))), w, block_m=128, block_n=128,
        block_k=128, interpret=True, **kw)[:m]
    assert jnp.array_equal(planned, fixed)


@pytest.mark.slow
@pytest.mark.parametrize("granularity", ["conv", "row", "unit"])
@pytest.mark.parametrize(
    "m,k,n", [(128, 128, 128), (256, 384, 128), (128, 256, 256)]
)
def test_kernel_matches_ref(granularity, m, k, n):
    x, w = _data(jax.random.PRNGKey(0), m, k, n)
    fmt_w = FP4_E2M1
    fmt_x = FP6_E3M2
    kw = dict(fmt_x=fmt_x, fmt_w=fmt_w, n_r=32, enob=8.0, granularity=granularity)
    ref = grmac_matmul_ref(x, w, **kw)
    out = grmac_matmul_pallas(x, w, block_m=128, block_n=128, block_k=128,
                              interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("fmt_x", [FP4_E2M1, FP6_E3M2, FPFormat(2, 3)])
def test_kernel_shape_dtype_sweep(dtype, fmt_x):
    x, w = _data(jax.random.PRNGKey(1), 128, 128, 128)
    x = x.astype(dtype)
    kw = dict(fmt_x=fmt_x, fmt_w=FP4_E2M1, n_r=32, enob=8.0, granularity="row")
    ref = grmac_matmul_ref(x.astype(jnp.float32), w, **kw)
    out = grmac_matmul_pallas(x.astype(jnp.float32), w, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_kernel_multi_kblock_accumulation():
    # K spans several kernel grid steps AND several n_r sub-blocks per step.
    x, w = _data(jax.random.PRNGKey(2), 128, 512, 128)
    kw = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=64, enob=9.0, granularity="unit")
    ref = grmac_matmul_ref(x, w, **kw)
    out = grmac_matmul_pallas(x, w, block_k=128, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_dispatch_pallas_interpret_matches_xla():
    """The debug backend and the fast backend agree through dispatch,
    including the shared zero-padding contract."""
    x, w = _data(jax.random.PRNGKey(3), 64, 160, 40)
    kw = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=32, enob=8.0,
              granularity="row")
    slow = grmac_matmul(x, w, backend="pallas_interpret", **kw)
    fast = grmac_matmul(x, w, backend="xla", **kw)
    np.testing.assert_allclose(np.asarray(slow), np.asarray(fast),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- cim_matmul op
def test_grmac_accuracy_vs_fakequant():
    # GR-MAC adds only ADC noise on top of format quantization: the distance
    # to the fakequant (exact-accumulation) output must be small at ENOB=8.
    x, w = _data(jax.random.PRNGKey(3), 64, 256, 64)
    cfg_fq = CIMConfig(mode="fakequant", granularity="row", n_r=32)
    cfg_gr = CIMConfig(mode="grmac", granularity="row", n_r=32)
    fq = cim_matmul(x, w, cfg_fq, use_kernel=False)
    gr = cim_matmul(x, w, cfg_gr, use_kernel=False)
    rel = float(jnp.linalg.norm(gr - fq) / jnp.linalg.norm(fq))
    assert rel < 0.05, rel


def test_cim_matmul_modes_and_grad():
    x, w = _data(jax.random.PRNGKey(4), 32, 96, 48)
    for mode in ["off", "fakequant", "grmac"]:
        cfg = CIMConfig(mode=mode)
        out = cim_matmul(x, w, cfg, use_kernel=False)
        assert out.shape == (32, 48)
        assert bool(jnp.all(jnp.isfinite(out)))

    cfg = CIMConfig(mode="grmac")
    f = lambda xx, ww: jnp.sum(cim_matmul(xx, ww, cfg, use_kernel=False) ** 2)
    gx, gw = jax.grad(f, argnums=(0, 1))(x, w)
    assert gx.shape == x.shape and gw.shape == w.shape
    assert bool(jnp.all(jnp.isfinite(gx))) and bool(jnp.all(jnp.isfinite(gw)))


def test_batched_leading_dims():
    x = jax.random.uniform(jax.random.PRNGKey(5), (4, 8, 96), minval=-1, maxval=1)
    w = jax.random.uniform(jax.random.PRNGKey(6), (96, 32), minval=-1, maxval=1)
    cfg = CIMConfig(mode="grmac")
    out = cim_matmul(x, w, cfg, use_kernel=False)
    assert out.shape == (4, 8, 32)
