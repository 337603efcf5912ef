"""Tiled, fused GR-MAC backend — wins the large-M (training-shape) regime.

``ref.py`` and ``xla.py`` materialize the full ``(M, B, N)`` numerator (and,
for ``unit``, denominator) before the elementwise ADC epilogue runs.  At
training shapes (``train_large_m`` 2048x768x3072: M*B*N = 150M elements,
~600 MB per f32 intermediate) that turns the op bandwidth-bound: the
den / ``adc_quantize`` / renorm traffic streams each intermediate through
DRAM several times and dominates the GEMM FLOPs — the measured result is
the vectorized ``xla`` backend *losing* to the readable oracle on ``row``
granularity (experiments/bench/kernel_bench.json).

This backend restructures the computation as a ``lax.scan`` over M-tiles
(and optionally N-tiles): each tile body runs

    block-GEMM -> den -> adc_quantize -> renorm -> block-sum

on a ``(tile_m, B, tile_n)`` slab sized to stay resident in cache, so the
``(M, B, N)`` intermediates never exist and the elementwise epilogue reads
and writes cache lines the GEMM just touched.  This is the software
realization of the throughput-per-byte discipline the paper argues for in
hardware — normalization/ADC overhead stays off the critical (bandwidth)
path — and the same loop-reshaping lever IMAGINE applies to the analog
accumulation itself.

Numerics: each tile computes *exactly* the per-element expressions of
``ref.py`` (same ``quantize``/``decompose``/``pow2i`` grid primitives, same
einsum contraction over the ``n_r`` block, same block-sum reduction order),
so the output is bit-identical to the oracle at 0 ulp on every granularity
— asserted across tile shapes in tests/test_kernels.py and
tests/test_properties.py.

Tile-size defaults target a ~12 MiB slab (``default_tile_m`` /
``_SLAB_BUDGET_BYTES``, the measured CPU sweet spot); the dispatch layer
(``kernels.dispatch``) can override per shape, either from its static
heuristic or from a measured autotune plan (``REPRO_GRMAC_AUTOTUNE=1``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.formats import FPFormat, decompose, pow2i, quantize
from repro.core.mac import adc_quantize

__all__ = ["grmac_matmul_tiled", "default_tile_m", "pad_to_multiple"]

# Target footprint of the (tile_m, B, tile_n) f32 slab each tile body
# materializes. ~12 MiB (slab + epilogue temporaries stay inside a shared
# L3 partition) measured fastest at train_large_m: tile_m=32 beat 8/16/64/128
# and every N-tiled variant on CPU (see experiments/bench/kernel_bench.json).
_SLAB_BUDGET_BYTES = 12 << 20


def default_tile_m(k: int, n: int, n_r: int, tile_n: int = 0) -> int:
    """Largest power-of-two M-tile whose (tile_m, K/n_r, tile_n or N) f32
    slab fits the cache budget, clamped to [8, 256]."""
    blocks = max(1, k // max(1, n_r))
    ncol = tile_n if tile_n else n
    rows = _SLAB_BUDGET_BYTES // max(1, blocks * ncol * 4)
    tm = 8
    while tm * 2 <= rows and tm < 256:
        tm *= 2
    return tm


def pad_to_multiple(x: jax.Array, axis: int, mult: int) -> jax.Array:
    """Zero-pad ``axis`` up to a multiple of ``mult`` (shared padding
    contract — see kernels/dispatch.py's module docstring)."""
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


@functools.partial(
    jax.jit,
    static_argnames=("fmt_x", "fmt_w", "n_r", "enob", "granularity",
                     "tile_m", "tile_n", "sanitize", "tag"),
)
def grmac_matmul_tiled(
    x: jax.Array,
    wq: jax.Array,
    *,
    fmt_x: FPFormat,
    fmt_w: FPFormat,
    n_r: int = 32,
    enob: float = 8.0,
    granularity: str = "row",
    tile_m: int = 0,
    tile_n: int = 0,
    sanitize: bool = False,
    tag: str = "",
) -> jax.Array:
    """(M, K) @ (K, N) GR-MAC matmul, fused per M(xN)-tile; float32 out.

    Inputs pre-scaled to [-1, 1]; ``wq`` already on the weight format grid;
    ``K`` must be a multiple of ``n_r`` (dispatch.py pads).  ``tile_m`` /
    ``tile_n`` need not divide M / N (zero-padded rows/cols are computed and
    sliced away; padding is exact — see dispatch.py's padding contract).
    ``tile_m=0`` picks ``default_tile_m``; ``tile_n=0`` disables N-tiling.
    ``sanitize``/``tag`` stage the ``repro.analysis.sanitize`` checks per
    tile (structurally absent when ``sanitize=False``, the default).
    """
    if granularity not in ("conv", "row", "unit"):
        raise ValueError(f"unknown granularity {granularity!r}")
    x = x.astype(jnp.float32)
    wq = wq.astype(jnp.float32)
    m, k = x.shape
    k2, n = wq.shape
    assert k == k2 and k % n_r == 0
    blocks = k // n_r
    if tile_m <= 0:
        tile_m = default_tile_m(k, n, n_r, tile_n)
    tn = tile_n if 0 < tile_n < n else 0
    if sanitize:
        from repro.analysis import sanitize as _san

    def block_einsum(a, bb):
        return jnp.einsum("mbk,bkn->mbn", a, bb,
                          preferred_element_type=jnp.float32)

    def _exponents(g):
        # gains are exact powers of two: frexp(2^e) = (0.5, e + 1)
        return jnp.frexp(g)[1] - 1

    def fused_tile(xb_t, gxb_t, wb_t, gwb_t):
        """One resident slab: GEMM -> den -> ADC -> renorm -> block-sum.

        Shapes: xb_t/gxb_t (tile_m, B, n_r); wb_t/gwb_t (B, n_r, cols).
        Per-element math is ref.py's, verbatim — the 0-ulp contract.
        """
        with jax.named_scope("cim_values"):
            num = block_einsum(xb_t, wb_t)
        if granularity == "conv":
            v = num * (1.0 / n_r)
            if sanitize:
                _san.check_values(tag, v)
            z = adc_quantize(v, enob) * float(n_r)
        elif granularity == "row":
            den = jnp.sum(gxb_t, axis=-1)[:, :, None]        # (tile_m, B, 1)
            scale = 2.0**fmt_x.e_max
            v = num * scale / den
            if sanitize:
                _san.check_values(tag, v)
                ex_t = _exponents(gxb_t)
                _san.check_gain_span(
                    tag, jnp.max(ex_t, axis=-1) - jnp.min(ex_t, axis=-1))
            z = adc_quantize(v, enob) * (den * (1.0 / scale))
        else:  # unit
            with jax.named_scope("cim_gains"):
                den = block_einsum(gxb_t, gwb_t)
            scale = 2.0 ** (fmt_x.e_max + fmt_w.e_max)
            v = num * scale / den
            if sanitize:
                _san.check_values(tag, v)
                comb = (_exponents(gxb_t)[:, :, :, None]
                        + _exponents(gwb_t)[None])
                _san.check_gain_span(
                    tag, jnp.max(comb, axis=2) - jnp.min(comb, axis=2))
            z = adc_quantize(v, enob) * (den * (1.0 / scale))
        return jnp.sum(z, axis=1)                            # (tile_m, cols)

    # Weight-side operands are laid out once, outside the scan.
    npad = n if not tn else n + ((-n) % tn)
    wp = pad_to_multiple(wq, 1, tn) if tn else wq
    wb = wp.reshape(blocks, n_r, npad)
    gwb = None
    if granularity == "unit":
        _, _, ew = decompose(wp, fmt_w)
        gwb = pow2i(ew).reshape(blocks, n_r, npad)
    if tn:
        # (Tn, B, n_r, tn): leading axis scanned per N-tile
        wt = wb.reshape(blocks, n_r, npad // tn, tn).transpose(2, 0, 1, 3)
        gwt = (gwb.reshape(blocks, n_r, npad // tn, tn).transpose(2, 0, 1, 3)
               if gwb is not None else None)

    xp = pad_to_multiple(x, 0, tile_m)
    xs = xp.reshape(xp.shape[0] // tile_m, tile_m, k)

    def m_body(_, xt):
        xq = quantize(xt, fmt_x)
        xb_t = xq.reshape(tile_m, blocks, n_r)
        gxb_t = None
        if granularity != "conv":
            _, _, ex = decompose(xq, fmt_x)
            gxb_t = pow2i(ex).reshape(tile_m, blocks, n_r)
        if not tn:
            return None, fused_tile(xb_t, gxb_t, wb, gwb)
        if gwt is None:
            _, outs = lax.scan(
                lambda c, w_t: (None, fused_tile(xb_t, gxb_t, w_t, None)),
                None, wt)
        else:
            _, outs = lax.scan(
                lambda c, wg: (None, fused_tile(xb_t, gxb_t, wg[0], wg[1])),
                None, (wt, gwt))
        # (Tn, tile_m, tn) -> (tile_m, N)
        return None, outs.transpose(1, 0, 2).reshape(tile_m, npad)[:, :n]

    _, out = lax.scan(m_body, None, xs)
    return out.reshape(-1, n)[:m]
