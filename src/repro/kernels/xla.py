"""Fast pure-XLA GR-MAC backend — the default off-TPU.

Implements the same semantics contract as ``ref.py`` / the Pallas kernel
(see ``ref.py`` for the math), but is written for throughput on CPU/GPU
rather than as a readable oracle or a TPU lowering:

* the K dimension is reshaped into ``(K / n_r, n_r)`` sub-blocks and every
  gain-ranged partial dot product runs as **one batched einsum per
  operand pair** — there is no Python loop over blocks and no 128-padding
  requirement (only ``K % n_r == 0``, handled by ``dispatch.py``);
  stacking the ``unit`` values/gain matmuls into a single 6-D contraction
  was measured *slower* than two plain batched GEMMs (XLA CPU lowers the
  extra stacking dim poorly), so unit runs two einsums;
* quantization / exponent extraction reuse the exact grid primitives from
  ``core.formats`` (frexp + ldexp), so the output is bit-identical to
  ``grmac_matmul_ref`` — the cross-backend tests assert equality at 0 ulp
  tolerance on every granularity.

The whole function is jit-compiled with static format/shape knobs and is
vmap- and grad-safe (pure ``jnp``; gradients follow the usual
straight-through convention of ``jnp.round``). Interpret-mode Pallas runs
the same shapes ~3 orders of magnitude slower; ``benchmarks/kernel_bench.py
--backend all`` measures the gap.

"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.formats import FPFormat, decompose, pow2i, quantize
from repro.core.mac import adc_quantize

__all__ = ["grmac_matmul_xla"]


@functools.partial(
    jax.jit,
    static_argnames=("fmt_x", "fmt_w", "n_r", "enob", "granularity",
                     "sanitize", "tag"),
)
def grmac_matmul_xla(
    x: jax.Array,
    wq: jax.Array,
    *,
    fmt_x: FPFormat,
    fmt_w: FPFormat,
    n_r: int = 32,
    enob: float = 8.0,
    granularity: str = "row",
    sanitize: bool = False,
    tag: str = "",
) -> jax.Array:
    """(M, K) @ (K, N) GR-MAC matmul, fully vectorized; float32 out.

    Inputs pre-scaled to [-1, 1]; ``wq`` already on the weight format grid;
    ``K`` must be a multiple of ``n_r`` (dispatch.py pads).
    ``sanitize`` stages the ``repro.analysis.sanitize`` checks on the
    pre-ADC voltage / exponent spans, reported under ``tag``; when False
    (the default) the staged graph is exactly the uninstrumented one.
    """
    x = x.astype(jnp.float32)
    wq = wq.astype(jnp.float32)
    m, k = x.shape
    k2, n = wq.shape
    assert k == k2 and k % n_r == 0
    b = k // n_r
    if sanitize:
        from repro.analysis import sanitize as _san

    def block_einsum(a, bb):
        return jnp.einsum("mbk,bkn->mbn", a, bb,
                          preferred_element_type=jnp.float32)

    xq = quantize(x, fmt_x)
    xb = xq.reshape(m, b, n_r)
    wb = wq.reshape(b, n_r, n)

    if granularity == "conv":
        with jax.named_scope("cim_values"):
            num = block_einsum(xb, wb)
        v = num * (1.0 / n_r)
        if sanitize:
            _san.check_values(tag, v)
        z = adc_quantize(v, enob) * float(n_r)
        return jnp.sum(z, axis=1)

    # input gains 2^{E(xq)} — exponent of the *quantized* value (rounding
    # can promote into the next binade), identical to ref.py's decompose
    _, _, ex = decompose(xq, fmt_x)
    gxb = pow2i(ex).reshape(m, b, n_r)

    if granularity == "row":
        with jax.named_scope("cim_values"):
            num = block_einsum(xb, wb)
        den = jnp.sum(gxb, axis=-1)[:, :, None]          # (M, B, 1)
        scale = 2.0**fmt_x.e_max
        v = num * scale / den
        if sanitize:
            _san.check_values(tag, v)
            exb = ex.reshape(m, b, n_r)
            _san.check_gain_span(
                tag, jnp.max(exb, axis=-1) - jnp.min(exb, axis=-1))
        z = adc_quantize(v, enob) * (den * (1.0 / scale))
        return jnp.sum(z, axis=1)

    if granularity == "unit":
        _, _, ew = decompose(wq, fmt_w)
        gwb = pow2i(ew).reshape(b, n_r, n)
        with jax.named_scope("cim_values"):
            num = block_einsum(xb, wb)
        with jax.named_scope("cim_gains"):
            den = block_einsum(gxb, gwb)
        scale = 2.0 ** (fmt_x.e_max + fmt_w.e_max)
        v = num * scale / den
        if sanitize:
            _san.check_values(tag, v)
            # combined exponent per unit instance: E(x_i) + E(w_i,n)
            comb = (ex.reshape(m, b, n_r)[:, :, :, None]
                    + ew.reshape(b, n_r, n)[None])
            _san.check_gain_span(
                tag, jnp.max(comb, axis=2) - jnp.min(comb, axis=2))
        z = adc_quantize(v, enob) * (den * (1.0 / scale))
        return jnp.sum(z, axis=1)

    raise ValueError(f"unknown granularity {granularity!r}")
