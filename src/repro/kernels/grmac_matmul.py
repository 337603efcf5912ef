"""Pallas TPU kernel for the GR-MAC matmul (deployment-faithful CIM numerics).

TPU mapping of the paper's architecture (DESIGN.md §2):

* one CIM array column accumulation  <->  one ``n_r``-deep K sub-block
* mantissa multiply + charge redistribution  <->  MXU dot over the sub-block
* exponent adder tree (digital)  <->  VPU row-sum of gains (row norm) or a
  second MXU dot ``gx @ gw`` (unit norm), fused in the same VMEM pass
* ADC conversion  <->  mid-tread requantization of the block partial sum

The kernel streams (block_m × block_k) activation tiles and
(block_k × block_n) weight tiles through VMEM, quantizes activations onto the
input format grid in-register (exponent extraction via bitcast — exact, no
transcendentals on the hot path), performs ``block_k / n_r`` gain-ranged
partial dot products, and accumulates the renormalized, ADC-quantized block
outputs into the float32 output tile.

The values matmul runs in bfloat16: both operands live on low-bit format
grids (≤ 5 significant bits), so bf16 products/MXU accumulation are exact.

Shapes must be pre-padded to multiples of the block sizes, which
``dispatch.pallas_blocks`` plans from the call's shape: ``block_m`` is M
rounded up to 8 rows, up to 128, so a decode call's few rows run in an
8-row block, and ``block_n`` is as wide as the VMEM caps allow while it
divides N rounded up to 128; explicit ``tile_m``/``tile_n`` pin them.
``block_k`` must be a multiple of ``n_r`` and 128-aligned for the MXU. The
per-column epilogue (den -> ADC -> renorm -> accumulate) is fused in the
kernel body, matching kernels/tiled.py's formulation. The ``block_k / n_r``
columns of a K-tile are unrolled with static slices: Mosaic has no lowering
for a slice at a traced offset, and dispatch sizes ``block_k = lcm(128,
n_r)``, so the unrolled body stays small.

The kernel only ever compiles for the TPU. Off-TPU, pass
``interpret=True`` (the ``pallas_interpret`` dispatch backend) to run it
through the Pallas interpreter; ``interpret=None`` there is an error, so no
path falls into the interpreter by accident.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import FPFormat

__all__ = ["grmac_matmul_pallas", "named_kernel"]


def _pow2(e: jax.Array) -> jax.Array:
    """Exact 2**e for int32 ``e`` in [-126, 127] via IEEE-754 bit assembly.

    jnp.exp2 is not bit-exact on every backend; grid-exact quantization (and
    exact agreement with ref.py) requires true powers of two.
    """
    return jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)


def _quant_decompose(x: jax.Array, fmt: FPFormat):
    """Quantize f32 ``x`` onto the format grid; return (xq, gain=2^E).

    Exponent extraction via IEEE-754 bit manipulation: for positive normal
    f32, floor(log2 a) = ((bits >> 23) & 0xff) - 127. Subnormal-f32 inputs
    (< 2^-126) underflow to the format's lowest bin, which is correct.
    """
    def eff_exp(a):
        bits = jax.lax.bitcast_convert_type(a, jnp.int32)
        floor_log2 = ((bits >> 23) & 0xFF) - 127
        return jnp.clip(floor_log2 + 1 + fmt.e_max, 1, fmt.e_max)

    a = jnp.abs(x)
    e = eff_exp(a)
    lsb = _pow2(e - (fmt.e_max + fmt.n_man + 1))
    q = jnp.round(a / lsb) * lsb
    q = jnp.minimum(q, fmt.max_value)
    xq = jnp.where(x < 0, -q, q)
    # Gain must reflect the exponent of the *quantized* value: rounding can
    # promote a value into the next binade (e.g. 0.499 -> 0.5).
    gain = _pow2(eff_exp(q))
    return xq, gain


def _adc(v: jax.Array, enob: float) -> jax.Array:
    delta = 2.0 / (2.0**enob)
    return jnp.clip(jnp.round(v * (1.0 / delta)) * delta, -1.0, 1.0)


def _kernel(
    x_ref,
    w_ref,
    o_ref,
    *,
    fmt_x: FPFormat,
    fmt_w: FPFormat,
    n_r: int,
    enob: float,
    granularity: str,
    block_k: int,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)          # (bm, bk)
    w = w_ref[...].astype(jnp.float32)          # (bk, bn)
    xq, gx = _quant_decompose(x, fmt_x)
    if granularity == "unit":
        # Weights are already on the grid; recover their gains in-register
        # (cheaper than streaming a second K×N operand from HBM).
        _, gw = _quant_decompose(w, fmt_w)

    if granularity not in ("conv", "row", "unit"):
        raise ValueError(granularity)

    xq16 = xq.astype(jnp.bfloat16)
    w16 = w.astype(jnp.bfloat16)

    def sub_block(start, acc):
        """One n_r-deep analog column: MXU dot + fused den/ADC/renorm
        epilogue — the same per-tile formulation kernels/tiled.py scans on
        the host side, so the TPU lowering matches the planned backend."""
        cols = slice(start, start + n_r)
        num = jnp.dot(xq16[:, cols], w16[cols, :],
                      preferred_element_type=jnp.float32)
        if granularity == "conv":
            v = num * (1.0 / n_r)
            return acc + _adc(v, enob) * float(n_r)
        if granularity == "row":
            den = jnp.sum(gx[:, cols], axis=1, keepdims=True)      # (bm, 1)
            scale = 2.0**fmt_x.e_max
            v = num * scale / den
            return acc + _adc(v, enob) * (den * (1.0 / scale))
        den = jnp.dot(gx[:, cols], gw[cols, :],
                      preferred_element_type=jnp.float32)
        scale = 2.0 ** (fmt_x.e_max + fmt_w.e_max)
        v = num * scale / den
        return acc + _adc(v, enob) * (den * (1.0 / scale))

    acc = jnp.zeros_like(o_ref)
    for s in range(block_k // n_r):
        acc = sub_block(s * n_r, acc)
    o_ref[...] += acc


_STATIC = ("fmt_x", "fmt_w", "n_r", "enob", "granularity", "block_m",
           "block_n", "block_k", "interpret")


def _grmac_pallas(
    x: jax.Array,
    wq: jax.Array,
    *,
    fmt_x: FPFormat,
    fmt_w: FPFormat,
    n_r: int = 32,
    enob: float = 8.0,
    granularity: str = "row",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """(M, K) @ (K, N) GR-MAC matmul; inputs pre-scaled to [-1, 1]."""
    m, k = x.shape
    k2, n = wq.shape
    assert k == k2
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        f"shapes ({m},{k})x({k2},{n}) must be padded to blocks "
        f"({block_m},{block_k},{block_n}) — see ops.cim_matmul"
    )
    assert block_k % n_r == 0

    if interpret is None:
        if jax.default_backend() != "tpu":
            raise RuntimeError(
                "grmac_matmul_pallas compiles for the TPU only, and the "
                f"default backend is {jax.default_backend()!r}: pass "
                "interpret=True (the 'pallas_interpret' backend) to run the "
                "kernel through the Pallas interpreter")
        interpret = False

    kernel = functools.partial(
        _kernel,
        fmt_x=fmt_x,
        fmt_w=fmt_w,
        n_r=n_r,
        enob=enob,
        granularity=granularity,
        block_k=block_k,
    )
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, s: (i, s)),
            pl.BlockSpec((block_k, block_n), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x.astype(jnp.float32), wq.astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def named_kernel(name: str):
    """The kernel's jitted entry point under ``name``.

    On the TPU a launch's HLO instruction, and so its event in a device
    trace, takes the name of the innermost jitted function around its
    ``pallas_call`` (``%<name>.N``); neither a ``named_scope``/``named_call``
    nor an outer jitted wrapper changes it. ``dispatch`` launches each model
    call site's matmuls under ``grmac_<site>`` for that reason. The name
    changes nothing else: every name runs the same kernel body."""
    def fn(*args, **kwargs):
        return _grmac_pallas(*args, **kwargs)

    functools.update_wrapper(fn, _grmac_pallas)
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, static_argnames=_STATIC)


grmac_matmul_pallas = named_kernel("grmac_matmul_pallas")
