"""Plain float32 GR-MAC matmul: the paper's gain-ranged CIM column.

Written from the paper's signal chain, independent of the program:

* both operands are scaled into [-1, 1] by their per-tensor absmax;
* each is rounded to nearest (ties to even) onto its floating-point grid
  (sign, ``n_exp`` exponent bits, ``n_man`` stored mantissa bits, effective
  exponent ``E = max(1, E_stored)``, saturating at ``1 - 2^-(n_man+1)``);
* K is cut into analog columns of ``n_r`` rows. Row normalisation couples
  every input through a capacitor of gain ``2^E(x)``, so a column presents
  ``v = sum(xq * wq) * 2^e_max / sum(2^E)`` to the ADC, a mid-tread
  quantiser of step ``2 / 2^enob`` clipped to [-1, 1];
* the digital epilogue multiplies the ADC code back by the column's gain
  sum and adds the columns up.

``Numerics.act`` rounds activations where the program stores them: the
reference in the configuration's precision, its control one step below
(see ``REFERENCE`` and ``CONTROL``). Everything else runs in float32.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_TINY = 1e-30
ROW_CHUNK = 256


@dataclasses.dataclass(frozen=True)
class FloatGrid:
    n_exp: int
    n_man: int

    @property
    def e_max(self) -> int:
        return 2 ** self.n_exp - 1

    @property
    def max_value(self) -> float:
        return 1.0 - 2.0 ** (-self.n_man - 1)


def parse_grid(name: str) -> FloatGrid:
    """``"FP6_E3M2"`` -> FloatGrid(3, 2)."""
    spec = name.split("_", 1)[1]
    n_exp, n_man = spec[1:].split("M")
    return FloatGrid(int(n_exp), int(n_man))


@dataclasses.dataclass(frozen=True)
class Numerics:
    """How the reference rounds activations between operations."""
    name: str
    act: Callable[[jax.Array], jax.Array]


def _rounding(dtype):
    if dtype == jnp.float32:
        return lambda x: x.astype(jnp.float32)
    return lambda x: x.astype(dtype).astype(jnp.float32)


FLOAT32 = Numerics("float32", _rounding(jnp.float32))
BFLOAT16 = Numerics("bfloat16", _rounding(jnp.bfloat16))
FLOAT8 = Numerics("float8_e4m3fn", _rounding(jnp.float8_e4m3fn))

# The reference rounds where the program stores activations, in the
# precision the configuration states; its control one step below that.
REFERENCE = {"float32": FLOAT32, "bfloat16": BFLOAT16}
CONTROL = {"float32": BFLOAT16, "bfloat16": FLOAT8}


def exponent(a: jax.Array, grid: FloatGrid) -> jax.Array:
    """Effective exponent in [1, e_max] of magnitudes ``a``."""
    _, e = jnp.frexp(jnp.maximum(a, _TINY))
    return jnp.clip(e.astype(jnp.int32) + grid.e_max, 1, grid.e_max)


def round_to_grid(x: jax.Array, grid: FloatGrid) -> jax.Array:
    a = jnp.abs(x)
    e = exponent(a, grid)
    lsb = jnp.ldexp(jnp.ones((), jnp.float32), e - grid.e_max - grid.n_man - 1)
    q = jnp.minimum(jnp.round(a / lsb) * lsb, grid.max_value)
    return jnp.where(x < 0, -q, q)


def quantize_weight(w: jax.Array, grid: FloatGrid):
    """(wq, sw): the weight on its grid (exact in bfloat16) and its scale."""
    w = w.astype(jnp.float32)
    sw = jnp.maximum(jnp.max(jnp.abs(w)), 1e-12)
    return round_to_grid(w / sw, grid).astype(jnp.bfloat16), sw


def _columns(xq, wq, grid_x: FloatGrid, n_r: int, enob: float):
    """Row-normalised GR-MAC of (m, K) grid inputs with (K, N) grid
    weights, float32 (m, N)."""
    m, k = xq.shape
    n = wq.shape[1]
    nb = k // n_r
    xb = xq.reshape(m, nb, n_r)
    wb = wq.astype(jnp.float32).reshape(nb, n_r, n)
    num = jnp.einsum("mbk,bkn->mbn", xb, wb, precision=HIGHEST,
                     preferred_element_type=jnp.float32)
    gain = jnp.ldexp(jnp.ones((), jnp.float32), exponent(jnp.abs(xb), grid_x))
    den = jnp.sum(gain, axis=-1)[:, :, None]                 # (m, nb, 1)
    scale = 2.0 ** grid_x.e_max
    v = num * scale / den
    step = 2.0 / 2.0 ** enob
    code = jnp.clip(jnp.round(v / step) * step, -1.0, 1.0)
    return jnp.sum(code * (den / scale), axis=1)


def grmac(x: jax.Array, wq: jax.Array, sw: jax.Array, *, sx=None,
          grid_x: FloatGrid, n_r: int, enob: float) -> jax.Array:
    """(M, K) @ (K, N) through the CIM column, float32 out.

    ``sx`` is the activation scale; by default the absmax of ``x`` (the
    whole tensor, every row of the dispatch). Pass it to compute only some
    rows of a tensor whose scale the other rows set too."""
    x = x.astype(jnp.float32)
    if x.shape[1] % n_r:
        raise ValueError(f"K={x.shape[1]} is not a multiple of n_r={n_r}")
    if sx is None:
        sx = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    xq = round_to_grid(x / sx, grid_x)
    m = x.shape[0]
    if m > ROW_CHUNK and m % ROW_CHUNK == 0:
        out = jax.lax.map(
            lambda blk: _columns(blk, wq, grid_x, n_r, enob),
            xq.reshape(m // ROW_CHUNK, ROW_CHUNK, -1))
        out = out.reshape(m, -1)
    else:
        out = _columns(xq, wq, grid_x, n_r, enob)
    return out * (sx * sw)
