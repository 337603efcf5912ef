"""Paper Fig. 8 analogue: GR-MAC transfer-function linearity.

The silicon validation sweeps (a) W at each exponent E -> linear response
with bounded DNL/INL, (b) E across W -> exponential response. Our numerical
equivalent drives a single GR-MAC cell across its full input grid and checks
(i) exact linearity in the mantissa word at fixed exponent, (ii) exact
2^E scaling across exponents, (iii) DNL/INL under Pelgrom mismatch stays
within 1/2 LSB for K_C in the paper's measured 0.45-0.85 %·sqrt(fF) range.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats as F
from repro.core import mac as M
from benchmarks.common import emit, save_json
from repro.compile_cache import enable_compile_cache

FMT = F.FP6_E2M3   # the paper's implemented configuration


def _cell_response(w_vals, e_fixed, gain_err=None):
    """Single-cell column (n_r=1): output vs weight mantissa at fixed E."""
    x = jnp.full_like(w_vals, 0.96875)          # max-mantissa input
    xq = F.quantize(x, FMT)
    wq = F.compose(jnp.ones_like(w_vals), w_vals,
                   jnp.full(w_vals.shape, e_fixed, jnp.int32), FMT)
    out = M.gr_mac_unit(xq[:, None], wq[:, None], FMT, FMT, 30.0,
                        gain_err=gain_err)
    return np.asarray(out.z_hat)


def run():
    out = {}
    # (a) W sweep at each E: response linear in the mantissa
    m_grid = jnp.arange(2 ** (FMT.n_man + 1)) / 2 ** (FMT.n_man + 1)
    worst_inl = 0.0
    for e in range(1, FMT.e_max + 1):
        z = _cell_response(m_grid, e)
        fit = np.polyfit(np.asarray(m_grid), z, 1)
        resid = z - np.polyval(fit, np.asarray(m_grid))
        lsb = float(z[1] - z[0]) if len(z) > 1 else 1.0
        inl = float(np.max(np.abs(resid)) / max(abs(lsb), 1e-12))
        worst_inl = max(worst_inl, inl)
        emit(f"fig8/linearity_E{e}", 0.0, f"inl_lsb={inl:.4f}")
    out["nominal_worst_inl_lsb"] = worst_inl

    # (b) E sweep: exact 2^E gain steps
    m_fixed = jnp.full((FMT.e_max,), 0.75)
    es = jnp.arange(1, FMT.e_max + 1, dtype=jnp.int32)
    wq = F.compose(jnp.ones_like(m_fixed), m_fixed, es, FMT)
    z = np.asarray(M.gr_mac_unit(
        jnp.full((FMT.e_max, 1), 0.9375), wq[:, None], FMT, FMT, 30.0).z_hat)
    ratios = z[1:] / z[:-1]
    out["gain_step_ratios"] = ratios.tolist()
    emit("fig8/exp_gain", 0.0,
         f"ratios={[round(float(r),3) for r in ratios]}")

    # (c) mismatch Monte Carlo: DNL within 1/2 LSB (paper's 3-sigma claim)
    rng = jax.random.PRNGKey(0)
    for kc in (0.45, 0.85):
        worst = 0.0
        for trial in range(64):
            rng, sub = jax.random.split(rng)
            gerr = M.mismatch_gains(
                sub, jnp.full((len(m_grid), 1), FMT.e_max, jnp.int32), kc)
            z = _cell_response(m_grid, FMT.e_max, gain_err=gerr)
            dnl = np.diff(z) / (z[1] - z[0] + 1e-12) - 1.0 if len(z) > 1 else [0]
            worst = max(worst, float(np.max(np.abs(dnl))))
        out[f"mismatch_kc{kc}_worst_dnl_lsb"] = worst
        emit(f"fig8/mismatch_kc{kc}", 0.0, f"worst_dnl_lsb={worst:.3f}")
    save_json("fig8", out)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
