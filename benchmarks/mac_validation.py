"""Fig. 4 condition validation: N_eff, signal-power preservation, and
ideal-ADC exactness of the GR-MAC column simulators (paper §III-B2)."""
import jax
import jax.numpy as jnp

from repro.core import distributions as D
from repro.core import formats as F
from repro.core import mac as M
from benchmarks.common import emit, save_json, time_call
from repro.compile_cache import enable_compile_cache


def run():
    key = jax.random.PRNGKey(0)
    dist = D.gaussian_clipped(4.0)
    kx, kw = jax.random.split(key)
    out = {}
    for fmt in [F.FP6_E2M3, F.FP6_E3M2]:
        xs = F.quantize(dist(kx, (8192, 32)), fmt)
        ws = F.quantize(dist(kw, (8192, 32)), fmt)
        us = time_call(
            lambda a, b: M.gr_mac_unit(a, b, fmt, fmt, 8.0).z_hat, xs, ws,
            n_iter=3)
        gu = M.gr_mac_unit(xs, ws, fmt, fmt, 16.0)
        ii = M.int_mac(xs, ws, 16.0)
        neff = float(jnp.mean(gu.n_eff))
        pratio = float(jnp.mean(gu.v ** 2) / jnp.mean(ii.v ** 2))
        denob = 0.5 * float(jnp.log2(pratio))
        err = float(jnp.max(jnp.abs(gu.z - jnp.sum(xs * ws, -1))))
        out[fmt.name] = {"n_eff": neff, "power_ratio": pratio,
                         "delta_enob": denob, "ideal_err": err}
        emit(f"mac/{fmt.name}", us,
             f"neff={neff:.1f};power_x={pratio:.1f};dENOB={denob:.2f}")
    # mismatch robustness (paper §III-E1): K_C in 0.45–0.85 %·sqrt(fF)
    fmt = F.FP6_E2M3
    xs = F.quantize(dist(kx, (8192, 32)), fmt)
    ws = F.quantize(dist(kw, (8192, 32)), fmt)
    _, _, e = F.decompose(xs, fmt)
    for kc in (0.45, 0.85):
        gerr = M.mismatch_gains(jax.random.PRNGKey(5), e, kc)
        gm = M.gr_mac_row(xs, ws, fmt, 16.0, gain_err=gerr)
        g0 = M.gr_mac_row(xs, ws, fmt, 16.0)
        rel = float(jnp.sqrt(jnp.mean((gm.z_hat - g0.z_hat) ** 2)
                             / jnp.mean(g0.z_hat ** 2)))
        out[f"mismatch_kc{kc}"] = rel
        emit(f"mac/mismatch_kc{kc}", 0.0, f"rel_rms_err={rel:.4f}")
    save_json("mac_validation", out)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
