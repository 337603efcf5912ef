"""Paper Fig. 9: output quantization noise vs exponent bits per distribution.

Reproduces the key observations: (i) global SQNR saturates quickly with
exponent bits for outlier-heavy data, (ii) the Gaussian+outliers CORE is
unresolved (near-zero SQNR) until N_E,x >= 3, then plateaus at N_E,x = 4.
"""
import jax

from repro.core import distributions as D
from repro.core import formats as F
from benchmarks.common import emit, save_json, time_call
from repro.compile_cache import enable_compile_cache


def core_sqnr(key, fmt, eps=0.01, k=50.0, n=1 << 20):
    """SQNR restricted to non-outlier (core) samples."""
    sigma = 1.0 / (3.0 * k)
    x = sigma * jax.random.normal(key, (n,))
    xq = F.quantize(x, fmt)
    return float(F.measured_sqnr_db(x, xq))


def run():
    key = jax.random.PRNGKey(0)
    n_m = 2
    rows = {}
    for ne in [1, 2, 3, 4, 5]:
        fmt = F.FPFormat(ne, n_m)
        for dname, dist in [
            ("uniform", D.uniform()),
            ("max_entropy", D.max_entropy(fmt)),
            ("gauss_outliers", D.gaussian_outliers()),
        ]:
            x = dist(key, (1 << 20,))
            xq = F.quantize(x, fmt)
            us = time_call(lambda xx: F.quantize(xx, fmt), x, n_iter=3)
            sq = float(F.measured_sqnr_db(x, xq))
            rows[f"NE{ne}_{dname}"] = sq
            emit(f"fig9/NE{ne}/{dname}", us, f"sqnr_db={sq:.2f}")
        sq_core = core_sqnr(key, fmt)
        rows[f"NE{ne}_gauss_outliers_core"] = sq_core
        emit(f"fig9/NE{ne}/gauss_outliers_core", 0.0, f"sqnr_db={sq_core:.2f}")
    # paper observations
    obs = {
        "core_unresolved_at_NE2": rows["NE2_gauss_outliers_core"] < 10.0,
        "core_resolved_at_NE3": rows["NE3_gauss_outliers_core"] > F.sqnr_db(F.FPFormat(3, n_m)) - 6.0,
        "core_plateau_at_NE4": abs(rows["NE4_gauss_outliers_core"] - rows["NE5_gauss_outliers_core"]) < 1.5,
    }
    save_json("fig9", {"rows": rows, "observations": obs})
    return {"rows": rows, "observations": obs}


if __name__ == "__main__":
    enable_compile_cache()
    run()
