"""Paper Fig. 10: required ADC ENOB vs input dynamic range (N_E,x sweep).

N_M,x = 2, weights FP4_E2M1 max-entropy, N_R = 32. Validates:
  C2  GR upper bound (uniform) >= 1.5 b below the conventional lower bound
  C3  >6 b reduction for Gaussian+outliers at N_E,x >= 3
  C8  GR ENOB stays below the ~10 b thermal crossover
"""
import time

import jax

from repro.core import adc as A
from repro.core import distributions as D
from repro.core import energy as E
from repro.core import formats as F
from benchmarks.common import emit, save_json
from repro.compile_cache import enable_compile_cache


def run():
    key = jax.random.PRNGKey(0)
    table = {}
    for ne in [1, 2, 3, 4, 5]:
        fmt = F.FPFormat(ne, 2)
        for dname, dist in [
            ("uniform", D.uniform()),
            ("max_entropy", D.max_entropy(fmt)),
            ("gauss_outliers", D.gaussian_outliers()),
        ]:
            t0 = time.perf_counter()
            rc = A.required_enob(key, "conv", dist, fmt)
            ru = A.required_enob(key, "gr_unit", dist, fmt)
            rr = A.required_enob(key, "gr_row", dist, fmt)
            us = (time.perf_counter() - t0) / 3 * 1e6
            table[f"NE{ne}_{dname}"] = {
                "dr_db": fmt.dr_db, "conv": rc.enob, "gr_unit": ru.enob,
                "gr_row": rr.enob, "delta_unit": rc.enob - ru.enob,
            }
            emit(f"fig10/NE{ne}/{dname}", us,
                 f"conv={rc.enob:.2f};gr_unit={ru.enob:.2f}")
    ncross = E.TechParams().n_cross()
    claims = {
        "C2_upper_bound_1p5b": min(
            table[f"NE{ne}_uniform"]["delta_unit"] for ne in (2, 3, 4)),
        "C3_outlier_delta_NE3": table["NE3_gauss_outliers"]["delta_unit"],
        "C3_outlier_delta_NE4": table["NE4_gauss_outliers"]["delta_unit"],
        "C8_max_gr_enob": max(
            table[f"NE{ne}_uniform"]["gr_unit"] for ne in (2, 3, 4, 5)),
        "n_cross": ncross,
    }
    save_json("fig10", {"table": table, "claims": claims})
    return {"table": table, "claims": claims}


if __name__ == "__main__":
    enable_compile_cache()
    run()
