"""Paper Fig. 11: required ADC ENOB vs input precision (N_M,x sweep).

N_E,x = 3 (so the studied distributions fit in range), weights FP4_E2M1
max-entropy, N_R = 32.  Validates the linear ENOB-vs-precision scaling and
the 1.5–6 b advantage holding independent of input resolution.
"""
import time

import jax
import numpy as np

from repro.core import adc as A
from repro.core import distributions as D
from repro.core import formats as F
from benchmarks.common import emit, save_json
from repro.compile_cache import enable_compile_cache


def run():
    key = jax.random.PRNGKey(0)
    table = {}
    for nm in [1, 2, 3, 4, 5]:
        fmt = F.FPFormat(3, nm)
        for dname, dist in [
            ("uniform", D.uniform()),
            ("gauss_outliers", D.gaussian_outliers()),
        ]:
            t0 = time.perf_counter()
            rc = A.required_enob(key, "conv", dist, fmt)
            ru = A.required_enob(key, "gr_unit", dist, fmt)
            us = (time.perf_counter() - t0) / 2 * 1e6
            table[f"NM{nm}_{dname}"] = {
                "conv": rc.enob, "gr_unit": ru.enob,
                "delta": rc.enob - ru.enob,
            }
            emit(f"fig11/NM{nm}/{dname}", us,
                 f"conv={rc.enob:.2f};gr_unit={ru.enob:.2f}")
    # linear scaling: ENOB grows ~1 b per mantissa bit
    u = [table[f"NM{nm}_uniform"]["gr_unit"] for nm in (1, 2, 3, 4, 5)]
    slope = np.polyfit([1, 2, 3, 4, 5], u, 1)[0]
    deltas = [table[f"NM{nm}_uniform"]["delta"] for nm in (1, 2, 3, 4, 5)]
    out = {"table": table, "slope_bits_per_mantissa_bit": float(slope),
           "delta_range": [min(deltas), max(deltas)]}
    save_json("fig11", out)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
