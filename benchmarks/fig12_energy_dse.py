"""Paper Fig. 12: CIM energy/Op across the (DR, SQNR) design space, plus the
pie-chart design points (FP4_E2M1, FP6_E3M2, FP8*_E4M3) and the ADC
parameter sensitivity study (C7).

Validates C5 (SQNR- vs DR-dominated scaling; iso-energy DR gains) and C6
(FP4 ~23 % improvement; FP6_E3M2 native ~29 fJ/Op).
"""
import time

import jax

from repro.core import dse as S
from repro.core import energy as E
from repro.core import formats as F
from benchmarks.common import emit, save_json
from repro.compile_cache import enable_compile_cache

ENERGY_LIMIT_FJ = 100.0


def run():
    key = jax.random.PRNGKey(2)
    t0 = time.perf_counter()
    pts = S.explore(key, n_exps=(0, 1, 2, 3), n_mans=(1, 2, 3, 4, 5, 6),
                    n_cols=1 << 12)
    us = (time.perf_counter() - t0) * 1e6 / len(pts)
    grid = []
    for p in pts:
        row = {
            "fmt": p.fmt_x.name, "dr_db": p.dr_db, "sqnr_db": p.sqnr_db,
            "conv_fj": p.conv.total if p.conv else None,
            "gr_fj": p.gr.total if p.gr else None,
            "gr_arch": p.gr_arch,
            "enob_conv": p.enob_conv, "enob_gr": p.enob_gr,
        }
        grid.append(row)
        emit(f"fig12/{p.fmt_x.name}", us,
             f"conv={row['conv_fj']:.1f};gr={row['gr_fj'] if row['gr_fj'] else -1:.1f}")

    # --- iso-energy DR gain (C5): contour comparison ---
    # At a fixed SQNR row, how many excess-DR bits (e_max - 1) can each
    # architecture afford within an energy budget? Fig. 12 labels mantissa
    # bits including the implicit one, so "35 dB" = stored N_M = 3
    # (6.02*4+10.79 = 34.9 dB) and "47 dB" = stored N_M = 5.
    def max_affordable_dr_bits(nm, budget_fj, which):
        best = -1
        for b_bits, fmt in [(0, F.IntFormat(nm + 2)),
                            (1, F.FPFormat(1, nm)),
                            (2, F.FPFormat(2, nm)),
                            (6, F.FPFormat(3, nm))]:
            p = S.evaluate_point(key, fmt, n_cols=1 << 12)
            e = p.conv if which == "conv" else p.gr
            if e is not None and e.total <= budget_fj:
                best = max(best, b_bits)
        return best

    # The strict-budget contour is knee-sensitive (±1 b of ENOB calibration
    # moves the affordable-B step); anchor the budget at the energy the
    # GR-CIM needs for its full gain-ranging span (B=6) and report it.
    gr_b6_35 = S.evaluate_point(key, F.FPFormat(3, 3), n_cols=1 << 12).gr
    budget_35 = gr_b6_35.total if gr_b6_35 else 30.0
    dr_gain_35db = 6 - max(0, max_affordable_dr_bits(3, budget_35, "conv"))
    dr_gain_47db_100fj = (max_affordable_dr_bits(5, 100.0, "gr")
                          - max(0, max_affordable_dr_bits(5, 100.0, "conv")))

    # --- design points (pie charts) ---
    fp4 = S.evaluate_point(key, F.FP4_E2M1, n_cols=1 << 13)
    fp6 = S.evaluate_point(key, F.FP6_E3M2, n_cols=1 << 13)
    fp4_improvement = (fp4.conv.total - fp4.gr.total) / fp4.conv.total

    # --- FP8*_E4M3: needs global normalization for either architecture ---
    # (e_max=15 exceeds the 6-octave gain-ranging span). The GR array
    # processes the post-normalization FP(3,3) segment natively; the
    # wrapper cost is the paper-external overhead model.
    from repro.core.energy import global_norm_energy_per_op_fj
    seg = S.evaluate_point(key, F.FPFormat(3, 3), n_cols=1 << 12)
    gnorm = global_norm_energy_per_op_fj(
        width_bits=F.FP8_E4M3.n_man + 1 + 6, shift_range=2 ** 4,
        n_r=32, n_c=32)
    fp8_star = {"segment_gr_fj": seg.gr.total if seg.gr else None,
                "global_norm_overhead_fj": gnorm,
                "total_fj": (seg.gr.total + gnorm) if seg.gr else None}
    emit("fig12/FP8*_E4M3_globalnorm", 0.0,
         f"gr+wrapper={fp8_star['total_fj']:.1f}")

    # --- C7: ADC parameter sensitivity (±10 % on k1, k2) ---
    sens = {}
    for tag, f in [("nominal", 1.0), ("+10%", 1.1), ("-10%", 0.9)]:
        p = E.TechParams(k1_ff=100.0 * f, k2_ff=1e-3 * f)
        pt = S.evaluate_point(key, F.FP4_E2M1, p=p, n_cols=1 << 13)
        sens[tag] = (pt.conv.total - pt.gr.total) / pt.conv.total
        emit(f"fig12/sens{tag}", 0.0, f"improvement={sens[tag]*100:.1f}%")

    out = {
        "grid": grid,
        "fp4": {"conv_fj": fp4.conv.total, "gr_fj": fp4.gr.total,
                "improvement": fp4_improvement, "gr_arch": fp4.gr_arch},
        "fp6_e3m2": {"gr_fj": fp6.gr.total, "conv_fj": fp6.conv.total,
                     "conv_out_of_range": fp6.conv.total > ENERGY_LIMIT_FJ,
                     "gr_native": fp6.gr.total < ENERGY_LIMIT_FJ},
        "fp8_star": fp8_star,
        "dr_gain_bits_at_35db_iso_energy": dr_gain_35db,
        "iso_energy_budget_35db_fj": budget_35,
        "dr_gain_bits_at_47db_100fj": dr_gain_47db_100fj,
        "sensitivity": sens,
    }
    save_json("fig12", out)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
