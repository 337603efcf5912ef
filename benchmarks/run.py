"""Benchmark harness — one module per paper table/figure, plus the perf
suites (``kernel_bench``, ``serve_bench``) and the bench-regression gate
(``compare``, which diffs fresh --smoke runs against the committed
experiments/bench/*_smoke.json records).

Prints ``name,us_per_call,derived`` CSV rows and writes JSON payloads under
experiments/bench/ for EXPERIMENTS.md. Exit code is nonzero if any paper
claim check or bench-regression check fails.
"""
from __future__ import annotations

import sys

from repro.compile_cache import enable_compile_cache


def main() -> None:
    from benchmarks import (
        compare,
        e2e_energy,
        fig8_linearity,
        fig9_quant_noise,
        fig10_enob_vs_dr,
        fig11_enob_vs_precision,
        fig12_energy_dse,
        kernel_bench,
        mac_validation,
    )

    print("name,us_per_call,derived")
    failures = []

    r9 = fig9_quant_noise.run()
    for k, v in r9["observations"].items():
        if not v:
            failures.append(f"fig9:{k}")

    r10 = fig10_enob_vs_dr.run()
    c = r10["claims"]
    if c["C2_upper_bound_1p5b"] < 1.3:
        failures.append("fig10:C2")
    if c["C3_outlier_delta_NE3"] < 6.0:
        failures.append("fig10:C3")
    if c["C8_max_gr_enob"] > c["n_cross"]:
        failures.append("fig10:C8")

    r11 = fig11_enob_vs_precision.run()
    if not (0.7 < r11["slope_bits_per_mantissa_bit"] < 1.3):
        failures.append("fig11:linear-scaling")

    r12 = fig12_energy_dse.run()
    if not r12["fp6_e3m2"]["gr_native"]:
        failures.append("fig12:C6-fp6-native")
    if not r12["fp6_e3m2"]["conv_out_of_range"]:
        failures.append("fig12:C6-conv-range")
    if not (0.10 < r12["fp4"]["improvement"] < 0.60):
        failures.append("fig12:C6-fp4")
    if r12["dr_gain_bits_at_35db_iso_energy"] < 2:
        failures.append("fig12:C5-dr-gain")

    r8 = fig8_linearity.run()
    if r8["nominal_worst_inl_lsb"] > 1e-3:
        failures.append("fig8:nominal-linearity")
    if r8["mismatch_kc0.85_worst_dnl_lsb"] > 0.5:
        failures.append("fig8:mismatch-halflsb")

    mac_validation.run()
    # edge shape only: the claims harness stays fast (train_large_m takes
    # minutes) and must not overwrite the committed kernel_bench record
    kernel_bench.run(shapes={"edge_decode": kernel_bench._SHAPES["edge_decode"]},
                     record="kernel_bench_claims")
    e2e_energy.run()
    e2e_energy.run_pareto()   # per-site fronts (launch/summary --energy)

    # bench-regression gate: fresh --smoke runs vs the committed records
    # (see benchmarks/compare.py; CI runs the same check per push). The
    # threshold is machine-tolerant, like the CI lane's: the committed
    # baselines come from one reference machine, and a uniformly slower
    # box is not a regression — only order-of-magnitude rot should fail
    # the harness.
    failures += [f"bench-regression:{r}"
                 for r in compare.run(threshold=3.0, min_us=500.0)]

    if failures:
        print(f"\n[benchmarks] CLAIM CHECK FAILURES: {failures}",
              file=sys.stderr)
        raise SystemExit(1)
    print("\n[benchmarks] all paper-claim checks passed")


if __name__ == "__main__":
    enable_compile_cache()
    main()
