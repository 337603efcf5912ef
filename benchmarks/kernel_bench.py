"""GR-MAC backend benchmark: wall time and oracle agreement per backend.

Sweeps the dispatchable backends (``--backend all`` or a comma list) over
the three granularities and emits a comparison table, so the fast XLA
path's speedup over interpret-mode Pallas is *measured*, not asserted.

Two times per cell:

* ``cold``  — first call on a fresh executable: trace + compile + run.
  This is the cost that made interpret-mode Pallas unusable off-TPU
  (the interpreter traces the kernel body per grid step; every new
  shape/config pays it again).
* ``warm``  — steady-state per-call time after compilation.

Two shapes by default, both recorded to experiments/bench/kernel_bench.json:

* ``edge_decode`` (16×768×3072, paper-cim-120m FFN) — the paper's
  deployment regime: small-M matmuls are where the CIM path actually runs
  per decoded token, and where the Pallas path's mandatory 128-alignment
  padding wastes the most work.
* ``train_large_m`` (2048×768×3072) — the training-shape regime the
  ROADMAP flagged: the blocked einsum is bandwidth-bound there, and the
  fused ``tiled`` backend is the fix (the headline
  ``tiled_warm_speedup_over_{ref,xla}`` rows record its win).
  ``pallas_interpret`` is excluded here (the interpreter would take hours
  at this size, and the debug cross-check adds nothing at scale).

Select named shapes with ``--shapes train_large_m`` (comma list), or
override with --m/--k/--n for a single custom shape; --smoke runs one tiny
shape with minimal iterations (the CI bench lane). Named-subset and custom
runs write ``kernel_bench_partial``/``kernel_bench_custom`` records so
they can never clobber the committed full-sweep numbers.

On TPU the figure of merit for the ``pallas`` backend is the lowered
structure; off-TPU ``pallas`` is skipped (it would silently interpret)
and ``pallas_interpret`` carries the debug cross-check.
"""
import argparse
import time

import jax
import numpy as np

from repro.core.formats import FP4_E2M1, FP6_E3M2, quantize
from repro.kernels.dispatch import grmac_matmul
from benchmarks.common import emit, save_json, time_call
from repro.compile_cache import enable_compile_cache

_DEFAULT_BACKENDS = ("xla", "tiled", "ref", "pallas_interpret")
_GRANS = ["conv", "row", "unit"]
_SHAPES = {
    "edge_decode": (16, 768, 3072),
    "train_large_m": (2048, 768, 3072),
}
_SMOKE_SHAPE = (8, 96, 64)
# backends too slow to run at a given shape (documented above)
_SHAPE_SKIP = {"train_large_m": {"pallas_interpret"}}


def run_shape(backends, m, k, n, n_iter=5):
    key = jax.random.PRNGKey(0)
    kx, kw = jax.random.split(key)
    x = jax.random.uniform(kx, (m, k), minval=-1, maxval=1)
    w = quantize(jax.random.uniform(kw, (k, n), minval=-1, maxval=1), FP4_E2M1)
    out = {"shape": [m, k, n], "backends": {}}
    results = {}
    for gran in _GRANS:
        kwargs = dict(fmt_x=FP6_E3M2, fmt_w=FP4_E2M1, n_r=32, enob=8.0,
                      granularity=gran)
        for b in backends:
            # jit the full dispatch for every backend so cells are
            # apples-to-apples (the ref oracle is not internally jitted)
            fn = jax.jit(
                lambda a, bb, _b=b: grmac_matmul(a, bb, backend=_b, **kwargs))
            t0 = time.perf_counter()
            got = jax.block_until_ready(fn(x, w))
            cold_us = (time.perf_counter() - t0) * 1e6
            interp = b == "pallas_interpret"
            warm_us = time_call(fn, x, w,
                                n_iter=min(3, n_iter) if interp else n_iter,
                                warmup=0)
            results[(b, gran)] = np.asarray(got)
            out["backends"].setdefault(b, {})[gran] = {
                "cold_us": cold_us, "warm_us": warm_us}
            emit(f"kernel/{m}x{k}x{n}/{b}/{gran}", warm_us,
                 f"cold_us={cold_us:.0f}")
        # oracle agreement (ref is always exact-by-construction)
        oracle = results.get(("ref", gran))
        if oracle is not None:
            for b in backends:
                ok = bool(np.allclose(results[(b, gran)], oracle, atol=1e-5))
                out["backends"][b][gran]["allclose"] = ok

    # comparison table + headline speedups
    hdr = " ".join(f"{g + ' cold/warm(us)':>24}" for g in _GRANS)
    print(f"\nshape {m}x{k}x{n}\n{'backend':<18} {hdr}")
    for b in backends:
        per = out["backends"][b]
        print(f"{b:<18} " + " ".join(
            f"{per[g]['cold_us']:>13.0f}/{per[g]['warm_us']:>10.1f}"
            for g in _GRANS))
    if "xla" in out["backends"] and "pallas_interpret" in out["backends"]:
        pi, xl = out["backends"]["pallas_interpret"], out["backends"]["xla"]
        out["xla_cold_speedup_over_interpret"] = {
            g: pi[g]["cold_us"] / xl[g]["cold_us"] for g in _GRANS}
        out["xla_warm_speedup_over_interpret"] = {
            g: pi[g]["warm_us"] / xl[g]["warm_us"] for g in _GRANS}
        print("xla speedup over pallas_interpret (cold trace+compile+run): "
              + ", ".join(f"{g}={v:.0f}x" for g, v in
                          out["xla_cold_speedup_over_interpret"].items()))
        print("xla speedup over pallas_interpret (warm steady-state):      "
              + ", ".join(f"{g}={v:.1f}x" for g, v in
                          out["xla_warm_speedup_over_interpret"].items()))
        warm = list(out["xla_warm_speedup_over_interpret"].values())
        gm = float(np.exp(np.mean(np.log(warm))))
        out["xla_warm_speedup_geomean"] = gm
        print(f"geomean warm speedup: {gm:.1f}x")
    if "xla" in out["backends"] and "ref" in out["backends"]:
        rf, xl = out["backends"]["ref"], out["backends"]["xla"]
        out["xla_warm_speedup_over_ref"] = {
            g: rf[g]["warm_us"] / xl[g]["warm_us"] for g in _GRANS}
    if "tiled" in out["backends"]:
        td = out["backends"]["tiled"]
        for base in ("ref", "xla"):
            if base not in out["backends"]:
                continue
            bs = out["backends"][base]
            sp = {g: bs[g]["warm_us"] / td[g]["warm_us"] for g in _GRANS}
            out[f"tiled_warm_speedup_over_{base}"] = sp
            print(f"tiled speedup over {base} (warm): "
                  + ", ".join(f"{g}={v:.2f}x" for g, v in sp.items()))
    return out


def run(backends=None, shapes=None, smoke=False, n_iter=5, record=None):
    """``record`` names the JSON written under experiments/bench/. Only the
    full default sweep (all default backends, all named shapes) writes the
    committed ``kernel_bench`` record — smoke/custom/partial runs get their
    own file so a quick local run can never clobber the measured numbers
    the ROADMAP cites. ``shapes`` may be a {label: (m, k, n)} dict or a
    list of names from ``_SHAPES``."""
    explicit_backends = bool(backends) and backends != ["all"]
    if not explicit_backends:
        backends = list(_DEFAULT_BACKENDS)
        if jax.default_backend() == "tpu":
            backends.insert(0, "pallas")
    if isinstance(shapes, (list, tuple)):
        unknown = [s for s in shapes if s not in _SHAPES]
        if unknown:
            raise SystemExit(
                f"unknown shape names {unknown}; choose from "
                f"{sorted(_SHAPES)}")
        shapes = {s: _SHAPES[s] for s in shapes}
    default_sweep = (shapes is None or shapes == _SHAPES) \
        and not smoke and not explicit_backends
    # only a *plain* --smoke run (default backends, no shape selection) may
    # write the committed kernel_bench_smoke record the CI compare gate
    # diffs against; any named/custom/partial combination gets _partial
    plain_smoke = smoke and shapes is None and not explicit_backends
    if shapes is None:
        shapes = {"smoke": _SMOKE_SHAPE} if smoke else dict(_SHAPES)
    if smoke:
        n_iter = 2
    out = {"shapes": {}}
    for label, (m, k, n) in shapes.items():
        bl = [b for b in backends if b not in _SHAPE_SKIP.get(label, ())]
        out["shapes"][label] = run_shape(bl, m, k, n, n_iter=n_iter)
    if record is None:
        record = ("kernel_bench" if default_sweep
                  else "kernel_bench_smoke" if plain_smoke
                  else "kernel_bench_partial")
    save_json(record, out)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="all",
                    help="'all' or comma list of dispatch backends "
                         "(xla,tiled,ref,pallas,pallas_interpret)")
    ap.add_argument("--shapes", default="",
                    help="comma list of named shapes "
                         f"({','.join(_SHAPES)}); empty -> default sweep")
    ap.add_argument("--m", type=int, default=0,
                    help="custom shape (with --k/--n); 0 -> default sweep")
    ap.add_argument("--k", type=int, default=768)
    ap.add_argument("--n", type=int, default=3072)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shape + minimal iterations (CI bench lane)")
    args = ap.parse_args()
    if args.m:
        shapes = {"custom": (args.m, args.k, args.n)}
    elif args.shapes:
        shapes = [s.strip() for s in args.shapes.split(",") if s.strip()]
    else:
        shapes = None
    run([b.strip() for b in args.backend.split(",")],
        shapes=shapes, smoke=args.smoke)
