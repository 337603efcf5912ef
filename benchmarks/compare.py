"""Bench-regression checker: fresh --smoke runs vs the committed records.

Runs the smoke configuration of the bench scripts (kernel_bench,
serve_bench, e2e_energy), then walks the committed ``experiments/bench/*_smoke.json``
records and compares every timing leaf against the fresh run at the same
path:

* ``warm_us`` / ``ttft_ms``  — time-like: fresh / committed > threshold
  (default 1.5x) is a regression;
* ``decode_tok_s``           — throughput-like: committed / fresh >
  threshold is a regression;
* ``ops_per_token`` / ``analog_ops_per_token`` (the e2e_energy op-count
  leaves) — **exact**: these are deterministic ledger traces of the model
  structure, so ANY drift from the committed record is a regression (the
  models changed without the committed energy record being refreshed, or
  the cost accounting broke);
* ``on_front`` / ``front_size`` (the e2e_pareto frontier-membership
  leaves) — **exact**, same contract: Pareto fronts are derived from
  seeded Monte-Carlo solves and trace op counts, so a committed front
  reshuffling silently means the design-space explorer or the energy
  model changed without the record being refreshed. A vanished front
  candidate shows up as a missing ``on_front`` leaf.
* the traffic_bench scheduling leaves (``completed`` /
  ``completed_in_slo`` / ``decode_steps`` / ``prefill_dispatches`` /
  ``queue_depth_max`` / ``goodput_tokens`` / ``knee_rate_frac`` /
  ``beats_static_above_capacity`` ...) — **exact**: the open-loop sweep
  schedules on a virtual dispatch-cost clock over seeded traffic, so
  every scheduling decision is machine-independent; drift means the
  scheduler's policy changed without the record being refreshed. Its
  wall-clock latency percentiles (``ttft_p50_ms`` / ``ttft_p99_ms`` /
  ``tpot_p50_ms`` / ``tpot_p99_ms`` / ``goodput_tok_s``) get the usual
  ratio + noise-floor gates.
* the prefix_bench leaves (shared-prefix traffic, cache-on vs
  cache-off) — cache counters (``prefix_hits`` / ``prefix_misses`` /
  ``prefix_inserts`` / ``prefix_evictions`` / ``prefix_bytes``), token
  savings (``prefill_tokens_dispatched`` / ``prefill_tokens_saved`` /
  ``recompute_tokens_saved``), admission reorders, and the derived win
  booleans (``outputs_identical`` / ``cache_wins_ttft`` /
  ``cache_wins_dispatches`` / ``prefill_pj_reduced``) — **exact**: all
  pure functions of the seeded traffic under the virtual clock, and the
  booleans are the prefix-cache tentpole's acceptance criteria. Wall
  latency percentiles get the ratio gate as in traffic_bench.
* the goodput_bench drill counters (``faults_injected`` /
  ``faults_detected`` / ``ckpt_local`` / ``ckpt_durable`` /
  ``steps_recomputed`` / ``restore_local`` / ``restore_durable`` /
  ``final_step`` / ``dp_width_final`` /
  ``trajectory_bit_identical`` ...) — **exact**: faults fire at
  scheduled steps of a deterministic loop, fleet detection runs on a
  virtual clock, and the async checkpoint writer drains at each fault
  boundary, so every counter is a pure function of (arch, plan,
  config); drift means the resilience policy changed without the record
  being refreshed. The drill's ``goodput_pct`` is wall-clock-derived
  and gets the ratio gate.
* the spec_bench leaves — self-draft speculative decode's
  ``accepted_tokens_per_step`` / ``spec_steps`` / ``spec_tokens`` /
  draft/verify/repair dispatch counts, the greedy bit-exactness
  boolean ``outputs_identical``, and the analytic pJ/accepted-token
  ``energy_win`` verdict — **exact**: self-draft greedy acceptance is
  structurally total and the energy account prices deterministic
  counters through seeded-MC ENOB pricing, so any drift means the
  draft/verify/accept policy (or the energy model) changed without the
  record being refreshed. The sequential and speculative ``ttlt_ms``
  wall times get the usual ratio + noise-floor gate.
* the ``--bench audit`` leaves (``experiments/audit/audit_report.json``,
  see ``src/repro/analysis``) — **exact**: jaxpr MAC counts, ledger
  cross-check totals, and engine compile/transfer counters are structural
  facts about the traced programs, so any drift from the committed golden
  means ledger coverage or a hot-path invariant changed without the
  golden being regenerated.

Cells faster than ``--min-us`` (default 300 us) in the committed record
are skipped: at smoke sizes those measure pure dispatch overhead and are
machine-noise, not kernel behavior. Cold times are ignored for the same
reason (compile time varies wildly across runners), and so are
``pallas_interpret`` cells — the debug interpreter's wall time is
Python-loop overhead with multi-x run-to-run variance, not a hot path
this gate protects. A first-pass regression is re-measured once and only
fails if it reproduces (per-cell best of both runs).

Exit code is nonzero on any regression, so the CI bench-smoke lane fails
when the hot paths the committed numbers document rot. Refresh the
committed smoke records (run the bench scripts with ``--smoke`` on the
reference machine and commit the JSONs) when a *deliberate* perf change
moves them.

Run:  PYTHONPATH=src python -m benchmarks.compare [--threshold 1.5]
          [--min-us 300] [--bench kernel,serve,energy,pareto] [--no-run]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import RESULTS_DIR
from repro.compile_cache import enable_compile_cache

# timing leaves: key -> True when larger-is-better (throughput)
_TIME_KEYS = {"warm_us": False, "ttft_ms": False, "decode_tok_s": True,
              # spec_bench: wall time to the last token, seq vs spec
              "ttlt_ms": False,
              # traffic_bench wall-clock latency percentiles + goodput
              "ttft_p50_ms": False, "ttft_p99_ms": False,
              "tpot_p50_ms": False, "tpot_p99_ms": False,
              "goodput_tok_s": True,
              # goodput_bench: the drill's productive fraction of wall
              # clock — wall-derived, so ratio-gated, not exact
              "goodput_pct": True}
# deterministic leaves compared with exact equality (op-count drift gate +
# e2e_pareto frontier-membership gate + the static-analysis audit report —
# every audit leaf is a structural count over jaxpr traces, so any drift
# means ledger coverage changed without the golden being refreshed)
_EXACT_KEYS = ("ops_per_token", "analog_ops_per_token", "on_front",
               "front_size",
               # audit report leaves (experiments/audit/audit_report.json)
               "dot_generals", "convs", "tagged_values", "tagged_gains",
               "tagged_other", "declared_digital", "transposes", "untagged",
               "ledger_mismatches", "dtype_f32", "dtype_bf16", "calls",
               "macs", "ledger", "traced", "compiles", "fetches", "steps",
               "violations", "failures",
               # traffic_bench scheduling leaves: the virtual dispatch-cost
               # clock makes admission order, chunk slicing, completion and
               # queue-depth counts pure functions of the seeded traffic —
               # any drift means the scheduler's *decisions* changed, not a
               # machine got slower
               "completed", "completed_in_slo", "rejected", "preempted",
               "sched_steps", "decode_steps", "prefill_dispatches",
               "queue_depth_max", "generated_tokens", "goodput_tokens",
               "knee_rate_frac", "beats_static_above_capacity",
               "prefill_executables",
               # prefix_bench leaves: cache counters and token savings are
               # pure functions of the seeded shared-prefix traffic, and
               # the derived win booleans (bit-identical outputs, TTFT /
               # dispatch / prefill-energy wins of cache-on over
               # cache-off) are the tentpole's acceptance criteria — any
               # drift means the reuse policy changed without the record
               # being refreshed
               "prefix_hits", "prefix_misses", "prefix_inserts",
               "prefix_evictions", "prefix_bytes",
               "prefill_tokens_dispatched", "prefill_tokens_saved",
               "recompute_tokens_saved", "admission_reorders",
               "outputs_identical", "cache_wins_ttft",
               "cache_wins_dispatches", "prefill_pj_reduced",
               # goodput_bench drill counters: faults fire at scheduled
               # steps, detection runs on a virtual fleet clock, and the
               # async writer drains at fault boundaries — every counter
               # is a pure function of (arch, plan, config), so any drift
               # means the resilience *policy* changed
               "final_step", "attempts", "faults_injected",
               "faults_detected", "fault_kill", "fault_device_loss",
               "fault_straggler", "steps_recomputed", "ckpt_local",
               "ckpt_durable", "restore_local", "restore_durable",
               "remesh_events", "dp_width_initial", "dp_width_final",
               "trajectory_bit_identical", "step", "severity",
               # spec_bench leaves: self-draft greedy acceptance is
               # structurally total, so the acceptance counters and the
               # dispatch arithmetic are pure functions of the config;
               # outputs_identical (gated above) is the tentpole's
               # bit-exactness acceptance criterion, and energy_win is
               # the deterministic analytic pJ/accepted-token verdict
               "accepted_tokens_per_step", "spec_steps", "spec_tokens",
               "draft_dispatches", "verify_dispatches",
               "repair_dispatches", "energy_win")
# committed-value scale to microseconds, for the noise floor
_TO_US = {"warm_us": 1.0, "ttft_ms": 1e3, "ttlt_ms": 1e3,
          "ttft_p50_ms": 1e3,
          "ttft_p99_ms": 1e3, "tpot_p50_ms": 1e3, "tpot_p99_ms": 1e3}

# "audit" is gated by its own CI lane (which writes the report first and
# compares with --no-run), so it is not in the default bench set.
_BENCHES = ("kernel", "serve", "energy", "pareto", "traffic", "prefix",
            "goodput", "spec")

# records that don't live under experiments/bench/
_REL_OVERRIDE = {"audit_report": "experiments/audit/audit_report.json"}


def _walk(tree, path=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _walk(v, path + (k,))
    elif isinstance(tree, (int, float)) and path and (
            path[-1] in _TIME_KEYS or path[-1] in _EXACT_KEYS):
        yield path, float(tree)


def _lookup(tree, path):
    for k in path:
        if not isinstance(tree, dict) or k not in tree:
            return None
        tree = tree[k]
    return tree if isinstance(tree, (int, float)) else None


def compare(committed: dict, fresh: dict, *, threshold: float = 1.5,
            min_us: float = 300.0, label: str = "") -> list:
    """Return a list of regression strings (empty = clean)."""
    regressions = []
    for path, want in _walk(committed):
        if "pallas_interpret" in path:
            continue  # debug interpreter: not a guarded hot path
        key = path[-1]
        got = _lookup(fresh, path)
        if key in _EXACT_KEYS:
            # deterministic structure counts: any drift is a regression,
            # including the leaf disappearing from the fresh record
            if got is None or got != want:
                regressions.append(
                    f"{label}{'/'.join(path)}: op count {want:.6g} -> "
                    f"{'missing' if got is None else f'{got:.6g}'} "
                    "(exact-match leaf; models and committed energy "
                    "record disagree)")
            continue
        us = want * _TO_US.get(key, 0.0)
        if not _TIME_KEYS[key] and us < min_us:
            continue  # dispatch-overhead noise at smoke sizes
        if got is None or got <= 0 or want <= 0:
            continue  # shape/backend set changed; absence is not slowness
        ratio = (want / got) if _TIME_KEYS[key] else (got / want)
        if ratio > threshold:
            regressions.append(
                f"{label}{'/'.join(path)}: {want:.1f} -> {got:.1f} "
                f"({ratio:.2f}x worse, threshold {threshold}x)")
    return regressions


def _committed(name: str) -> dict:
    """The committed baseline record.

    Read from git HEAD when available: a fresh smoke run overwrites the
    working-tree JSON, so reading the file would make any *second* compare
    invocation (or --no-run) diff a record against itself and pass
    vacuously. Falls back to the working-tree file outside a checkout."""
    rel = _REL_OVERRIDE.get(name, f"experiments/bench/{name}.json")
    root = os.path.abspath(os.path.join(RESULTS_DIR, "..", ".."))
    try:
        blob = subprocess.run(
            ["git", "show", f"HEAD:{rel}"], cwd=root,
            capture_output=True, check=True, text=True).stdout
        return json.loads(blob)
    except (OSError, subprocess.CalledProcessError, ValueError):
        pass
    return _on_disk(name)


def _on_disk(name: str) -> dict:
    """The working-tree record (what a just-finished smoke run wrote)."""
    if name in _REL_OVERRIDE:
        root = os.path.abspath(os.path.join(RESULTS_DIR, "..", ".."))
        path = os.path.join(root, _REL_OVERRIDE[name])
    else:
        path = os.path.join(RESULTS_DIR, f"{name}.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _merge_records(a, b, path=()):
    """Elementwise best of two bench records: min for time-like leaves,
    max for throughput-like — a regression must reproduce across runs."""
    if isinstance(a, dict) and isinstance(b, dict):
        out = dict(b)
        for k, v in a.items():
            out[k] = _merge_records(v, b[k], path + (k,)) if k in b else v
        return out
    if (isinstance(a, (int, float)) and isinstance(b, (int, float))
            and path and path[-1] in _TIME_KEYS):
        return max(a, b) if _TIME_KEYS[path[-1]] else min(a, b)
    return a


def _fresh_run(bench: str):
    if bench == "kernel":
        from benchmarks import kernel_bench
        return kernel_bench.run(smoke=True)
    if bench == "energy":
        from benchmarks import e2e_energy
        return e2e_energy.run(**e2e_energy.SMOKE_PARAMS)
    if bench == "pareto":
        from benchmarks import e2e_energy
        return e2e_energy.run_pareto(**e2e_energy.PARETO_SMOKE_PARAMS)
    if bench == "audit":
        from repro.analysis.cli import build_report
        from repro.configs import list_configs
        return build_report(list(list_configs()), verbose=False)
    if bench == "traffic":
        from benchmarks import traffic_bench
        return traffic_bench.run(**traffic_bench.SMOKE_PARAMS)
    if bench == "prefix":
        from benchmarks import traffic_bench
        return traffic_bench.run_shared_prefix(
            **traffic_bench.SHARED_SMOKE_PARAMS)
    if bench == "goodput":
        from benchmarks import goodput_bench
        return goodput_bench.run(**goodput_bench.SMOKE_PARAMS)
    if bench == "spec":
        from benchmarks import spec_bench
        return spec_bench.run(**spec_bench.SMOKE_PARAMS)
    from benchmarks import serve_bench
    return serve_bench.run(**serve_bench.SMOKE_PARAMS)


def run(benches=_BENCHES, threshold=1.5, min_us=300.0, fresh=True) -> list:
    """Returns the regression list (empty = clean). The committed record is
    snapshotted into memory *before* the fresh smoke run overwrites the
    on-disk JSON. A first-run regression is re-measured once and the
    per-cell best of both runs is compared — transient scheduler noise on
    shared runners must not fail the gate, a real slowdown reproduces.
    ``fresh=False`` compares the on-disk records against the git-HEAD
    baseline without running anything (for use after separate smoke
    steps)."""
    regressions = []
    names = {"kernel": "kernel_bench_smoke", "serve": "serve_bench_smoke",
             "energy": "e2e_energy_smoke", "pareto": "e2e_pareto_smoke",
             "traffic": "traffic_bench_smoke",
             "prefix": "prefix_bench_smoke",
             "goodput": "goodput_bench_smoke",
             "spec": "spec_bench_smoke", "audit": "audit_report"}
    for bench in benches:
        name = names[bench]
        committed = _committed(name)
        new = _fresh_run(bench) if fresh else _on_disk(name)
        found = compare(committed, new, threshold=threshold, min_us=min_us,
                        label=f"{bench}:")
        if found and fresh:
            print(f"[compare] {bench}: {len(found)} candidate regression(s); "
                  "re-measuring to confirm")
            new = _merge_records(new, _fresh_run(bench))
            found = compare(committed, new, threshold=threshold,
                            min_us=min_us, label=f"{bench}:")
        regressions += found
    return regressions


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--threshold", type=float, default=1.5,
                    help="warm-time ratio above which a cell is a regression")
    ap.add_argument("--min-us", type=float, default=300.0,
                    help="skip committed cells faster than this (noise floor)")
    ap.add_argument("--bench",
                    default="kernel,serve,energy,pareto,traffic,prefix,"
                            "goodput,spec",
                    help="comma list: kernel,serve,energy,pareto,traffic,"
                         "prefix,goodput,spec,audit "
                         "(audit gates experiments/audit/audit_report.json "
                         "exactly; its CI lane runs the CLI then this with "
                         "--no-run)")
    ap.add_argument("--no-run", action="store_true",
                    help="compare records already on disk instead of "
                         "running fresh --smoke benches")
    args = ap.parse_args()
    regressions = run(
        tuple(b.strip() for b in args.bench.split(",") if b.strip()),
        threshold=args.threshold, min_us=args.min_us, fresh=not args.no_run)
    if regressions:
        print("\n[compare] BENCH REGRESSIONS:", file=sys.stderr)
        for r in regressions:
            print(f"  {r}", file=sys.stderr)
        raise SystemExit(1)
    print("[compare] no bench regressions "
          f"(threshold {args.threshold}x, floor {args.min_us:.0f}us)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
