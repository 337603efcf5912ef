"""End-to-end deployment metric: ledger-derived per-token CIM energy for
every registered architecture, per phase (prefill / decode / train).

The per-arch reports come from ``serving.engine.energy_report``, i.e. from
``core.costs`` shape-only traces of the real model functions — no analytic
MAC census. Each arch record carries, per phase, the op counts and the
pJ/token under the arch's (per-site) CIM design next to the conventional
CIM pricing of the same ops — the paper's bottom-line deployment win.

``--pareto`` runs the per-site (format × n_r × granularity) design-space
explorer instead (``core.dse.explore_pareto``): per arch × phase it traces
the ledger, sweeps every site's candidate grid against the paper's 35 dB
accuracy standard, and records the per-site Pareto fronts, the chosen
``site_overrides`` deployment, and the deployment-level energy/accuracy
front (rendered by ``launch/summary.py --energy``).

``--smoke`` writes the separate ``*_smoke.json`` record with a reduced
Monte-Carlo sample count (and, for ``--pareto``, a reduced arch set); the
committed copies are compared by ``benchmarks/compare.py`` with **exact
equality on the op-count and frontier-membership leaves** — any drift
between the models and the energy accounting (or any silent reshuffle of
a committed Pareto front) fails the build. Timing gates don't apply here:
op counts and seeded-Monte-Carlo frontiers are deterministic.

Run:  PYTHONPATH=src python -m benchmarks.e2e_energy [--smoke] [--pareto]
"""
import argparse

from repro.configs import get_config, list_configs
from repro.core import costs, dse
from repro.serving.engine import energy_report
from benchmarks.common import emit, save_json
from repro.compile_cache import enable_compile_cache

# the one smoke configuration: shared by the --smoke CLI (which refreshes
# the committed e2e_energy_smoke.json) and benchmarks/compare.py's fresh
# run, so the op-count gate always compares like-for-like configs
SMOKE_PARAMS = dict(n_cols=1 << 8, prefill_bucket=64,
                    record="e2e_energy_smoke")

# Pareto smoke: one reduced arch per block family (attention, MoE, SSM,
# RG-LRU) at the FULL candidate grid — the memoized solver is what keeps
# this inside the CI bench-smoke budget, and the gate proves it stays so.
PARETO_SMOKE_PARAMS = dict(
    archs=("paper-cim-120m", "grok-1-314b", "mamba2-1.3b",
           "recurrentgemma-9b"),
    n_cols=1 << 8, prefill_bucket=64, record="e2e_pareto_smoke")


def run(archs=None, n_cols=1 << 11, prefill_bucket=128,
        record="e2e_energy"):
    out = {}
    for name in archs or list_configs():
        cfg = get_config(name)
        if not cfg.cim.enabled:
            cfg = cfg.replace(cim=cfg.cim.with_mode("grmac"))
        rep = energy_report(cfg, n_cols=n_cols,
                            prefill_bucket=prefill_bucket)
        out[name] = {
            "pj_per_token": rep["pj_per_token"],
            "fj_per_op": rep["fj_per_op"],
            "conventional_fj_per_op": rep["conventional_fj_per_op"],
            "phases": {
                phase: {
                    # integer op counts: the drift gate (exact compare)
                    "ops_per_token": ph["ops_per_token"],
                    "analog_ops_per_token": ph["analog_ops_per_token"],
                    "pj_per_token": ph["pj_per_token"],
                    "conventional_pj_per_token":
                        ph["conventional_pj_per_token"],
                }
                for phase, ph in rep["phases"].items()
            },
        }
        emit(f"e2e/{name}", 0.0,
             f"pj_per_token={rep['pj_per_token']:.1f}"
             f";fj_per_op={rep['fj_per_op']:.1f}")
    save_json(record, out)
    return out


def _phase_ledgers(arch, prefill_bucket: int):
    """(ledger, tokens) per phase, mirroring ``core.costs.phase_report``'s
    trace shapes and per-token normalization."""
    train_seq = costs.default_train_seq(arch)
    return {
        "decode": (costs.trace_decode(arch), 1),
        "prefill": (costs.trace_prefill(arch, bucket=prefill_bucket),
                    prefill_bucket),
        "train": (costs.trace_train(arch, seq_len=train_seq), train_seq),
    }


def _cand_key(c: dict) -> str:
    return f"{c['fmt_x']}/n{c['n_r']}/{c['granularity']}"


def _pareto_phase_record(res: dict, tokens: int) -> dict:
    """JSON-able arch×phase cell. ``on_front`` / ``front_size`` /
    ``ops_per_token`` are the exact-compare leaves benchmarks/compare.py
    gates (frontier membership is deterministic given the seeded
    Monte-Carlo, like the trace op counts)."""
    sites = {}
    for site, info in res["sites"].items():
        if "front" not in info:     # digital site: ops only
            sites[site] = {"ops_per_token": info["ops"] / tokens,
                           "mode": "off"}
            continue
        chosen = info["chosen"]
        sites[site] = {
            "ops_per_token": info["ops"] / tokens,
            "budget_sqnr_db": info["budget_sqnr_db"],
            "base": dict(info["base"]),
            "front_size": len(info["front"]),
            "front": {
                _cand_key(c): {
                    "fj_per_op": c["fj_per_op"], "sqnr_db": c["sqnr_db"],
                    "enob": c["enob"], "on_front": 1,
                }
                for c in info["front"]
            },
            "chosen": chosen if isinstance(chosen, str)
            else _cand_key(chosen),
            "chosen_fj_per_op": None if isinstance(chosen, str)
            else chosen["fj_per_op"],
        }
    return {
        "tokens": tokens,
        "pj_per_token": res["pj"] / tokens,
        "base_pj_per_token": res["base_pj"] / tokens,
        "front_size": len(res["front"]),
        "front": {
            f"{p['sqnr_db']:.2f}dB": {
                "pj_per_token": p["pj"] / tokens, "on_front": 1,
                "choices": dict(p["choices"]),
            }
            for p in res["front"]
        },
        "site_overrides": {
            site: ov if isinstance(ov, str) else ov.as_dict()
            for site, ov in res["site_overrides"].items()
        },
        "sites": sites,
    }


def run_pareto(archs=None, n_cols=1 << 11, prefill_bucket=128,
               budget_sqnr_db=dse.PAPER_SQNR_STANDARD_DB,
               record="e2e_pareto"):
    """Per-site Pareto DSE record: arch × phase fronts + chosen designs."""
    budget = dse.SiteBudget(min_sqnr_db=budget_sqnr_db)
    out = {}
    for name in archs or list_configs():
        cfg = get_config(name)
        if not cfg.cim.enabled:
            cfg = cfg.replace(cim=cfg.cim.with_mode("grmac"))
        phases = {}
        for phase, (ledger, tokens) in \
                _phase_ledgers(cfg, prefill_bucket).items():
            res = dse.explore_pareto(cfg.cim, ledger, budget=budget,
                                     n_cols=n_cols)
            phases[phase] = _pareto_phase_record(res, tokens)
            emit(f"pareto/{name}/{phase}", 0.0,
                 f"pj_per_token={phases[phase]['pj_per_token']:.1f}"
                 f";front_size={phases[phase]['front_size']}")
        out[name] = {"budget_sqnr_db": budget.floor_db(), "phases": phases}
    save_json(record, out)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny Monte-Carlo + separate record for the CI "
                         "op-count / frontier drift gate")
    ap.add_argument("--pareto", action="store_true",
                    help="run the per-site (format x n_r x granularity) "
                         "Pareto DSE instead of the energy report")
    args = ap.parse_args()
    if args.pareto:
        run_pareto(**PARETO_SMOKE_PARAMS) if args.smoke else run_pareto()
    elif args.smoke:
        run(**SMOKE_PARAMS)
    else:
        run()
