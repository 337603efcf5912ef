"""GoodPut fault-drill benchmark: supervised failure injection, per arch.

Runs the ``training.supervisor`` drill harness end-to-end on 2+ archs:
training under a seeded ``FaultPlan`` (one process kill, one simulated
device loss, one injected straggler by default), with async two-tier
checkpointing, heartbeat-driven detection, freshest-tier restore, and
elastic resume at a smaller data-parallel width after device loss.
Reports, per arch:

* the drill counters — faults injected / detected (by kind),
  checkpoints and restores per tier, steps recomputed, remesh events,
  logical DP width before/after, attempts, final step;
* the GoodPut partition — wall seconds per bucket (productive /
  recompute / checkpoint_stall / detection / recovery / overhead) and
  ``goodput_pct``, next to an uninterrupted baseline run's;
* the energy story — pJ/token from the arch's CIM train trace, inflated
  by recompute into ``pj_per_useful_token`` (BadPut priced through the
  CostLedger);
* ``trajectory_bit_identical`` — whether the drilled run's loss at every
  step matched the uninterrupted baseline's bit-for-bit (the
  (seed, step)-pure pipeline + exact checkpoint roundtrip make this a
  provable invariant, and the supervisor additionally asserts it inline
  on every recomputed step).

Determinism contract (the CI gate): faults fire at scheduled steps of a
deterministic loop, the fleet heartbeats on a virtual clock, and the
async writer is drained at each fault boundary — so every counter above
is a pure function of (arch, plan, config) and is compared with EXACT
equality by benchmarks/compare.py. ``goodput_pct`` is wall-clock-derived
and gets the usual ratio gate.

Run:  PYTHONPATH=src python -m benchmarks.goodput_bench [--smoke]
"""
import argparse
import tempfile

from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.training.fault import FaultPlan, make_fault_plan
from repro.training.supervisor import DrillConfig, Supervisor, price_drill
from repro.training.trainer import TrainConfig
from benchmarks.common import emit, save_json
from repro.compile_cache import enable_compile_cache

# the same two cache-state extremes the traffic bench sweeps: attention
# (KV growth) and SSM (fixed recurrent state)
ARCHS = [
    ("attn", "qwen2-1.5b"),
    ("ssm", "mamba2-1.3b"),
]

SMOKE_PARAMS = dict(steps=8, batch=2, seq_len=16, local_every=2,
                    durable_every=4, n_faults=3,
                    record="goodput_bench_smoke")


def bench_arch(name, *, steps, batch, seq_len, local_every, durable_every,
               n_faults, seed=0):
    arch = get_config(name).reduced().replace(n_layers=2)
    pipe = SyntheticLM(DataConfig(global_batch=batch, seq_len=seq_len,
                                  vocab_size=arch.vocab_size, seed=seed))
    tcfg = TrainConfig(steps=steps)
    plan = make_fault_plan(seed, steps, n_faults=n_faults)

    def drill(fault_plan):
        with tempfile.TemporaryDirectory() as wd:
            dcfg = DrillConfig(workdir=wd, steps=steps,
                               local_every=local_every,
                               durable_every=durable_every,
                               n_hosts=4, n_chips=8)
            return Supervisor(arch, tcfg, dcfg, pipe, fault_plan,
                              seed=seed).run_drill()

    rep = drill(plan)
    base = drill(FaultPlan(()))

    res = {
        "plan": {f"e{i}": {"step": e.step, "severity": e.severity,
                           "kind_" + e.kind: 1}
                 for i, e in enumerate(plan.events)},
        "drill": {k: v for k, v in rep.items()
                  if k not in ("losses", "goodput")},
        "goodput": rep["goodput"],
        "baseline": {"goodput_pct": base["goodput"]["goodput_pct"],
                     "wall_s": base["goodput"]["wall_s"]},
        "trajectory_bit_identical": rep["losses"] == base["losses"],
        "energy": price_drill(arch, rep, tokens_per_step=batch * seq_len,
                              seed=seed),
    }
    emit(f"goodput/{name}/drill", rep["goodput"]["wall_s"] * 1e6,
         f"goodput={rep['goodput']['goodput_pct']:.1f}%"
         f";detected={rep['faults_detected']}/{rep['faults_injected']}")
    return res


def run(steps=16, batch=4, seq_len=32, local_every=2, durable_every=6,
        n_faults=3, archs=None, record="goodput_bench", seed=0):
    out = {
        "params": {"steps": steps, "batch": batch, "seq_len": seq_len,
                   "local_every": local_every,
                   "durable_every": durable_every, "n_faults": n_faults,
                   "seed": seed},
        "archs": {},
    }
    for label, name in (archs or ARCHS):
        out["archs"][label] = {
            "config": name,
            **bench_arch(name, steps=steps, batch=batch, seq_len=seq_len,
                         local_every=local_every,
                         durable_every=durable_every, n_faults=n_faults,
                         seed=seed)}

    print(f"\n{'arch':<6} {'detected':>9} {'recomp':>7} {'attempts':>9} "
          f"{'goodput%':>9} {'base%':>7} {'bit-id':>7} "
          f"{'pJ/tok':>10} {'pJ/useful':>10}")
    for label, a in out["archs"].items():
        d, g, e = a["drill"], a["goodput"], a["energy"]
        print(f"{label:<6} {d['faults_detected']:>4}/{d['faults_injected']:<4} "
              f"{d['steps_recomputed']:>7} {d['attempts']:>9} "
              f"{g['goodput_pct']:>9.1f} {a['baseline']['goodput_pct']:>7.1f} "
              f"{str(a['trajectory_bit_identical']):>7} "
              f"{e['pj_per_token']:>10.1f} {e['pj_per_useful_token']:>10.1f}")
        print(f"{label:<6} dp {d['dp_width_initial']}->{d['dp_width_final']}; "
              f"ckpt local/durable {d['ckpt_local']}/{d['ckpt_durable']}; "
              f"restores local/durable "
              f"{d['restore_local']}/{d['restore_durable']}")
    save_json(record, out)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for the CI bench lane")
    args = ap.parse_args()
    if args.smoke:
        # separate record: a smoke run must not clobber the committed
        # full-size goodput_bench.json
        run(**SMOKE_PARAMS)
    else:
        run(steps=args.steps, batch=args.batch, n_faults=args.faults)
