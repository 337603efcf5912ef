"""The control of a serving cell's check, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s>

For each seed, one run of the cell's measured path at its own load, then
the window's dispatches replayed, each from the program's state before it,
through the reference in the configuration's precision and, from the same
state, through the same reference one precision step below it (bfloat16 ->
float8 e4m3), the control. For every token the window served, the gap by
which a token's reference logit lies below the reference's best is read
for three choices: the program's token, the program's token altered (+1,
the fault "a token altered where it is produced") and the control's first
choice. Per layer, it reads how far the keys and values the program wrote
lie from the reference's, and how far the control's lie from the
reference's. Prints the readings per seed, and over the seeds the
program's largest and the others' smallest: the limits in
``configs/<config>.json`` lie between them. The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    harness.strict_precision()
    from chipbench import run as bench_run
    from chipbench.reference import replay
    bench = harness.benchmark()
    cell = harness.load_cell(args.workload, bench)
    harness.import_program()
    harness.enable_compile_cache()
    try:
        device = harness.device_info(cell["entry"]["chips"])
    except harness.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0)
        ctx = bench_run.Context(ns, cell, bench)
        rows.append(control_readings(ctx))
        print(json.dumps(rows[-1]), flush=True)
        del ctx
        gc.collect()
    summary = {"device": device, "workload": args.workload}
    for stat in ("mean_gap", "max_gap"):
        summary[f"program_{stat}_largest"] = max(
            r["program"][stat] for r in rows)
        summary[f"control_{stat}_smallest"] = min(
            r["control"][stat] for r in rows)
    summary["altered_max_gap_smallest"] = min(
        r["altered"]["max_gap"] for r in rows)
    summary["program_state_error_worst_layer_largest"] = max(
        replay.worst(r["program"]["state_error"]) for r in rows)
    summary["control_state_error_worst_layer_smallest"] = min(
        replay.worst(r["control"]["state_error"]) for r in rows)
    summary["stream_faults_largest"] = max(r["stream_faults"] for r in rows)
    for name in ("program", "control", "altered"):
        summary[f"{name}_correct_runs"] = sum(r[name]["correct"]
                                              for r in rows)
    print(json.dumps(summary))
    return 0


def control_readings(ctx) -> dict:
    from chipbench import serve
    from chipbench.reference import load_model, replay
    from chipbench.reference.grmac import CONTROL, REFERENCE

    params, events = serve.window(ctx)
    b = ctx.settings["serve"]["batch_slots"]
    host = replay.host_events(events)
    w = replay.walk(host, b)
    faults = replay.stream_faults(
        w, [(tr.req.prompt, tr.req.generated) for tr in ctx.tracks])
    replay.attach_final_state(w, ctx.program_state)
    model = load_model(ctx.spec, params)
    del params
    prog = replay.as_choices(w.served, b)
    vocab = ctx.spec["arch"]["vocab_size"]
    altered = {i: (ids + 1) % vocab for i, ids in prog.items()}
    dtype = ctx.spec["arch"]["dtype"]
    t = harness.now()
    ref = replay.run(model, w, host, REFERENCE[dtype], [prog, altered], b,
                     ctx.settings["serve"]["max_ctx"],
                     others=[CONTROL[dtype]])
    out = {"seed": ctx.seed, "tokens": int(sum(len(v[0])
                                               for v in w.served.values())),
           "stream_faults": len(faults), "replay_s": harness.now() - t}
    for which, name in enumerate(("program", "altered", "control")):
        g = replay.gaps(ref, w.served, which)
        out[name] = {"mean_gap": float(g.mean()), "max_gap": float(g.max()),
                     "off_argmax": int((g > 0).sum())}
    out["program"]["state_error"] = ref.state_error
    out["control"]["state_error"] = ref.other_state_error[0]
    # each side held to the cell's own limits, as a run holds the program
    limits = ctx.spec["check"]
    for name in ("program", "control", "altered"):
        r = out[name]
        checks = [harness.Check("max_logit_gap", r["max_gap"],
                                float(limits["max_logit_gap"]))]
        if "state_error" in r:
            checks.append(harness.Check(
                "state_error_worst_layer", replay.worst(r["state_error"]),
                float(limits["state_error_worst_layer"])))
        r["correct"] = all(c.ok for c in checks)
    out["window"] = ctx.notes[-3:]
    return out


if __name__ == "__main__":
    sys.exit(main())
