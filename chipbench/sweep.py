"""Offered-load sweep of an open-loop serving cell, on the chip: the rate
that the cell's traffic mix names was set from it.

    python3 chipbench/sweep.py --workload <cell> --rates 0.2,0.4,0.6 \\
        --seconds 40 --seed 1

One process makes the weights and warms up once, then runs the cell's
window at each rate on a fresh engine and reports time to first token,
inter-token gaps, and whether the backlog drained: a rate the system
sustains leaves no request waiting long after the window closes. The
reference check is not run here.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drain", type=float, default=60.0)
    args = ap.parse_args(argv)
    harness.strict_precision()
    cell = harness.load_cell(args.workload)
    harness.import_program()
    harness.enable_compile_cache()
    try:
        device = harness.device_info(cell["entry"]["chips"])
    except harness.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    import jax
    import numpy as np
    from repro.serving.engine import Engine
    from repro.serving.scheduler import Scheduler

    from chipbench import serve, weights
    from chipbench.traffic.generator import make_requests

    spec, serve_cfg = cell["spec"], cell["settings"]["serve"]
    arch = harness.arch_from_spec(spec)
    vocab = spec["arch"]["vocab_size"]
    params = weights.make_params(spec, args.seed)
    serve.warm_up(arch, params, serve_cfg, vocab, names=False)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell["mix"])
        mix["rate_per_s"] = rate
        mix["requests"] = int(rate * args.seconds) + 2 * mix["block"]
        reqs = make_requests(mix, args.seed, vocab)
        scfg, qcfg = serve.engine_configs(serve_cfg)
        eng = Engine(arch, params, scfg)
        sched = Scheduler(eng, qcfg)
        t0, tracks, lateness = serve.open_window(sched, reqs, args.seconds,
                                                 args.drain)
        jax.block_until_ready(eng.cache)
        t_end = harness.now()
        ttft = [(tr.req.wall_first - tr.due) * 1e3
                if tr.req.wall_first is not None else float("inf")
                for tr in tracks]
        gaps = [(b - a) * 1e3 for tr in tracks
                for a, b in zip(tr.times, tr.times[1:])]
        wait = [(tr.req.wall_admit - tr.due) * 1e3 for tr in tracks
                if tr.req.wall_admit is not None]
        half = len(wait) // 2
        print(json.dumps({
            "rate_per_s": rate, "requests": len(tracks),
            "unfinished_first": sum(tr.req.wall_first is None
                                    for tr in tracks),
            "drain_s": t_end - t0 - args.seconds,
            "ttft_p50_ms": harness.percentile(ttft, 0.5),
            "ttft_p90_ms": harness.percentile(ttft, 0.9),
            "itl_p50_ms": harness.percentile(gaps, 0.5),
            "itl_p95_ms": harness.percentile(gaps, 0.95),
            "queue_wait_first_half_ms": float(np.mean(wait[:half]))
            if half else None,
            "queue_wait_second_half_ms": float(np.mean(wait[half:]))
            if half else None,
            "decode_steps": eng.stats["decode_steps"],
            "device": device}), flush=True)
        del sched, eng
    return 0


if __name__ == "__main__":
    sys.exit(main())
