"""A tiny serving cell (published layout, toy widths, float32) that the
harness can run end to end on the CPU without a chip."""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# Both programs agree bit for bit in float32 on the CPU, so the tiny cells'
# limit only has to sit above float32 round-off.
TINY_GAP_LIMIT = 1e-3


def tiny_cell(dtype: str = "float32") -> tuple:
    """(bench, cell) for a run of a toy-width dense cell."""
    spec = copy.deepcopy(json.loads(
        (ROOT / "chipbench" / "configs" / "qwen2-1.5b-grmac.json")
        .read_text()))
    spec["check"] = {name: TINY_GAP_LIMIT for name in (
        "max_logit_gap", "state_error_worst_layer")}
    spec["arch"].update(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                        d_head=32, d_ff=256, vocab_size=500, dtype=dtype)
    mix = {"loop": "open", "rate_per_s": 8.0, "block": 8, "requests": 12,
           "prompt": {"median": 20, "sigma": 0.8, "lo": 4, "hi": 60},
           "output": {"median": 6, "sigma": 0.5, "lo": 2, "hi": 12}}
    settings = {"runner": "serve", "drain_s": 60,
                "serve": {"batch_slots": 4, "max_ctx": 128,
                          "prefill_token_budget": 16}}
    name = "tiny.dense"
    entry = {"name": name, "config": spec["name"], "traffic": "tiny",
             "chips": 1}
    bench = {"workloads": [entry], "per_layer": [],
             "end_to_end": [{"name": m, "unit": "x", "workloads": [name]}
                            for m in ("ttft_p90_ms", "itl_p95_ms")]
             + [{"name": "setup_s", "unit": "s"}]}
    cell = {"entry": entry, "settings": settings, "spec": spec, "mix": mix}
    return bench, cell


def tiny_args(seed: int = 2 ** 33 + 5, seconds: float = 1.5):
    return argparse.Namespace(workload="tiny.dense", seed=seed,
                              seconds=seconds, trace=0)


def main(argv) -> int:
    """Prints the control's readings of a tiny cell in ``argv[1]``'s
    dtype, with XLA held to the stated precision."""
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness
    harness.strict_precision()
    from chipbench import control
    from chipbench import run as bench_run
    bench, cell = tiny_cell(argv[1])
    cell["spec"]["arch"]["n_layers"] = 4
    ctx = bench_run.Context(tiny_args(), cell, bench)
    r = control.control_readings(ctx)
    print(json.dumps({k: r[k] for k in ("program", "control", "altered",
                                        "tokens", "stream_faults")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
