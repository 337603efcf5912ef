"""The chip benchmark: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Finds the cell in BENCHMARK.json and its files by name (settings in
``workloads/<cell>.json``, the configuration in ``configs/<config>.json``,
the traffic mix in ``traffic/<mix>.json``), makes weights and requests
from ``--seed``, warms up, measures for ``--seconds`` and checks what the
measured path produced against the configuration's plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number with its limit.
Needs the cell's TPU chips: on anything else it exits 3 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402

TRACE_DIR = harness.ROOT / ".bench_trace"


class Context:
    """What one run knows and what it found."""

    def __init__(self, args, cell: dict, bench: dict):
        self.name = args.workload
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.bench = bench
        self.entry, self.settings = cell["entry"], cell["settings"]
        self.spec, self.mix = cell["spec"], cell["mix"]
        self.t_start = T_START
        self.notes, self.checks = [], []
        self.values = {}
        self.trace_dir = TRACE_DIR / self.name
        self.compiles = harness.CompileCounter()

    def tracing(self):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return jax.profiler.trace(str(self.trace_dir))


def run(args, require_tpu: bool = True, bench=None, cell=None,
        compile_cache: bool = True):
    """One run; returns the result line (without its checks) and the
    context. Raises NoDevice before any work where the chips are absent.
    Tests pass ``bench`` and ``cell`` in place of the files, and may skip
    the look for a chip and the persistent compile cache."""
    bench = bench or harness.benchmark()
    cell = cell or harness.load_cell(args.workload, bench)
    harness.import_program()
    cache_dir = harness.enable_compile_cache() if compile_cache else None
    device = harness.device_info(cell["entry"]["chips"], require_tpu)
    ctx = Context(args, cell, bench)
    ctx.device = device
    ctx.notes.append(f"device {device}; compile cache {cache_dir}")
    runner = {"serve": "chipbench.serve"}[ctx.settings["runner"]]
    import importlib
    importlib.import_module(runner).run(ctx)
    result = {"correct": all(c.ok for c in ctx.checks),
              "attempted": ctx.attempted, "failed": ctx.failed}
    dev = dict(device)
    dev["memory_peak_bytes"] = ctx.peak_bytes
    if ctx.trace:
        from chipbench import trace
        t = harness.now()
        run_data = trace.reduce(ctx)
        result["metrics"] = harness.read_layer_metrics(bench, ctx.name,
                                                       run_data)
        dev["busy_s"], dev["window_s"] = run_data.busy_s, run_data.window_s
        result["breakdown"] = run_data.breakdown
        ctx.notes.append(f"trace read in {harness.now() - t:.1f} s")
    else:
        result["metrics"] = harness.e2e_metrics(bench, ctx.name, ctx.values)
    result["device"] = dev
    return result, ctx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    harness.strict_precision()
    # libtpu logs to a fixed path under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        result, ctx = run(args)
    except harness.NoDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    harness.emit(result, ctx.checks, ctx.notes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
