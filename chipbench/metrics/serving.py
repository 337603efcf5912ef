"""Arithmetic shared by the serving cells' readers. ``run`` is a
``chipbench.trace.RunData``: the traced window's device events, matched in
order with the dispatches that the window logged."""
from __future__ import annotations

from chipbench import flops


def prefill_us_per_token(run):
    runs = run.program_runs("prefill")
    if runs is None or not run.prefill_tokens():
        return None
    return sum(runs) / 1e3 / run.prefill_tokens()


def decode_step_ms(run):
    runs = run.program_runs("decode")
    if not runs:
        return None
    return sum(runs) / len(runs) / 1e6


def grmac_roofline_pct(run):
    """Roofline time of every GR-MAC kernel call over its device time (see
    ``RunData.kernel_calls`` for where a call's shape comes from)."""
    kernels = run.kernel_calls()
    if not kernels:
        return None
    pk = run.peaks
    floor = sum(flops.grmac_roofline_s(m, k, n, run.spec, pk)
                for (m, k, n), _ in kernels)
    spent = sum(d for _, d in kernels) / 1e9
    return 100.0 * floor / spent


def program_mfu_pct(run, kind: str):
    """Model FLOPs of one kind of program's useful tokens over those
    programs' device time x bf16 peak."""
    runs = run.program_runs(kind)
    work = run.prefill_flops() if kind == "prefill" else run.decode_flops()
    if not runs or work <= 0:
        return None
    return 100.0 * work / (sum(runs) / 1e9 * run.peaks["bf16_flops_per_s"])


def queue_wait_p50_ms(run):
    from chipbench.harness import percentile
    waits = [(t.req.wall_admit - t.due) * 1e3 for t in run.tracks
             if t.req.wall_admit is not None]
    return percentile(waits, 0.5) if waits else None
