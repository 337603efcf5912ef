"""Arithmetic of the readers that read the program's own names in the
trace: its host spans (``repro.*``), its GR-MAC launches by site and its
ops by their operands (``chipbench.program_trace``); and of the prefill
rows the harness logged."""
from __future__ import annotations

from chipbench import program_trace as pt


def cim_prep_share_pct(run):
    """Device time of the ops that made the GR-MAC launches' weights (the
    re-quantisation and the weight's absmax), over the prefill and decode
    programs' device time."""
    runs = pt.runs_with_ops(run, ("prefill", "decode"))
    if not any(pt.trace.is_kernel(o) for _, _, _, ops in runs for o in ops):
        return None
    texts = pt.op_texts(run)
    if not texts:
        return None
    heads = {}                      # the same ops in every run of a program
    prep = 0
    for m, _, _, ops in runs:
        if m not in heads:
            known = texts.get(m, {})
            heads[m] = pt.prep_heads(ops, known.get)
        prep += pt.prep_ns(ops, heads[m])
    return 100.0 * prep / sum(ns for _, _, ns, _ in runs)


def head_ms_per_prefill(run):
    """Device time of the LM head's launches (site ``head``) per prefill
    program run."""
    runs = pt.runs_with_ops(run, ("prefill",))
    head = [o.dur for _, _, _, ops in runs for o in ops
            if pt.kernel_site(o) == "head"]
    if not head:
        return None
    return sum(head) / len(runs) / 1e6


def host_idle_ms_per_step(run):
    """Device idle time while the host was inside ``Scheduler.step``, per
    step."""
    idle, steps = pt.idle_in_steps(run.devices[0].ops, pt.program_spans(run)) \
        if run.devices else (0, 0)
    return idle / steps / 1e6 if steps else None


def prefill_row_use_pct(run):
    """Prompt tokens over the rows the window's prefill dispatches ran, as
    the harness logged them (each dispatch's tokens array is every lane's
    bucket; the engine counts the same in ``stats["prefill_rows"]``)."""
    chunks = [e for e in run.events if e[0] == "prefill"]
    rows = sum(int(e[1].size) for e in chunks)
    if not rows:
        return None
    return 100.0 * sum(int(e[3].sum()) for e in chunks) / rows
