"""Prompt tokens over the rows the logged prefill dispatches run (%)."""
from chipbench.metrics import program


def read(run):
    return program.prefill_row_use_pct(run)
