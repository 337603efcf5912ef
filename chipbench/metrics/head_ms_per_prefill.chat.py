"""Device time of the LM head's GR-MAC launches per prefill program run (ms)."""
from chipbench.metrics import program


def read(run):
    return program.head_ms_per_prefill(run)
