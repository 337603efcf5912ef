"""Device time of the ops that make the GR-MAC launches' weights (their
re-quantisation and absmax) over the prefill and decode programs' device
time (%)."""
from chipbench.metrics import program


def read(run):
    return program.cim_prep_share_pct(run)
