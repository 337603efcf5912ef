"""Roofline share of the Pallas GR-MAC kernel's calls (%)."""
from chipbench.metrics import serving


def read(run):
    return serving.grmac_roofline_pct(run)
