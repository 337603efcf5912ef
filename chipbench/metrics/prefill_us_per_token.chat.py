"""Device time of the prefill programs per useful prompt token (us)."""
from chipbench.metrics import serving


def read(run):
    return serving.prefill_us_per_token(run)
