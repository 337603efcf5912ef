"""Device time per decode program run (ms)."""
from chipbench.metrics import serving


def read(run):
    return serving.decode_step_ms(run)
