"""Median wait of a request from its due time to its admission by the scheduler (ms)."""
from chipbench.metrics import serving


def read(run):
    return serving.queue_wait_p50_ms(run)
