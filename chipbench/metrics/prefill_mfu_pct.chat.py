"""Model FLOPs of the useful prompt tokens over the prefill programs' device time x bf16 peak (%)."""
from chipbench.metrics import serving


def read(run):
    return serving.program_mfu_pct(run, "prefill")
