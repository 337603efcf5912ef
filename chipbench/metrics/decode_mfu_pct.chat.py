"""Model FLOPs of the active lanes' tokens over the decode programs' device time x bf16 peak (%)."""
from chipbench.metrics import serving


def read(run):
    return serving.program_mfu_pct(run, "decode")
