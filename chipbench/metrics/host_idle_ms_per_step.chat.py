"""Device idle time while the host is inside Scheduler.step, per step (ms)."""
from chipbench.metrics import program


def read(run):
    return program.host_idle_ms_per_step(run)
