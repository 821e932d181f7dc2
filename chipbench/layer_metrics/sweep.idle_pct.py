"""sweep.idle_pct: share of the traced window in which the device ran no
operation (averaged over the chips used), from the profiler trace."""
from chipbench import trace as tr


def read(r):
    return tr.idle_pct(r.trace)
