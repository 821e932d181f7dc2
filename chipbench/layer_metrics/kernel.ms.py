"""kernel.ms: device time of the parallelism-search kernel (its Mosaic
custom-call events in the trace) per 65,536 designs evaluated."""
from chipbench import trace as tr

PER = 65536


def read(r):
    ns = tr.kernel_ns(r.trace)
    if not ns or not r.work.get("designs"):
        return None
    return ns / 1e6 / r.work["designs"] * PER
