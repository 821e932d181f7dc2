"""evaluator.ms: device time of the batch evaluator's program
(``_evaluate_jit``) less the kernel's events in it, per 65,536 designs."""
from chipbench import trace as tr

PER = 65536


def read(r):
    prog = tr.module_ns(r.trace, "_evaluate_jit")
    if not prog or not r.work.get("designs"):
        return None
    return (prog - tr.kernel_ns(r.trace)) / 1e6 / r.work["designs"] * PER
