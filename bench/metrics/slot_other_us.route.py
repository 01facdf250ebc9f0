"""Device time per routed slot outside the block kernel (us/slot): the
CG slot loop's delegation, controller, queue model and glue. Busy time
(the union of all device ops in the window) less the kernel's time."""
from bench.trace import kernel_s


def read(r):
    if r.trace is None or not r.trace.ops:
        return None
    other = r.trace.busy_s() - (kernel_s(r.trace) or 0.0)
    return other / r.work["slots"] * 1e6
