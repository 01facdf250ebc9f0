"""Block-engine kernel time per routed slot (us/slot), from the device
trace: the Pallas multisource kernel's events in the window over the
slots the window routed."""
from bench.trace import kernel_s


def read(r):
    t = kernel_s(r.trace)
    return None if t is None else t / r.work["slots"] * 1e6
