"""Block-engine kernel time per served batch (us/batch), from the
device trace: the Pallas multisource kernel's events in the window over
the batches the window dispatched."""
from bench.trace import kernel_s


def read(r):
    t = kernel_s(r.trace)
    return None if t is None else t / r.work["batches"] * 1e6
