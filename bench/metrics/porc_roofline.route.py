"""The block kernel's share of its roofline (%). Its least work is fixed
by the semantics, whatever implements it: each routed message reads its
key and writes its VW id, 4 + 4 bytes through HBM. The least time is
those bytes over the chip's HBM bandwidth (peaks.json); the share is
that over the kernel's measured time. Bound by bandwidth."""
from bench.trace import kernel_s

BYTES_PER_MESSAGE = 8


def read(r):
    t = kernel_s(r.trace)
    if t is None or r.peaks is None:
        return None
    least = BYTES_PER_MESSAGE * r.work["messages"] / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t
