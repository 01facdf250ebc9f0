"""Host time of one engine tick (ms/tick): the harness's span around
``ServingEngine.step`` (admission, drain, delegation signals, rebalance),
averaged over the window's ticks."""


def read(r):
    t = r.span_mean("step")
    return None if t is None else t * 1e3
