"""Host time of the serving rebalance per engine tick (ms/tick): the
self time of the program's ``cg.rebalance`` spans (launching the
controller and delegation step; the waits on its results are under
``cg.device_wait``), over the window's ticks."""
from bench.scopes import per_tick_ms


def read(r):
    return per_tick_ms(r, "cg.rebalance")
