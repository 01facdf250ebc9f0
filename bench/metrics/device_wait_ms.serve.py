"""Host time blocked on device results per engine tick (ms/tick): the
total of the program's ``cg.device_wait`` spans (the owner gather's
readback, the rebalance's move count and queue flags, the rare rebase
minimum), over the window's ticks."""
from bench.scopes import per_tick_ms


def read(r):
    return per_tick_ms(r, "cg.device_wait", self_time=False)
