"""Host time of admission per engine tick (ms/tick): the self time of
the program's ``cg.admit`` spans (binding the parked dispatches and
building and enqueueing each request; the owner gather under
``cg.finalize`` left out), over the window's ticks."""
from bench.scopes import per_tick_ms


def read(r):
    return per_tick_ms(r, "cg.admit")
