"""Host time in full (generation-2) garbage collections per engine tick
(ms/tick): the total of the program's ``cg.gc`` spans, over the
window's ticks; 0.0 where the program's spans were recorded and no full
collection fell in the window."""
from bench.scopes import per_tick_ms


def read(r):
    return per_tick_ms(r, "cg.gc", self_time=False)
