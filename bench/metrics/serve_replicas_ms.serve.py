"""Host time of the replica drain loop per engine tick (ms/tick): the
self time of the program's ``cg.serve_replicas`` spans (popping each
queue, the replica call, latency bookkeeping, the capacity estimate and
the busy/idle signals), over the window's ticks."""
from bench.scopes import per_tick_ms


def read(r):
    return per_tick_ms(r, "cg.serve_replicas")
