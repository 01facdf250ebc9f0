"""Device time of binding per routed slot (us/slot): the union of the
ops under the program's ``cg.bind`` scope (the owner gather of the
slot's VWs and the scatter-add of its worker arrivals), over the slots
the window routed."""
from bench.scopes import scope_us


def read(r):
    return scope_us(r, "cg.bind", "slots")
