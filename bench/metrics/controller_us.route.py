"""Device time of the delegation controller per routed slot (us/slot):
the union of the ops under the program's ``cg.controller`` scope
(busy/idle signals and the slot's move budget), over the slots the
window routed."""
from bench.scopes import scope_us


def read(r):
    return scope_us(r, "cg.controller", "slots")
