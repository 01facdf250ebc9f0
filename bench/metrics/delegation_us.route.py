"""Device time of worker delegation per routed slot (us/slot): the union
of the ops under the program's ``cg.delegation`` scope (rates, FCFS
queues, budgets and the paired-move loop), over the slots the window
routed."""
from bench.scopes import scope_us


def read(r):
    return scope_us(r, "cg.delegation", "slots")
