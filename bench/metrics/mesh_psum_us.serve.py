"""Device time of the mesh merge per served batch (us/batch): the union
of the ops under the program's ``cg.merge`` scope (the psum of the
source lanes' load deltas across the chips), on both op lines, averaged
over the chips, over the batches the window dispatched."""
from bench.scopes import scope_us


def read(r):
    return scope_us(r, "cg.merge", "batches", asynchronous=True)
