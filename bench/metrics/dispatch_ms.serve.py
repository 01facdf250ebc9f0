"""Router time per batch (ms/batch): the harness's spans around
``dispatch_batch`` (launching the PoRC routing) and ``finalize_batch``
(binding VWs to replicas, where the host waits for the device), summed
over the window and divided by its batches."""


def read(r):
    d, f = r.span_total("dispatch"), r.span_total("finalize")
    if d is None or f is None:
        return None
    return (d + f) / r.work["batches"] * 1e3
