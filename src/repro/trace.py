"""The names of the program's trace, and the helpers that record them.

One scheme, ``cg.<what>``, on both sides of the chip and on the
profiler's one clock:

* ``span(name, **ids)``: a host span, a ``jax.profiler.TraceAnnotation``.
  It records only while a profiler session is open
  (``jax.profiler.trace``); otherwise it is a no-op. ``ids`` (a batch's
  sequence number) ride on the event.
* ``scope(name)``: a device scope, a ``jax.named_scope``. Every op traced
  inside it carries the scope in its name stack, which the compiled
  program keeps as op metadata and a device trace shows per op. It costs
  nothing at run time.
* ``install_gc_spans()``: a ``cg.gc`` span around every full
  (generation-2) collection, wherever it lands. Full collections walk
  every long-lived container; generation-0 and -1 collections stay
  unspanned, so the callback stays off the hot path.

Spans nest as the serving layers do::

    cg.dispatch (batch=n)             ServingEngine.submit_batch
    cg.step                           ServingEngine.step
      cg.admit                        parked dispatches bound and enqueued
        cg.finalize (batch=n)         owner gather and binding
          cg.device_wait              the host blocks on the device
      cg.serve_replicas               the replica drain loop
      cg.rebalance                    delegation launch and its readbacks
        cg.device_wait
    cg.gc                             a full collection, anywhere

Scopes on the device: ``cg.bind`` (owner lookup and arrival count of a
slot), ``cg.controller``, ``cg.delegation``, ``cg.merge`` (the psum of
the source lanes across a mesh). ``docs/tracing.md`` says how to capture
a trace and what each name covers.

The module imports nothing of the program: the core, kernels and serving
layers all import it.
"""
from __future__ import annotations

import gc

import jax

# host spans
STEP = "cg.step"
ADMIT = "cg.admit"
SERVE_REPLICAS = "cg.serve_replicas"
REBALANCE = "cg.rebalance"
DISPATCH = "cg.dispatch"
FINALIZE = "cg.finalize"
DEVICE_WAIT = "cg.device_wait"
GC = "cg.gc"
# device scopes
BIND = "cg.bind"
CONTROLLER = "cg.controller"
DELEGATION = "cg.delegation"
MERGE = "cg.merge"


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span ``name``, with ``ids`` attached to its event."""
    return jax.profiler.TraceAnnotation(name, **ids)


def scope(name: str):
    """A device scope ``name`` over the ops traced inside it."""
    return jax.named_scope(name)


_open_gc: list = []     # the span of the full collection under way


def _gc_span(phase: str, info: dict) -> None:
    if info["generation"] != 2:
        return
    if phase == "start":
        s = span(GC)
        s.__enter__()
        _open_gc.append(s)
    elif _open_gc:
        _open_gc.pop().__exit__(None, None, None)


def install_gc_spans() -> None:
    """Span every full collection as ``cg.gc``; calling it again adds
    nothing."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
