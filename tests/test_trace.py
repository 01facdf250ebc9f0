"""The program's trace: host spans of the serving path, the full-
collection span, and device scopes in the routing and mesh programs."""
import gc

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import ProfileData

from repro import trace
from repro.core import cg
from repro.kernels import mesh as kmesh
from repro.launch.mesh import make_source_mesh
from repro.serve import CGRequestRouter, ServingEngine


def host_events(tmp_path, fn):
    """``(name, start_ns, end_ns, line, stats)`` of every ``cg.`` event
    on a host plane while ``fn`` runs under the profiler."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    path = next(tmp_path.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if name.startswith("cg."):
                    out.append((name, ev.start_ns, ev.end_ns,
                                f"{plane.name}/{line.name}", dict(ev.stats)))
    return out


def inside(child, parents):
    return any(p[3] == child[3] and p[1] <= child[1] and child[2] <= p[2]
               for p in parents)


def test_serving_engine_writes_its_spans(tmp_path):
    router = CGRequestRouter(n_replicas=4, alpha=2, block_size=8,
                             n_sources=2)
    engine = ServingEngine([lambda p: None] * 4, router, max_batch=8,
                           async_submit=True)
    rng = np.random.default_rng(0)

    def ticks():
        for b in range(6):
            engine.submit_batch(rng.integers(0, 1000, 64, dtype=np.int32),
                                list(range(b * 64, (b + 1) * 64)))
            engine.step()
        while engine.in_flight:
            engine.step()

    evs = host_events(tmp_path, ticks)
    names = {e[0] for e in evs}
    assert {trace.STEP, trace.ADMIT, trace.SERVE_REPLICAS, trace.REBALANCE,
            trace.DISPATCH, trace.FINALIZE, trace.DEVICE_WAIT} <= names
    steps = [e for e in evs if e[0] == trace.STEP]
    for name in (trace.ADMIT, trace.SERVE_REPLICAS, trace.REBALANCE):
        kids = [e for e in evs if e[0] == name]
        assert kids and all(inside(k, steps) for k in kids), name
    # one span per batch, not per request; a batch's two halves carry
    # the same sequence number, its admission after its launch
    dispatch = {e[4]["batch"]: e for e in evs if e[0] == trace.DISPATCH}
    finalize = {e[4]["batch"]: e for e in evs if e[0] == trace.FINALIZE}
    assert sorted(dispatch) == sorted(finalize) == list(range(1, 7))
    for b, f in finalize.items():
        assert dispatch[b][2] <= f[1]
        assert inside(f, [e for e in evs if e[0] == trace.ADMIT])
    waits = [e for e in evs if e[0] == trace.DEVICE_WAIT]
    assert all(inside(w, [e for e in evs if e[0] in (
        trace.FINALIZE, trace.REBALANCE)]) for w in waits)


def test_full_collections_are_spanned_once(tmp_path):
    trace.install_gc_spans()
    trace.install_gc_spans()
    assert gc.callbacks.count(trace._gc_span) == 1

    def collect():
        # no automatic collection inside the window: only these two
        gc.disable()
        try:
            gc.collect(0)        # a young collection: no span
            gc.collect()         # a full one
        finally:
            gc.enable()

    evs = host_events(tmp_path, collect)
    assert [e[0] for e in evs].count(trace.GC) == 1


def test_routing_program_carries_its_scopes():
    cfg = cg.CGConfig(n_workers=4, alpha=2, slot_len=64, block_size=8,
                      n_sources=2)
    text = cg.run.lower(cfg, jnp.zeros(128, jnp.int32),
                        jnp.ones(4, jnp.float32)).as_text(debug_info=True)
    for name in (trace.BIND, trace.CONTROLLER, trace.DELEGATION):
        assert name in text, name


def test_mesh_program_carries_the_merge_scope():
    mesh = make_source_mesh(1)
    prog = kmesh._mesh_scan(mesh, 8, 2, 1, 4, 0.05, 8)
    lowered = prog.lower(jnp.zeros(8, jnp.float32),
                         jnp.zeros((2, 8), jnp.float32),
                         jnp.zeros((), jnp.int32),
                         jnp.zeros((2, 3, 4), jnp.int32))
    assert trace.MERGE in lowered.as_text(debug_info=True)

