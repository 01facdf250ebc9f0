"""The ``serve`` loop: ``ServingEngine`` over a CG request router, a
closed loop with back-pressure.

One batch of WP-shaped requests per engine tick, the next submitted
when the tick returns, through ``ServingEngine`` (async submit) over the
router the configuration names (``router_class``, ``CGRequestRouter``
unless it says otherwise; with ``source_mesh`` its source lanes on a
mesh of that many chips). The configuration's ``router`` group is passed
to the router as it stands (the control doubles its ``sync_every``).
Replica functions are the benchmark's: each records which requests it
served and when. The window's throughput and latency are taken from
those stamps; every request is then checked against the reference's
routing and the owner map in force at its admission.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import log, reference, streams


def run(run) -> None:
    import repro.serve
    from repro.runtime import ChaosEvent, ChaosSchedule
    from repro.serve import ServingEngine

    c, t = run.config, run.traffic
    rc, fleet = c["router"], c["fleet"]
    if rc.get("hh_scheme"):
        raise SystemExit("the reference does not model heavy-hitter "
                         "probe policies (router.hh_scheme)")
    n, B = rc["n_replicas"], t["batch"]
    pool_n = t["pool"]
    if pool_n % B:
        raise ValueError(f"pool {pool_n} is not a whole number of batches")
    pool = np.asarray(streams.sample_keys(run.seed, 1, c["stream"], pool_n))
    kw = {**rc, "sync_every": run.sync(rc)}
    if c.get("source_mesh"):
        from repro.launch.mesh import make_source_mesh
        kw["mesh"] = make_source_mesh(c["source_mesh"])
    router = getattr(repro.serve, c.get("router_class", "CGRequestRouter"))(**kw)

    # -- what the harness records: dispatches, admissions, service ------
    handles, bound, owners = [], [], []
    dispatch, finalize = router.dispatch_batch, router.finalize_batch

    def recording_dispatch(keys):
        with run.spans("dispatch"):
            h = dispatch(keys)
        handles.append(h)
        return h

    def recording_finalize(h):
        owners.append(router._owner_view())
        with run.spans("finalize"):
            r = finalize(h)
        bound.append(r)
        return r

    router.dispatch_batch = recording_dispatch
    router.finalize_batch = recording_finalize
    served = [[] for _ in range(n)]

    def replica(r):
        out = served[r]

        def fn(payloads):
            # a tuple of ints leaves the collector's view after one pass,
            # where a list would be walked again by every full collection
            out.append((tuple(payloads), time.perf_counter()))
        return fn

    slow, frac = fleet["slow_workers"], fleet["slow_fraction"]
    size = n - len(slow) + len(slow) * frac      # in full-speed replicas
    chaos = ChaosSchedule([ChaosEvent(1, "slow", r, factor=1.0 / frac)
                           for r in slow])
    engine = ServingEngine([replica(r) for r in range(n)], router,
                           max_batch=math.ceil(B / (fleet["rho"] * size)),
                           async_submit=True, chaos=chaos)
    submit_t: list[float] = []

    def tick(b: int) -> None:
        lo = (b * B) % pool_n
        with run.spans("submit"):
            submit_t.append(time.perf_counter())
            engine.submit_batch(pool[lo:lo + B], list(range(b * B, (b + 1) * B)))
        with run.spans("step"):
            engine.step()

    # warm up until every program the loop reaches has been built: at
    # least warmup_ticks, then until as many ticks in a row compiled
    # nothing (delegation first runs once a replica turns busy)
    b = quiet = 0
    while b < t["warmup_ticks"] or quiet < t["warmup_ticks"]:
        seen = run.compiles.count
        tick(b)
        b += 1
        quiet = quiet + 1 if run.compiles.count == seen else 0
    run.setup_done()
    first_b = b
    with run.window():
        t0 = time.perf_counter()
        while True:
            tick(b)
            b += 1
            if time.perf_counter() - t0 >= run.window_seconds:
                break
        t1 = time.perf_counter()
    window_ticks = b - first_b
    drain = 0
    while engine.in_flight and drain < 100 * t["warmup_ticks"] + 1000:
        engine.step()
        drain += 1

    # -- throughput and latency from the replicas' stamps ----------------
    ids = np.concatenate([np.asarray(p, np.int64)
                          for calls in served for p, _ in calls])
    done_t = np.concatenate([np.full(len(p), s)
                             for calls in served for p, s in calls])
    rep = np.concatenate([np.full(len(p), r, np.int32)
                          for r, calls in enumerate(served) for p, _ in calls])
    in_win = (done_t >= t0) & (done_t <= t1)
    lat = done_t[in_win] - np.asarray(submit_t)[ids[in_win] // B]
    run.end_to_end["serve_req_per_s"] = float(in_win.sum()) / (t1 - t0)
    run.end_to_end["serve_p95_s"] = float(np.quantile(lat, 0.95))
    run.work.update(ticks=window_ticks, batches=window_ticks,
                    requests=int(in_win.sum()), window_s=t1 - t0)
    log(f"serve: {window_ticks} ticks, {int(in_win.sum()):,} requests "
         f"completed in {t1 - t0:.3f} s; drained in {drain} ticks; "
         f"{router.moves} moves")

    # -- the reference: routing, binding, exactly-once --------------------
    tr = time.perf_counter()
    total = b * B
    ms = reference.MultiSource(n * rc["alpha"], rc["n_sources"],
                               eps=rc["eps"], block=rc["block_size"],
                               sync_every=rc["sync_every"])
    vw_bad = bind_bad = 0
    want_rep = np.full(total, -1, np.int64)
    bad_req = np.zeros(total, bool)
    for i, (h, r_got, own) in enumerate(zip(handles, bound, owners)):
        lo = (i * B) % pool_n
        h = np.asarray(h)
        want = ms.route(pool[lo:lo + B], h if h.shape == (B,) else None)
        dv = reference.differ(h, want)
        db = reference.differ(r_got, np.asarray(own)[want])
        vw_bad += int(dv.sum())
        bind_bad += int(db.sum())
        want_rep[i * B:(i + 1) * B] = np.asarray(own)[want]
        bad_req[i * B:(i + 1) * B] = dv | db
    counts = np.bincount(ids, minlength=total)
    elsewhere = want_rep[ids] != rep
    bad_req[ids[elsewhere]] = True
    bad_req |= counts != 1
    owner_jumps = 0
    prev = None
    most = rc["max_moves_per_rebalance"]
    for own in owners:
        o = np.asarray(own)
        if not ((o >= 0) & (o < n)).all():
            owner_jumps += 1
        if prev is not None and int((o != prev).sum()) > most:
            owner_jumps += 1
        prev = o
    log(f"reference: {len(handles)} batches in {time.perf_counter() - tr:.3f} s")
    run.check("vw_mismatch", vw_bad, 0)
    run.check("binding_mismatch", bind_bad, 0)
    run.check("served_elsewhere", int(elsewhere.sum()), 0)
    run.check("lost", int((counts == 0).sum()), 0)
    run.check("duplicated", int((counts > 1).sum()), 0)
    run.check("dropped", engine.dropped + engine.retried, 0)
    run.check("conservation_gap", abs(
        engine.submitted - sum(r.served for r in engine.replicas)
        - engine.in_flight), 0)
    run.check("owner_map_faults", owner_jumps, 0)
    run.check("batches_unchecked", b - len(handles), 0)
    # bins the reference took from the program because they were probed
    # within rounding of a capacity
    run.check("rounding_choices", ms.rounding_choices,
              t["limits"]["rounding_choices"])
    run.attempted = window_ticks * B
    run.failed = int(bad_req[first_b * B: b * B].sum())
