#!/usr/bin/env python3
"""Chip smoke: CG routing and serving, once, at deployment size on a TPU.

    python chip_smoke.py                # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4      # four chips: the mesh router only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]

The deployment is the paper's Storm topology (``configs/paper_stream.py``):
24 workers, 8 sources, 10 virtual workers per worker, two workers held to
30% capacity, eps 0.01, slots of 10,000 messages, blocks of 128. The
stream is WP-shaped at the Table I scale: 22,000,000 messages over
2,900,000 keys, top key 9.32%, with ``streams.WP_TRACE``'s Zipf tail.
Keys are sampled on the device from ``--seed``.

One chip:
  (a) ``cg.run`` over the whole stream with the default engine
      (``"auto"``, which is the compiled Pallas kernel on a TPU);
  (b) the same call with ``engine="ref"`` (the jnp engine): assignment,
      VW assignment and imbalance must be bit-identical to (a);
  (c) a ``ServingEngine`` of 24 replicas (two slowed to 30%) behind
      ``CGRequestRouter`` with async submit takes 2^20 WP-shaped
      requests in batches of 8,192 and drains: everything submitted is
      served, nothing dropped, and the first batches' VW assignment
      equals an ``engine="ref"`` router's.
Four chips (``--chips 4``): ``mesh_porc_multisource`` with the 8 source
lanes on ``make_source_mesh(4)`` must be bit-identical to the single-device
``ref_porc_multisource`` at sync_every 1 and 4, with the lanes on 4
devices; then a ``ServingEngine`` on ``MeshCGRequestRouter`` takes the
requests through a replica kill with ``submitted == served + in_flight``
at every tick and nothing dropped.

Wall times are printed per phase with compilation included; they are
smoke times, not benchmark metrics. ``--rehearse`` shrinks the sizes and
allows the CPU, with the Pallas kernels in interpret mode; it is never
the default. Any failed check, and any platform other than ``tpu``
without ``--rehearse``, exits non-zero before the last line, which is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path
from typing import NamedTuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BATCH = 8192          # requests per submit


class Sizes(NamedTuple):
    n_messages: int   # stream length, a whole number of slots
    n_keys: int
    n_requests: int   # served requests, a whole number of batches


FULL = Sizes(n_messages=22_000_000, n_keys=2_900_000, n_requests=2 ** 20)
REHEARSAL = Sizes(n_messages=200_000, n_keys=29_000, n_requests=4 * BATCH)


def check(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAILED: {what}")
    print(f"  ok: {what}", flush=True)


def timed(label: str, fn):
    import jax
    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    print(f"  {label}: {time.perf_counter() - t:.2f} s wall "
          f"(compile included)", flush=True)
    return out


def wp_keys(seed: int, sizes: Sizes, n: int, stream: int):
    """``n`` WP-shaped keys (Table I: top key 9.32%, WP_TRACE's tail)."""
    import jax
    from repro.core import streams
    spec = streams.TraceSpec("WP", n_messages=sizes.n_messages,
                             n_keys=sizes.n_keys, p1=0.0932,
                             z_tail=streams.WP_TRACE.z_tail, diurnal=True)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), stream)
    return streams.sample_trace(key, spec, n)


def slowed_replicas(extra=()):
    """The two executors held to 30% capacity, from the first tick."""
    from repro.configs.paper_stream import CPULIMIT_FRACTION
    from repro.runtime import ChaosEvent, ChaosSchedule
    return ChaosSchedule([ChaosEvent(1, "slow", r,
                                     factor=1.0 / CPULIMIT_FRACTION)
                          for r in (0, 1)] + list(extra))


def max_batch(n_replicas: int) -> int:
    """Per-replica drain per tick that provisions the fleet (two at 30%)
    at the paper's rho = 0.8 for one batch of requests per tick."""
    from repro.configs.paper_stream import CPULIMIT_FRACTION, RHO
    fleet = n_replicas - 2 + 2 * CPULIMIT_FRACTION
    return math.ceil(BATCH / (RHO * fleet))


def serve(engine, keys, *, per_tick=None) -> int:
    """Submit ``keys`` one batch per tick, then drain. Returns ticks run.
    ``per_tick(engine)`` runs after every tick."""
    import numpy as np
    keys = np.asarray(keys)
    payloads = [None] * BATCH
    ticks = 0
    for lo in range(0, len(keys), BATCH):
        engine.submit_batch(keys[lo:lo + BATCH], payloads)
        engine.step()
        ticks += 1
        if per_tick:
            per_tick(engine)
    while engine.in_flight and ticks < 100 * len(keys) // BATCH:
        engine.step()
        ticks += 1
        if per_tick:
            per_tick(engine)
    return ticks


def served(engine) -> int:
    return sum(r.served for r in engine.replicas)


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------

def one_chip(seed: int, sizes: Sizes, engine: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.paper_stream import (CPULIMIT_FRACTION, RHO,
                                            STORM_SOURCES, STORM_WORKERS)
    from repro.core import cg
    from repro.serve import CGRequestRouter, ServingEngine

    n = STORM_WORKERS
    cfg = cg.CGConfig(n_workers=n, alpha=10, eps=0.01, slot_len=10_000,
                      block_size=128, n_sources=STORM_SOURCES,
                      engine=engine)
    slots = sizes.n_messages // cfg.slot_len
    cut = FULL.n_messages // cfg.slot_len - slots
    print(f"stream: {sizes.n_messages:,} msgs = {slots} slots x "
          f"{cfg.slot_len:,}, {sizes.n_keys:,} keys"
          + (f" (cut by {cut} whole slots)" if cut else ""), flush=True)
    frac = np.ones(n)
    frac[:2] = CPULIMIT_FRACTION
    caps = jnp.asarray(frac / frac.sum() / RHO, jnp.float32)
    keys = timed("sample stream", lambda: wp_keys(seed, sizes,
                                                  sizes.n_messages, 0))

    print(f"(a) cg.run, engine={engine!r}", flush=True)
    res = timed("cg.run", lambda: cg.run(cfg, keys, caps))
    imb = np.asarray(res.imbalance)
    a = np.asarray(res.assignment)
    vw = np.asarray(res.vw_assignment)
    check(a.shape == (sizes.n_messages,) and a.min() >= 0 and a.max() < n,
          f"assignment: {a.shape[0]:,} worker ids in [0, {n})")
    check(vw.min() >= 0 and vw.max() < n * cfg.alpha,
          f"vw_assignment in [0, {n * cfg.alpha})")
    check(imb.shape == (slots,) and np.isfinite(imb).all(),
          f"imbalance finite over {slots} slots")
    print(f"  imbalance: mean {imb.mean():.4f}, last slot {imb[-1]:.4f}; "
          f"moves {int(res.moves)}", flush=True)

    print("(b) cg.run, engine='ref' (jnp reference)", flush=True)
    ref = timed("cg.run", lambda: cg.run(cfg._replace(engine="ref"),
                                         keys, caps))
    for field in ("assignment", "vw_assignment", "imbalance"):
        check(bool(jnp.array_equal(getattr(res, field), getattr(ref, field))),
              f"{field} bit-identical to the jnp engine")
    del res, ref, keys

    print(f"(c) ServingEngine, {n} replicas (2 at {CPULIMIT_FRACTION:.0%}), "
          f"async submit, {sizes.n_requests:,} requests in batches of "
          f"{BATCH:,}", flush=True)
    reqs = np.asarray(wp_keys(seed, sizes, sizes.n_requests, 1))
    router = CGRequestRouter(n_replicas=n, alpha=10, n_sources=STORM_SOURCES,
                             capacity_weighted=True, engine=engine)
    n_check = 4
    first = []
    dispatch = router.dispatch_batch

    def recording_dispatch(batch):
        handle = dispatch(batch)
        if len(first) < n_check:
            first.append(handle)
        return handle

    router.dispatch_batch = recording_dispatch
    eng = ServingEngine([lambda b: b] * n, router, max_batch=max_batch(n),
                        async_submit=True, chaos=slowed_replicas())
    t = time.perf_counter()
    ticks = serve(eng, reqs)
    print(f"  serve + drain: {time.perf_counter() - t:.2f} s wall "
          f"(compile included), {ticks} ticks", flush=True)
    check(eng.submitted == served(eng) == sizes.n_requests,
          f"submitted == served == {eng.submitted:,}")
    check(eng.dropped == 0 and eng.in_flight == 0,
          "dropped == 0 and in_flight == 0")
    print(f"  moves {router.moves}; served per replica min "
          f"{min(r.served for r in eng.replicas):,} max "
          f"{max(r.served for r in eng.replicas):,}", flush=True)
    ref_router = CGRequestRouter(n_replicas=n, alpha=10,
                                 n_sources=STORM_SOURCES,
                                 capacity_weighted=True, engine="ref")
    for b, handle in enumerate(first):
        check(np.array_equal(np.asarray(handle), np.asarray(
            ref_router.dispatch_batch(reqs[b * BATCH:(b + 1) * BATCH]))),
            f"batch {b}: VW assignment equals the engine='ref' router's")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

def four_chips(seed: int, sizes: Sizes) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs.paper_stream import STORM_SOURCES, STORM_WORKERS
    from repro.kernels.mesh import mesh_porc_multisource
    from repro.kernels.ref import ref_porc_multisource
    from repro.launch.mesh import make_source_mesh
    from repro.runtime import ChaosSchedule
    from repro.serve import MeshCGRequestRouter, ServingEngine

    n, S, V = STORM_WORKERS, STORM_SOURCES, STORM_WORKERS * 10
    mesh = make_source_mesh(4)
    print(f"mesh: {dict(mesh.shape)}, {S} source lanes, {V} VWs; stream "
          f"{sizes.n_messages:,} msgs over {sizes.n_keys:,} keys",
          flush=True)
    keys = timed("sample stream", lambda: wp_keys(seed, sizes,
                                                  sizes.n_messages, 0))
    for sync in (1, 4):
        kw = dict(sync_every=sync, block=128, eps=0.01)
        print(f"mesh_porc_multisource vs ref_porc_multisource, "
              f"sync_every={sync}", flush=True)
        a_mesh, st = timed("mesh", lambda: mesh_porc_multisource(
            keys, V, mesh, n_sources=S, **kw))
        a_ref, st_ref = timed("single device", lambda: ref_porc_multisource(
            keys, V, S, **kw))
        check(bool(jnp.array_equal(a_mesh, a_ref)),
              "assignment bit-identical to the single-device engine")
        check(bool(jnp.array_equal(st.base, st_ref.base))
              and bool(jnp.array_equal(st.delta, st_ref.delta)),
              "merged base and lane deltas bit-identical")
        devs = st.delta.sharding.device_set
        check(len(devs) == 4, f"lane deltas span {len(devs)} devices")
    del keys, a_mesh, a_ref, st, st_ref

    kill_at = max(2, sizes.n_requests // BATCH // 4)
    print(f"ServingEngine on MeshCGRequestRouter, {n} replicas (2 slowed), "
          f"async submit, replica 5 killed at tick {kill_at}; "
          f"{sizes.n_requests:,} requests", flush=True)
    reqs = wp_keys(seed, sizes, sizes.n_requests, 1)
    router = MeshCGRequestRouter(n_replicas=n, alpha=10, n_sources=S,
                                 mesh=mesh, capacity_weighted=True)
    eng = ServingEngine(
        [lambda b: b] * n, router, max_batch=max_batch(n), async_submit=True,
        chaos=slowed_replicas(ChaosSchedule.kill_one(5, at=kill_at).events),
        heartbeat_timeout_steps=2)

    def conserved(e):
        if e.submitted != served(e) + e.in_flight:
            raise SystemExit(f"FAILED: conservation at tick {e.step_idx}: "
                             f"{e.submitted} != {served(e)} + {e.in_flight}")

    t = time.perf_counter()
    ticks = serve(eng, reqs, per_tick=conserved)
    print(f"  serve + drain: {time.perf_counter() - t:.2f} s wall "
          f"(compile included), {ticks} ticks", flush=True)
    print(f"  ok: submitted == served + in_flight at each of {ticks} "
          f"ticks", flush=True)
    check(eng.evacuations == 1 and not (router.vw_owner == 5).any(),
          f"replica 5 evacuated: owns no VW; {eng.retried:,} retried")
    check(eng.dropped == 0 and eng.in_flight == 0
          and eng.submitted == served(eng) == sizes.n_requests,
          f"drained: {served(eng):,} served, 0 dropped, 0 in flight")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-router phase, on 4 devices")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: small sizes, any platform, "
                         "Pallas in interpret mode")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    from repro.kernels.backend import resolve_engine, resolve_interpret

    devices = jax.devices()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
          f"compile cache {cache}", flush=True)
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"no TPU: JAX found {dev.platform!r}; this smoke "
                         f"runs on the chip (--rehearse for the CPU)")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices;"
                         f" JAX found {len(devices)}")
    sizes = REHEARSAL if args.rehearse else FULL
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed, sizes)
    else:
        # on the chip the default engine must be the compiled kernel; the
        # rehearsal names the kernel explicitly (interpreted on the CPU)
        engine = "pallas" if args.rehearse else "auto"
        if not args.rehearse:
            check(resolve_engine("auto") == "pallas"
                  and not resolve_interpret(None),
                  "engine 'auto' resolves to the compiled Pallas kernel")
        one_chip(args.seed, sizes, engine)
    print(f"total {time.perf_counter() - t0:.2f} s wall", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
