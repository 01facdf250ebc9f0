"""Multi-host serving on a device mesh — the scale-out gate.

Routes a keyed Zipf stream across ``--hosts`` devices (default: every
device this process has): source lanes live on a 1-D ``("sources",)``
device mesh, per-block routing runs under ``shard_map`` and the
delta-merge is a ``jax.lax.psum`` (``repro.kernels.mesh``).

Three measurements, all recorded into BENCH_results.json:

* **exactness** — sharded assignment bit-identical to the vmapped
  single-host engine at ``sync_every=1`` (asserted unconditionally;
  this is the acceptance-criteria cell CI gates).
* **throughput** — sharded msgs/sec vs the vmapped single-host engine
  on the same stream. ``--gate`` asserts the ratio: ≥ 1.0 when the
  machine has at least ``hosts`` CPU cores (real parallel headroom),
  else ≥ 0.7 (the partitioning-overhead bound — 8 fake devices on
  fewer cores share the same silicon, so parity is the ceiling, not
  speedup; the measured ratio is printed either way).
* **chaos conservation** — a ``ServingEngine`` on a
  ``MeshCGRequestRouter`` with the async submit path takes a kill-one
  mid-run; ``submitted == served + in_flight`` is asserted at every
  tick and the drain must end with zero in flight, zero dropped.

The measurement runs in this process on the devices it has: one
process per chip, so nothing here starts a child that would need the
chip this process already holds. Asking for more hosts than devices
fails. To simulate hosts on the CPU, set the device count from outside,
before JAX starts:
``XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu``.

``--demo`` routes a paper-scale stream (2^21 messages, 8192 bins)
across the mesh and prints per-host lane stats — the §V-C topology at
deployment size.
"""
from __future__ import annotations

import argparse
import os

from .common import fmt, record, table, time_median


def _workload(quick: bool, demo: bool):
    if demo:
        return dict(M=2**21, n_bins=8192, block=2048, chunk=16)
    if quick:
        return dict(M=131072, n_bins=8192, block=2048, chunk=16)
    return dict(M=524288, n_bins=8192, block=2048, chunk=16)


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def _measure(hosts: int, quick: bool, demo: bool) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.mesh import mesh_porc_multisource
    from repro.kernels.ref import ref_porc_multisource
    from repro.launch.mesh import make_source_mesh
    from repro.runtime.chaos import ChaosSchedule
    from repro.serve import MeshCGRequestRouter, ServingEngine

    S = hosts
    mesh = make_source_mesh(hosts)
    w = _workload(quick, demo)
    rng = np.random.default_rng(0)
    keys = jnp.asarray((rng.zipf(1.2, w["M"]) % 100_000).astype(np.int32))
    rows = []

    # -- exactness: the CI-gated sync_every=1 cell (ragged length on
    # purpose: spans + tail must match too)
    ke = keys[: (4096 + 7 if quick else 65536 + 7)]
    a_ref, _ = ref_porc_multisource(ke, w["n_bins"], S, sync_every=1,
                                    block=w["block"], chunk=w["chunk"])
    a_mesh, _ = mesh_porc_multisource(ke, w["n_bins"], mesh, n_sources=S,
                                      sync_every=1, block=w["block"],
                                      chunk=w["chunk"])
    exact = bool(jnp.array_equal(a_ref, a_mesh))
    assert exact, "sharded routing diverged from the single-host engine"
    rows.append(dict(scenario="exactness", hosts=hosts, sync_every=1,
                     n_msgs=int(ke.shape[0]), exact=True))

    # -- throughput: sharded vs vmapped single-host on the same stream
    with jax.set_mesh(mesh):
        t_mesh, _ = time_median(lambda: mesh_porc_multisource(
            keys, w["n_bins"], mesh, n_sources=S, sync_every=1,
            block=w["block"], chunk=w["chunk"]))
    t_ref, _ = time_median(lambda: ref_porc_multisource(
        keys, w["n_bins"], S, sync_every=1, block=w["block"],
        chunk=w["chunk"]))
    ratio = t_ref / t_mesh
    rows.append(dict(scenario="throughput", hosts=hosts, mode="sharded",
                     n_msgs=w["M"], msgs_per_sec=w["M"] / t_mesh,
                     ratio=ratio, cpu_cores=os.cpu_count()))
    rows.append(dict(scenario="throughput", hosts=hosts, mode="single_host",
                     n_msgs=w["M"], msgs_per_sec=w["M"] / t_ref))

    if demo:
        a, st = mesh_porc_multisource(keys, w["n_bins"], mesh, n_sources=S,
                                      sync_every=1, block=w["block"],
                                      chunk=w["chunk"])
        load = np.asarray(st.base)
        rows.append(dict(scenario="demo", hosts=hosts, n_msgs=w["M"],
                         n_bins=w["n_bins"],
                         imbalance=float(load.max() / load.mean() - 1.0)))

    # -- chaos conservation on the mesh: async submit + kill-one
    n_rep = 8
    router = MeshCGRequestRouter(n_replicas=n_rep, alpha=4, n_sources=S,
                                 mesh=mesh, capacity_weighted=True)
    eng = ServingEngine([lambda b: b for _ in range(n_rep)], router,
                        max_batch=8, async_submit=True,
                        chaos=ChaosSchedule.kill_one(3, at=6),
                        heartbeat_timeout_steps=2)
    ticks = 20 if quick else 40
    for _ in range(ticks):
        kb = (rng.zipf(1.3, 64) % 4096).astype(np.int32)
        eng.submit_batch(kb, [None] * 64)
        eng.step()
        served = sum(r.served for r in eng.replicas)
        assert eng.submitted == served + eng.in_flight, \
            "per-tick conservation violated under chaos"
    for _ in range(500):
        if eng.in_flight == 0:
            break
        eng.step()
    served = sum(r.served for r in eng.replicas)
    assert eng.submitted == served + eng.in_flight
    rows.append(dict(scenario="chaos_kill_one", hosts=hosts,
                     submitted=eng.submitted, served=served,
                     in_flight_end=eng.in_flight, dropped=eng.dropped,
                     retried=eng.retried, evacuations=eng.evacuations))
    return rows


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def run(quick: bool = False, gate: bool = False, demo: bool = False,
        hosts: int | None = None, min_ratio: float | None = None):
    import jax
    have = len(jax.devices())
    hosts = have if hosts is None else hosts
    if hosts > have:
        raise RuntimeError(
            f"--hosts {hosts} needs {hosts} devices; this process has "
            f"{have} ({jax.devices()[0].device_kind}). On the CPU, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={hosts} "
            f"before starting.")
    rows = _measure(hosts, quick, demo)
    for r in rows:
        record("multihost", **r)

    thr = {r["mode"]: r for r in rows if r.get("scenario") == "throughput"}
    chaos = next(r for r in rows if r["scenario"] == "chaos_kill_one")
    ratio = thr["sharded"]["ratio"]
    cores = thr["sharded"].get("cpu_cores") or 1
    print(table(
        f"multi-host serving on {hosts} x {jax.devices()[0].device_kind}",
        ["scenario", "msgs/sec", "ratio", "dropped"],
        [["sharded", fmt(thr["sharded"]["msgs_per_sec"], 0),
          fmt(ratio, 2), "-"],
         ["single_host", fmt(thr["single_host"]["msgs_per_sec"], 0),
          "1.00", "-"],
         ["chaos_kill_one", "-", "-", chaos["dropped"]]]))
    print(f"exactness at sync_every=1: OK; chaos: served "
          f"{chaos['served']}/{chaos['submitted']}, "
          f"retried {chaos['retried']}, dropped {chaos['dropped']}")
    d = next((r for r in rows if r.get("scenario") == "demo"), None)
    if d:
        print(f"demo: {d['n_msgs']:,} msgs over {d['hosts']} hosts, "
              f"{d['n_bins']} bins, imbalance {d['imbalance']:.4f}")
    if gate:
        need = min_ratio if min_ratio is not None else (
            1.0 if cores >= hosts else 0.7)
        assert ratio >= need, (
            f"sharded throughput ratio {ratio:.2f} below the "
            f"{need:.2f} gate ({cores} cores for {hosts} hosts)")
        assert chaos["dropped"] == 0 and chaos["in_flight_end"] == 0
        print(f"gate OK (ratio {ratio:.2f} >= {need:.2f}, zero dropped)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--gate", action="store_true")
    ap.add_argument("--demo", action="store_true")
    ap.add_argument("--hosts", type=int, default=None,
                    help="mesh size (default: every device present)")
    ap.add_argument("--min-ratio", type=float, default=None)
    args = ap.parse_args()
    run(quick=args.quick, gate=args.gate, demo=args.demo,
        hosts=args.hosts, min_ratio=args.min_ratio)


if __name__ == "__main__":
    main()
