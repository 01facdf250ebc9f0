"""Benchmark driver: one module per paper figure + roofline.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
                                          [--out BENCH_results.json]

Every run writes a single ``BENCH_results.json`` (per-figure wall time
plus the structured rows each module records — msgs/sec, imbalance,
memory) which CI uploads as an artifact; diffing those files across
commits is the benchmark regression signal.
"""
from __future__ import annotations

import argparse
import inspect
import platform
import time

import jax

from repro.launch.compile_cache import enable_compile_cache

from . import (bench_deployment, bench_dynamic, bench_epsilon,
               bench_failures, bench_heterogeneous, bench_hh_probing,
               bench_moe_router, bench_moe_train, bench_multihost,
               bench_porc_schemes, bench_queue, bench_schemes_workers,
               bench_sources, bench_virtual_workers, common, roofline)

ALL = [
    ("porc_schemes", bench_porc_schemes),      # Fig 4 + block-path gate
    ("epsilon", bench_epsilon),                # Fig 6
    ("schemes_workers", bench_schemes_workers),  # Fig 7/8
    ("queue", bench_queue),                    # Fig 9/10
    ("sources", bench_sources),                # Fig 11
    ("virtual_workers", bench_virtual_workers),  # Fig 12
    ("dynamic", bench_dynamic),                # Fig 13
    ("deployment", bench_deployment),          # Fig 14/15
    ("heterogeneous", bench_heterogeneous),    # Figs 9/10+12/13+15 via
                                               # the delegation runtime
    ("hh_probing", bench_hh_probing),          # D/W-Choices skew sweep
                                               # (arXiv:1510.05714)
    ("failures", bench_failures),              # kill-1-of-8 chaos +
                                               # migration-cost metering
    ("moe_router", bench_moe_router),          # beyond paper
    ("moe_train", bench_moe_train),            # end-to-end MoE training:
                                               # topk vs CG x uniform vs
                                               # skewed expert capacity
    ("multihost", bench_multihost),            # mesh-sharded serving
                                               # across the devices present
    ("roofline", roofline),                    # §Roofline
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="BENCH_results.json",
                    help="results JSON path ('' disables)")
    args = ap.parse_args()
    enable_compile_cache()
    names = [n for n, _ in ALL]
    if args.only and args.only not in names:
        raise SystemExit(f"unknown --only {args.only!r}; "
                         f"choose from: {', '.join(names)}")
    common.start_run({
        "quick": args.quick,
        "only": args.only,
        "backend": jax.default_backend(),
        "device": jax.devices()[0].device_kind,
        "platform": platform.platform(),
        "started_unix": round(time.time(), 1),
    })
    t0 = time.time()
    failed = []
    for name, mod in ALL:
        if args.only and args.only != name:
            continue
        t = time.time()
        print(f"\n{'='*72}\n[{name}]")
        accepts_quick = "quick" in inspect.signature(mod.run).parameters
        err = None
        try:
            mod.run(quick=args.quick) if accepts_quick else mod.run()
        except Exception as e:  # noqa: BLE001 — keep the sweep going
            err = f"{type(e).__name__}: {e}"
            failed.append(name)
            common.record(name, error=err)
        common.note_timing(name, time.time() - t)
        status = "done in" if err is None else "FAILED after"
        print(f"[{name}] {status} {time.time()-t:.1f}s"
              + (f": {err}" if err else ""), flush=True)
    print(f"\nall benchmarks done in {time.time()-t0:.1f}s")
    if args.out:
        path = common.write_results(args.out)
        print(f"wrote {path}")
    if failed:
        raise SystemExit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
