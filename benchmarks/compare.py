"""Diff two ``BENCH_results.json`` files — the benchmark regression signal.

  python -m benchmarks.compare BASELINE.json CURRENT.json
         [--max-wall-ratio X] [--max-rate-drop X] [--max-imbalance-ratio X]

For every figure the driver recorded it prints the wall-time ratio, and
for every record row present in both files (matched on its non-metric
identity fields) the msgs/sec and normalized-imbalance movement.
Without flags the diff is informational (exit 0); each ``--max-*`` flag
turns the corresponding movement into a hard gate. CI runs the
informational diff against the committed ``benchmarks/baseline_quick.json``
on every PR so perf drift is visible in the log, while the absolute
asserts (block-path speedup, delegation gates) live in the workflow.
"""
from __future__ import annotations

import argparse
import json
import sys

# record fields that are measurements, not identity
_METRICS = {
    "msgs_per_sec", "imbalance", "memory", "wall_s", "speedup",
    "speedup_vs_sequential", "best_speedup",
    "replication", "b1_exact", "error",
    "mean_latency_ms", "max_latency_ms", "mean_latency", "queue_spread",
    "moves", "spike_imbalance", "settled_imbalance",
    "kg_over_cg_mean_latency", "cg_over_kg_throughput", "parity",
    "settle_slots", "post_mean_imbalance", "flaps", "peak_budget",
    "settle_adaptive", "settle_best_static", "flash_flap_ratio",
    "flash_moves_ratio", "alpha10_flap_ratio",
    "repl_bound", "ms_parity",
    "pre_mean_latency_steps", "during_mean_latency_steps",
    "during_p99_latency_steps", "settled_mean_latency_steps",
    "settled_over_pre", "lost", "retried", "evacuations", "bytes_moved",
    "ratio", "exact", "served", "in_flight_end", "dropped", "submitted",
    "cpu_cores", "block_msgs_per_sec", "pallas_msgs_per_sec",
    "pallas_over_block", "pallas_exact",
    "drop_frac", "load_cv", "max_load_frac", "step_ms", "loss_first",
    "loss_final", "loss_finite", "drop_cg", "drop_tk", "cv_cg", "cv_tk",
    "overhead", "loss_final_cg", "loss_final_tk",
}


def _identity(rec: dict) -> tuple:
    return tuple(sorted((k, str(v)) for k, v in rec.items()
                        if k not in _METRICS))


def _index(bench: dict) -> dict:
    out = {}
    for rec in bench.get("records", []):
        out.setdefault(_identity(rec), rec)
    return out


def _fmt_ratio(new, old) -> str:
    if not old:
        return "-"
    return f"{new / old:.2f}x"


def compare(base: dict, cur: dict, max_wall_ratio: float | None,
            max_rate_drop: float | None,
            max_imbalance_ratio: float | None) -> list[str]:
    """Print the diff; return the list of gate violations."""
    violations: list[str] = []
    figures = sorted(set(base["benchmarks"]) | set(cur["benchmarks"]))
    for fig in figures:
        b = base["benchmarks"].get(fig)
        c = cur["benchmarks"].get(fig)
        if b is None or c is None:
            print(f"[{fig}] only in {'current' if b is None else 'baseline'}")
            continue
        wb, wc = b.get("wall_time_s"), c.get("wall_time_s")
        head = f"[{fig}] wall {wb}s -> {wc}s"
        if wb and wc:
            ratio = wc / wb
            head += f" ({ratio:.2f}x)"
            if max_wall_ratio and ratio > max_wall_ratio:
                violations.append(
                    f"{fig}: wall time {ratio:.2f}x > {max_wall_ratio}x")
        print(head)
        bi, ci = _index(b), _index(c)
        matched = sorted(set(bi) & set(ci))
        unmatched = len(set(bi) ^ set(ci))
        for key in matched:
            rb, rc = bi[key], ci[key]
            lines = []
            if "msgs_per_sec" in rb and "msgs_per_sec" in rc:
                rate_ratio = rc["msgs_per_sec"] / max(rb["msgs_per_sec"], 1e-9)
                lines.append(f"rate {_fmt_ratio(rc['msgs_per_sec'], rb['msgs_per_sec'])}")
                if max_rate_drop and rate_ratio < 1.0 / max_rate_drop:
                    violations.append(
                        f"{fig} {dict(key)}: msgs/sec dropped "
                        f"{1 / rate_ratio:.2f}x > {max_rate_drop}x")
            if "imbalance" in rb and "imbalance" in rc:
                lines.append(f"imbalance {rb['imbalance']:.4g} -> "
                             f"{rc['imbalance']:.4g}")
                imb_ratio = rc["imbalance"] / max(rb["imbalance"], 1e-9)
                if max_imbalance_ratio and imb_ratio > max_imbalance_ratio:
                    violations.append(
                        f"{fig} {dict(key)}: imbalance {imb_ratio:.2f}x "
                        f"> {max_imbalance_ratio}x")
            if lines:
                ident = " ".join(f"{k}={v}" for k, v in key)
                print(f"    {ident or '(run)'}: {', '.join(lines)}")
        if unmatched:
            print(f"    ({unmatched} rows without a counterpart skipped)")
    return violations


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("baseline")
    ap.add_argument("current")
    ap.add_argument("--max-wall-ratio", type=float, default=None,
                    help="fail if any figure's wall time grows past this")
    ap.add_argument("--max-rate-drop", type=float, default=None,
                    help="fail if any row's msgs/sec drops past this factor")
    ap.add_argument("--max-imbalance-ratio", type=float, default=None,
                    help="fail if any row's imbalance grows past this")
    args = ap.parse_args(argv)
    with open(args.baseline) as f:
        base = json.load(f)
    with open(args.current) as f:
        cur = json.load(f)
    print(f"baseline: {args.baseline} ({base['meta'].get('device', '?')}, "
          f"quick={base['meta'].get('quick')})")
    print(f"current:  {args.current} ({cur['meta'].get('device', '?')}, "
          f"quick={cur['meta'].get('quick')})")
    violations = compare(base, cur, args.max_wall_ratio, args.max_rate_drop,
                         args.max_imbalance_ratio)
    if violations:
        print("\nREGRESSIONS:")
        for v in violations:
            print(f"  - {v}")
        sys.exit(1)
    print("\nno gated regressions")


if __name__ == "__main__":
    main()
