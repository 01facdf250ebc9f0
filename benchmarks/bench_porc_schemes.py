"""Paper Fig. 4 — normalized imbalance & memory for all schemes across
zipf skew and virtual-worker counts (standalone partitioner comparison),
plus the block-path throughput gate: the block-parallel PoRC runtime
must (a) be bit-identical to the sequential oracle at block=1 and
(b) route ≥10x more msgs/sec than the oracle while staying inside the
(1+eps) capacity envelope (up to block staleness).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics, partitioners as P, streams
from repro.kernels.ref import ref_porc_route

from .common import fmt, record, table, time_median

SCHEMES = ("KG", "PKG", "POTC", "CH", "PORC", "SG")


def _fig4(m: int, n_keys: int, eps: float, quick: bool):
    zs = (0.8, 1.4) if quick else (0.4, 0.8, 1.2, 1.6, 2.0)
    vws = (10, 100) if quick else (10, 100, 1000)
    rows = []
    for z in zs:
        keys = streams.sample_zipf_stream(jax.random.PRNGKey(0), m, n_keys, z)
        for n in vws:
            caps = jnp.ones(n) / n
            row = [z, n]
            for s in SCHEMES:
                a = P.route(s, keys, n, eps=eps)
                imb = float(metrics.normalized_imbalance(a, caps))
                record("porc_schemes", section="fig4_imbalance", z=z,
                       n_bins=n, scheme=s, imbalance=imb)
                row.append(fmt(imb, 3))
            rows.append(row)
    print(table("Fig 4a — normalized imbalance (zipf × #virtual workers)",
                ["z", "VWs", *SCHEMES], rows))

    rows = []
    for z in zs:
        keys = streams.sample_zipf_stream(jax.random.PRNGKey(0), m, n_keys, z)
        uniq = int(jnp.unique(keys).size)
        for n in vws:
            row = [z, n]
            for s in SCHEMES:
                a = P.route(s, keys, n, eps=eps)
                mem = int(metrics.memory_footprint(a, keys, n, n_keys))
                record("porc_schemes", section="fig4_memory", z=z, n_bins=n,
                       scheme=s, replication=mem / uniq)
                row.append(fmt(mem / uniq, 2))      # replication factor
            rows.append(row)
    print(table("Fig 4b — memory overhead (replication factor = keys stored "
                "/ unique keys)", ["z", "VWs", *SCHEMES], rows))
    print("paper-claim check: PoRC/CH imbalance ≈ eps; PoRC replication "
          "≈ KG(=1.0) ≪ SG/PoTC")


def _block_path_gate(quick: bool):
    """Throughput + exactness gate for the block-parallel fast path."""
    n, eps = 100, 0.05
    m = 65_536 if quick else 262_144
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(0), m, 10_000, 1.2)

    # (a) bit-exactness of the block path at block=1
    short = keys[:4096]
    a_seq = np.asarray(P.power_of_random_choices(short, n, eps=eps))
    a_b1 = np.asarray(
        P.power_of_random_choices_blocked(short, n, eps=eps, block=1))
    exact = bool((a_seq == a_b1).all())
    assert exact, "block path with block=1 diverged from the oracle"

    t_seq, a0 = time_median(lambda: P.power_of_random_choices(keys, n, eps=eps),
                      reps=3)
    seq_rate = m / t_seq
    caps = jnp.ones(n) / n
    imb_seq = float(metrics.normalized_imbalance(a0, caps))
    record("porc_schemes", section="block_throughput", path="sequential",
           block=1, m=m, n_bins=n, eps=eps, msgs_per_sec=seq_rate,
           imbalance=imb_seq, b1_exact=exact)

    rows = [["oracle", fmt(t_seq * 1e3, 1), fmt(seq_rate / 1e6, 2), "1.0",
             fmt(imb_seq, 4)]]
    best = 0.0
    for B in (128, 256, 512):
        tb, (a, state) = time_median(
            lambda: ref_porc_route(keys, n, block=B, eps=eps), reps=10)
        load = state.load
        imb = float(metrics.normalized_imbalance(a, caps))
        # capacity envelope up to block staleness (≤ B dupes per bin)
        assert float(load.max()) <= (1 + eps) * m / n + B, \
            f"block={B} breached the (1+eps) envelope"
        rate = m / tb
        best = max(best, rate / seq_rate)
        record("porc_schemes", section="block_throughput", path="block",
               block=B, m=m, n_bins=n, eps=eps, msgs_per_sec=rate,
               imbalance=imb, speedup_vs_sequential=rate / seq_rate)
        rows.append([f"block {B}", fmt(tb * 1e3, 1), fmt(rate / 1e6, 2),
                     fmt(rate / seq_rate, 1), fmt(imb, 4)])
    print(table(f"Block-parallel PoRC vs sequential oracle "
                f"(m={m}, {n} VWs, eps={eps})",
                ["path", "ms", "M msg/s", "speedup", "imbalance"], rows))
    print(f"gate: block=1 bit-identical: {exact}; "
          f"best speedup {best:.1f}x (target ≥ 10x)")
    record("porc_schemes", section="block_throughput_summary",
           best_speedup=best, b1_exact=exact)


def run(m: int = 50_000, n_keys: int = 10_000, eps: float = 0.01,
        quick: bool = False):
    _fig4(m, n_keys, eps, quick)
    _block_path_gate(quick)


if __name__ == "__main__":
    run()
