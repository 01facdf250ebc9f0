"""Paper Fig. 11 — CG stability across 1/10/50/100 sources.

Sources partition the stream round-robin (the paper assigns messages to
sources by SG); each source routes against its local load view — a
shared merged base plus its own unpublished delta — synchronized by
delta-merge every ``sync_every`` routing steps (§V-C piggybacking).
The whole figure is one ``ref_porc_multisource`` call per point
(vmapped across sources), so it also reports throughput.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import metrics
from repro.kernels.ref import ref_porc_multisource

from .common import fmt, record, table, time_median, wp_keys

# Per-source routing granularity for this figure: block=1 is the
# paper's one-message-per-unit-time semantics per source (zero in-block
# staleness; the vmap over sources is what makes it fast). The sync
# period in messages is then S·sync_every.
BLOCK = 1


def run(m: int = 128_000, quick: bool = False):
    # all source counts run in both modes
    srcs = (1, 10, 50, 100)
    ns = (10, 50) if quick else (5, 10, 50, 100)
    if quick:
        m = 64_000
    eps = 0.01
    keys = jnp.asarray(wp_keys(m))
    n_keys = 130_000
    rows = []
    for n in ns:
        vws = n * 10
        caps = jnp.ones(n) / n
        for s in srcs:
            t_ms, (a_vw, _) = time_median(
                lambda: ref_porc_multisource(keys, vws, s, sync_every=1,
                                             block=BLOCK, eps=eps))
            a_w = jnp.asarray(np.asarray(a_vw) % n, jnp.int32)
            imb = float(metrics.normalized_imbalance(a_w, caps))
            mem = int(metrics.memory_footprint(a_w, keys, n, n_keys))
            rate = m / t_ms
            record("sources", n_workers=n, sources=s, imbalance=imb,
                   memory=mem, msgs_per_sec=rate, wall_s=t_ms)
            rows.append([n, s, fmt(imb, 4), mem, fmt(rate / 1e6, 2)])
    print(table("Fig 11 — CG/PoRC imbalance, memory & throughput vs "
                "#sources (WP)",
                ["workers", "sources", "imbalance", "memory", "M msg/s"],
                rows))

    # sync-period knob: staleness window = S·sync_every messages
    rows = []
    n, vws = 10, 100
    caps = jnp.ones(n) / n
    for s in (10, 100):
        for sync_every in (1, 8, 64):
            t_ms, (a_vw, _) = time_median(
                lambda: ref_porc_multisource(keys, vws, s,
                                             sync_every=sync_every,
                                             block=BLOCK, eps=eps))
            imb = float(metrics.normalized_imbalance(
                jnp.asarray(np.asarray(a_vw) % n, jnp.int32), caps))
            record("sources", section="sync_sweep", sources=s,
                   sync_every=sync_every, imbalance=imb,
                   msgs_per_sec=m / t_ms)
            rows.append([s, sync_every, s * sync_every, fmt(imb, 4),
                         fmt(m / t_ms / 1e6, 2)])
    print(table(f"Sync-period tradeoff ({vws} VWs, block={BLOCK})",
                ["sources", "sync_every", "window msgs", "imbalance",
                 "M msg/s"], rows))

    print("paper-claim check: imbalance and memory stay flat (log scale) "
          "as sources grow 1→100 — local load views suffice")


if __name__ == "__main__":
    run()
