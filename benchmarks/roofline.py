"""§Roofline — three-term roofline per (arch × shape × mesh) cell.

Sources
-------
* ``results/dryrun/*.json`` — the compiled dry-run artifacts:
  memory_analysis (per-device bytes), cost_analysis flops (RAW —
  undercounts lax.scan bodies, see note), parsed per-device collective
  bytes from the post-SPMD HLO.
* Analytic FLOP/byte model below — exact matmul dims from the configs,
  with documented factors for backward (2×fwd), full-remat recompute
  (+1×fwd) and the baseline's causal-waste in chunked attention (it
  computes all chunk pairs). This is the scan-corrected compute/memory
  number; EXPERIMENTS.md §Dry-run records the raw cost_analysis values
  alongside.

Terms (TPU v5e):
  compute    = FLOPs / (chips · 197e12)
  memory     = HBM bytes / (chips · 819e9)
  collective = collective bytes per device / 50e9

MODEL_FLOPS (useful) = 6·N_active·tokens (train) or 2·N_active·tokens
(inference) + causality-honoring attention flops. The ratio
useful/computed exposes remat + causal waste.
"""
from __future__ import annotations

import glob
import json
import os

import jax

from repro import configs
from repro.configs.base import SHAPES
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS

from .common import fmt, record, table, time_median, wp_keys

# CPU-backend bf16→f32 legalization inflates temp memory vs a native-bf16
# TPU program; measured 2.1× on the layer microbenchmark (DESIGN.md §9 /
# EXPERIMENTS.md §Dry-run methodology).
CPU_BF16_TEMP_FACTOR = 2.1


# ---------------------------------------------------------------------------
# Analytic FLOPs / bytes
# ---------------------------------------------------------------------------

def _attn_flops(cfg, B, S, causal_honored: bool):
    """Attention matmul flops (qk + av), forward, whole model."""
    if cfg.family == "ssm":
        return 0.0
    H, Dh = cfg.n_heads, cfg.d_head
    if cfg.family == "hybrid":
        L_attn = cfg.n_layers // cfg.shared_attn_every
        f = 4.0 * B * S * S * H * Dh * L_attn
        return f / 2 if causal_honored else f
    if cfg.family == "audio":
        L = cfg.n_layers
        enc = 4.0 * B * S * S * H * Dh * cfg.n_enc_layers
        dec_self = 4.0 * B * S * S * H * Dh * L
        cross = 4.0 * B * S * S * H * Dh * L
        if causal_honored:
            dec_self /= 2
        return enc + dec_self + cross
    L = cfg.n_layers
    if cfg.sliding_window:
        ge = cfg.global_every
        n_glob = (L // ge) if ge else 0
        n_loc = L - n_glob
        W = min(cfg.sliding_window, S)
        f = 4.0 * B * H * Dh * (n_loc * S * W + n_glob * S * S)
    else:
        f = 4.0 * B * H * Dh * L * S * S
    return f / 2 if causal_honored else f


def _ssd_flops(cfg, B, S):
    if cfg.family not in ("ssm", "hybrid"):
        return 0.0
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    Q = min(s.chunk, S)
    # per chunk per head: CBᵀ (2Q²N) + WX (2Q²P) + state in/out (4QPN)
    per_head = 2.0 * Q * Q * (s.d_state + s.head_dim) \
        + 4.0 * Q * s.head_dim * s.d_state
    return B * (S / Q) * H * per_head * cfg.n_layers


def cell_flops(cfg, shape, n_active):
    """(useful, computed) model flops, whole step, all chips."""
    B, S = shape.global_batch, shape.seq_len
    # triangular attention schedule (§Perf H1): computed attention ≈
    # causal-honoring with one boundary chunk of slack per q chunk
    tri_slack = 1.0 + 1.0 / max(S // cfg.q_chunk, 1)
    if shape.kind == "train":
        tokens = B * S
        fwd_useful = 2.0 * n_active * tokens \
            + _attn_flops(cfg, B, S, True) + _ssd_flops(cfg, B, S)
        fwd_computed = 2.0 * n_active * tokens \
            + _attn_flops(cfg, B, S, True) * tri_slack + _ssd_flops(cfg, B, S)
        useful = 3.0 * fwd_useful            # fwd + bwd(2×)
        computed = 4.0 * fwd_computed        # + full-remat re-forward
        return useful, computed
    if shape.kind == "prefill":
        tokens = B * S
        useful = 2.0 * n_active * tokens \
            + _attn_flops(cfg, B, S, True) + _ssd_flops(cfg, B, S)
        computed = 2.0 * n_active * tokens \
            + _attn_flops(cfg, B, S, True) * tri_slack + _ssd_flops(cfg, B, S)
        return useful, computed
    # decode: one token against S of history
    tokens = B
    f = 2.0 * n_active * tokens
    if cfg.family in ("ssm",):
        attn = 0.0
    elif cfg.family == "hybrid":
        L_attn = cfg.n_layers // cfg.shared_attn_every
        attn = 4.0 * B * S * cfg.n_heads * cfg.d_head * L_attn
    elif cfg.family == "audio":
        attn = 4.0 * B * S * cfg.n_heads * cfg.d_head * cfg.n_layers * 2
    elif cfg.sliding_window and cfg.global_every:
        L = cfg.n_layers
        ng = L // cfg.global_every
        attn = 4.0 * B * cfg.n_heads * cfg.d_head * (
            (L - ng) * min(cfg.sliding_window, S) + ng * S)
    else:
        attn = 4.0 * B * S * cfg.n_heads * cfg.d_head * cfg.n_layers
    return f + attn, f + attn


def cell_bytes(cfg, shape, n_total, report):
    """Analytic HBM bytes per device per step (whole-step traffic)."""
    chips = report["n_devices"]
    B, S = shape.global_batch, shape.seq_len
    p_bf16 = 2.0 * n_total / chips
    if shape.kind == "train":
        # params: fwd read + remat re-read + bwd read + write (4×);
        # optimizer m/v/master read+write (2 × 12B/param)
        param_traffic = 4.0 * p_bf16 + 2.0 * 12.0 * n_total / chips
        # activations: saved residual stream write+read (seq-sharded)
        carry = 2.0 * cfg.n_layers * B * S * cfg.d_model * 2.0 / chips
        # transient traffic proxy: corrected temp touched ~2×
        temp = (report["memory"].get("temp_size_in_bytes") or 0)
        transient = 2.0 * temp / CPU_BF16_TEMP_FACTOR
        return param_traffic + carry + transient
    if shape.kind == "prefill":
        param_traffic = p_bf16
        temp = (report["memory"].get("temp_size_in_bytes") or 0)
        return param_traffic + 2.0 * temp / CPU_BF16_TEMP_FACTOR
    # decode: read all (active) params once + read/write KV cache slice
    act_bytes = 2.0 * report["params_active"] / chips
    cache_bytes = (report["memory"].get("argument_size_in_bytes") or 0) * 0.5
    return act_bytes + cache_bytes


# ---------------------------------------------------------------------------
# Table builder
# ---------------------------------------------------------------------------

def load_reports(results_dir="results/dryrun"):
    reps = []
    for f in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        r = json.load(open(f))[0]
        if r.get("ok"):
            reps.append(r)
    return reps


def analyze(report):
    cfg = configs.get_config(report["arch"])
    shape = SHAPES[report["shape"]]
    chips = report["n_devices"]
    useful, computed = cell_flops(cfg, shape, report["params_active"])
    t_comp = computed / (chips * PEAK_FLOPS)
    t_useful = useful / (chips * PEAK_FLOPS)
    hbm = cell_bytes(cfg, shape, report["params_total"], report)
    t_mem = hbm / HBM_BW
    t_coll = report["collectives"]["total_bytes"] / ICI_BW
    dom = max((t_comp, "compute"), (t_mem, "memory"), (t_coll, "collective"))
    frac = t_useful / max(t_comp, t_mem, t_coll, 1e-12)
    return {
        "arch": report["arch"], "shape": report["shape"],
        "mesh": report["mesh"],
        "t_comp": t_comp, "t_mem": t_mem, "t_coll": t_coll,
        "dominant": dom[1], "useful_frac": frac,
        "useful_flops": useful, "computed_flops": computed,
        "raw_hlo_flops": (report.get("flops_per_device") or 0) * chips,
        "temp_gib": (report["memory"].get("temp_size_in_bytes") or 0) / 2**30,
    }


_FIX_NOTES = {
    "compute": "cut remat/causal waste: triangular attention schedule, "
               "dots remat where memory allows",
    "memory": "raise arithmetic intensity: fuse norms/rope, larger "
              "per-chip batch, bf16 optimizer reads",
    "collective": "overlap or shrink the exchange: shard_map all-to-all "
                  "for MoE, reduce-scatter grads, avoid SP round-trips",
}


def _measured_porc(quick: bool):
    """Measured routing roofline: per-(block, n_bins, scheme) cells on
    the WP trace, the one PoRC block engine two ways each —

      jnp-block   the jnp scan (``ref_porc_route``: the multi-source
                  engine at S=1)
      pallas      the same block engine as a Pallas kernel with the
                  load vector in VMEM and candidate hashing fused into
                  the probe scan (``kernels.porc_snapshot``)

    Unlike the dry-run table below this always runs, so CI's
    BENCH_results.json carries real routing-roofline rows even when no
    compiled dry-run artifacts are present. On the CI backend (CPU) the
    Pallas rows execute in **interpret mode** — they are a semantics +
    bit-parity signal (``pallas_exact``), *not* a kernel speed number;
    ``pallas_over_block < 1`` is expected there. The compiled column is
    run manually on a TPU VM (``python -m benchmarks.run --quick``
    writes the same rows with ``backend=tpu`` and Mosaic timings).
    """
    import numpy as np

    from repro.kernels.blocks import HHPolicy
    from repro.kernels.ref import ref_porc_route

    backend = jax.default_backend()
    eps = 0.05
    M = 8192 if quick else 65536
    cells = [(512, 1024)] if quick else [(512, 1024), (128, 256)]
    keys = wp_keys(M)
    rows = []
    # W-Choices cell: the HH policy path, where the Pallas kernel also
    # fuses the count-min sketch update + budget lookup into the scan.
    schemes = [("porc", cell, None) for cell in cells] + [
        ("wchoices", cells[0], HHPolicy(scheme="w", width=1024))]
    for scheme, (block, n_bins), pol in schemes:
        t_block, (a_block, _) = time_median(
            lambda: ref_porc_route(keys, n_bins, block=block, eps=eps,
                                   policy=pol))
        t_pal, (a_pal, _) = time_median(
            lambda: ref_porc_route(keys, n_bins, block=block, eps=eps,
                                   policy=pol, engine="pallas"))
        exact = bool((np.asarray(a_block) == np.asarray(a_pal)).all())
        record("roofline", scenario="porc_engines", scheme=scheme,
               backend=backend, n_msgs=M, n_bins=n_bins, block=block,
               block_msgs_per_sec=M / t_block,
               pallas_msgs_per_sec=M / t_pal,
               pallas_over_block=t_block / t_pal,
               pallas_exact=exact)
        rows.append([scheme, block, n_bins, fmt(M / t_block, 0),
                     fmt(M / t_pal, 0), fmt(t_block / t_pal, 2), exact])
    mode = "compiled" if backend == "tpu" else "interpret"
    print(table(f"§Roofline — measured PoRC engines (WP trace, "
                f"backend={backend}, pallas={mode})",
                ["scheme", "block", "n_bins", "jnp-block msg/s",
                 "pallas msg/s", "pallas/jnp", "exact"], rows))


def run(quick: bool = False, results_dir: str = "results/dryrun"):
    _measured_porc(quick)
    reps = load_reports(results_dir)
    if not reps:
        print("no dry-run reports found — run "
              "`python -m repro.launch.dryrun --all --out ...` first "
              "(measured PoRC rows above were still recorded)")
        return
    rows = []
    for r in reps:
        a = analyze(r)
        rows.append([a["arch"], a["shape"], a["mesh"],
                     fmt(a["t_comp"] * 1e3, 2), fmt(a["t_mem"] * 1e3, 2),
                     fmt(a["t_coll"] * 1e3, 2), a["dominant"],
                     fmt(a["useful_frac"], 3)])
    print(table("§Roofline — per-cell terms (ms/step per chip, TPU v5e "
                "197TF/819GBs/50GBs)",
                ["arch", "shape", "mesh", "compute", "memory",
                 "collective", "dominant", "useful/roof"], rows))
    print("\nfix-it guide per dominant term:")
    for k, v in _FIX_NOTES.items():
        print(f"  {k:10s}: {v}")


if __name__ == "__main__":
    run()
