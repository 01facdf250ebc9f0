"""End-to-end CG-routed MoE training (ROADMAP: MoE at training scale).

Runs a few hundred real optimizer steps on the dry-run (smoke) mesh for
both assigned MoE geometries, comparing the standard capacity-bounded
top-k router (drops overflow tokens) against the paper's CG router
(overflow probes the token's next-choice experts), each under uniform
and skewed per-expert capacities — the Fig 15 heterogeneous-cluster
story transplanted onto the expert axis. Records tokens dropped,
expert-load CV, median step time and the loss curve per cell.

Gates (``--gate`` / the moe_train CI block):
  * CG drop_frac <= top-k drop_frac at capacity skew >= 1
  * per-expert load never exceeds cap_e (max load/cap_e <= 1 exactly)
  * CG step-time overhead <= 1.15x top-k at the same skew
  * scalar-capacity dispatch bit-identical to the uniform capacities-
    vector path (ref and Pallas kernel)
  * loss finite everywhere and decreasing over the run

  python -m benchmarks.bench_moe_train [--quick] [--gate]
         [--arch phi3.5-moe-42b-a6.6b] [--steps N]
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, optim
from repro.data import PipelineConfig, ShardedTokenPipeline
from repro.kernels.cg_dispatch import cg_dispatch
from repro.kernels.ref import ref_cg_dispatch
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_smoke_mesh
from repro.models import model_zoo as zoo

from .common import fmt, record, table

GEOMS = ("phi3.5-moe-42b-a6.6b", "qwen3-moe-235b-a22b")
SKEW = 3.0          # cap_0/cap_{E-1} = 1+SKEW at constant total budget
WARMUP = 3          # steps excluded from the step-time median
OVERHEAD_GATE = 1.15


def _cell_cfg(arch: str, router: str, skew: float):
    # widen the smoke geometry (d 64->128, expert FFN 32->128) so the
    # step is expert-compute-dominated like real training — at d=64 the
    # router is half the step and the overhead gate measures probe
    # latency, not training overhead
    cfg = configs.get_smoke_config(arch)
    return cfg.replace(
        d_model=128, d_head=32,
        moe=dataclasses.replace(cfg.moe, router=router, capacity_skew=skew,
                                d_ff_expert=128))


def _train_cell(arch: str, router: str, skew: float, n_steps: int,
                batch: int = 4, seq: int = 64) -> dict:
    """One (geometry, router, capacity-skew) training run."""
    cfg = _cell_cfg(arch, router, skew)
    mesh = make_smoke_mesh()
    steps_mod.install_act_rules(mesh)
    opt_cfg = optim.AdamWConfig(lr_peak=3e-4,
                                warmup_steps=max(2, n_steps // 10),
                                total_steps=n_steps)
    pipe = ShardedTokenPipeline(PipelineConfig(
        vocab=cfg.vocab, seq_len=seq, global_batch=batch))
    with jax.set_mesh(mesh):
        params = zoo.init_params(cfg, jax.random.PRNGKey(0))
        opt_state = optim.init(params)
        train_step = jax.jit(steps_mod.make_train_step(cfg, opt_cfg))
        losses, drops, loads, times = [], [], [], []
        max_load_frac = 0.0
        for step in range(n_steps):
            b = {"tokens": pipe.global_batch(step)[:batch]}
            t0 = time.time()
            params, opt_state, m = train_step(params, opt_state, b)
            jax.block_until_ready(m["loss"])
            dt = time.time() - t0
            losses.append(float(m["loss"]))
            drops.append(float(m["moe_drop_frac"]))
            max_load_frac = max(max_load_frac,
                                float(m["moe_max_load_frac"]))
            loads.append(np.asarray(m["moe_load"]))
            if step >= WARMUP:
                times.append(dt)
    load = np.mean(np.stack(loads[WARMUP:]), axis=0)
    return {
        "arch": arch, "router": router, "skew": skew, "steps": n_steps,
        "drop_frac": float(np.mean(drops[WARMUP:])),
        "load_cv": float(np.std(load) / (np.mean(load) + 1e-9)),
        "max_load_frac": max_load_frac,
        "step_ms": float(np.median(times) * 1e3),
        "loss_first": losses[0], "loss_final": float(np.mean(losses[-5:])),
        "loss_finite": bool(np.isfinite(losses).all()),
    }


def _scalar_vector_parity() -> bool:
    """Scalar-capacity dispatch must stay bit-identical to the uniform
    capacities-vector path — on the jnp oracle AND the Pallas kernel."""
    T, E, k, D = 256, 16, 2, 6
    r1, r2 = jax.random.split(jax.random.PRNGKey(0))
    probs = jax.nn.softmax(
        jax.random.normal(r1, (T, E)) + 2.0 * jax.random.normal(r2, (1, E)),
        -1)
    gates, pref = jax.lax.top_k(probs, D)
    pref = pref.astype(jnp.int32)
    cap = max(1, int(1.25 * T * k / E))
    caps = jnp.full((E,), cap, jnp.float32)
    for fn in (ref_cg_dispatch, cg_dispatch):
        s = fn(pref, gates, n_experts=E, k=k, capacity=cap)
        v = fn(pref, gates, n_experts=E, k=k, capacities=caps)
        if not all(bool(jnp.array_equal(a, b)) for a, b in zip(s, v)):
            return False
    return True


def run(quick: bool = False, gate: bool = False, arch: str | None = None,
        n_steps: int | None = None):
    n_steps = n_steps or (200 if quick else 400)
    geoms = [arch] if arch else list(GEOMS)
    parity = _scalar_vector_parity()
    record("moe_train", section="parity", exact=parity)
    print(f"scalar-capacity vs uniform-vector dispatch parity: "
          f"{'exact' if parity else 'DIVERGED'}")

    rows, failures = [], []
    if not parity:
        failures.append("scalar-capacity dispatch diverged from the "
                        "uniform capacities-vector path")
    for geom in geoms:
        cells = {}
        for router in ("topk", "cg"):
            for skew in (0.0, SKEW):
                c = _train_cell(geom, router, skew, n_steps)
                cells[(router, skew)] = c
                record("moe_train", section="cell", **c)
                rows.append([geom.split("-")[0], router, skew,
                             fmt(c["drop_frac"], 4), fmt(c["load_cv"], 3),
                             fmt(c["max_load_frac"], 3),
                             fmt(c["step_ms"], 1),
                             fmt(c["loss_first"], 3),
                             fmt(c["loss_final"], 3)])
        for skew in (0.0, SKEW):
            tk, cg = cells[("topk", skew)], cells[("cg", skew)]
            overhead = cg["step_ms"] / max(tk["step_ms"], 1e-9)
            record("moe_train", section="gate", arch=geom, skew=skew,
                   drop_cg=cg["drop_frac"], drop_tk=tk["drop_frac"],
                   cv_cg=cg["load_cv"], cv_tk=tk["load_cv"],
                   overhead=overhead,
                   max_load_frac=max(cg["max_load_frac"],
                                     tk["max_load_frac"]),
                   loss_final_cg=cg["loss_final"],
                   loss_final_tk=tk["loss_final"])
            if skew >= 1.0 and cg["drop_frac"] > tk["drop_frac"] + 1e-9:
                failures.append(
                    f"{geom} skew={skew}: CG drop {cg['drop_frac']:.4f} > "
                    f"top-k {tk['drop_frac']:.4f}")
            if overhead > OVERHEAD_GATE:
                failures.append(
                    f"{geom} skew={skew}: CG step-time overhead "
                    f"{overhead:.2f}x > {OVERHEAD_GATE}x")
            for c in (tk, cg):
                if c["max_load_frac"] > 1.0 + 1e-6:
                    failures.append(
                        f"{geom} {c['router']} skew={skew}: expert load "
                        f"{c['max_load_frac']:.4f}x its capacity (> 1)")
                if not c["loss_finite"]:
                    failures.append(
                        f"{geom} {c['router']} skew={skew}: non-finite loss")
                if c["loss_final"] >= c["loss_first"]:
                    failures.append(
                        f"{geom} {c['router']} skew={skew}: loss did not "
                        f"decrease ({c['loss_first']:.3f} -> "
                        f"{c['loss_final']:.3f})")

    print(table(
        f"MoE train: top-k-drop vs CG-overflow x uniform vs skewed "
        f"capacities ({n_steps} steps, drop/loadCV/step-time/loss)",
        ["geometry", "router", "skew", "drop", "loadCV", "maxload/cap",
         "step ms", "loss0", "lossN"], rows))
    for f in failures:
        print(f"GATE FAIL: {f}")
    if failures and gate:
        raise AssertionError("; ".join(failures))
    if not failures:
        print("gates OK: CG drop <= top-k at skew, load <= cap_e, "
              f"overhead <= {OVERHEAD_GATE}x, scalar parity, loss decreasing")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--gate", action="store_true",
                    help="fail (nonzero exit) on any gate violation")
    ap.add_argument("--arch", default=None, choices=GEOMS,
                    help="run one geometry only (CI smoke job)")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args()
    run(quick=args.quick, gate=args.gate, arch=args.arch,
        n_steps=args.steps)


if __name__ == "__main__":
    main()
