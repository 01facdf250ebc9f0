"""End-to-end training driver.

Wires together: CG-sharded data pipeline → jit'd train step (FSDP×TP
mesh) → AdamW → async checkpointing → straggler delegation → elastic
failure response. On this CPU container it runs the reduced (smoke)
configs end-to-end; on a fleet the same driver runs the full configs
(the dry-run proves those compile and fit).

  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
      --steps 20 --batch 8 --seq 128 [--smoke] [--resume]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, optim
from repro.checkpoint import checkpointer as ckpt
from repro.data import PipelineConfig, ShardedTokenPipeline
from repro.models import model_zoo as zoo
from repro.runtime import DelegationBalancer, FTConfig, FaultTolerantRunner

from . import steps
from .mesh import make_smoke_mesh


def train(arch: str, n_steps: int = 20, batch: int = 8, seq: int = 128,
          smoke: bool = True, ckpt_dir: str = "/tmp/repro_ckpt",
          resume: bool = False, ckpt_every: int = 10,
          n_hosts: int = 4, lr: float = 3e-4, log_every: int = 1,
          fail_host_at: int | None = None):
    cfg = configs.get_smoke_config(arch) if smoke else configs.get_config(arch)
    mesh = make_smoke_mesh()
    steps.install_act_rules(mesh)
    mesh_ctx = jax.set_mesh(mesh)
    mesh_ctx.__enter__()
    opt_cfg = optim.AdamWConfig(lr_peak=lr, warmup_steps=max(2, n_steps // 10),
                                total_steps=n_steps)

    pipe = ShardedTokenPipeline(PipelineConfig(
        vocab=cfg.vocab, seq_len=seq, global_batch=batch, n_hosts=n_hosts))
    runner = FaultTolerantRunner(
        FTConfig(ckpt_dir=ckpt_dir, ckpt_every=ckpt_every),
        n_hosts=n_hosts, pipeline=pipe)
    balancer = DelegationBalancer(n_hosts)

    key = jax.random.PRNGKey(0)
    params = zoo.init_params(cfg, key)
    opt_state = optim.init(params)
    start_step = 0
    if resume:
        start_step, restored = runner.restore_latest(
            {"params": params, "opt": opt_state})
        if restored is not None:
            params, opt_state = restored["params"], restored["opt"]
            print(f"resumed from step {start_step}")

    # no donation here: freshly-initialized zero leaves can share a
    # deduped constant buffer, and donating it twice is an XLA error.
    train_step = jax.jit(steps.make_train_step(cfg, opt_cfg))

    def make_batch(step):
        tokens = pipe.global_batch(step)[:batch]
        b = {"tokens": tokens}
        if cfg.family == "audio":
            fkey = jax.random.fold_in(key, step)
            b["frames"] = jax.random.normal(
                fkey, (batch, seq, cfg.d_model), jnp.bfloat16)
        if cfg.family == "vlm":
            fkey = jax.random.fold_in(key, step)
            b["patches"] = jax.random.normal(
                fkey, (batch, cfg.n_patches, cfg.vision_dim), jnp.bfloat16)
        return b

    losses = []
    for step in range(start_step, n_steps):
        if fail_host_at is not None and step == fail_host_at:
            moved = runner.on_failure(n_hosts - 1)     # simulate a loss
            print(f"[ft] host {n_hosts-1} failed; re-paired shards: {moved}")
        t0 = time.time()
        params, opt_state, metrics = train_step(params, opt_state,
                                                make_batch(step))
        loss = float(metrics["loss"])
        losses.append(loss)
        dt = time.time() - t0
        # worker delegation: hosts report step time; balancer re-pairs
        for h in range(n_hosts):
            if runner.hosts[h].alive:
                balancer.observe(h, dt * (1.0 + 0.05 * h))
                runner.heartbeat(h)
        balancer.rebalance(pipe)
        runner.maybe_save(step, {"params": params, "opt": opt_state})
        if step % log_every == 0:
            print(f"step {step:4d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms", flush=True)
    runner.saver.wait()
    mesh_ctx.__exit__(None, None, None)
    return np.asarray(losses)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCH_IDS)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full config (fleet scale) instead of smoke")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--fail-host-at", type=int, default=None)
    args = ap.parse_args()
    losses = train(args.arch, n_steps=args.steps, batch=args.batch,
                   seq=args.seq, smoke=not args.full, resume=args.resume,
                   ckpt_dir=args.ckpt_dir, fail_host_at=args.fail_host_at)
    print(f"final loss {losses[-1]:.4f} (from {losses[0]:.4f})")


if __name__ == "__main__":
    main()
