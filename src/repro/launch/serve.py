"""Serving driver: prefill + decode with the CG request router.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b \
      --requests 64 --decode-steps 8 [--replicas 4] [--hetero]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import model_zoo as zoo
from repro.serve import CGRequestRouter, ServingEngine

from . import steps
from .mesh import make_smoke_mesh


def build_replica(cfg, params, decode_steps: int, slow: float = 0.0,
                  max_batch: int = 8, decode=None):
    """A replica fn: batch of token prompts → generated ids.

    Batches are padded to ``max_batch`` so the decode step keeps one
    fixed compiled shape (continuous-batching style). All replicas share
    one jitted ``decode`` (pass it in) — they serve the same model."""
    if decode is None:
        decode = jax.jit(lambda p, c, t: zoo.decode_step(p, cfg, c, t))

    def run(payloads):
        B = len(payloads)
        prompts = np.zeros((max_batch, 1), np.int32)
        prompts[:B] = np.asarray(payloads, np.int32).reshape(B, 1)
        cache = zoo.init_cache(cfg, max_batch, 64)
        tok = jnp.asarray(prompts[:, :1])
        out = []
        for _ in range(decode_steps):
            logits, cache = decode(params, cache, tok)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            out.append(np.asarray(tok))
        if slow:
            time.sleep(slow)                                # heterogeneity
        return np.concatenate(out, axis=1)[:B]

    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=configs.ARCH_IDS)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--decode-steps", type=int, default=8)
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--hetero", action="store_true",
                    help="make one replica 5x slower (Fig 15 setup)")
    args = ap.parse_args()

    cfg = configs.get_smoke_config(args.arch)
    mesh = make_smoke_mesh()
    steps.install_act_rules(mesh)
    mesh_ctx = jax.set_mesh(mesh)
    mesh_ctx.__enter__()
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))

    shared_decode = jax.jit(lambda p, c, t: zoo.decode_step(p, cfg, c, t))
    fns = []
    for r in range(args.replicas):
        slow = 0.05 if (args.hetero and r == 0) else 0.0
        fns.append(build_replica(cfg, params, args.decode_steps, slow,
                                 decode=shared_decode))
    engine = ServingEngine(fns, CGRequestRouter(args.replicas))

    rng = np.random.default_rng(0)
    zipf_keys = rng.zipf(1.3, size=args.requests) % 1000    # skewed sessions
    prompts = rng.integers(0, cfg.vocab, size=(args.requests, 1))
    t0 = time.time()
    engine.submit_batch(zipf_keys.astype(np.int32), list(prompts))
    served = 0
    while served < args.requests:
        served += engine.step()
    dt = time.time() - t0
    lat = np.asarray(engine.latencies)
    print(f"served {served} requests in {dt:.2f}s "
          f"({served/dt:.1f} req/s); latency mean {lat.mean()*1e3:.1f}ms "
          f"p99 {np.percentile(lat, 99)*1e3:.1f}ms; "
          f"router moves {engine.router.moves}; "
          f"per-replica served {[r.served for r in engine.replicas]}")


if __name__ == "__main__":
    main()
