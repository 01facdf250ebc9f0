"""Optimizer, checkpointing, data pipeline, runtime, serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.checkpoint import checkpointer as ckpt
from repro.data import PipelineConfig, ShardedTokenPipeline
from repro.runtime import (DelegationBalancer, FTConfig, FaultTolerantRunner,
                           plan_remesh)
from repro.serve import CGRequestRouter, ServingEngine


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_minimizes_quadratic():
    cfg = optim.AdamWConfig(lr_peak=0.1, warmup_steps=5, total_steps=200,
                            weight_decay=0.0)
    params = {"w": jnp.full((4,), 5.0, jnp.bfloat16)}
    state = optim.init(params)
    for _ in range(100):
        g = jax.grad(lambda p: jnp.sum(p["w"].astype(jnp.float32) ** 2))(params)
        params, state, m = optim.update(params, g, state, cfg)
    assert float(jnp.abs(params["w"].astype(jnp.float32)).max()) < 1.0


def test_grad_clipping():
    cfg = optim.AdamWConfig(clip_norm=1.0)
    params = {"w": jnp.zeros((3,), jnp.float32)}
    state = optim.init(params)
    huge = {"w": jnp.full((3,), 1e6, jnp.float32)}
    _, _, m = optim.update(params, huge, state, cfg)
    assert float(m["grad_norm"]) > 1e5          # reported pre-clip


def test_schedule_warmup_and_decay():
    cfg = optim.AdamWConfig(lr_peak=1e-3, lr_min=1e-5, warmup_steps=10,
                            total_steps=100)
    lrs = [float(optim.schedule(cfg, jnp.asarray(s))) for s in
           [0, 5, 10, 50, 100]]
    assert lrs[0] < lrs[1] < lrs[2]
    assert lrs[2] >= lrs[3] >= lrs[4]
    assert abs(lrs[2] - 1e-3) < 1e-9


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def _tree():
    return {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.ones((2,), jnp.bfloat16)},
            "step": jnp.asarray(7, jnp.int32)}


def test_roundtrip(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 10, t)
    out = ckpt.restore(str(tmp_path), 10, jax.tree.map(np.asarray, t))
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


def test_latest_ignores_tmp(tmp_path):
    ckpt.save(str(tmp_path), 1, _tree())
    os.makedirs(tmp_path / "step_00000099.tmp")   # crashed write
    assert ckpt.latest_step(str(tmp_path)) == 1


def test_gc_keeps_max(tmp_path):
    for s in range(6):
        ckpt.save(str(tmp_path), s, _tree(), max_keep=3)
    assert sorted(ckpt.all_steps(str(tmp_path))) == [3, 4, 5]


def test_async_save(tmp_path):
    saver = ckpt.AsyncCheckpointer(str(tmp_path))
    saver.save(3, _tree())
    saver.wait()
    assert ckpt.latest_step(str(tmp_path)) == 3


def test_restore_shape_mismatch_raises(tmp_path):
    ckpt.save(str(tmp_path), 2, _tree())
    bad = _tree()
    bad["a"] = jnp.zeros((5, 5))
    with pytest.raises(AssertionError):
        ckpt.restore(str(tmp_path), 2, bad)


# ---------------------------------------------------------------------------
# data pipeline + straggler/elastic runtime
# ---------------------------------------------------------------------------

def test_pipeline_deterministic():
    cfg = PipelineConfig(vocab=100, seq_len=16, global_batch=8, n_hosts=2)
    p1 = ShardedTokenPipeline(cfg)
    p2 = ShardedTokenPipeline(cfg)
    np.testing.assert_array_equal(np.asarray(p1.global_batch(5)),
                                  np.asarray(p2.global_batch(5)))


def test_shard_move_shifts_share():
    cfg = PipelineConfig(vocab=100, seq_len=8, global_batch=16, n_hosts=2,
                         n_shards_per_host=4)
    p = ShardedTokenPipeline(cfg)
    b0 = p.host_batch(0, 0).shape[0]
    sid = p.move_shard(0, 1)
    assert sid is not None
    assert p.host_batch(0, 0).shape[0] < b0
    assert set(p.shards_of(0)) | set(p.shards_of(1)) == set(range(8))


def test_balancer_pairs_busy_idle():
    cfg = PipelineConfig(vocab=10, seq_len=4, global_batch=8, n_hosts=4)
    pipe = ShardedTokenPipeline(cfg)
    bal = DelegationBalancer(4)
    for _ in range(8):
        bal.observe(0, 2.0)     # straggler
        bal.observe(1, 1.0)
        bal.observe(2, 1.0)
        bal.observe(3, 0.5)     # fast
    moved = bal.rebalance(pipe)
    assert moved == [(0, 3)]
    assert len(pipe.shards_of(0)) == 7 and len(pipe.shards_of(3)) == 9


def test_balancer_fcfs_carryover():
    """A busy host the per-slot move budget could not serve keeps its
    queue position (shared delegation engine FCFS): it is paired next
    slot ahead of newer signals."""
    from repro.runtime import StragglerConfig
    cfg = PipelineConfig(vocab=10, seq_len=4, global_batch=8, n_hosts=4)
    pipe = ShardedTokenPipeline(cfg)
    bal = DelegationBalancer(4, StragglerConfig(max_moves_per_slot=1))
    for _ in range(8):
        bal.observe(0, 3.0)     # worst straggler
        bal.observe(1, 2.0)     # straggler too
        bal.observe(2, 1.0)
        bal.observe(3, 0.5)     # fast
    assert bal.rebalance(pipe) == [(0, 3)]   # budget 1: host 1 carried
    # next slot: host 0 recovered, host 1 unchanged — the carried host 1
    # is served even though its signal is a slot old, and pairs with
    # host 2, which also carried over from the slot-1 idle queue (its
    # relative slowdown put it under θ_i×median then)
    for _ in range(8):
        bal.observe(0, 1.0)
        bal.observe(1, 2.0)
        bal.observe(2, 1.0)
        bal.observe(3, 0.5)
    assert bal.rebalance(pipe) == [(1, 2)]
    assert bal.moves == [(0, 3), (1, 2)]


def test_failure_repairs_shards(tmp_path):
    cfg = PipelineConfig(vocab=10, seq_len=4, global_batch=8, n_hosts=3)
    pipe = ShardedTokenPipeline(cfg)
    runner = FaultTolerantRunner(FTConfig(ckpt_dir=str(tmp_path)),
                                 n_hosts=3, pipeline=pipe)
    moved = runner.on_failure(1)
    assert len(moved) == 8                      # all of host 1's shards
    assert len(pipe.shards_of(1)) == 0
    assert len(pipe.shards_of(0)) + len(pipe.shards_of(2)) == 24


def test_restore_latest_roundtrip(tmp_path):
    runner = FaultTolerantRunner(FTConfig(ckpt_dir=str(tmp_path),
                                          ckpt_every=1), n_hosts=1)
    tree = _tree()
    assert runner.maybe_save(0, tree)
    runner.saver.wait()
    step, restored = runner.restore_latest(jax.tree.map(np.asarray, tree))
    assert step == 0 and restored is not None


def test_plan_remesh():
    assert plan_remesh(256) == (16, 16)
    assert plan_remesh(240) == (15, 16)         # one host of 16 chips lost
    assert plan_remesh(8) == (1, 16)


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------

def test_engine_serves_all_and_rebalances():
    served_by = [0, 0, 0]

    def mk(i, delay=0):
        def fn(batch):
            served_by[i] += len(batch)
        return fn

    eng = ServingEngine([mk(0), mk(1), mk(2)],
                        CGRequestRouter(3, alpha=4, max_queue=16))
    rng = np.random.default_rng(0)
    keys = (rng.zipf(1.5, 300) % 50).astype(np.int32)
    eng.submit_batch(keys, list(range(300)))
    total = 0
    for _ in range(100):
        total += eng.step()
        if total >= 300:
            break
    assert total == 300
    assert sum(served_by) == 300
    assert min(served_by) > 0                    # skew got spread


def test_router_porc_single_matches_stream():
    r = CGRequestRouter(4, alpha=4, eps=0.05)
    outs = [r.route(k) for k in [1, 1, 1, 1, 2, 3, 1, 1]]
    assert all(0 <= o < 4 for o in outs)
    assert r.vw_load.sum() == 8


# ---------------------------------------------------------------------------
# persistent compilation cache placement
# ---------------------------------------------------------------------------

def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    checkout = compile_cache.CHECKOUT_CACHE.parent
    assert got == str(checkout / ".jax_cache")
    assert (checkout / "src" / "repro").is_dir()
    ignored = (checkout / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_compile_cache_follows_env(tmp_path):
    """A directory placed from outside wins, and compiles land there."""
    import subprocess
    import sys
    from repro.launch import compile_cache
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.jit(lambda x: jnp.sin(x) * 3.0)(1.0).block_until_ready()\n")
    src = str(compile_cache.CHECKOUT_CACHE.parent / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.pathsep.join(
                   p for p in (src, os.environ.get("PYTHONPATH")) if p))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[-1] == str(tmp_path)
    assert any(tmp_path.iterdir())
