"""Small-mesh lower+compile of the production step builders.

The full 512-device sweep runs via repro.launch.dryrun (results in
EXPERIMENTS.md); this test proves the same machinery works end-to-end
on the local device so CI catches sharding-rule regressions fast.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, optim
from repro.launch import steps
from repro.launch.mesh import make_smoke_mesh
from repro.models import model_zoo as zoo

ARCHS = ["gemma3-1b", "qwen3-moe-235b-a22b", "mamba2-130m", "whisper-small"]


def _batch_specs(cfg, B=2, S=32):
    tok = jnp.int32
    if cfg.family == "audio":
        return {"frames": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                               jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((B, S), tok)}
    if cfg.family == "vlm":
        return {"patches": jax.ShapeDtypeStruct(
                    (B, cfg.n_patches, cfg.vision_dim), jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((B, S), tok)}
    return {"tokens": jax.ShapeDtypeStruct((B, S), tok)}


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_compiles(arch):
    cfg = configs.get_smoke_config(arch)
    mesh = make_smoke_mesh()
    with jax.set_mesh(mesh):
        jit_for, p_sh, o_sh = steps.jit_train_step(cfg, mesh)
        pspecs = zoo.param_specs(cfg)
        ospecs = jax.eval_shape(optim.init, pspecs)
        batch = _batch_specs(cfg)
        compiled = jit_for(batch).lower(pspecs, ospecs, batch).compile()
        assert compiled.cost_analysis() is not None


@pytest.mark.parametrize("arch", ARCHS)
def test_serve_step_compiles(arch):
    cfg = configs.get_smoke_config(arch)
    mesh = make_smoke_mesh()
    with jax.set_mesh(mesh):
        jit_for, p_sh = steps.jit_serve_step(cfg, mesh)
        pspecs = zoo.param_specs(cfg)
        cache = zoo.cache_spec(cfg, 2, 32)
        tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
        compiled = jit_for(cache, tok).lower(pspecs, cache, tok).compile()
        assert compiled is not None


def test_prefill_step_compiles():
    cfg = configs.get_smoke_config("internlm2-20b")
    mesh = make_smoke_mesh()
    with jax.set_mesh(mesh):
        jit_for, _ = steps.jit_prefill_step(cfg, mesh)
        pspecs = zoo.param_specs(cfg)
        batch = _batch_specs(cfg)
        compiled = jit_for(batch).lower(pspecs, batch).compile()
        assert compiled is not None


@pytest.mark.slow
def test_train_executes_and_checkpoints(tmp_path):
    """Tiny end-to-end: the real train driver, 6 steps + resume."""
    from repro.launch.train import train
    losses = train("mamba2-130m", n_steps=6, batch=4, seq=32, smoke=True,
                   ckpt_dir=str(tmp_path), ckpt_every=2, n_hosts=2)
    assert len(losses) == 6 and np.isfinite(losses).all()
    losses2 = train("mamba2-130m", n_steps=8, batch=4, seq=32, smoke=True,
                    ckpt_dir=str(tmp_path), resume=True, n_hosts=2)
    assert len(losses2) <= 8     # resumed from a later step


@pytest.mark.slow
def test_train_survives_host_failure(tmp_path):
    from repro.launch.train import train
    losses = train("gemma3-1b", n_steps=6, batch=4, seq=32, smoke=True,
                   ckpt_dir=str(tmp_path), n_hosts=3, fail_host_at=3)
    assert len(losses) == 6 and np.isfinite(losses).all()
