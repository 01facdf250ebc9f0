"""The routing hot path compiles for a TPU v5e (described, not attached).

Interpret-mode parity (``test_porc_snapshot_pallas.py``) cannot see what
only the chip's compiler refuses: vector gathers, scatters, unaligned
blocks, too much fast memory. These tests compile the main-path kernels
and programs with the TPU compiler for a described ``v5e:2x2`` topology,
at the chip smoke's shapes: the Storm deployment's 240 virtual workers,
blocks of 128, 8 source lanes, 2^21 keys. Nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports every test file.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import cg
from repro.kernels import backend
from repro.kernels import mesh as kmesh
from repro.kernels.porc_snapshot import porc_multisource_scan

N_BINS = 240          # 24 workers x 10 virtual workers
BLOCK = 128
S = 8                 # source lanes
M = 1 << 21           # keys
EPS = 0.01


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile():
    """No persistent cache (a described chip's entries cannot be read
    back) and no jit trace shared with CPU tests in this process."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_sources,sync_every", [(S, 1), (S, 4), (1, 1)])
def test_porc_multisource_scan_compiles(one_chip, chip_compile, n_sources,
                                        sync_every):
    """The block kernel at the Storm deployment's 8 lanes, and at one
    source (S=1, single-source routing)."""
    compiled = jax.jit(lambda k, b, d, t: porc_multisource_scan(
        k, N_BINS, n_sources, sync_every, BLOCK, EPS, 8, b, d, t,
        interpret=False)).lower(
        _shape((M,), jnp.int32, one_chip),
        _shape((N_BINS,), jnp.float32, one_chip),
        _shape((n_sources, N_BINS), jnp.float32, one_chip),
        _shape((), jnp.int32, one_chip)).compile()
    assert _has_kernel(compiled)


def test_mesh_scan_compiles_on_four_chips(topo, chip_compile):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), (kmesh.SOURCES_AXIS,),
                axis_types=(AxisType.Auto,))
    scan = kmesh._mesh_scan(mesh, N_BINS, S, 1, BLOCK, EPS, 8)

    def on(*spec):
        return NamedSharding(mesh, P(*spec))

    nb = M // (S * BLOCK)
    compiled = scan.lower(
        _shape((N_BINS,), jnp.float32, on()),
        _shape((S, N_BINS), jnp.float32, on(kmesh.SOURCES_AXIS, None)),
        _shape((), jnp.int32, on()),
        _shape((S, nb, BLOCK), jnp.int32,
               on(kmesh.SOURCES_AXIS, None, None))).compile()
    assert "all-reduce" in compiled.as_text()     # the lane-delta psum


def test_cg_run_default_engine_compiles_to_the_kernel(one_chip, chip_compile,
                                                      monkeypatch):
    """``cg.run`` at the Storm deployment (24 workers, 8 sources) with
    the default engine: on a TPU that is the compiled Pallas kernel."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    cfg = cg.CGConfig(n_workers=24, alpha=10, eps=EPS, slot_len=10_000,
                      block_size=BLOCK, n_sources=S)
    assert cfg.engine == "auto" and backend.resolve_engine(cfg.engine) == \
        "pallas"
    compiled = cg.run.lower(
        cfg, _shape((20 * cfg.slot_len,), jnp.int32, one_chip),
        _shape((cfg.n_workers,), jnp.float32, one_chip)).compile()
    assert _has_kernel(compiled)
    # the chip trace names the kernel's events after this instruction
    assert re.search(r'^\s*%porc_multisource_scan[.0-9]* = '
                     r'.*custom_call_target="tpu_custom_call"',
                     compiled.as_text(), re.M)


def test_cg_run_binds_without_gather_or_scatter(one_chip, chip_compile,
                                                 monkeypatch):
    """The slot loop's bind step (the ``cg.bind`` scope) compiles to
    compare-and-reduce fusions: the TPU runs a gather or a scatter one
    index at a time, and a slot would have 10,000 of each."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    cfg = cg.CGConfig(n_workers=24, alpha=10, eps=EPS, slot_len=10_000,
                      block_size=BLOCK, n_sources=S)
    text = cg.run.lower(
        cfg, _shape((2 * cfg.slot_len,), jnp.int32, one_chip),
        _shape((cfg.n_workers,), jnp.float32, one_chip)).compile().as_text()
    bind = [line for line in text.splitlines()
            if re.search(r'op_name="[^"]*cg\.bind[/"]', line)]
    assert bind, "no instruction carries the cg.bind scope"
    assert not [line for line in bind
                if re.search(r'\b(gather|scatter)\(', line)]


def test_cg_run_delegates_without_a_device_loop(one_chip, chip_compile,
                                                monkeypatch):
    """The slot loop's delegation step (the ``cg.delegation`` scope)
    chooses its paired moves in one pass: a device ``while`` pays a
    fixed cost on every trip, and a loop over the moves would make
    ``max_moves_per_slot`` trips a slot."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    cfg = cg.CGConfig(n_workers=24, alpha=10, eps=EPS, slot_len=10_000,
                      block_size=BLOCK, n_sources=S)
    text = cg.run.lower(
        cfg, _shape((2 * cfg.slot_len,), jnp.int32, one_chip),
        _shape((cfg.n_workers,), jnp.float32, one_chip)).compile().as_text()
    delegation = [line for line in text.splitlines()
                  if re.search(r'op_name="[^"]*cg\.delegation[/"]', line)]
    assert delegation, "no instruction carries the cg.delegation scope"
    assert not [line for line in delegation
                if re.search(r'\bwhile\(', line)]
