"""Pallas snapshot-probing block engine vs the jnp oracles (interpret).

The kernel (``kernels.porc_snapshot``) must be *bit-identical* to the
jnp engine — same assignments, same float load vectors, same sketch
counters — because its block body calls the very same ``kernels.blocks``
math the jnp scan uses. Everything here runs the kernel in interpret
mode (the CI backend is CPU), which executes the kernel body with real
JAX ops: parity here is the semantics gate for the compiled TPU path.
One source is the kernel at S=1; the n_bins × block × S parity sweep is
``tests/test_kernels_porc.py::test_kernel_matches_ref``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import partitioners as P
from repro.core import streams
from repro.kernels.backend import resolve_engine, resolve_interpret
from repro.kernels.blocks import HHPolicy, neutral_hh_policy
from repro.kernels.porc_snapshot import porc_multisource_scan
from repro.kernels.ref import (multisource_state_init, porc_state_init,
                               ref_porc_multisource, ref_porc_route)


def zipf_keys(m, z=1.3, n_keys=1000, seed=1):
    return streams.sample_zipf_stream(jax.random.PRNGKey(seed), m, n_keys, z)


# ---------------------------------------------------------------------------
# single source (the kernel at S=1)
# ---------------------------------------------------------------------------

def test_kernel_b1_equals_sequential_oracle():
    """block=1 runs the full lazy probe chain — exact Alg. 1."""
    keys = zipf_keys(512)
    oracle = P.power_of_random_choices(keys, 32, eps=0.05)
    a, _ = ref_porc_multisource(keys, 32, 1, block=1, eps=0.05,
                                engine="pallas")
    np.testing.assert_array_equal(np.asarray(oracle), np.asarray(a))


def test_kernel_continuation_equals_one_shot():
    """The raw kernel at S=1 carries (base, delta) across calls."""
    n = 32
    keys = zipf_keys(2048, n_keys=500, z=1.2, seed=3)
    base, delta = jnp.zeros(n, jnp.float32), jnp.zeros((1, n), jnp.float32)
    a_full, b_full, d_full, _, _, _ = porc_multisource_scan(
        keys, n, 1, 1, 128, 0.05, 8, base, delta, 0, interpret=True)
    a1, b1, d1, t1, _, _ = porc_multisource_scan(
        keys[:1024], n, 1, 1, 128, 0.05, 8, base, delta, 0, interpret=True)
    a2, b2, d2, _, _, _ = porc_multisource_scan(
        keys[1024:], n, 1, 1, 128, 0.05, 8, b1, d1, t1, interpret=True)
    np.testing.assert_array_equal(np.asarray(a_full),
                                  np.concatenate([a1, a2]))
    np.testing.assert_array_equal(np.asarray(b_full), np.asarray(b2))
    np.testing.assert_array_equal(np.asarray(d_full), np.asarray(d2))


def test_route_engine_pallas_ragged_stream():
    """ref_porc_route(engine='pallas') on a ragged length: full blocks
    through the kernel, power-of-two remainder spans, same state."""
    keys = zipf_keys(4096 + 37)
    a_ref, s_ref = ref_porc_route(keys, 64, block=128, eps=0.05)
    a_k, s_k = ref_porc_route(keys, 64, block=128, eps=0.05,
                              engine="pallas")
    np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a_k))
    np.testing.assert_array_equal(np.asarray(s_ref.load),
                                  np.asarray(s_k.load))
    assert float(s_ref.routed) == float(s_k.routed)


def test_route_state_carry_across_calls():
    keys = zipf_keys(2048)
    a_full, _ = ref_porc_route(keys, 32, block=64, engine="pallas")
    state = porc_state_init(32)
    a1, state = ref_porc_route(keys[:1024], 32, block=64, state=state,
                               engine="pallas")
    a2, state = ref_porc_route(keys[1024:], 32, block=64, state=state,
                               engine="pallas")
    np.testing.assert_array_equal(np.asarray(a_full),
                                  np.concatenate([a1, a2]))


# ---------------------------------------------------------------------------
# heavy-hitter policy path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", [
    HHPolicy(scheme="w", width=256),
    HHPolicy(scheme="d", width=256, d_heavy=16, d_tail=2),
], ids=["wchoices", "dchoices"])
def test_hh_policy_parity(policy):
    keys = zipf_keys(4096, z=1.4)
    a_ref, s_ref = ref_porc_route(keys, 64, block=128, policy=policy)
    a_k, s_k = ref_porc_route(keys, 64, block=128, policy=policy,
                              engine="pallas")
    np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a_k))
    np.testing.assert_array_equal(np.asarray(s_ref.load),
                                  np.asarray(s_k.load))
    np.testing.assert_array_equal(np.asarray(s_ref.sketch),
                                  np.asarray(s_k.sketch))


def test_neutral_policy_matches_policy_free_kernel():
    """The neutral policy reproduces the plain engine through the HH
    code path — on the Pallas kernel too."""
    keys = zipf_keys(2048)
    a_plain, _ = ref_porc_route(keys, 32, block=128, engine="pallas")
    a_neut, _ = ref_porc_route(keys, 32, block=128, engine="pallas",
                               policy=neutral_hh_policy(32))
    np.testing.assert_array_equal(np.asarray(a_plain), np.asarray(a_neut))


# ---------------------------------------------------------------------------
# multisource
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_sources", [1, 4])
@pytest.mark.parametrize("sync_every", [1, 3])
def test_multisource_parity(n_sources, sync_every):
    keys = zipf_keys(4096 + 21)
    a_ref, s_ref = ref_porc_multisource(keys, 64, n_sources,
                                        sync_every=sync_every, block=64)
    a_k, s_k = ref_porc_multisource(keys, 64, n_sources,
                                    sync_every=sync_every, block=64,
                                    engine="pallas")
    np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a_k))
    np.testing.assert_array_equal(np.asarray(s_ref.base),
                                  np.asarray(s_k.base))
    np.testing.assert_array_equal(np.asarray(s_ref.delta),
                                  np.asarray(s_k.delta))
    assert int(s_ref.ticks) == int(s_k.ticks)


def test_multisource_hh_sketch_lanes_parity():
    policy = HHPolicy(scheme="w", width=256)
    keys = zipf_keys(4096, z=1.4)
    a_ref, s_ref = ref_porc_multisource(keys, 64, 4, sync_every=2,
                                        block=64, policy=policy)
    a_k, s_k = ref_porc_multisource(keys, 64, 4, sync_every=2, block=64,
                                    policy=policy, engine="pallas")
    np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a_k))
    np.testing.assert_array_equal(np.asarray(s_ref.sketch_base),
                                  np.asarray(s_k.sketch_base))
    np.testing.assert_array_equal(np.asarray(s_ref.sketch_delta),
                                  np.asarray(s_k.sketch_delta))


def test_multisource_state_carry_across_calls():
    keys = zipf_keys(3072)
    a_full, _ = ref_porc_multisource(keys, 32, 2, sync_every=3, block=64,
                                     engine="pallas")
    state = multisource_state_init(32, 2)
    a1, state = ref_porc_multisource(keys[:1536], 32, 2, sync_every=3,
                                     block=64, state=state, engine="pallas")
    a2, state = ref_porc_multisource(keys[1536:], 32, 2, sync_every=3,
                                     block=64, state=state, engine="pallas")
    np.testing.assert_array_equal(np.asarray(a_full),
                                  np.concatenate([a1, a2]))


def test_multisource_scan_kernel_direct():
    """The raw Pallas scan (full blocks only) against the ref state."""
    S, block, n_bins = 4, 64, 32
    keys = zipf_keys(S * block * 6)
    a_ref, s_ref = ref_porc_multisource(keys, n_bins, S, sync_every=2,
                                        block=block)
    base = jnp.zeros(n_bins, jnp.float32)
    delta = jnp.zeros((S, n_bins), jnp.float32)
    a_k, base_k, delta_k, ticks_k, _, _ = porc_multisource_scan(
        keys, n_bins, S, 2, block, 0.05, 8, base, delta, 0,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a_k))
    np.testing.assert_array_equal(np.asarray(s_ref.base),
                                  np.asarray(base_k))
    np.testing.assert_array_equal(np.asarray(s_ref.delta),
                                  np.asarray(delta_k))


# ---------------------------------------------------------------------------
# engine selection plumbing
# ---------------------------------------------------------------------------

def test_resolve_engine_mapping():
    on_tpu = jax.default_backend() == "tpu"
    assert resolve_engine("ref") == "ref"
    assert resolve_engine("pallas") == "pallas"
    assert resolve_engine("auto") == ("pallas" if on_tpu else "ref")
    assert resolve_engine("auto", HHPolicy()) == "ref"
    for unknown in ("mosaic", "jnp", "snapshot", "strict"):
        with pytest.raises(ValueError):
            resolve_engine(unknown)


def test_resolve_interpret_default():
    on_tpu = jax.default_backend() == "tpu"
    assert resolve_interpret(None) == (not on_tpu)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_route_engine_validation():
    keys = zipf_keys(256)
    with pytest.raises(ValueError, match="no kernel engine"):
        P.route("KG", keys, 16, engine="pallas")
    with pytest.raises(ValueError, match="block path"):
        P.route("PORC", keys, 16, engine="pallas")   # sequential oracle
    # the block path accepts it and matches the ref engine
    a_ref = P.route("PORC", keys, 16, block_size=64, engine="ref")
    a_k = P.route("PORC", keys, 16, block_size=64, engine="pallas")
    np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a_k))
    a_auto = P.route("PORC", keys, 16, block_size=64, engine="auto")
    np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a_auto))
    for unknown in ("strict", "snapshot"):
        with pytest.raises(ValueError, match="unknown engine"):
            P.route("PORC", keys, 16, block_size=64, engine=unknown)


def test_config_surfaces_reject_unknown_engine():
    """``CGConfig`` and ``CGRequestRouter`` resolve their ``engine`` at
    the boundary: a spelling the resolver does not know fails there."""
    from repro.core import cg
    from repro.serve import CGRequestRouter, MeshCGRequestRouter
    keys = zipf_keys(256)
    cfg = cg.CGConfig(n_workers=2, alpha=4, slot_len=128, block_size=64,
                      engine="strict")
    with pytest.raises(ValueError, match="unknown engine"):
        cg.run(cfg, keys, jnp.ones(2))
    for router in (CGRequestRouter, MeshCGRequestRouter):
        with pytest.raises(ValueError, match="unknown engine"):
            router(n_replicas=2, engine="strict")
    assert CGRequestRouter(n_replicas=2, engine="ref").route_batch(
        np.asarray(keys)).shape == (256,)
