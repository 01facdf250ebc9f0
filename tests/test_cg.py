import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import stream_len

from repro.core import cg, streams

M = stream_len(200_000, 100_000)
N_KEYS = 5000


@pytest.fixture(scope="module")
def keys():
    return streams.sample_zipf_stream(jax.random.PRNGKey(0), M, N_KEYS, 1.1)


def _caps(n, y, z, rho=0.8):
    # service rates: sum = arrival_rate / rho = 1.25 msgs/unit
    c = streams.heterogeneous_capacities(n, y, z)
    return jnp.asarray(c / rho, jnp.float32)


def test_cg_converges_on_heterogeneous(keys):
    cfg = cg.CGConfig(n_workers=10, alpha=10, eps=0.01, slot_len=10_000)
    res = cg.run(cfg, keys, _caps(10, 3, 5.0))
    early = float(np.mean(np.asarray(res.imbalance)[:3]))
    late = float(np.mean(np.asarray(res.imbalance)[-3:]))
    assert late < early, f"no convergence: early {early} late {late}"
    assert int(res.moves) > 0


def test_vw_population_conserved(keys):
    """Pairing keeps the virtual-worker count per system constant."""
    cfg = cg.CGConfig(n_workers=8, alpha=10, eps=0.01, slot_len=10_000)
    res = cg.run(cfg, keys, _caps(8, 2, 4.0))
    owners = np.asarray(res.state.vw_owner)
    assert owners.shape == (80,)
    assert owners.min() >= 0 and owners.max() < 8


def test_assignment_valid_and_complete(keys):
    cfg = cg.CGConfig(n_workers=10, alpha=10, slot_len=10_000)
    res = cg.run(cfg, keys, _caps(10, 1, 1.0))
    a = np.asarray(res.assignment)
    assert a.shape == (M,)
    assert a.min() >= 0 and a.max() < 10
    vw = np.asarray(res.vw_assignment)
    assert vw.min() >= 0 and vw.max() < 100


def test_cg_beats_kg_on_heterogeneous(keys):
    from repro.core import partitioners as P, simulation
    n = 10
    caps = _caps(n, 3, 5.0)
    cfg = cg.CGConfig(n_workers=n, alpha=10, eps=0.01, slot_len=10_000)
    res = cg.run(cfg, keys, caps)
    kg = simulation.simulate_queues(
        P.key_grouping(keys, n), caps, n, 10_000)
    # steady-state latency spread: CG flat, KG diverging (Fig 10)
    assert float(res.latency_spread[-1]) < float(kg.latency_spread[-1])
    assert float(res.imbalance[-1]) < float(kg.imbalance[-1])


def test_cg_adapts_to_capacity_change(keys):
    """Fig 13: resources change mid-stream; CG re-converges."""
    n = 10
    slot = 4000
    slots = M // slot
    sched = streams.dynamic_capacity_schedule(n, M)
    caps = np.zeros((slots, n))
    for start, c in sched:
        caps[start // slot:] = c / 0.8
    cfg = cg.CGConfig(n_workers=n, alpha=10, eps=0.01, slot_len=slot,
                      max_moves_per_slot=8)
    res = cg.run(cfg, keys, jnp.asarray(caps, jnp.float32))
    imb = np.asarray(res.imbalance)
    third = slots // 3
    # the spike right after the last change decays by the end
    spike = np.mean(imb[2 * third + 1: 2 * third + 4])
    settled = np.mean(imb[-3:])
    assert settled < spike, (spike, settled)
    assert int(res.moves) >= 10


def test_inner_scheme_variants(keys):
    for inner in ("PORC", "KG", "SG"):
        cfg = cg.CGConfig(n_workers=6, alpha=5, slot_len=10_000, inner=inner)
        res = cg.run(cfg, keys[:100_000], _caps(6, 1, 1.0))
        assert np.asarray(res.assignment).max() < 6


# ---------------------------------------------------------------------------
# block-parallel routing path (CGConfig.block_size)
# ---------------------------------------------------------------------------

def test_block_path_b1_bit_identical_to_oracle(keys):
    """block_size=1 must reproduce the per-message oracle bit-for-bit."""
    sub = keys[:30_000]
    caps = _caps(10, 3, 5.0)
    cfg0 = cg.CGConfig(n_workers=10, slot_len=10_000, block_size=0)
    cfg1 = cg.CGConfig(n_workers=10, slot_len=10_000, block_size=1)
    r0, r1 = cg.run(cfg0, sub, caps), cg.run(cfg1, sub, caps)
    np.testing.assert_array_equal(np.asarray(r0.assignment),
                                  np.asarray(r1.assignment))
    np.testing.assert_array_equal(np.asarray(r0.vw_assignment),
                                  np.asarray(r1.vw_assignment))
    np.testing.assert_allclose(np.asarray(r0.state.vw_load),
                               np.asarray(r1.state.vw_load))
    assert int(r0.moves) == int(r1.moves)


@pytest.mark.parametrize("block_size", [64, 128, 1024])
def test_block_path_divergence_bounded(keys, block_size):
    """For B>1 the VW loads must stay inside the paper's (1+eps)
    capacity envelope, up to one block of staleness per bin."""
    eps = 0.05
    cfg = cg.CGConfig(n_workers=10, alpha=10, eps=eps, slot_len=10_000,
                      block_size=block_size)
    res = cg.run(cfg, keys, _caps(10, 1, 1.0))
    vw_load = np.asarray(res.state.vw_load)
    V = cfg.n_workers * cfg.alpha
    assert vw_load.max() <= (1 + eps) * len(keys) / V + block_size
    assert vw_load.sum() == len(keys)            # every message placed


def test_block_path_converges_like_oracle(keys):
    """The fast path must keep CG's qualitative behavior: imbalance
    decays on a heterogeneous cluster as pairing kicks in."""
    cfg = cg.CGConfig(n_workers=10, alpha=10, eps=0.01, slot_len=10_000,
                      block_size=128)
    res = cg.run(cfg, keys, _caps(10, 3, 5.0))
    early = float(np.mean(np.asarray(res.imbalance)[:3]))
    late = float(np.mean(np.asarray(res.imbalance)[-3:]))
    assert late < early
    assert int(res.moves) > 0


# ---------------------------------------------------------------------------
# distributed sources (CGConfig.n_sources / sync_every)
# ---------------------------------------------------------------------------

def test_multisource_s1_bit_identical_to_single(keys):
    """n_sources=1 must keep the single-source block path bit-for-bit
    (it routes through the same code path, not the multisource one)."""
    sub = keys[:30_000]
    caps = _caps(10, 3, 5.0)
    cfg1 = cg.CGConfig(n_workers=10, slot_len=10_000, block_size=128)
    cfgS = cg.CGConfig(n_workers=10, slot_len=10_000, block_size=128,
                       n_sources=1, sync_every=4)
    r1, rS = cg.run(cfg1, sub, caps), cg.run(cfgS, sub, caps)
    np.testing.assert_array_equal(np.asarray(r1.vw_assignment),
                                  np.asarray(rS.vw_assignment))


@pytest.mark.parametrize("n_sources", [10, 100])
def test_multisource_divergence_bounded(keys, n_sources):
    """With S sources the VW loads stay inside the (1+eps) envelope up
    to one sync window of staleness — the Fig 11 flatness claim inside
    the full CG simulation."""
    eps, block, sync_every = 0.05, 8, 2
    cfg = cg.CGConfig(n_workers=10, alpha=10, eps=eps, slot_len=10_000,
                      block_size=block, n_sources=n_sources,
                      sync_every=sync_every)
    res = cg.run(cfg, keys, _caps(10, 1, 1.0))
    vw_load = np.asarray(res.state.vw_load)
    V = cfg.n_workers * cfg.alpha
    window = n_sources * sync_every * block
    assert vw_load.max() <= (1 + eps) * len(keys) / V + window + 1
    assert vw_load.sum() == len(keys)            # every message placed


def test_multisource_converges_on_heterogeneous(keys):
    """Delegation still converges when routing is sharded over sources."""
    cfg = cg.CGConfig(n_workers=10, alpha=10, eps=0.01, slot_len=10_000,
                      block_size=16, n_sources=10)
    res = cg.run(cfg, keys, _caps(10, 3, 5.0))
    early = float(np.mean(np.asarray(res.imbalance)[:3]))
    late = float(np.mean(np.asarray(res.imbalance)[-3:]))
    assert late < early
    assert int(res.moves) > 0


def test_multisource_requires_block_path(keys):
    cfg = cg.CGConfig(n_workers=4, slot_len=10_000, block_size=0,
                      n_sources=4)
    with pytest.raises(ValueError):
        cg.run(cfg, keys[:10_000], _caps(4, 1, 1.0))


# ---------------------------------------------------------------------------
# SG round-robin pointer (exact int32, not the f32 t_offset)
# ---------------------------------------------------------------------------

def test_sg_pointer_exact_over_full_stream(keys):
    """inner=SG is a global round-robin: the VW sequence must be exactly
    arange(m) % V with no drift across slot boundaries."""
    cfg = cg.CGConfig(n_workers=6, alpha=5, slot_len=10_000, inner="SG")
    m = 100_000
    res = cg.run(cfg, keys[:m], _caps(6, 1, 1.0))
    np.testing.assert_array_equal(np.asarray(res.vw_assignment),
                                  np.arange(m, dtype=np.int64) % 30)
    assert int(res.state.sg_ptr) == m % 30


def test_sg_pointer_survives_f32_clock_saturation(keys):
    """Past 2^24 routed messages the f32 t_offset cannot advance by
    slot_len·k exactly; the int32 sg_ptr must keep the round-robin
    exact. Simulated by continuing from a state whose clock sits at the
    f32 precision edge."""
    cfg = cg.CGConfig(n_workers=6, alpha=5, slot_len=10_000, inner="SG")
    V = 30
    big = 2.0 ** 24                     # t_offset += 10_000 is inexact here
    state = cg.init_state(cfg)._replace(
        t_offset=jnp.float32(big), sg_ptr=jnp.int32(7))
    res = cg.run(cfg, keys[:20_000], _caps(6, 1, 1.0), state)
    np.testing.assert_array_equal(
        np.asarray(res.vw_assignment),
        (7 + np.arange(20_000, dtype=np.int64)) % V)
    assert int(res.state.sg_ptr) == (7 + 20_000) % V


# ---------------------------------------------------------------------------
# run(..., state=...) continuation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("inner", ["PORC", "SG"])
def test_run_state_continuation_matches_single_run(keys, inner):
    """Two runs chained through ``state`` must equal one run over the
    concatenated stream (routing loads, owner map, queues, delegation
    FCFS queues and the SG pointer all carry over)."""
    sub = keys[:60_000]
    caps = _caps(10, 3, 5.0)
    cfg = cg.CGConfig(n_workers=10, slot_len=10_000, inner=inner,
                      capacity_weighted=True, rate_decay=0.5,
                      fcfs_pairing=True)
    full = cg.run(cfg, sub, caps)
    r1 = cg.run(cfg, sub[:30_000], caps)
    r2 = cg.run(cfg, sub[30_000:], caps, r1.state)
    np.testing.assert_array_equal(
        np.asarray(full.assignment),
        np.concatenate([np.asarray(r1.assignment), np.asarray(r2.assignment)]))
    np.testing.assert_allclose(np.asarray(full.state.vw_load),
                               np.asarray(r2.state.vw_load))
    np.testing.assert_array_equal(np.asarray(full.state.vw_owner),
                                  np.asarray(r2.state.vw_owner))
    assert int(full.moves) == int(r2.moves)


# ---------------------------------------------------------------------------
# capacity-weighted delegation (the shared engine inside the simulator)
# ---------------------------------------------------------------------------

def test_capacity_weighted_conserves_vw_population(keys):
    cfg = cg.CGConfig(n_workers=8, alpha=10, eps=0.01, slot_len=10_000,
                      capacity_weighted=True, rate_decay=0.6,
                      fcfs_pairing=True, max_moves_per_slot=16)
    res = cg.run(cfg, keys, _caps(8, 2, 4.0))
    owners = np.asarray(res.state.vw_owner)
    assert owners.shape == (80,)
    assert owners.min() >= 0 and owners.max() < 8
    assert np.bincount(owners, minlength=8).sum() == 80
    assert int(res.moves) > 0


def test_capacity_weighted_converges_to_capacity_shares(keys):
    """On a static heterogeneous cluster the weighted engine re-homes
    VWs until ownership ≈ capacity shares — within a few slots, not one
    VW per slot."""
    n, alpha = 10, 20
    caps = _caps(n, 3, 5.0)
    cfg = cg.CGConfig(n_workers=n, alpha=alpha, eps=0.01, slot_len=10_000,
                      capacity_weighted=True, rate_decay=0.6,
                      fcfs_pairing=True, max_moves_per_slot=16)
    res = cg.run(cfg, keys[:100_000], caps)    # 10 slots
    counts = np.bincount(np.asarray(res.state.vw_owner), minlength=n)
    share = np.asarray(caps) / float(np.asarray(caps).sum())
    np.testing.assert_allclose(counts, share * n * alpha, atol=2.5)
    # uniform pairing cannot have moved enough VWs by then: ideal needs
    # ~3*(45-20)=75 rebalancing moves, one-per-pair does ≤3/slot here
    res_u = cg.run(cfg._replace(capacity_weighted=False, rate_decay=1.0,
                                fcfs_pairing=False), keys[:100_000], caps)
    counts_u = np.bincount(np.asarray(res_u.state.vw_owner), minlength=n)
    err_w = np.abs(counts - share * n * alpha).max()
    err_u = np.abs(counts_u - share * n * alpha).max()
    assert err_w < err_u, (err_w, err_u)


def test_capacity_weighted_tracks_time_varying_capacity(keys):
    """Fig 12/13 shape: capacities change at ⅓ and ⅔; the windowed-rate
    weighted engine re-converges after each change and settles below
    the post-change spike."""
    n = 10
    slot = 4000
    slots = M // slot
    sched = streams.dynamic_capacity_schedule(n, M)
    caps = np.zeros((slots, n))
    for start, c in sched:
        caps[start // slot:] = c / 0.8
    cfg = cg.CGConfig(n_workers=n, alpha=20, eps=0.01, slot_len=slot,
                      max_moves_per_slot=16, capacity_weighted=True,
                      rate_decay=0.6, fcfs_pairing=True)
    res = cg.run(cfg, keys, jnp.asarray(caps, jnp.float32))
    imb = np.asarray(res.imbalance)
    third = slots // 3
    spike = np.mean(imb[2 * third: 2 * third + 3])
    settled = np.mean(imb[-3:])
    assert settled < spike, (spike, settled)
    # and it must also beat the uniform (seed) pairing's settled level
    res_u = cg.run(cfg._replace(capacity_weighted=False, rate_decay=1.0,
                                fcfs_pairing=False),
                   keys, jnp.asarray(caps, jnp.float32))
    settled_u = np.mean(np.asarray(res_u.imbalance)[-3:])
    assert settled < settled_u, (settled, settled_u)


# ---------------------------------------------------------------------------
# the bind step: a slot's workers and per-worker arrivals
# ---------------------------------------------------------------------------

def _moved_owner(n, alpha):
    """An owner map after real paired moves: a short heterogeneous run."""
    cfg = cg.CGConfig(n_workers=n, alpha=alpha, eps=0.01, slot_len=1000)
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(1), 20_000, 500,
                                      1.1)
    res = cg.run(cfg, keys, _caps(n, 2, 4.0))
    assert int(res.moves) > 0
    owner = np.asarray(res.state.vw_owner)
    assert not np.array_equal(owner, np.tile(np.arange(n), alpha))
    return owner


@pytest.mark.parametrize("V, n, m, case", [
    (240, 24, 10_000, "skewed"),       # the Storm deployment's slot
    (1000, 100, 10_000, "skewed"),     # Fig 11's largest fleets
    (6, 3, 64, "skewed"),
    (240, 24, 10_000, "one_vw"),       # every message on one VW
    (240, 24, 10_000, "idle_worker"),  # a worker owning no VW
    (40, 8, 1000, "moved"),            # an owner map after moves
])
def test_bind_matches_gather_and_bincount(V, n, m, case):
    rs = np.random.RandomState(V + m)
    if case == "moved":
        owner = _moved_owner(n, V // n)
    elif case == "idle_worker":
        owner = np.arange(V) % (n - 1)
    else:
        owner = rs.permutation(np.tile(np.arange(n), V // n))
    if case == "one_vw":
        vw = np.full(m, V // 3)
    else:
        p = 1.0 / np.arange(1, V + 1)
        vw = rs.choice(V, size=m, p=p / p.sum())
    owner = owner.astype(np.int32)
    vw = vw.astype(np.int32)
    workers, arrivals = jax.jit(cg._bind, static_argnums=2)(
        jnp.asarray(owner), jnp.asarray(vw), n)
    assert workers.dtype == jnp.int32 and arrivals.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(workers), owner[vw])
    np.testing.assert_array_equal(
        np.asarray(arrivals),
        np.bincount(owner[vw], minlength=n).astype(np.float32))
