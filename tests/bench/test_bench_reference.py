"""The benchmark's plain reference agrees with the program on the CPU.

``bench/reference.py`` recomputes routing from the keys alone; these
tests hold it to ``repro.core.cg.run`` (the stream deployment's slot
loop) and to ``CGRequestRouter`` (the serving path) at small sizes, on
the Storm deployment's shapes: 24 workers, 240 VWs, 8 sources."""
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import reference as R  # noqa: E402
from bench import streams  # noqa: E402

WP = {"keys": 29_000, "p1": 0.0932, "z_tail": 1.0}


def wp_keys(n, seed=3):
    return np.asarray(streams.sample_keys(seed, 0, WP, n))


@pytest.mark.parametrize("sync_every", [1, 2])
def test_reference_matches_cg_run(sync_every):
    """Assignment, VW assignment, queue spread and moves equal, slot by
    slot, with state carried across two calls; imbalance to float32
    rounding."""
    from repro.core import cg
    caps = R.capacities(24, [0, 1], 0.3, 0.8)
    cfg = cg.CGConfig(n_workers=24, alpha=10, eps=0.01, slot_len=10_000,
                      block_size=128, n_sources=8, sync_every=sync_every,
                      engine="ref")
    keys = wp_keys(60_000)
    ref = R.CGSlots(n_workers=24, alpha=10, eps=0.01, slot_len=10_000,
                    block_size=128, n_sources=8, sync_every=sync_every,
                    theta_busy=0.85, theta_idle=0.75, max_moves_per_slot=8,
                    caps=caps)
    state = cg.init_state(cfg)
    moves = 0
    for part in (keys[:30_000], keys[30_000:]):
        res = cg.run(cfg, jnp.asarray(part), jnp.asarray(caps), state)
        want = ref.run(part)
        state = res.state
        np.testing.assert_array_equal(np.asarray(res.assignment),
                                      want["assignment"])
        np.testing.assert_array_equal(np.asarray(res.vw_assignment),
                                      want["vw_assignment"])
        np.testing.assert_array_equal(np.asarray(res.queue_spread),
                                      want["queue_spread"])
        np.testing.assert_allclose(np.asarray(res.imbalance),
                                   want["imbalance"], rtol=1e-5)
        assert want["moves"][-1] == {int(res.moves)}
        moves = int(res.moves)
    assert moves > 0, "the slowed workers must shed VWs"


@pytest.mark.parametrize("n,sync_every", [(1053, 1), (1053, 3), (4096, 2)])
def test_multisource_matches_program_engine(n, sync_every):
    """Remainder spans (including a block of one) and the sub-S ragged
    tail route like the program's multisource engine."""
    from repro.kernels.ref import ref_porc_multisource
    keys = wp_keys(2 * n, seed=11)
    ms = R.MultiSource(240, 8, eps=0.01, block=128, sync_every=sync_every)
    state = None
    for part in (keys[:n], keys[n:]):
        got, state = ref_porc_multisource(jnp.asarray(part), 240, 8,
                                          sync_every=sync_every, block=128,
                                          eps=0.01, state=state)
        np.testing.assert_array_equal(np.asarray(got), ms.route(part))
    np.testing.assert_array_equal(
        np.asarray(state.base + state.delta.sum(0)), ms.load)


def test_reference_matches_request_router():
    """Batch after batch, the router's VW assignment is the reference's,
    and its replica binding is its owner map at that VW."""
    from repro.serve import CGRequestRouter
    router = CGRequestRouter(n_replicas=24, alpha=10, eps=0.01,
                             n_sources=8, capacity_weighted=True,
                             engine="ref")
    ms = R.MultiSource(240, 8, eps=0.01, block=128)
    keys = wp_keys(6 * 1024, seed=5)
    for b in range(6):
        batch = keys[b * 1024:(b + 1) * 1024]
        owner = router.vw_owner
        handle = router.dispatch_batch(batch)
        bound = router.finalize_batch(handle)
        want = ms.route(batch)
        np.testing.assert_array_equal(np.asarray(handle), want)
        np.testing.assert_array_equal(bound, owner[want])
        router.rebalance([0, 1], list(range(2, 24)),
                         pressure=np.linspace(1, 0, 24),
                         capacities=R.capacities(24, [0, 1], 0.3, 0.8))


def test_rounding_ties_allow_every_order():
    """Two busy workers whose utilisations differ by an ulp may shed in
    either order: both outcomes are correct."""
    caps = np.full(4, 0.25 / 0.8, np.float32)
    ref = R.CGSlots(n_workers=4, alpha=2, eps=0.01, slot_len=100,
                    block_size=1, n_sources=1, sync_every=1, theta_busy=0.85,
                    theta_idle=0.75, max_moves_per_slot=1, caps=caps)
    ref.rate = np.arange(8, dtype=np.float32)
    u = np.float32(0.95)
    util = np.array([u, np.nextafter(u, np.float32(2)), 0.1, 0.5], np.float32)
    outcomes = ref._outcomes(util, ref.stories[0].owner)
    assert len(outcomes) == 2
    # exact order: worker 1 is busier, so its hottest VW (5) goes to the
    # idlest worker, 2; the other order moves worker 0's hottest (4)
    assert outcomes[0][0][5] == 2 and outcomes[1][0][4] == 2
    util[1] = np.float32(1.2)               # no tie: one outcome
    assert len(ref._outcomes(util, ref.stories[0].owner)) == 1


def test_departures_count_the_outcomes_off_the_exact_reading():
    """Where a slot's delegation has a second correct outcome and the
    program took it, the reference follows it and counts one departure;
    a program that took the exact outcome counts none."""
    caps = R.capacities(24, [0, 1], 0.3, 0.8)
    kw = dict(n_workers=24, alpha=10, eps=0.01, slot_len=10_000,
              block_size=128, n_sources=8, sync_every=1, theta_busy=0.85,
              theta_idle=0.75, max_moves_per_slot=8, caps=caps)
    keys = wp_keys(20_000, seed=9)
    plain = R.CGSlots(**kw)
    first = plain.slot(keys[:10_000])
    exact_owner = plain.stories[0].owner
    vw2 = plain.slot(keys[10_000:])[1]
    alt_owner = exact_owner.copy()
    hot = int(np.bincount(vw2, minlength=240).argmax())
    alt_owner[hot] = (alt_owner[hot] + 1) % 24      # another correct map

    for owner, want in ((exact_owner, 0), (alt_owner, 1)):
        ref = R.CGSlots(**kw)
        outcomes = ref._outcomes
        # the first slot's delegation: the exact map, then the other
        ref._outcomes = lambda util, own, ref=ref, outcomes=outcomes: (
            [(exact_owner, 0), (alt_owner, 1)] if ref.load.sum() <= 10_000
            else outcomes(util, own))
        ref.slot(keys[:10_000], seen=(first[0], first[1]))
        ref.slot(keys[10_000:], seen=(owner[vw2], vw2))
        assert ref.departures == want


def test_capacity_rounding_allows_either_side():
    """A probed load equal to the capacity may compare either way under
    float rounding; the program's bin is taken there and nowhere else."""
    ms = R.MultiSource(8, 1, eps=0.0, block=2)
    ms.base = np.array([4, 4, 4, 4, 4, 4, 3, 3], np.float32)  # cap 4.0
    keys = np.array([[11, 12]])
    cand = R.hash_bins(keys, np.arange(1, 9), 8)
    exact = ms._probe(keys, cand, 2)
    assert (ms.base[exact] == 3).all()
    first = cand[..., 0]                     # the first candidate, load 4
    np.testing.assert_array_equal(ms._probe(keys, cand, 2, first),
                                  np.where(ms.base[first] <= 4, first, exact))
    ms.base[:6] = 5                          # surely over: no choice left
    np.testing.assert_array_equal(ms._probe(keys, cand, 2, first), exact)


def test_hash_matches_program():
    from repro.core.hashing import hash_to_bins
    keys = np.array([0, 1, 7, 2_899_999, 2 ** 31 - 1, -5], np.int32)
    for salt in (1, 2, 8, 961):
        np.testing.assert_array_equal(
            R.hash_bins(keys, [salt], 240)[:, 0],
            np.asarray(hash_to_bins(jnp.asarray(keys), salt, 240)))


def test_streams_seed_uses_all_bits():
    """Seeds past 32 bits give streams of their own, and a seed gives
    the same stream every time."""
    a = np.asarray(streams.sample_keys(7, 0, WP, 512))
    b = np.asarray(streams.sample_keys(2 ** 32 + 7, 0, WP, 512))
    c = np.asarray(streams.sample_keys(7, 0, WP, 512))
    assert (a != b).any()
    np.testing.assert_array_equal(a, c)
    assert a.min() >= 0 and a.max() < WP["keys"]
    assert abs((a == 0).mean() - WP["p1"]) < 0.05
