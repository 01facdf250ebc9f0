"""The shared delegation engine (repro.core.delegation): seed-pairing
parity, capacity-weighted budgets, FCFS carry-over, windowed rates."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import delegation as D


def _state(owner, n, rate=None):
    owner = np.asarray(owner, np.int32)
    V = owner.shape[0]
    return D.DelegationState(
        vw_owner=jnp.asarray(owner),
        vw_rate=(jnp.zeros(V, jnp.float32) if rate is None
                 else jnp.asarray(rate, jnp.float32)),
        queues=D.init_queues(n),
        moves=jnp.zeros((), jnp.int32))


def _step(cfg, st, util, load, caps=None):
    n = cfg.n_workers
    return D.rebalance_step(
        cfg, st, jnp.asarray(util, jnp.float32),
        jnp.asarray(util > 0.85), jnp.asarray(util < 0.75),
        jnp.asarray(load, jnp.float32),
        jnp.ones(n, jnp.float32) if caps is None
        else jnp.asarray(caps, jnp.float32))


# the seed-pairing specification lives next to the engine so the test
# suite and the benchmark parity gate assert against one oracle
_seed_paired_moves = D.seed_pairing_reference


def test_uniform_parity_with_seed_pairing():
    """The uniform-capacity engine must reproduce the seed's
    one-VW-per-pair severity pairing bit-for-bit whenever every busy
    worker owns at least one VW (the seed's well-defined regime)."""
    rng = np.random.default_rng(7)
    for _ in range(100):
        n = int(rng.integers(2, 12))
        a = int(rng.integers(1, 6))
        V, M = n * a, int(rng.integers(1, 10))
        owner = np.repeat(np.arange(n), a).astype(np.int32)
        rng.shuffle(owner)
        owner[:n] = np.arange(n)
        load = (rng.random(V) * 100).astype(np.float32)
        util = (rng.random(n) * 1.6).astype(np.float32)
        exp_owner, exp_done = _seed_paired_moves(n, M, load, owner, util)
        cfg = D.DelegationConfig(n_workers=n, n_virtual=V,
                                 max_moves_per_slot=M)
        st, moved = _step(cfg, _state(owner, n), util, load)
        np.testing.assert_array_equal(np.asarray(st.vw_owner), exp_owner)
        assert int(moved) == exp_done


def test_busy_worker_with_no_vws_skipped():
    """A busy worker owning no VWs must not burn a pairing slot: the
    budget skips to the next eligible busy worker (the seed burned the
    pair and moved nothing)."""
    n, V = 4, 8
    # worker 0: most severe, owns nothing; worker 1: busy, owns all
    owner = np.full(V, 1, np.int32)
    util = np.array([1.5, 1.2, 0.5, 0.8], np.float32)
    load = np.arange(V, dtype=np.float32)
    cfg = D.DelegationConfig(n_workers=n, n_virtual=V, max_moves_per_slot=1)
    seed_owner, seed_done = _seed_paired_moves(n, 1, load, owner, util)
    assert seed_done == 0                      # the seed burns the slot
    st, moved = _step(cfg, _state(owner, n), util, load)
    assert int(moved) == 1                     # the engine does real work
    got = np.asarray(st.vw_owner)
    # worker 1's hottest VW (id 7) moved to the most idle worker (2)
    assert got[7] == 2
    assert (got[:7] == 1).all()


def test_counts_only_executed_moves():
    n, V = 3, 3
    owner = np.array([0, 1, 2], np.int32)
    util = np.array([1.5, 0.5, 0.8], np.float32)   # one pair possible
    cfg = D.DelegationConfig(n_workers=n, n_virtual=V, max_moves_per_slot=8)
    st, moved = _step(cfg, _state(owner, n), util, np.ones(V))
    assert int(moved) == 1 == int(st.moves)


def test_capacity_weighted_sheds_to_share():
    """A slow worker sheds VWs until its load matches its capacity
    share — several per slot, not one per signal — and the VW
    population is conserved."""
    n, a = 4, 8
    V = n * a
    owner = np.repeat(np.arange(n), a).astype(np.int32)   # 8 VWs each
    load = np.ones(V, np.float32)                          # uniform rates
    caps = np.array([0.3, 1.0, 1.0, 1.0], np.float32)
    # worker 0 is 0.3x: its fair share is 32*0.3/3.3 ≈ 2.9 VWs, so it
    # should shed ~5 VWs; workers 1-3 idle, worker 0 busy.
    util = np.array([2.0, 0.5, 0.5, 0.5], np.float32)
    cfg = D.DelegationConfig(n_workers=n, n_virtual=V, max_moves_per_slot=8,
                             capacity_weighted=True, rate_decay=0.6)
    st, moved = _step(cfg, _state(owner, n), util, load, caps)
    got = np.asarray(st.vw_owner)
    counts = np.bincount(got, minlength=n)
    assert counts.sum() == V                    # population conserved
    assert int(moved) == 5
    assert counts[0] == 3                       # ≈ capacity share of 2.9
    # uniform budgets would have moved exactly one
    cfg_u = cfg._replace(capacity_weighted=False)
    _, moved_u = _step(cfg_u, _state(owner, n), util, load, caps)
    assert int(moved_u) == 1


def test_capacity_weighted_respects_global_budget():
    n, a = 4, 8
    V = n * a
    owner = np.repeat(np.arange(n), a).astype(np.int32)
    caps = np.array([0.1, 1.0, 1.0, 1.0], np.float32)
    util = np.array([3.0, 0.5, 0.5, 0.5], np.float32)
    cfg = D.DelegationConfig(n_workers=n, n_virtual=V, max_moves_per_slot=3,
                             capacity_weighted=True)
    st, moved = _step(cfg, _state(owner, n), util, np.ones(V), caps)
    assert int(moved) == 3                      # clipped at the budget
    assert np.bincount(np.asarray(st.vw_owner), minlength=n).sum() == V


def test_fcfs_carryover_across_slots():
    """A busy signal the budget could not serve keeps its place at the
    head of the queue: next slot it is served before a newer, even more
    severe, signal (the paper's FCFS queues)."""
    n, V = 4, 8
    owner = np.array([0, 0, 1, 1, 2, 2, 3, 3], np.int32)
    cfg = D.DelegationConfig(n_workers=n, n_virtual=V, max_moves_per_slot=1,
                             fcfs=True)
    # slot 0: workers 0 (severe) and 1 (less) busy, worker 3 idle
    util0 = np.array([1.8, 1.2, 0.80, 0.5], np.float32)
    st = _state(owner, n)
    st, moved = _step(cfg, st, util0, np.ones(V))
    assert int(moved) == 1
    assert np.asarray(st.vw_owner)[0] == 3      # worker 0 shed first
    assert int(st.queues.busy_since[1]) != int(D.NOT_QUEUED)  # 1 carried
    # slot 1: worker 2 turns busy *more severe* than 1; FCFS serves 1
    util1 = np.array([0.8, 1.2, 1.9, 0.5], np.float32)
    st, moved = _step(cfg, st, util1, np.zeros(V))
    assert int(moved) == 1
    got = np.asarray(st.vw_owner)
    assert (got == np.array([3, 0, 3, 1, 2, 2, 3, 3])).all()
    # worker 2 is still queued for the next slot
    assert int(st.queues.busy_since[2]) != int(D.NOT_QUEUED)


def test_fcfs_opposite_signal_dequeues():
    n, V = 3, 6
    owner = np.repeat(np.arange(n), 2).astype(np.int32)
    cfg = D.DelegationConfig(n_workers=n, n_virtual=V, max_moves_per_slot=1,
                             fcfs=True)
    st = _state(owner, n)
    # two busy, no idle: nothing can move, both carried
    st, moved = _step(cfg, st, np.array([1.5, 1.2, 0.8], np.float32),
                      np.ones(V))
    assert int(moved) == 0
    assert int(st.queues.busy_since[0]) != int(D.NOT_QUEUED)
    # worker 0 flips to idle: it must leave the busy queue and absorb
    st, moved = _step(cfg, st, np.array([0.5, 1.5, 0.8], np.float32),
                      np.zeros(V))
    assert int(moved) == 1
    assert int(st.queues.busy_since[0]) == int(D.NOT_QUEUED)
    assert np.bincount(np.asarray(st.vw_owner), minlength=n)[0] == 3


def test_ewma_rate_tracks_recent_traffic():
    """With rate_decay < 1 the migrated VW is the *recently* hottest
    one, not the cumulatively hottest (the seed behaviour)."""
    n, V = 2, 4
    owner = np.array([0, 0, 1, 1], np.int32)
    util = np.array([1.5, 0.5], np.float32)
    cfg = D.DelegationConfig(n_workers=n, n_virtual=V, max_moves_per_slot=1,
                             rate_decay=0.5)
    st = _state(owner, n)
    # slot 0: VW 0 historically hot, no move possible yet (both busy? no:
    # worker 1 idle) — use a no-signal slot to load history instead
    st, _ = _step(cfg, st, np.array([0.8, 0.8], np.float32),
                  np.array([100.0, 0.0, 0.0, 0.0], np.float32))
    # slots 1-4: VW 1 is the hot one now; rates decay 100 → 6.25
    for _ in range(4):
        st, _ = _step(cfg, st, np.array([0.8, 0.8], np.float32),
                      np.array([0.0, 15.0, 0.0, 0.0], np.float32))
    st, moved = _step(cfg, st, util,
                      np.array([0.0, 15.0, 0.0, 0.0], np.float32))
    assert int(moved) == 1
    assert np.asarray(st.vw_owner)[1] == 1      # recent-hot VW moved
    assert np.asarray(st.vw_owner)[0] == 0      # cumulative-hot stayed
    # cumulative mode (the seed) would have moved VW 0 instead
    cfg_c = cfg._replace(rate_decay=1.0)
    st_c = _state(owner, n)
    st_c, _ = _step(cfg_c, st_c, np.array([0.8, 0.8], np.float32),
                    np.array([100.0, 0.0, 0.0, 0.0], np.float32))
    for _ in range(4):
        st_c, _ = _step(cfg_c, st_c, np.array([0.8, 0.8], np.float32),
                        np.array([0.0, 15.0, 0.0, 0.0], np.float32))
    st_c, _ = _step(cfg_c, st_c, util,
                    np.array([0.0, 15.0, 0.0, 0.0], np.float32))
    assert np.asarray(st_c.vw_owner)[0] == 1


def test_plan_pairs_severity_and_carryover():
    """plan_pairs (the owner-less entry point) pairs in severity order
    with unit budgets and carries the unserved signal over."""
    n = 4
    cfg = D.DelegationConfig(n_workers=n, n_virtual=0,
                             max_moves_per_slot=1, fcfs=True)
    q = D.init_queues(n)
    pressure = jnp.asarray([2.0, 3.0, 0.5, 1.0])
    busy = jnp.asarray([True, True, False, False])
    idle = jnp.asarray([False, False, True, False])
    src, dst, k, q = D.plan_pairs(cfg, q, pressure, busy, idle)
    assert int(k) == 1
    assert int(src[0]) == 1 and int(dst[0]) == 2   # most severe ↔ most idle
    # next slot: same signals — the carried worker 0 is served first
    src, dst, k, q = D.plan_pairs(cfg, q, pressure, busy, idle)
    assert int(k) == 1
    assert int(src[0]) == 0 and int(dst[0]) == 2


def test_plan_pairs_byte_clamp():
    """With a per-slot byte budget and a caller-supplied unit_bytes, the
    pair count is clamped to the bytes the slot may migrate."""
    n = 6
    cfg = D.DelegationConfig(n_workers=n, n_virtual=0,
                             max_moves_per_slot=4,
                             byte_budget_per_slot=250.0)
    pressure = jnp.asarray([3.0, 2.5, 2.0, 0.1, 0.2, 0.3])
    busy = jnp.asarray([True, True, True, False, False, False])
    idle = ~busy
    # 100 bytes per move → floor(250/100) = 2 of the 3 eligible pairs
    _, _, k, _ = D.plan_pairs(cfg, D.init_queues(n), pressure, busy, idle,
                              unit_bytes=100.0)
    assert int(k) == 2
    # no unit_bytes → byte budget inert, all 3 pairs scheduled
    _, _, k, _ = D.plan_pairs(cfg, D.init_queues(n), pressure, busy, idle)
    assert int(k) == 3
    # budget off → unit_bytes inert too
    cfg0 = cfg._replace(byte_budget_per_slot=0.0)
    _, _, k, _ = D.plan_pairs(cfg0, D.init_queues(n), pressure, busy, idle,
                              unit_bytes=100.0)
    assert int(k) == 3


def test_plan_pairs_byte_clamp_floors_at_one():
    """A unit_bytes larger than the whole slot budget rate-limits to one
    pair per slot — the same floor controller_step applies — instead of
    clamping to zero and wedging callers that need forward progress."""
    n = 6
    cfg = D.DelegationConfig(n_workers=n, n_virtual=0,
                             max_moves_per_slot=4,
                             byte_budget_per_slot=250.0)
    pressure = jnp.asarray([3.0, 2.5, 2.0, 0.1, 0.2, 0.3])
    busy = jnp.asarray([True, True, True, False, False, False])
    idle = ~busy
    _, _, k, _ = D.plan_pairs(cfg, D.init_queues(n), pressure, busy, idle,
                              unit_bytes=1000.0)
    assert int(k) == 1


@pytest.mark.parametrize("capacity_weighted", [False, True])
def test_random_streams_conserve_population(capacity_weighted):
    rng = np.random.default_rng(3)
    n, a = 6, 5
    V = n * a
    cfg = D.DelegationConfig(n_workers=n, n_virtual=V, max_moves_per_slot=6,
                             capacity_weighted=capacity_weighted,
                             rate_decay=0.7, fcfs=True)
    st = _state(np.repeat(np.arange(n), a), n)
    caps = rng.random(n).astype(np.float32) + 0.2
    for _ in range(30):
        util = (rng.random(n) * 1.6).astype(np.float32)
        load = (rng.random(V) * 10).astype(np.float32)
        st, _ = _step(cfg, st, util, load, caps)
        got = np.asarray(st.vw_owner)
        assert got.shape == (V,)
        assert got.min() >= 0 and got.max() < n
        assert np.bincount(got, minlength=n).sum() == V


def _sequential_step(cfg, st, pressure, busy, idle, arrivals, caps,
                     budget=None, vw_bytes=None):
    """A NumPy specification of ``rebalance_step`` that executes the
    scheduled moves one at a time, each taking the source's highest-rate
    VW left (first index on ties) by argmax over the current owner map.
    Returns the new (owner, rate, busy_since, idle_since, moves,
    bytes_moved) and what the slot exercised."""
    f32, nq = np.float32, int(D.NOT_QUEUED)
    n, V, M = cfg.n_workers, cfg.n_virtual, cfg.max_moves_per_slot
    owner = np.array(st.vw_owner)
    rate = f32(cfg.rate_decay) * np.asarray(st.vw_rate) + arrivals
    b0, i0 = np.asarray(st.queues.busy_since), np.asarray(st.queues.idle_since)
    slot = int(st.queues.slot)
    if cfg.fcfs:
        b = np.where(idle, nq, np.where(busy & (b0 == nq), slot, b0))
        i = np.where(busy, nq, np.where(idle & (i0 == nq), slot, i0))
    else:
        b = np.where(busy & ~idle, slot, nq)
        i = np.where(idle & ~busy, slot, nq)

    def rank(enq, sev):
        order = np.argsort(np.where(enq == nq, np.inf, sev), kind="stable")
        return order[np.argsort(enq[order], kind="stable")]

    busy_rank, idle_rank = rank(b, -pressure), rank(i, pressure)
    owned = np.bincount(owner, minlength=n)
    one = np.minimum(owned, 1)
    if cfg.capacity_weighted:
        rate_w = np.bincount(owner, weights=rate, minlength=n).astype(f32)
        total = rate_w.sum(dtype=f32)
        target = caps / np.maximum(caps.sum(dtype=f32), f32(1e-9)) * total
        per_vw = np.maximum(total / f32(V), f32(1e-9))
        surplus = np.round((rate_w - target) / per_vw).astype(np.int64)
        deficit = np.round((target - rate_w) / per_vw).astype(np.int64)
        shed = np.where(b != nq, np.clip(surplus, one, owned), 0)
        absorb = np.where(i != nq, np.maximum(deficit, 1), 0)
    else:
        shed = np.where(b != nq, one, 0)
        absorb = (i != nq).astype(np.int64)
    shed_cap = shed if budget is None or np.ndim(budget) == 0 else \
        np.minimum(shed, budget)
    cs, ca = np.cumsum(shed_cap[busy_rank]), np.cumsum(absorb[idle_rank])
    n_exec = min(cs[-1], ca[-1], M)
    if budget is not None and np.ndim(budget) == 0:
        n_exec = min(n_exec, int(budget))
    eligible = np.ones(V, bool)
    metered = vw_bytes is not None
    if metered:
        eligible = rate >= f32(cfg.min_gain_per_byte) * vw_bytes
    served_src, served_dst = np.zeros(n, np.int64), np.zeros(n, np.int64)
    nbytes, done = f32(0.0), 0
    seen = {"repeat_source": 0, "ran_out": 0, "over_budget": 0}
    for j in range(n_exec):
        s = busy_rank[min(np.searchsorted(cs, j, side="right"), n - 1)]
        d = idle_rank[min(np.searchsorted(ca, j, side="right"), n - 1)]
        cand = (owner == s) & eligible
        if not cand.any():
            seen["ran_out"] += 1
            continue
        v = int(np.argmax(np.where(cand, rate, -np.inf)))
        if (metered and cfg.byte_budget_per_slot > 0
                and nbytes + vw_bytes[v] > f32(cfg.byte_budget_per_slot)):
            seen["over_budget"] += 1
            continue
        seen["repeat_source"] += int(served_src[s] > 0)
        owner[v] = d
        served_src[s] += 1
        served_dst[d] += 1
        nbytes = f32(nbytes + (vw_bytes[v] if metered else 0.0))
        done += 1
    b = np.where(served_src >= shed, nq, b)
    i = np.where(served_dst >= absorb, nq, i)
    return (owner, rate, b, i, int(st.moves) + done,
            f32(float(st.bytes_moved) + nbytes)), seen


# name: (config knobs, how many workers start owning no VWs, integer
# rates, budget kind, vw_bytes, what the slots must exercise at least once)
_SPEC_CASES = {
    "uniform": (dict(), 0, False, None, False, ()),
    "capacity_weighted": (dict(capacity_weighted=True, rate_decay=0.5), 0,
                          False, None, False, ("repeat_source",)),
    "fcfs": (dict(fcfs=True, rate_decay=0.5), 0, False, None, False, ()),
    "fcfs_capacity_weighted": (dict(fcfs=True, capacity_weighted=True), 0,
                               False, None, False, ("repeat_source",)),
    "scalar_budget": (dict(capacity_weighted=True), 0, False, "scalar",
                      False, ("repeat_source",)),
    "per_worker_budget": (dict(capacity_weighted=True, fcfs=True), 0, False,
                          "per_worker", False, ("repeat_source",)),
    "integer_rate_ties": (dict(capacity_weighted=True), 0, True, None, False,
                          ("repeat_source",)),
    "busy_owns_nothing": (dict(), 2, False, None, False, ()),
    "min_gain_per_byte": (dict(capacity_weighted=True, min_gain_per_byte=0.5),
                          0, True, None, True, ("ran_out",)),
    "byte_budget": (dict(capacity_weighted=True, byte_budget_per_slot=12.0),
                    0, True, None, True, ("over_budget", "repeat_source")),
}


@pytest.mark.parametrize("case", list(_SPEC_CASES))
def test_rebalance_step_matches_sequential_specification(case):
    """``rebalance_step`` chooses a slot's moves in one pass; the result
    must be that of executing them one at a time, bit for bit: owner
    map, rates, queues, move count and bytes moved, slot after slot."""
    knobs, n_empty, integer_rates, budget_kind, metered, must = \
        _SPEC_CASES[case]
    rng = np.random.default_rng(sorted(_SPEC_CASES).index(case))
    n, a = 8, 6
    V = n * a
    cfg = D.DelegationConfig(n_workers=n, n_virtual=V, max_moves_per_slot=8,
                             **knobs)
    owner = rng.integers(n_empty, n, V).astype(np.int32)
    st = _state(owner, n)._replace(bytes_moved=jnp.zeros((), jnp.float32))
    caps = np.where(np.arange(n) < 2, 0.25, 1.0).astype(np.float32)
    vw_bytes = (rng.integers(1, 8, V).astype(np.float32) if metered
                else None)
    seen = dict.fromkeys(("repeat_source", "ran_out", "over_budget"), 0)
    for t in range(12):
        if integer_rates:
            arrivals = rng.integers(0, 4, V).astype(np.float32)
        else:
            arrivals = (rng.random(V) * 10).astype(np.float32)
        load = np.bincount(np.asarray(st.vw_owner), minlength=n,
                           weights=arrivals) / caps
        util = (1.6 * load / max(load.max(), 1e-9)
                + rng.random(n) * 0.3).astype(np.float32)
        if n_empty and t == 0:
            util[:n_empty] = 1.5          # busy, and owns no VW
        busy, idle = util > 0.85, util < 0.75
        budget = {"scalar": np.int32(rng.integers(0, 9)),
                  "per_worker": rng.integers(0, 4, n).astype(np.int32),
                  None: None}[budget_kind]
        want, slot_seen = _sequential_step(cfg, st, util, busy, idle,
                                           arrivals, caps, budget, vw_bytes)
        st, moved = D.rebalance_step(
            cfg, st, jnp.asarray(util), jnp.asarray(busy), jnp.asarray(idle),
            jnp.asarray(arrivals), jnp.asarray(caps),
            None if budget is None else jnp.asarray(budget),
            None if vw_bytes is None else jnp.asarray(vw_bytes))
        got = (np.asarray(st.vw_owner), np.asarray(st.vw_rate),
               np.asarray(st.queues.busy_since),
               np.asarray(st.queues.idle_since), int(st.moves),
               np.float32(st.bytes_moved))
        for name, g, w in zip(("owner", "rate", "busy_since", "idle_since",
                               "moves", "bytes_moved"), got, want):
            np.testing.assert_array_equal(g, w, err_msg=f"slot {t}: {name}")
        for k, c in slot_seen.items():
            seen[k] += c
    for k in must:
        assert seen[k], f"no slot exercised {k}: {seen}"
    if n_empty:
        assert int(st.moves) > 0
