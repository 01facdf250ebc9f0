"""Capacity-weighted worker delegation — the shared rebalance engine.

The paper's delegation half of CG (§V-B "pairing virtual workers",
§V-C monitoring/piggybacking) in one jit-able engine, shared by the
simulator (``core.cg``), the serving router (``serve.engine``) and the
straggler balancer (``runtime.straggler``) — previously three divergent
implementations.

Semantics
---------
* **Windowed load rates.** Per-VW arrival rates are tracked as an
  exponentially-windowed sum ``rate ← rate_decay·rate + arrivals`` with
  effective window ≈ 1/(1−rate_decay) monitoring slots.
  ``rate_decay=1.0`` keeps the cumulative-since-t₀ counts of the seed
  implementation (and the paper's m_t bookkeeping); < 1 makes the
  migration choice and the capacity-weighted budgets track *recent*
  traffic, which is what lets the engine follow Fig 12/13's
  time-varying capacities instead of averaging over the whole past.
* **Severity order with FCFS carry-over.** Busy and idle signals enter
  per-worker queues; pairing order is FIFO over *enqueue slot* with
  ties (signals that arrived in the same slot) broken by severity —
  exactly the degenerate-FCFS argument of §V-B, but the queues now
  survive across slots (``fcfs=True``): a busy worker that the move
  budget could not serve this slot keeps its place at the head of the
  queue next slot, the paper's queue behaviour that previously lived
  only in ``runtime/straggler.py``. ``fcfs=False`` rebuilds the queues
  from the current signals each slot (the seed behaviour).
* **Capacity-proportional move budgets.** With
  ``capacity_weighted=True`` a busy worker sheds as many VWs as its
  rate surplus over its capacity-proportional share
  (``round((R_w − c_w/Σc·R)/​(R/V))``, clipped to what it owns), and an
  idle worker absorbs up to its deficit — a 0.3×-speed worker drains to
  the fleet's normalized utilization in one or two slots instead of one
  VW per slot. ``capacity_weighted=False`` is the seed's one-VW-per-pair
  pacing. Either way at most ``max_moves_per_slot`` moves execute per
  slot and **only executed moves** consume budget: a busy worker that
  owns no VWs is skipped (run-length zero in the schedule), it does not
  burn the pair's slot like the seed pairing reference did
  (``seed_pairing_reference`` below preserves that quirk as the parity
  specification). ``rebalance_step``/``plan_pairs`` also accept a
  runtime ``budget`` below the static ceiling — the adaptive
  queue-depth budgets of ``repro.core.controller``.
* **Device residency.** The owner map, rates and queues are jnp arrays
  threaded through ``rebalance_step`` (fully jit-compiled); callers
  never loop over VWs on the host.
* **Migration cost (bytes moved).** Flipping the owner map is free only
  for stateless operators; a stateful VW (keyed session state, KV
  cache) pays a transfer proportional to its state size
  (arXiv:1610.05121 makes this the first-class rebalancing term).
  Passing ``vw_bytes`` ([V] f32 per-VW state sizes) to
  ``rebalance_step`` turns it on: cumulative bytes moved are tracked in
  ``DelegationState.bytes_moved``, ``byte_budget_per_slot`` caps the
  bytes one slot may transfer (moves that would overflow it are
  skipped, budget left for smaller VWs later in the schedule), and
  ``min_gain_per_byte`` is the cost-benefit test — a VW only moves if
  its rate (the traffic relief) amortizes its transfer
  (``rate ≥ min_gain_per_byte · bytes``). With ``vw_bytes=None`` (the
  default) or both knobs at 0 the planner is bit-identical to the
  cost-free engine. ``evacuate`` is the exception: a dead worker's VWs
  always move (there is no cheaper option than off a corpse), the
  bytes are accounted but never gated.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import trace

NOT_QUEUED = jnp.iinfo(jnp.int32).max     # sorts after every real slot


class DelegationConfig(NamedTuple):
    n_workers: int
    n_virtual: int                 # 0 is fine for pairing-only use
    max_moves_per_slot: int = 8
    capacity_weighted: bool = False  # budgets ∝ rate surplus/deficit
    rate_decay: float = 1.0        # EWMA decay of per-VW rates
                                   # (1.0 = cumulative, the seed behaviour)
    fcfs: bool = False             # carry unpaired signals across slots
    byte_budget_per_slot: float = 0.0  # max VW state bytes one slot may
                                   # migrate (0 = unmetered); only
                                   # effective when vw_bytes is passed
    min_gain_per_byte: float = 0.0  # cost-benefit: move a VW only if
                                   # rate ≥ this · its state bytes


class PairQueues(NamedTuple):
    """FCFS signal queues: the slot each worker entered the busy/idle
    queue (``NOT_QUEUED`` = not enqueued) plus the slot counter."""
    busy_since: jnp.ndarray   # [n] i32
    idle_since: jnp.ndarray   # [n] i32
    slot: jnp.ndarray         # []  i32


class DelegationState(NamedTuple):
    vw_owner: jnp.ndarray     # [V] i32 physical worker owning each VW
    vw_rate: jnp.ndarray      # [V] f32 windowed per-VW arrival rate
    queues: PairQueues
    moves: jnp.ndarray        # []  i32 cumulative executed moves
    bytes_moved: jnp.ndarray | float = 0.0  # [] f32 cumulative VW state
                              # bytes migrated (stays 0 unless the
                              # caller passes vw_bytes)


def init_queues(n_workers: int) -> PairQueues:
    return PairQueues(
        busy_since=jnp.full((n_workers,), NOT_QUEUED, jnp.int32),
        idle_since=jnp.full((n_workers,), NOT_QUEUED, jnp.int32),
        slot=jnp.zeros((), jnp.int32))


def init_state(cfg: DelegationConfig,
               vw_owner: jnp.ndarray | None = None) -> DelegationState:
    if vw_owner is None:
        vw_owner = jnp.tile(
            jnp.arange(cfg.n_workers, dtype=jnp.int32),
            max(1, cfg.n_virtual // max(cfg.n_workers, 1)))[: cfg.n_virtual]
    return DelegationState(
        vw_owner=jnp.asarray(vw_owner, jnp.int32),
        vw_rate=jnp.zeros((cfg.n_virtual,), jnp.float32),
        queues=init_queues(cfg.n_workers),
        moves=jnp.zeros((), jnp.int32),
        bytes_moved=jnp.zeros((), jnp.float32))


def _enqueue(cfg: DelegationConfig, busy, idle, q: PairQueues):
    """Admit this slot's signals into the FCFS queues. A worker whose
    signal flips is dequeued from the opposite queue; with ``fcfs``
    off the queues are rebuilt from the current signals (seed mode).
    A worker signalled both busy and idle enters neither queue, in
    either mode: no worker is ever queued on both sides, so no worker
    both sheds and absorbs in one slot (``_execute`` relies on it)."""
    if cfg.fcfs:
        b = jnp.where(busy & (q.busy_since == NOT_QUEUED), q.slot,
                      q.busy_since)
        b = jnp.where(idle, NOT_QUEUED, b)
        i = jnp.where(idle & (q.idle_since == NOT_QUEUED), q.slot,
                      q.idle_since)
        i = jnp.where(busy, NOT_QUEUED, i)
        return b, i
    return (jnp.where(busy & ~idle, q.slot, NOT_QUEUED),
            jnp.where(idle & ~busy, q.slot, NOT_QUEUED))


def _fcfs_rank(enq, severity):
    """Queued workers first, ordered by (enqueue slot asc, severity asc),
    ties by worker index — the FCFS queue with in-slot severity order.
    ``severity`` must already be ascending-is-first (negate for busy)."""
    sev = jnp.where(enq == NOT_QUEUED, jnp.inf, severity)
    order = jnp.argsort(sev, stable=True)
    return order[jnp.argsort(enq[order], stable=True)]


def _budgets(cfg: DelegationConfig, owned_count, rate_w, in_busy, in_idle,
             capacities):
    """Per-worker shed/absorb budgets (VW counts) for this slot."""
    one = jnp.minimum(owned_count, 1)
    if not cfg.capacity_weighted:
        shed = jnp.where(in_busy, one, 0)
        absorb = jnp.where(in_idle, 1, 0)
        return shed.astype(jnp.int32), absorb.astype(jnp.int32)
    total = jnp.sum(rate_w)
    share = capacities / jnp.maximum(jnp.sum(capacities), 1e-9)
    target = share * total                       # capacity-proportional
    per_vw = jnp.maximum(total / max(cfg.n_virtual, 1), 1e-9)
    surplus = jnp.round((rate_w - target) / per_vw).astype(jnp.int32)
    deficit = jnp.round((target - rate_w) / per_vw).astype(jnp.int32)
    # a busy signal always sheds at least one VW if it owns any (the
    # FCFS pacing floor — the seed behaviour is the lower bound), and
    # never more than it owns; an idle signal absorbs at least one.
    shed = jnp.where(in_busy, jnp.clip(surplus, one, owned_count), 0)
    absorb = jnp.where(in_idle, jnp.maximum(deficit, 1), 0)
    return shed.astype(jnp.int32), absorb.astype(jnp.int32)


def _schedule(cfg: DelegationConfig, busy_rank, idle_rank, shed, absorb):
    """Expand per-worker budgets into per-move (src, dst) sequences.

    Move j draws its source from the run-length decoding of the shed
    budgets in FCFS/severity order (a worker with budget 0 — e.g. no
    VWs — occupies zero run length, i.e. is skipped for free) and its
    destination from the absorb budgets likewise.
    """
    M = cfg.max_moves_per_slot
    last = max(cfg.n_workers - 1, 0)
    cs = jnp.cumsum(shed[busy_rank])
    ca = jnp.cumsum(absorb[idle_rank])
    j = jnp.arange(M, dtype=jnp.int32)
    # compare_all: n·M compares in one fusion; the default binary search
    # is a device loop
    src = busy_rank[jnp.clip(jnp.searchsorted(cs, j, side="right",
                                              method="compare_all"), 0, last)]
    dst = idle_rank[jnp.clip(jnp.searchsorted(ca, j, side="right",
                                              method="compare_all"), 0, last)]
    n_exec = jnp.minimum(jnp.minimum(cs[-1], ca[-1]),
                         jnp.int32(M)).astype(jnp.int32)
    return src, dst, n_exec


def _execute(cfg: DelegationConfig, vw_owner, vw_rate, src, dst, n_exec,
             vw_bytes=None):
    """Apply the scheduled moves, all chosen at once from the owner map
    at the start of the slot.

    Each move re-homes the highest-rate VW its source still owns
    (greatest relief). No worker is queued both busy and idle
    (``_enqueue``), so a source's VWs change only by its own moves, and
    its k-th executed move takes its k-th VW in the order repeated
    argmax would pick them: rate descending, index ascending. Every
    count below is an integer compare-and-sum, so the result is that of
    executing the moves one at a time, bit for bit.

    With ``vw_bytes`` given, moves additionally pay migration cost: a VW
    is only *eligible* if its rate amortizes its state transfer
    (``rate ≥ min_gain_per_byte · bytes``), and a move whose VW would
    push the slot past ``byte_budget_per_slot`` is skipped (the budget
    is left for smaller VWs later in the schedule). Skipped moves don't
    count as executed. ``vw_bytes=None`` compiles the cost-free path.
    """
    M = cfg.max_moves_per_slot
    V = vw_owner.shape[0]
    j = jnp.arange(M, dtype=jnp.int32)
    u = jnp.arange(V, dtype=jnp.int32)
    if vw_bytes is None:
        eligible = jnp.ones((V,), bool)
    else:
        vw_bytes = jnp.asarray(vw_bytes, jnp.float32)
        eligible = vw_rate >= cfg.min_gain_per_byte * vw_bytes
    # each eligible VW's place among its owner's eligible VWs
    ahead = ((vw_rate[:, None] > vw_rate[None, :])
             | ((vw_rate[:, None] == vw_rate[None, :])
                & (u[:, None] < u[None, :])))
    rank = jnp.sum(ahead & eligible[:, None]
                   & (vw_owner[:, None] == vw_owner[None, :]), axis=0)
    mine = ((j < n_exec)[:, None] & eligible[None, :]
            & (vw_owner[None, :] == src[:, None]))              # [M, V]
    earlier = (src[None, :] == src[:, None]) & (j[None, :] < j[:, None])
    if vw_bytes is None:
        # every earlier move of the source took a VW, or it has none left
        pick = mine & (rank[None, :] == jnp.sum(earlier, axis=1)[:, None])
        n_bytes = jnp.zeros((), jnp.float32)
    else:
        pick, n_bytes = _metered_picks(cfg, mine, rank, earlier, vw_bytes)
    went = jnp.any(pick, axis=1)
    owner = jnp.where(jnp.any(pick, axis=0),
                      jnp.sum(jnp.where(pick, dst[:, None], 0), axis=0),
                      vw_owner).astype(vw_owner.dtype)
    w = jnp.arange(cfg.n_workers, dtype=jnp.int32)[:, None]
    served_src = jnp.sum(went & (src == w), axis=1, dtype=jnp.int32)
    served_dst = jnp.sum(went & (dst == w), axis=1, dtype=jnp.int32)
    return (owner, jnp.sum(went, dtype=jnp.int32), served_src, served_dst,
            n_bytes)


def _metered_picks(cfg: DelegationConfig, mine, rank, earlier, vw_bytes):
    """The moves of a slot that pays migration cost, in schedule order.

    A move skipped for the byte budget leaves its VW with the source, so
    the source's later moves try that VW again: move j takes its
    source's q-th VW, q counting the source's earlier moves that
    executed. Only the bytes so far carry from one move to the next, so
    the M moves are unrolled over scalars, summed in schedule order.
    """
    M = mine.shape[0]
    q = jnp.arange(M, dtype=jnp.int32)
    nth = mine[:, None, :] & (rank == q[:, None])[None]         # [M, q, V]
    has = jnp.any(nth, axis=2)
    cost = jnp.sum(jnp.where(nth, vw_bytes, 0.0), axis=2)        # one term
    went = jnp.zeros((M,), bool)
    n_bytes = jnp.zeros((), jnp.float32)
    for k in range(M):
        qk = jnp.sum(went & earlier[k])
        ok = has[k, qk]
        if cfg.byte_budget_per_slot > 0:
            ok = ok & (n_bytes + cost[k, qk] <= cfg.byte_budget_per_slot)
        n_bytes = n_bytes + jnp.where(ok, cost[k, qk], 0.0)
        went = went | ((q == k) & ok)
    taken = jnp.sum(went[None, :] & earlier, axis=1)
    return went[:, None] & mine & (rank[None, :] == taken[:, None]), n_bytes


def seed_pairing_reference(n, max_moves, vw_load, vw_owner, util,
                           theta_busy=0.85, theta_idle=0.75):
    """The seed pairing reference — a NumPy specification of the seed
    simulator's pairing semantics, which the uniform-capacity engine is
    gated against (tests and ``benchmarks/bench_heterogeneous``'s
    parity gate both use it).

    One VW per busy/idle pair in severity order, the migrated VW is the
    busy worker's most loaded, and — deliberately preserved — a busy
    worker owning no VWs *burns* its pairing slot. The engine fixes
    that last behaviour (run-length-zero skip), so parity holds exactly
    on scenarios where every busy worker owns at least one VW.
    """
    busy, idle = util > theta_busy, util < theta_idle
    n_pairs = min(busy.sum(), idle.sum(), max_moves)
    busy_rank = np.argsort(np.where(busy, -util, np.inf), kind="stable")
    idle_rank = np.argsort(np.where(idle, util, np.inf), kind="stable")
    owner, done = vw_owner.copy(), 0
    for i in range(min(max_moves, n)):
        src, dst = busy_rank[i], idle_rank[i]
        owned = owner == src
        if i < n_pairs and owned.any():
            owner[np.argmax(np.where(owned, vw_load, -np.inf))] = dst
            done += 1
    return owner, done


@functools.partial(jax.jit, static_argnames=("cfg",))
def plan_pairs(cfg: DelegationConfig, queues: PairQueues, pressure,
               busy, idle, budget=None, unit_bytes=None):
    """Pairing-only entry point (no owner map): returns the (src, dst)
    move schedule with unit budgets, for callers that execute moves
    themselves (e.g. the straggler balancer moving pipeline shards).

    Args:
      queues: persistent ``PairQueues`` (FCFS carry-over when cfg.fcfs).
      pressure: [n] f32, higher = more overloaded (orders busy workers
        descending and idle workers ascending).
      busy/idle: [n] bool signal masks for this slot.
      budget: optional i32 — this slot's move budget (e.g. from
        ``controller.controller_step``), clamped by
        ``max_moves_per_slot``; None keeps the static budget. An [n]
        vector is taken as per-worker shed caps instead (a worker with
        cap 0 moves nothing but keeps its FCFS queue position).
      unit_bytes: optional f32 scalar — the state bytes one move
        transfers (callers without per-VW accounting use the mean shard
        state size). With ``cfg.byte_budget_per_slot > 0`` the pair
        count is clamped so ``n_pairs · unit_bytes`` stays within the
        byte budget, floored at one pair (matching
        ``controller_step``'s byte clamp) so a unit larger than the
        budget rate-limits to one move per slot instead of wedging
        callers that rely on forward progress; None skips the byte
        clamp.

    Returns (src [M] i32, dst [M] i32, n_pairs i32, new PairQueues);
    only the first ``n_pairs`` schedule entries are valid.
    """
    pressure = jnp.asarray(pressure, jnp.float32)
    busy_since, idle_since = _enqueue(cfg, busy, idle, queues)
    busy_rank = _fcfs_rank(busy_since, -pressure)
    idle_rank = _fcfs_rank(idle_since, pressure)
    shed = (busy_since != NOT_QUEUED).astype(jnp.int32)
    absorb = (idle_since != NOT_QUEUED).astype(jnp.int32)
    shed_cap, n_exec_cap = shed, None
    if budget is not None:
        budget = jnp.asarray(budget, jnp.int32)
        if budget.ndim:        # [n] per-worker caps (0 = hold in queue)
            shed_cap = jnp.minimum(shed, budget)
        else:
            n_exec_cap = budget
    src, dst, n_exec = _schedule(cfg, busy_rank, idle_rank, shed_cap,
                                 absorb)
    if n_exec_cap is not None:
        n_exec = jnp.minimum(n_exec, n_exec_cap)
    if unit_bytes is not None and cfg.byte_budget_per_slot > 0:
        fit = jnp.floor(cfg.byte_budget_per_slot
                        / jnp.maximum(jnp.asarray(unit_bytes, jnp.float32),
                                      1e-9)).astype(jnp.int32)
        n_exec = jnp.minimum(n_exec, jnp.maximum(fit, 1))
    lt = jnp.arange(cfg.max_moves_per_slot, dtype=jnp.int32) < n_exec
    served_src = jnp.zeros((cfg.n_workers,), jnp.int32).at[src].add(
        lt.astype(jnp.int32))
    served_dst = jnp.zeros((cfg.n_workers,), jnp.int32).at[dst].add(
        lt.astype(jnp.int32))
    busy_since = jnp.where(served_src >= shed, NOT_QUEUED, busy_since)
    idle_since = jnp.where(served_dst >= absorb, NOT_QUEUED, idle_since)
    return src, dst, n_exec, PairQueues(busy_since, idle_since,
                                        queues.slot + 1)


@functools.partial(jax.jit, static_argnames=("cfg",))
def rebalance_step(cfg: DelegationConfig, state: DelegationState, pressure,
                   busy, idle, vw_arrivals, capacities, budget=None,
                   vw_bytes=None):
    """One monitoring-slot tick of the full engine.

    Updates the windowed VW rates from this slot's arrivals, admits the
    signals into the FCFS queues, computes (capacity-weighted) move
    budgets, schedules busy→idle pairs in severity/FCFS order and
    executes them on the device-resident owner map.

    Args:
      pressure: [n] f32 severity (e.g. utilization or queue occupancy).
      busy/idle: [n] bool delegation signals.
      vw_arrivals: [V] f32 per-VW arrivals since the previous tick.
      capacities: [n] f32 service-rate estimates (any scale — only the
        shares matter); ignored unless ``cfg.capacity_weighted``.
      budget: optional i32 — this slot's move budget, typically derived
        from queue depth by ``controller.controller_step``. A scalar
        clamps the slot's executed-move count; an [n] vector clamps
        each worker's shed count individually (per-worker budgets — a
        worker with cap 0 moves nothing but keeps its FCFS queue
        position). The static ``max_moves_per_slot`` stays the hard
        ceiling (schedule arrays are sized by it); None keeps the
        static budget, which is bit-identical to the pre-controller
        engine.
      vw_bytes: optional [V] f32 per-VW state sizes — turns on
        migration-cost accounting: ``byte_budget_per_slot`` caps the
        bytes this slot migrates and ``min_gain_per_byte`` gates each
        move on rate/bytes (see ``_execute``). None (the default) is
        bit-identical to the cost-free engine.

    Returns (new DelegationState, n_moved i32).
    """
    with trace.scope(trace.DELEGATION):
        return _rebalance_step(cfg, state, pressure, busy, idle,
                               vw_arrivals, capacities, budget, vw_bytes)


def _rebalance_step(cfg, state, pressure, busy, idle, vw_arrivals,
                    capacities, budget, vw_bytes):
    pressure = jnp.asarray(pressure, jnp.float32)
    rate = cfg.rate_decay * state.vw_rate + jnp.asarray(vw_arrivals,
                                                       jnp.float32)
    busy_since, idle_since = _enqueue(cfg, busy, idle, state.queues)
    in_busy = busy_since != NOT_QUEUED
    in_idle = idle_since != NOT_QUEUED
    busy_rank = _fcfs_rank(busy_since, -pressure)
    idle_rank = _fcfs_rank(idle_since, pressure)
    n = cfg.n_workers
    owned_count = jnp.zeros((n,), jnp.int32).at[state.vw_owner].add(1)
    rate_w = jnp.zeros((n,), jnp.float32).at[state.vw_owner].add(rate)
    shed, absorb = _budgets(cfg, owned_count, rate_w, in_busy, in_idle,
                            jnp.asarray(capacities, jnp.float32))
    # ``shed`` (uncapped demand) drives the FCFS dequeue below; the
    # schedule may additionally be capped by the controller's budget —
    # a scalar clamps the executed-move count, an [n] vector clamps
    # each worker's shed count individually (per-worker budgets). A
    # budget-starved worker keeps its queue position either way.
    shed_cap, n_exec_cap = shed, None
    if budget is not None:
        budget = jnp.asarray(budget, jnp.int32)
        if budget.ndim:
            shed_cap = jnp.minimum(shed, budget)
        else:
            n_exec_cap = budget
    src, dst, n_exec = _schedule(cfg, busy_rank, idle_rank, shed_cap,
                                 absorb)
    if n_exec_cap is not None:
        n_exec = jnp.minimum(n_exec, n_exec_cap)
    owner, n_done, served_src, served_dst, n_bytes = _execute(
        cfg, state.vw_owner, rate, src, dst, n_exec, vw_bytes)
    # fully-served workers leave their queue; partially-served ones keep
    # their FCFS position for the next slot (budgets are re-derived from
    # fresh rates each slot, only membership carries over).
    busy_since = jnp.where(served_src >= shed, NOT_QUEUED, busy_since)
    idle_since = jnp.where(served_dst >= absorb, NOT_QUEUED, idle_since)
    new_state = DelegationState(
        vw_owner=owner,
        vw_rate=rate,
        queues=PairQueues(busy_since, idle_since, state.queues.slot + 1),
        moves=state.moves + n_done,
        bytes_moved=state.bytes_moved + n_bytes)
    return new_state, n_done


class VersionedOwnerMap:
    """Replicated owner map with atomic versioned commits (§V-C owner
    propagation on a mesh).

    On a multi-host mesh every source router holds a copy of the
    VW→worker map; ``rebalance_step``/``evacuate`` *commit* a new map
    atomically under a monotonically increasing version, and the head
    propagates to the routers asynchronously. A router that has not yet
    adopted the head keeps routing against the **base** view — the last
    snapshot every router is known to hold — so a stale router is
    merely conservative (it routes on the pre-move map), never torn:
    ``view()`` always returns one committed snapshot whole, no mix of
    two maps.

    Versions only move forward: ``commit`` increments, ``adopt``
    promotes head→base at the head's version. Passing ``mesh`` pins
    both snapshots replicated (``PartitionSpec()``) across the mesh's
    devices — the layout a real deployment broadcasts.
    """

    def __init__(self, owner, mesh=None):
        self._sharding = (NamedSharding(mesh, PartitionSpec())
                          if mesh is not None else None)
        owner = self._pin(jnp.asarray(owner, jnp.int32))
        self._base = owner
        self._head = owner
        self._version = 0
        self._base_version = 0

    def _pin(self, arr):
        if self._sharding is not None:
            return jax.device_put(arr, self._sharding)
        return arr

    @property
    def version(self) -> int:
        """Version of the latest committed map (monotonic)."""
        return self._version

    @property
    def base_version(self) -> int:
        """Version of the snapshot every router is known to hold."""
        return self._base_version

    def commit(self, owner) -> int:
        """Atomically publish a new owner map as the head of the next
        version. Returns the new version."""
        self._head = self._pin(jnp.asarray(owner, jnp.int32))
        self._version += 1
        return self._version

    def adopt(self) -> int:
        """Every router has received the head: promote it to base.
        Returns the adopted version."""
        self._base = self._head
        self._base_version = self._version
        return self._base_version

    def view(self, version: int | None = None) -> jnp.ndarray:
        """The snapshot a router holding ``version`` routes against:
        the head when it has the current version, else the base
        fallback. ``None`` means current."""
        if version is None or version >= self._version:
            return self._head
        return self._base


def evacuate(vw_owner, vw_rate, dead, capacities, vw_bytes=None):
    """Re-home every VW owned by the ``dead`` worker(s) onto survivors,
    capacity-proportionally — the shared dead-replica shedding path
    (serve-side replica death and train-side host loss both land here).

    A dead worker is a capacity→0 event: its target share is zero, so
    *all* of its VWs must move this instant, unmetered (no
    ``max_moves_per_slot`` pacing, no byte budget — the state transfer
    is mandatory, only accounted). VWs are assigned hottest-first to the
    survivor with the largest remaining rate *deficit* against its
    capacity-proportional share, so the evacuated traffic lands where
    the spare capacity is instead of round-robin.

    Host-side NumPy on purpose: failure is a rare event and the greedy
    deficit loop is data-dependent; the steady-state path stays the
    jitted ``rebalance_step``.

    Args:
      vw_owner: [V] int owner map (any array-like; not mutated).
      vw_rate: [V] f32 per-VW rates (the delegation engine's).
      dead: int or sequence of ints — the worker(s) being evacuated.
      capacities: [n] f32 service-rate estimates; dead entries ignored.
      vw_bytes: optional [V] f32 per-VW state sizes for the bytes-moved
        accounting.

    Returns ``(new_owner [V] np.int32, n_moved int, bytes_moved float)``.
    """
    owner = np.array(vw_owner, np.int32)
    rate = np.asarray(vw_rate, np.float64)
    if rate.sum() <= 0:
        rate = np.ones_like(rate)             # cold engine: balance counts
    n = len(np.asarray(capacities))
    dead = np.atleast_1d(np.asarray(dead, np.int64))
    alive = np.ones(n, bool)
    alive[dead] = False
    if not alive.any():
        return owner, 0, 0.0                  # nowhere to go: no-op
    caps = np.where(alive, np.asarray(capacities, np.float64), 0.0)
    if caps.sum() <= 0:
        caps = alive.astype(np.float64)       # degenerate: uniform
    evac = np.flatnonzero(np.isin(owner, dead))
    if len(evac) == 0:
        return owner, 0, 0.0
    # survivors' deficit against their capacity-proportional share of
    # the *whole* rate (the dead workers' traffic has to land somewhere)
    rate_w = np.bincount(owner, weights=np.maximum(rate, 0.0), minlength=n)
    target = caps / caps.sum() * rate_w.sum()
    deficit = np.where(alive, target - rate_w, -np.inf)
    order = evac[np.argsort(-rate[evac], kind="stable")]   # hottest first
    for v in order:
        d = int(np.argmax(deficit))
        owner[v] = d
        deficit[d] -= max(float(rate[v]), 1e-9)
    bytes_moved = (float(np.asarray(vw_bytes, np.float64)[evac].sum())
                   if vw_bytes is not None else 0.0)
    return owner, len(evac), bytes_moved
