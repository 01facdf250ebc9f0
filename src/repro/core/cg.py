"""Consistent Grouping (CG) — the paper's contribution (§V-B, §V-C).

CG = (1) PoRC routing of messages onto α·n *homogeneous virtual workers*
+ (2) capacity-driven assignment of virtual workers to heterogeneous
physical workers via *worker delegation* signals and *paired* moves.

Model fidelity notes
--------------------
* **Time slot** (t₀): the monitoring period. One slot = ``slot_len``
  messages (one message per unit time, §IV). Signals computed at slot
  end take effect the next slot — this one-slot lag *is* the
  piggybacking/eventual-consistency delay of §V-C.
* **Delegation**: worker w signals *busy* when its slot utilization
  ``U_w = arrivals_w/(c_w·slot_len)`` exceeds θ_b and *idle* below θ_i
  (paper uses θ_i=0.75, θ_b=0.85 around a ρ=0.8 provisioning point).
  Capacities are **never revealed to the sources** — only the binary
  signals are.
* **Pairing**: every VW removal from a busy worker is paired with an
  addition to an idle worker (§V-B "pairing virtual workers"), keeping
  the VW population constant. Pairing runs through the shared
  ``repro.core.delegation`` engine: within a slot signals pair in
  severity order (most-overloaded busy ↔ most-underloaded idle, the
  degenerate-FCFS argument of §V-B); ``fcfs_pairing=True`` keeps
  unserved signals queued across slots (the paper's FCFS queues). The
  migrated VW is the busy worker's highest-rate one (greatest relief;
  ``rate_decay`` windows the rate — 1.0 = the seed's cumulative
  counts); ``capacity_weighted=True`` lets a busy worker shed as many
  VWs per slot as its rate surplus over its capacity-proportional
  share instead of one per signal. Routing changes affect only
  *future* messages — no message migration (§V-C).
* **Adaptive control** (``adaptive_moves``/``hysteresis``): the
  ``repro.core.controller`` layer can derive the per-slot move budget
  from EWMA'd worker queue depths (clamped to
  ``[min_moves, max_moves_per_slot]``) and latch the busy/idle signals
  between separate enter/exit levels with a dwell, damping the Fig-12
  integer ping-pong at the α-granularity boundary. Both default off —
  the defaults stay bit-identical to the seed engine.
* **Queues**: each worker drains ``c_w·slot_len`` messages per slot from
  an unbounded FIFO — the queueing model of §IV used for Fig 9/10/12/13.
* **Block-parallel routing** (``block_size``): the paper defines PoRC
  one-message-per-unit-time; the runtime routes ``block_size`` messages
  per load snapshot (``repro.kernels.ref.ref_porc_snapshot``) — the
  §V-C eventual-consistency license, same as sources with local load
  views. ``block_size=0`` keeps the exact per-message oracle;
  ``block_size=1`` takes the block path and is bit-identical to it.
* **Distributed sources** (``n_sources``/``sync_every``): §V-C's
  multiple sources become first-class — the slot's stream splits
  round-robin across ``n_sources`` sources, each routing against a
  local load view (shared base + own delta) that delta-merges every
  ``sync_every`` blocks. The slot boundary (the monitoring period t₀)
  forces a final merge: that is when the piggybacked signals all
  arrive, so no unpublished delta survives into the next slot.
  ``n_sources=1`` is exactly the single-source block path.
* **Heavy-hitter probing** (``hh_scheme``): D/W-Choices probe depths for
  the PORC inner scheme — a count-min sketch (``sketch_depth`` ×
  ``sketch_width``, carried in ``CGState.sketch``) classifies keys at
  block boundaries; keys above ``hot_fraction`` of the routed mass get
  up to ``d_heavy`` ("d") or V ("w") probe choices, the tail keeps
  ``d_tail``. Off ("") = seed-exact. Requires the block path. See
  ``repro.kernels.ref.HHPolicy`` and docs/partitioners.md.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import trace

from . import controller, delegation
from .hashing import hash_to_bins


class CGConfig(NamedTuple):
    n_workers: int
    alpha: int = 10               # virtual workers per worker at init
    eps: float = 0.01             # PoRC imbalance/memory knob
    theta_busy: float = 0.85
    theta_idle: float = 0.75
    slot_len: int = 10_000        # messages per time slot t0
    max_moves_per_slot: int = 8   # paired (busy→idle) moves per slot
    inner: str = "PORC"           # VW-level scheme: PORC | KG | SG
    block_size: int = 128         # PoRC messages per load snapshot;
                                  # 0 = exact per-message oracle, 1 = block
                                  # path (bit-identical to the oracle)
    n_sources: int = 1            # §V-C distributed sources routing with
                                  # local load views (round-robin split);
                                  # >1 requires the block path
    sync_every: int = 1           # blocks between delta-merge syncs of
                                  # the sources' local views
    capacity_weighted: bool = False  # delegation budgets ∝ rate surplus
                                  # over the capacity-proportional share
                                  # (False = one VW per pair, seed-exact)
    rate_decay: float = 1.0       # EWMA decay of per-VW rates per slot;
                                  # window ≈ 1/(1-decay) slots, 1.0 =
                                  # cumulative-since-t0 (seed-exact)
    fcfs_pairing: bool = False    # carry unserved busy/idle signals
                                  # across slots (the paper's queues)
    adaptive_moves: bool = False  # per-slot move budget derived from
                                  # queue depth (repro.core.controller),
                                  # clamped [min_moves, max_moves_per_slot]
                                  # (False = static budget, seed-exact)
    min_moves: int = 1            # adaptive budget floor at equilibrium
    depth_decay: float = 0.5      # EWMA decay of worker queue depths
                                  # feeding the adaptive budget
    hysteresis: bool = False      # latch busy/idle between separate
                                  # enter/exit levels + dwell (damps the
                                  # Fig-12 α-granularity ping-pong)
    theta_margin: float = 0.05    # exit-level offset: busy exits below
                                  # theta_busy-margin, idle exits above
                                  # theta_idle+margin
    dwell: int = 3                # slots a raw signal must persist
                                  # before it latches
    hh_scheme: str = ""           # heavy-hitter probe-depth policy for
                                  # the PORC inner scheme: "" = off
                                  # (seed-exact), "d" = D-Choices,
                                  # "w" = W-Choices (registry spellings
                                  # "DCHOICES"/"WCHOICES" also accepted;
                                  # requires block_size >= 1)
    sketch_depth: int = 4         # count-min sketch rows
    sketch_width: int = 4096      # count-min sketch columns per row
    hot_fraction: float = 1e-3    # heavy when sketch est >= fraction of
                                  # the routed message mass
    d_heavy: int = 32             # heavy-key probe ceiling under "d"
    d_tail: int = 2               # tail-key probe budget
    hh_headroom: float = 2.0      # probe-depth schedule slack over the
                                  # Eq.-2 spread ceil(p·n/(1+eps))
    engine: str = "auto"          # block-engine implementation for the
                                  # PORC inner scheme: "ref" (jnp scan),
                                  # "pallas" (Pallas kernel, bit-identical
                                  # — load/delta/sketch lanes in VMEM),
                                  # "auto" = Pallas on TPU, jnp elsewhere.
                                  # Applies to the block path only; the
                                  # block_size=0 sequential oracle and
                                  # KG/SG ignore it.


class CGState(NamedTuple):
    """Everything that continues across ``run`` calls / slot boundaries.

    State-carry contract: every field carries across slots *and* across
    chained ``run`` calls (``run(cfg, rest, caps, state=prev.state)`` ==
    one run over the whole stream, slot-aligned). Nothing here resets at
    slot boundaries; the only slot-boundary action is the §V-C forced
    delta-merge inside ``_route_slot`` (multi-source load views and
    sketch deltas publish at the monitoring boundary).
    """
    vw_load: jnp.ndarray     # [V]  source-side per-VW message counts
    vw_owner: jnp.ndarray    # [V]  physical worker owning each VW
    vw_rate: jnp.ndarray     # [V]  windowed per-VW arrival rate (EWMA)
    queues: jnp.ndarray      # [n]  worker FIFO occupancy
    signal_queues: delegation.PairQueues   # FCFS busy/idle queues +
                                           # slot counter (delegation)
    t_offset: jnp.ndarray    # []   messages routed so far (f32 clock)
    sg_ptr: jnp.ndarray      # []   exact SG round-robin pointer (i32,
                             #      kept in [0, V) so it never loses
                             #      precision, unlike the f32 t_offset)
    moves: jnp.ndarray       # []   cumulative paired moves
    controller: controller.ControllerState   # adaptive-budget EWMA,
                             # signal latches/dwell counters, flap count
    sketch: jnp.ndarray | None = None   # [depth, width] count-min key
                             # frequencies (heavy-hitter policy only;
                             # None when cfg.hh_scheme is off)


class DelegationTelemetry(NamedTuple):
    """Per-slot controller/engine telemetry (benchmarks consume this)."""
    budget: jnp.ndarray       # [slots] move budget the controller set
    executed: jnp.ndarray     # [slots] paired moves actually executed
    flaps: jnp.ndarray        # [slots] busy/idle signal flips this slot
    queue_depth: jnp.ndarray  # [slots, n] worker FIFO depth at slot end


class CGResult(NamedTuple):
    assignment: jnp.ndarray        # [m] physical-worker id per message
    vw_assignment: jnp.ndarray     # [m] virtual-worker id per message
    imbalance: jnp.ndarray         # [slots] I(t) over normalized load
    queue_spread: jnp.ndarray      # [slots] max-min queue length
    latency_spread: jnp.ndarray    # [slots] max-min latency proxy
    mean_latency: jnp.ndarray      # [slots] arrival-weighted mean latency
    utilization: jnp.ndarray       # [slots, n] per-worker utilization
    moves: jnp.ndarray             # [] total VW migrations
    telemetry: DelegationTelemetry  # per-slot budget/moves/flaps/depths
    state: CGState


def _hh_letter(name: str) -> str:
    """Normalize an hh_scheme spelling to the kernel letter. Accepts
    the HHPolicy letters ("d"/"w") and the partitioner-registry names
    ("DCHOICES"/"WCHOICES"), case-insensitively."""
    letter = {"d": "d", "w": "w",
              "dchoices": "d", "wchoices": "w"}.get(name.lower())
    if letter is None:
        raise ValueError(f"unknown hh_scheme {name!r}; use 'd'/'w' "
                         f"(or 'DCHOICES'/'WCHOICES')")
    return letter


def hh_policy(cfg: CGConfig):
    """The kernel ``HHPolicy`` a CGConfig's heavy-hitter knobs describe
    (None when ``hh_scheme`` is off — the seed-exact default)."""
    if not cfg.hh_scheme:
        return None
    if cfg.inner != "PORC":
        raise ValueError("hh_scheme requires the PORC inner scheme")
    if cfg.block_size < 1:
        raise ValueError("hh_scheme requires the block path "
                         "(block_size >= 1); the sketch classifies keys "
                         "at block boundaries")
    from repro.kernels.ref import HHPolicy  # deferred: core ← kernels
    return HHPolicy(scheme=_hh_letter(cfg.hh_scheme), depth=cfg.sketch_depth,
                    width=cfg.sketch_width, hot_fraction=cfg.hot_fraction,
                    d_heavy=cfg.d_heavy, d_tail=cfg.d_tail,
                    headroom=cfg.hh_headroom)


def init_state(cfg: CGConfig) -> CGState:
    n, a = cfg.n_workers, cfg.alpha
    V = n * a
    policy = hh_policy(cfg)
    if policy is not None:
        from repro.kernels.ref import hh_sketch_init
        sketch = hh_sketch_init(policy)
    else:
        sketch = None
    return CGState(
        sketch=sketch,
        vw_load=jnp.zeros(V, jnp.float32),
        vw_owner=jnp.tile(jnp.arange(n, dtype=jnp.int32), a),
        vw_rate=jnp.zeros(V, jnp.float32),
        queues=jnp.zeros(n, jnp.float32),
        signal_queues=delegation.init_queues(n),
        t_offset=jnp.zeros((), jnp.float32),
        sg_ptr=jnp.zeros((), jnp.int32),
        moves=jnp.zeros((), jnp.int32),
        controller=controller.init_controller(controller_config(cfg)),
    )


def delegation_config(cfg: CGConfig) -> delegation.DelegationConfig:
    """The shared-engine view of a CGConfig's delegation knobs."""
    return delegation.DelegationConfig(
        n_workers=cfg.n_workers,
        n_virtual=cfg.n_workers * cfg.alpha,
        max_moves_per_slot=cfg.max_moves_per_slot,
        capacity_weighted=cfg.capacity_weighted,
        rate_decay=cfg.rate_decay,
        fcfs=cfg.fcfs_pairing)


def controller_config(cfg: CGConfig) -> controller.ControllerConfig:
    """The adaptive-controller view of a CGConfig's knobs."""
    return controller.ControllerConfig(
        n_workers=cfg.n_workers,
        adaptive_moves=cfg.adaptive_moves,
        min_moves=cfg.min_moves,
        max_moves=cfg.max_moves_per_slot,
        depth_decay=cfg.depth_decay,
        hysteresis=cfg.hysteresis,
        dwell=cfg.dwell)


def _route_slot(cfg: CGConfig, vw_load, t_offset, sg_ptr, sketch, keys):
    """Route one slot of messages onto virtual workers (inner scheme).

    Returns ``(vw_load, sketch, vw)``; ``sketch`` is the heavy-hitter
    count-min state (threaded unchanged for KG/SG and when the policy is
    off, updated per block and fully published at the slot boundary for
    PORC with ``cfg.hh_scheme`` set).
    """
    V = cfg.n_workers * cfg.alpha
    policy = hh_policy(cfg)
    if cfg.inner == "KG":
        vw = hash_to_bins(keys, 1, V)
        vw_load = vw_load.at[vw].add(1.0)
        return vw_load, sketch, vw
    if cfg.inner == "SG":
        # exact int32 round-robin pointer: the f32 t_offset loses ±1
        # precision past 2^24 routed messages, which would freeze the
        # pointer; sg_ptr lives in [0, V) and never degrades.
        m = keys.shape[0]
        vw = (sg_ptr + jnp.arange(m, dtype=jnp.int32)) % V
        vw_load = vw_load.at[vw].add(1.0)
        return vw_load, sketch, vw

    if cfg.n_sources > 1:
        # §V-C distributed sources: the slot's stream splits round-robin
        # across n_sources local load views (shared merged base + own
        # delta, synchronized every sync_every blocks). The slot end is
        # the monitoring boundary, where piggybacked deltas all arrive —
        # merge them so CGState keeps a single [V] load vector.
        if cfg.block_size < 1:
            raise ValueError("n_sources > 1 requires the block path "
                             "(block_size >= 1)")
        from repro.kernels.ref import (MultiSourcePorcState,
                                       ref_porc_multisource)
        state = MultiSourcePorcState(
            base=vw_load,
            delta=jnp.zeros((cfg.n_sources, V), jnp.float32),
            routed=t_offset,
            ticks=jnp.zeros((), jnp.int32),
            sketch_base=sketch,
            sketch_delta=None if sketch is None else jnp.zeros(
                (cfg.n_sources,) + sketch.shape, jnp.float32))
        from repro.kernels import resolve_engine
        vw, state = ref_porc_multisource(
            keys, V, cfg.n_sources, sync_every=cfg.sync_every,
            block=cfg.block_size, eps=cfg.eps, state=state, policy=policy,
            engine=resolve_engine(cfg.engine, policy))
        sketch = (None if state.sketch_base is None
                  else state.sketch_base + state.sketch_delta.sum(0))
        return state.base + state.delta.sum(0), sketch, vw

    if cfg.block_size >= 1:
        # Block-parallel PoRC: route the slot in blocks of B messages
        # against per-block load snapshots (eventually-consistent, the
        # kernels' block-synchronous semantics). Bit-identical to the
        # sequential path below when block_size == 1.
        from repro.kernels import resolve_engine
        from repro.kernels.ref import PorcState, ref_porc_route
        state = PorcState(load=vw_load, routed=t_offset, sketch=sketch)
        vw, state = ref_porc_route(keys, V, block=cfg.block_size,
                                   eps=cfg.eps, state=state, policy=policy,
                                   engine=resolve_engine(cfg.engine, policy))
        return state.load, state.sketch, vw

    # PoRC (Alg. 1) continuing across slots: capacity uses global time.
    max_probes = 4 * V

    def step(carry, xt):
        load, t = carry
        key = xt
        cap = (1.0 + cfg.eps) * (t + 1.0) / V

        def cond(c):
            _, bin_, probes = c
            return (load[bin_] >= cap) & (probes < max_probes)

        def body(c):
            salt, _, probes = c
            salt = salt + 1
            return salt, hash_to_bins(key, salt, V), probes + 1

        init = (jnp.uint32(1), hash_to_bins(key, jnp.uint32(1), V), jnp.int32(0))
        _, bin_, probes = jax.lax.while_loop(cond, body, init)
        bin_ = jnp.where(probes >= max_probes,
                         jnp.argmin(load).astype(jnp.int32), bin_)
        return (load.at[bin_].add(1.0), t + 1.0), bin_

    (vw_load, _), vw = jax.lax.scan(step, (vw_load, t_offset), keys)
    return vw_load, sketch, vw


def _bind(vw_owner, vw, n_workers: int):
    """Bind a slot's messages to workers: ``(vw_owner[vw], arrivals)``.

    ``arrivals`` is ``bincount(workers, minlength=n_workers)`` as float32.
    Both are compare-against-iota reductions, as the kernels' ``[n_bins,
    1]`` tables are read (``porc_snapshot._take``/``_count``): the TPU
    lowers a gather or scatter-add one index at a time, and a slot has
    ``slot_len`` of each. Every sum is an integer count below 2^24, so it
    is exact in any order and the result is the gather's and the
    scatter-add's bit for bit.
    """
    m = vw.shape[0]
    hits = vw[None, :] == jax.lax.broadcasted_iota(
        jnp.int32, (vw_owner.shape[0], m), 0)                   # [V, m]
    workers = jnp.sum(jnp.where(hits, vw_owner[:, None], 0),
                      axis=0).astype(vw_owner.dtype)            # [m]
    mine = workers[None, :] == jax.lax.broadcasted_iota(
        jnp.int32, (n_workers, m), 0)                           # [n, m]
    arrivals = jnp.sum(mine, axis=1).astype(jnp.float32)        # [n]
    return workers, arrivals


@functools.partial(jax.jit, static_argnames=("cfg",))
def run(cfg: CGConfig, keys: jnp.ndarray, capacities: jnp.ndarray,
        state: CGState | None = None) -> CGResult:
    """Run CG over a key stream.

    Args:
      cfg: CGConfig (n_workers, alpha, eps, thresholds, slot_len, inner).
      keys: [m] int32 key stream; m must be a multiple of slot_len.
      capacities: [n] static, or [slots, n] time-varying *service rates*
        in messages per unit time (arrival rate is 1 msg/unit time).
      state: optional CGState to continue from (e.g. ``result.state`` of
        a previous ``run`` over the stream prefix) — routing loads, the
        owner map, delegation queues and the SG pointer all carry over.
        ``capacities`` rows, if 2-D, cover only the *remaining* slots.

    Returns CGResult with per-slot metrics and the full assignment.
    """
    m = keys.shape[0]
    slots = m // cfg.slot_len
    assert slots * cfg.slot_len == m, "stream length must be slots*slot_len"
    keys = keys[: slots * cfg.slot_len].reshape(slots, cfg.slot_len)
    if capacities.ndim == 1:
        caps = jnp.broadcast_to(capacities, (slots, cfg.n_workers))
    else:
        caps = capacities
    caps = caps.astype(jnp.float32)
    dcfg = delegation_config(cfg)
    ccfg = controller_config(cfg)
    # backlog one executed move drains per slot ≈ mean per-VW arrivals
    move_unit = cfg.slot_len / max(cfg.n_workers * cfg.alpha, 1)

    def slot_step(state: CGState, xs):
        slot_keys, c = xs
        vw_load, sketch, vw = _route_slot(cfg, state.vw_load,
                                          state.t_offset, state.sg_ptr,
                                          state.sketch, slot_keys)
        with trace.scope(trace.BIND):
            workers, arrivals = _bind(state.vw_owner, vw, cfg.n_workers)

        service = c * cfg.slot_len                          # msgs drainable
        q0 = state.queues
        q1 = jnp.maximum(q0 + arrivals - service, 0.0)

        util = arrivals / jnp.maximum(service, 1e-9)
        # latency proxy: wait behind queue + own service (units of time)
        lat = (q0 + 0.5 * arrivals) / jnp.maximum(c, 1e-9) + 1.0 / jnp.maximum(c, 1e-9)
        mean_lat = jnp.sum(lat * arrivals) / jnp.maximum(jnp.sum(arrivals), 1.0)

        norm_load = arrivals / jnp.maximum(c, 1e-9)
        imb = (jnp.max(norm_load) - jnp.mean(norm_load)) / jnp.maximum(
            jnp.mean(norm_load), 1e-9)

        # the adaptive controller turns raw pressure into (possibly
        # hysteresis-latched) busy/idle signals and this slot's move
        # budget from the EWMA'd queue depths; with both knobs off the
        # masks are the raw threshold comparisons and the budget is the
        # static ceiling (bit-identical to the pre-controller engine).
        cstate, busy, idle, budget = controller.controller_step(
            ccfg, state.controller, util, q1, move_unit,
            cfg.theta_busy, cfg.theta_busy - cfg.theta_margin,
            cfg.theta_idle, cfg.theta_idle + cfg.theta_margin)

        # worker delegation through the shared engine (§V-B pairing):
        # per-VW arrivals this slot feed the windowed rates; capacities
        # drive the capacity-proportional budgets when enabled.
        dstate = delegation.DelegationState(
            vw_owner=state.vw_owner,
            vw_rate=state.vw_rate,
            queues=state.signal_queues,
            moves=state.moves)
        dstate, n_done = delegation.rebalance_step(
            dcfg, dstate, util, busy, idle, vw_load - state.vw_load, c,
            budget if cfg.adaptive_moves else None)

        new_state = CGState(
            vw_load=vw_load,
            vw_owner=dstate.vw_owner,
            vw_rate=dstate.vw_rate,
            queues=q1,
            signal_queues=dstate.queues,
            t_offset=state.t_offset + cfg.slot_len,
            sg_ptr=(state.sg_ptr + cfg.slot_len) % (cfg.n_workers * cfg.alpha),
            moves=dstate.moves,
            controller=cstate,
            sketch=sketch,
        )
        metrics = (workers, vw, imb, jnp.max(q1) - jnp.min(q1),
                   jnp.max(lat) - jnp.min(lat), mean_lat, util,
                   budget, n_done, cstate.flaps - state.controller.flaps, q1)
        return new_state, metrics

    state0 = init_state(cfg) if state is None else state
    # normalize the sketch lane to cfg: a state carried from a policy-off
    # run cold-starts an empty sketch (scan carries need a fixed pytree
    # structure); turning the policy off drops the lane
    policy = hh_policy(cfg)
    if policy is not None and state0.sketch is None:
        from repro.kernels.ref import hh_sketch_init
        state0 = state0._replace(sketch=hh_sketch_init(policy))
    elif policy is None and state0.sketch is not None:
        state0 = state0._replace(sketch=None)
    state, (workers, vw, imb, qs, ls, ml, util,
            budget, executed, flaps, depths) = jax.lax.scan(
        slot_step, state0, (keys, caps))
    return CGResult(
        assignment=workers.reshape(-1),
        vw_assignment=vw.reshape(-1),
        imbalance=imb,
        queue_spread=qs,
        latency_spread=ls,
        mean_latency=ml,
        utilization=util,
        moves=state.moves,
        telemetry=DelegationTelemetry(budget=budget, executed=executed,
                                      flaps=flaps, queue_depth=depths),
        state=state,
    )
