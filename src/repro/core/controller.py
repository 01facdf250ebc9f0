"""Adaptive delegation controller — closed-loop budgets + hysteresis.

The delegation engine (``repro.core.delegation``) executes at most
``max_moves_per_slot`` paired moves per monitoring slot, and its
callers raise busy/idle signals the moment a worker's pressure crosses
a single threshold. Both are open-loop: the move budget is a constant
the operator must guess, and a worker whose ideal virtual-worker count
sits on the busy/idle boundary (the paper's Fig 12 granularity effect
at α≈10 VWs/worker) integer-ping-pongs between the two signals slot
after slot. This module closes both loops:

* **Adaptive move budgets** (``adaptive_moves=True``). The per-slot
  budget is derived from observed queue depth: per-worker depths are
  EWMA'd (``depth_decay``), the backlog *above the fleet mean* is
  converted into "how many virtual workers' worth of traffic must be
  re-homed to drain it in about one slot" (the caller supplies
  ``unit`` — the traffic one move re-routes per slot, typically
  ``slot_len / n_virtual``), and the result is clamped to
  ``[min_moves, max_moves]``. Under a flash crowd the budget opens up
  to ``max_moves`` within a couple of slots; at equilibrium it falls
  back to ``min_moves`` so steady state is not churned.
  ``per_worker_budget=True`` refines this from one fleet-wide scalar
  to an [n] vector: each worker's *own* depth excess caps how many VWs
  it may shed this slot (``plan_pairs``/``rebalance_step`` consume the
  vector as per-worker shed caps), so one flooded worker no longer
  opens the budget for every mildly-backed-up one.
* **Busy/idle hysteresis** (``hysteresis=True``). Signals latch:
  a worker *enters* the busy set only after its pressure has exceeded
  the enter level for ``dwell`` consecutive slots, and *exits* only
  when pressure falls below a separate, lower exit level (and
  symmetrically for idle). Near the granularity boundary the raw
  signal flips every slot; the latched signal does not.

``controller_step`` is jit-able alongside ``rebalance_step`` — all
state lives in a ``ControllerState`` of device arrays, and the flap
counter (latched-signal transitions) is the telemetry the Fig-12
flap benchmark consumes. With both features off the emitted masks are
exactly the raw threshold comparisons and the budget equals
``max_moves``, so the delegation engine's behaviour is bit-identical
to the static configuration (CI-gated).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import trace


class ControllerConfig(NamedTuple):
    n_workers: int
    # --- adaptive move budget ---
    adaptive_moves: bool = False   # derive the budget from queue depth
    min_moves: int = 1             # budget floor at equilibrium
    max_moves: int = 8             # = the engine's max_moves_per_slot
    depth_decay: float = 0.5       # EWMA decay of per-worker depths;
                                   # window ≈ 1/(1-decay) slots
    per_worker_budget: bool = False  # emit an [n] budget vector (each
                                   # worker's own EWMA'd depth excess)
                                   # instead of one fleet-wide scalar;
                                   # delegation caps each worker's shed
                                   # count by its entry
    # --- busy/idle hysteresis ---
    hysteresis: bool = False       # latch signals between enter/exit
    dwell: int = 3                 # consecutive over-enter slots before
                                   # a new signal latches
    # --- migration-cost cap ---
    byte_budget: float = 0.0       # max VW state bytes one slot may
                                   # migrate (0 = unmetered); divided by
                                   # the caller's ``unit_bytes`` (bytes
                                   # one move transfers) to cap the
                                   # emitted move budget


class ControllerState(NamedTuple):
    depth_ewma: jnp.ndarray   # [n] f32 EWMA'd queue depth / backlog
    busy_latch: jnp.ndarray   # [n] bool signals emitted last slot
    idle_latch: jnp.ndarray   # [n] bool
    busy_dwell: jnp.ndarray   # [n] i32 consecutive slots above enter
    idle_dwell: jnp.ndarray   # [n] i32 consecutive slots below enter
    flaps: jnp.ndarray        # []  i32 cumulative emitted-signal flips
    budget: jnp.ndarray       # []  i32 budget emitted last slot


def init_controller(cfg: ControllerConfig) -> ControllerState:
    n = cfg.n_workers
    return ControllerState(
        depth_ewma=jnp.zeros((n,), jnp.float32),
        busy_latch=jnp.zeros((n,), bool),
        idle_latch=jnp.zeros((n,), bool),
        busy_dwell=jnp.zeros((n,), jnp.int32),
        idle_dwell=jnp.zeros((n,), jnp.int32),
        flaps=jnp.zeros((), jnp.int32),
        budget=jnp.full((), cfg.max_moves, jnp.int32))


@functools.partial(jax.jit, static_argnames=("cfg",))
def controller_step(cfg: ControllerConfig, state: ControllerState,
                    pressure, depths, unit,
                    enter_busy, exit_busy, enter_idle, exit_idle,
                    unit_bytes=None):
    """One monitoring-slot tick of the controller.

    Args:
      pressure: [n] f32 signal the thresholds compare against (slot
        utilization in the simulator, queue occupancy in serve,
        step-time ratio in the straggler balancer).
      depths: [n] f32 queue depth / backlog per worker, any unit.
      unit: scalar — the backlog one executed move drains per slot
        (typically mean per-VW arrivals per slot); sets the scale of
        the adaptive budget.
      enter_busy/exit_busy: scalars, exit_busy <= enter_busy. A worker
        turns busy above enter_busy (after ``dwell`` slots) and stays
        busy until pressure falls below exit_busy.
      enter_idle/exit_idle: scalars, exit_idle >= enter_idle,
        symmetrically.
      unit_bytes: optional f32 scalar — the state bytes one move
        migrates (e.g. the mean per-VW state size). With
        ``cfg.byte_budget > 0`` the emitted budget is additionally
        capped at ``byte_budget / unit_bytes`` (floored, but never
        below 1 so a starved budget cannot wedge the engine); None or
        ``byte_budget=0`` leaves the budget purely move-count-driven.

    Returns ``(new_state, busy [n] bool, idle [n] bool, budget)``;
    ``budget`` is a scalar i32 — or an [n] i32 vector of per-worker
    shed caps under ``cfg.per_worker_budget``. Feed
    ``busy``/``idle``/``budget`` straight into
    ``delegation.rebalance_step`` (both shapes are accepted).
    """
    with trace.scope(trace.CONTROLLER):
        return _controller_step(cfg, state, pressure, depths, unit,
                                enter_busy, exit_busy, enter_idle,
                                exit_idle, unit_bytes)


def _controller_step(cfg, state, pressure, depths, unit, enter_busy,
                     exit_busy, enter_idle, exit_idle, unit_bytes):
    pressure = jnp.asarray(pressure, jnp.float32)
    depths = jnp.asarray(depths, jnp.float32)
    raw_busy = pressure > enter_busy
    raw_idle = pressure < enter_idle

    busy_dwell = jnp.where(raw_busy, state.busy_dwell + 1, 0)
    idle_dwell = jnp.where(raw_idle, state.idle_dwell + 1, 0)
    if cfg.hysteresis:
        busy = jnp.where(state.busy_latch, pressure > exit_busy,
                         busy_dwell >= cfg.dwell)
        idle = jnp.where(state.idle_latch, pressure < exit_idle,
                         idle_dwell >= cfg.dwell)
        idle = idle & ~busy       # shedding wins if both ever latch
    else:
        busy, idle = raw_busy, raw_idle

    flips = (jnp.sum(busy != state.busy_latch)
             + jnp.sum(idle != state.idle_latch)).astype(jnp.int32)

    depth_ewma = (cfg.depth_decay * state.depth_ewma
                  + (1.0 - cfg.depth_decay) * depths)
    unit_f = jnp.maximum(jnp.asarray(unit, jnp.float32), 1e-9)
    if cfg.adaptive_moves and cfg.per_worker_budget:
        # per-worker: each worker's own backlog above the fleet mean
        # sets how many VWs *it* may shed this slot. Busy workers keep
        # the min_moves pacing floor (a latched busy signal must be
        # able to make progress); everyone else may sit at 0.
        excess_w = jnp.maximum(depth_ewma - jnp.mean(depth_ewma), 0.0)
        demand_w = jnp.ceil(excess_w / unit_f).astype(jnp.int32)
        budget = jnp.clip(demand_w, 0, cfg.max_moves)
        budget = jnp.where(busy, jnp.maximum(budget, cfg.min_moves),
                           budget)
    elif cfg.adaptive_moves:
        excess = jnp.sum(jnp.maximum(
            depth_ewma - jnp.mean(depth_ewma), 0.0))
        demand = jnp.ceil(excess / unit_f)
        budget = jnp.clip(demand.astype(jnp.int32),
                          cfg.min_moves, cfg.max_moves)
    else:
        budget = jnp.full((), cfg.max_moves, jnp.int32)
    if cfg.byte_budget > 0 and unit_bytes is not None:
        fit = jnp.floor(cfg.byte_budget / jnp.maximum(
            jnp.asarray(unit_bytes, jnp.float32), 1e-9)).astype(jnp.int32)
        budget = jnp.minimum(budget, jnp.maximum(fit, 1))

    new_state = ControllerState(
        depth_ewma=depth_ewma,
        busy_latch=busy,
        idle_latch=idle,
        busy_dwell=busy_dwell,
        idle_dwell=idle_dwell,
        flaps=state.flaps + flips,
        # telemetry stays a scalar either way (the cg scan stacks it):
        # the vector's effective total is what the engine can execute
        budget=(jnp.minimum(jnp.sum(budget), cfg.max_moves)
                .astype(jnp.int32) if budget.ndim else budget))
    return new_state, busy, idle, budget


class DelegationController:
    """Stateful host-side wrapper over ``controller_step`` for callers
    that tick from Python (the serving router, the straggler balancer);
    the CG simulator threads ``ControllerState`` through its scan
    directly. Holds the config, the device-resident state and the
    threshold levels; ``step`` mutates the state in place and returns
    the masks + budget for this slot."""

    def __init__(self, cfg: ControllerConfig, *,
                 enter_busy: float, exit_busy: float,
                 enter_idle: float, exit_idle: float):
        self.cfg = cfg
        self.enter_busy, self.exit_busy = enter_busy, exit_busy
        self.enter_idle, self.exit_idle = enter_idle, exit_idle
        self.state = init_controller(cfg)

    @classmethod
    def from_thresholds(cls, cfg: ControllerConfig, *, theta_busy: float,
                        theta_idle: float, margin: float):
        """The standard enter/exit derivation every consumer uses: busy
        exits ``margin`` below its enter level, idle ``margin`` above."""
        return cls(cfg, enter_busy=theta_busy,
                   exit_busy=theta_busy - margin,
                   enter_idle=theta_idle,
                   exit_idle=theta_idle + margin)

    def step(self, pressure, depths, unit=1.0, unit_bytes=None):
        self.state, busy, idle, budget = controller_step(
            self.cfg, self.state, pressure, depths, unit,
            self.enter_busy, self.exit_busy,
            self.enter_idle, self.exit_idle, unit_bytes)
        return busy, idle, budget

    @property
    def flaps(self) -> int:
        return int(self.state.flaps)

    @property
    def last_budget(self) -> int:
        return int(self.state.budget)
