"""Faults planted under a run of the benchmark, to show that its
comparison catches them and to read the limits' upper ends.

Each fault takes ``setattr`` (``pytest``'s ``monkeypatch.setattr``, or
the builtin) and the cell's configuration, and breaks the program
underneath the loop. ``tests/bench/test_bench_faults.py`` plants them
at the rehearsal size; on the chip, a run at the cell's own size is

    python3 bench/faults.py <fault> --workload <cell> --seed <n> \\
        --seconds <s> --trace 0

Faults of the route loop (``cg.run``):

* ``state_unchanged``: a call returns the state it was given;
* ``half_left_out``: half of a call's messages are routed and stand in
  for the other half;
* ``answer_altered``: one message's worker is changed;
* ``util_reciprocal``: delegation reads each worker's utilisation as
  arrivals times the reciprocal of its service rate, not their quotient;
* ``util_drift``: delegation reads each utilisation two ulps off, up for
  even workers and down for odd ones.

Faults of the serve loop (the request router):

* ``router_state_unchanged``, ``router_half_left_out``,
  ``router_answer_altered``: as above, in ``dispatch_batch`` and
  ``finalize_batch``;
* ``psum_dropped``: the mesh router's exchange of lane deltas between
  chips left out (the merge carries the first chip's lanes alone).

Both loops: ``cap_drift``, every PoRC capacity two ulps high.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TWO_ULPS = 2.0 ** -22          # relative: two ulps of a float32 in [1, 2)


# -- cg.run ------------------------------------------------------------------

def state_unchanged(setattr, config) -> None:
    from repro.core import cg
    run = cg.run

    def broken(cfg, keys, caps, state=None):
        return run(cfg, keys, caps, state)._replace(state=state)
    setattr(cg, "run", broken)


def half_left_out(setattr, config) -> None:
    import jax.numpy as jnp
    from repro.core import cg
    run = cg.run

    def broken(cfg, keys, caps, state=None):
        res = run(cfg, keys[: keys.shape[0] // 2], caps, state)
        twice = lambda x: jnp.concatenate([x, x])  # noqa: E731
        return res._replace(assignment=twice(res.assignment),
                            vw_assignment=twice(res.vw_assignment),
                            imbalance=twice(res.imbalance),
                            queue_spread=twice(res.queue_spread))
    setattr(cg, "run", broken)


def answer_altered(setattr, config) -> None:
    from repro.core import cg
    run = cg.run

    def broken(cfg, keys, caps, state=None):
        res = run(cfg, keys, caps, state)
        a = res.assignment
        return res._replace(assignment=a.at[7].set((a[7] + 1) % cfg.n_workers))
    setattr(cg, "run", broken)


def _pressure(setattr, change) -> None:
    """Delegation (the controller's busy/idle signals and the pairing
    order) reads ``change(util)`` for the slot's utilisations."""
    from repro.core import controller, delegation
    step, rebalance = controller.controller_step, delegation.rebalance_step

    def broken_step(ccfg, state, pressure, *a, **k):
        return step(ccfg, state, change(pressure), *a, **k)

    def broken_rebalance(dcfg, dstate, pressure, *a, **k):
        return rebalance(dcfg, dstate, change(pressure), *a, **k)
    setattr(controller, "controller_step", broken_step)
    setattr(delegation, "rebalance_step", broken_rebalance)


def util_reciprocal(setattr, config) -> None:
    import jax.numpy as jnp
    from bench.reference import capacities
    fleet, spec = config["fleet"], config["cg"]
    service = jnp.asarray(capacities(spec["n_workers"], fleet["slow_workers"],
                                     fleet["slow_fraction"], fleet["rho"])
                          * spec["slot_len"])

    def change(util):
        # arrivals are whole messages, so the quotient gives them back
        return jnp.round(util * service) * (1.0 / service)
    _pressure(setattr, change)


def util_drift(setattr, config) -> None:
    import jax.numpy as jnp
    n = config["cg"]["n_workers"]
    sign = jnp.where(jnp.arange(n) % 2 == 0, 1.0, -1.0)
    _pressure(setattr, lambda util: util * (1.0 + sign * TWO_ULPS))


def cap_drift(setattr, config) -> None:
    from repro.kernels import blocks, mesh, ref
    view_cap = blocks.view_cap

    def broken(*a, **k):
        return view_cap(*a, **k) * (1.0 + TWO_ULPS)
    # the Pallas kernel calls it through the module; the jnp engine and
    # the mesh kernel hold their own names for it
    for module in (blocks, ref, mesh):
        setattr(module, "view_cap", broken)


# -- the request router ------------------------------------------------------

def _router(config):
    import repro.serve
    return getattr(repro.serve, config.get("router_class", "CGRequestRouter"))


def router_state_unchanged(setattr, config) -> None:
    cls = _router(config)
    dispatch = cls.dispatch_batch

    def broken(self, keys):
        before = self._state
        handle = dispatch(self, keys)
        self._state = before
        return handle
    setattr(cls, "dispatch_batch", broken)


def router_half_left_out(setattr, config) -> None:
    cls = _router(config)
    dispatch = cls.dispatch_batch

    def broken(self, keys):
        return dispatch(self, keys[: len(keys) // 2])
    setattr(cls, "dispatch_batch", broken)


def router_answer_altered(setattr, config) -> None:
    cls = _router(config)
    finalize = cls.finalize_batch

    def broken(self, handle):
        out = finalize(self, handle).copy()
        out[3] = (out[3] + 1) % self.n_replicas
        return out
    setattr(cls, "finalize_batch", broken)


def psum_dropped(setattr, config) -> None:
    import jax
    import jax.numpy as jnp
    psum = jax.lax.psum

    def broken(x, axis_name, **k):
        first = jax.lax.axis_index(axis_name) == 0
        return psum(jnp.where(first, x, jnp.zeros_like(x)), axis_name, **k)
    setattr(jax.lax, "psum", broken)


def main(argv=None) -> int:
    import json

    from bench import run
    argv = sys.argv[1:] if argv is None else argv
    fault, args = argv[0], argv[1:]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, config, _ = run.load_cell(args[args.index("--workload") + 1], spec)
    globals()[fault](setattr, config)
    return run.main(args, spec)


if __name__ == "__main__":
    sys.exit(main())
