"""The ``route`` loop: ``repro.core.cg.run`` over a deployment's stream.

The stream is routed in calls of ``chunk_slots`` slots, state carried
from call to call and restarted at the start of each pass over the
stream; every call ends in ``block_until_ready``. The configuration's
``cg`` group is passed to ``CGConfig`` as it stands (the control doubles
its ``sync_every``) and to the plain reference by the same names, so a
knob the reference does not model is refused before anything runs.
After the window, its outputs are compared with the reference's replay
of the pass.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import log, reference, streams


def run(run) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import cg

    c, t = run.config, run.traffic
    fleet = c["fleet"]
    spec = {k: v for k, v in c["cg"].items() if k != "engine"}
    caps_np = reference.capacities(spec["n_workers"], fleet["slow_workers"],
                                   fleet["slow_fraction"], fleet["rho"])
    ref = reference.CGSlots(caps=caps_np, **spec)
    cfg = cg.CGConfig(**{**c["cg"], "sync_every": run.sync(c["cg"])})
    caps = jnp.asarray(caps_np)
    slot_len = spec["slot_len"]
    chunk = t["chunk_slots"] * slot_len
    n_chunks = c["stream"]["messages"] // chunk
    keys = streams.sample_keys(run.seed, 0, c["stream"], n_chunks * chunk)
    part = jax.jit(lambda k, i: jax.lax.dynamic_slice(k, (i * chunk,),
                                                      (chunk,)))
    chunks = [part(keys, i) for i in range(n_chunks)]
    state0 = cg.init_state(cfg)

    def outputs(res):
        return (res.assignment, res.vw_assignment, res.imbalance,
                res.queue_spread, res.moves)

    differ = jax.jit(lambda a, b: sum(jnp.sum(x != y).astype(jnp.int32)
                                      for x, y in zip(a, b)))
    res = cg.run(cfg, chunks[0], caps, state0)
    res = cg.run(cfg, chunks[1 % n_chunks], caps, res.state)
    jax.block_until_ready(differ(outputs(res), outputs(res)))
    del res
    run.setup_done()

    first: dict[int, tuple] = {}      # chunk index -> its first outputs
    repeats = []                      # later passes against the first
    calls = 0
    with run.window():
        t0 = time.perf_counter()
        k, state = 0, state0
        while True:
            with run.spans("chunk"):
                res = cg.run(cfg, chunks[k], caps, state)
                jax.block_until_ready(res)
            calls += 1
            if k in first:
                repeats.append(differ(outputs(res), first[k]))
            else:
                first[k] = outputs(res)
            k += 1
            state = res.state if k < n_chunks else state0
            k %= n_chunks
            if time.perf_counter() - t0 >= run.window_seconds:
                break
        elapsed = time.perf_counter() - t0
    run.end_to_end["route_msgs_per_s"] = calls * chunk / elapsed
    run.work.update(calls=calls, messages=calls * chunk,
                    slots=calls * t["chunk_slots"], window_s=elapsed)
    log(f"route: {calls} calls of {chunk:,} messages in {elapsed:.3f} s")

    # -- the reference: replay the pass as far as the window got ---------
    repeat_mismatch = int(sum(int(x) for x in repeats))
    seen = sorted(first)                      # chunks 0..k of the pass
    host_keys = np.asarray(keys[: len(seen) * chunk])
    del chunks, keys
    got = [[np.asarray(x) for x in first[i]] for i in seen]
    tr = time.perf_counter()
    shown = (np.concatenate([g[0] for g in got]),
             np.concatenate([g[1] for g in got]))
    want = ref.run(host_keys, seen=shown if all(
        x.shape == host_keys.shape for x in shown) else None)
    bad_a = bad_vw = bad_msgs = bad_moves = 0
    imb_err = qs_err = 0.0
    cs = t["chunk_slots"]
    for i, (a, vw, imb, qs, moves) in zip(seen, got):
        m, s = slice(i * chunk, (i + 1) * chunk), slice(i * cs, (i + 1) * cs)
        da = reference.differ(a, want["assignment"][m])
        dv = reference.differ(vw, want["vw_assignment"][m])
        bad_a += int(da.sum())
        bad_vw += int(dv.sum())
        bad_msgs += int((da | dv).sum())
        bad_moves += int(int(moves) not in want["moves"][(i + 1) * cs - 1])
        if imb.shape != want["imbalance"][s].shape:
            imb_err = qs_err = math.inf
            continue
        imb_err = max(imb_err, float(np.max(
            np.abs(imb - want["imbalance"][s])
            / np.maximum(np.abs(want["imbalance"][s]), 1e-6))))
        qs_err = max(qs_err, float(np.max(
            np.abs(qs - want["queue_spread"][s]))))
    # how often the program's delegation took a correct outcome other
    # than the exact reading's; not compared: a program that divides
    # by a reciprocal reads as high as the TPU's own division
    log(f"reference: {len(seen)} chunks in {time.perf_counter() - tr:.3f} s;"
        f" {ref.open_slots} slots with more than one correct delegation, "
        f"{ref.departures} of them off the exact reading")
    lim = t["limits"]
    run.check("assignment_mismatch", bad_a, 0)
    run.check("vw_mismatch", bad_vw, 0)
    run.check("moves_mismatch", bad_moves, 0)
    run.check("repeat_mismatch", repeat_mismatch, 0)
    run.check("imbalance_rel_err", imb_err, lim["imbalance_rel_err"])
    run.check("queue_spread_err", qs_err, lim["queue_spread_err"])
    # bins the reference took from the program because they were probed
    # within rounding of a capacity
    run.check("rounding_choices", ref.rounding_choices,
              lim["rounding_choices"])
    # messages whose outputs differ from the reference's (first pass of
    # each chunk) or from their own first pass (later passes)
    run.attempted = calls * chunk
    run.failed = min(run.attempted, bad_msgs + repeat_mismatch)
