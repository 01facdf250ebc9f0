"""Mesh-sharded multi-source PoRC — §V-C source lanes on real devices.

``ref_porc_multisource`` simulates the paper's distributed sources as a
vmap axis on one device; this module puts the same semantics on a JAX
device mesh via ``shard_map``: the mesh's ``sources`` axis owns the
per-source delta lanes (``delta [S_local, n_bins]`` per host), the
merged ``base`` view is replicated, and the delta-merge synchronization
is a ``jax.lax.psum`` across the axis — the collective the paper's
piggybacked load exchange becomes on hardware.

Exactness: per-source block routing, the local-view capacity and the
merge are the *same arithmetic* as the vmapped engine (delta counts are
integer-valued f32 well below 2^24, so the psum's different summation
order is still exact), so ``mesh_porc_multisource`` is bit-identical to
``ref_porc_multisource`` at matching ``(n_sources, sync_every, block)``
— CI gates the ``sync_every=1`` case and the tests sweep wider.

The heavy-hitter sketch lanes are not mesh-sharded yet (the policy path
stays on the vmapped engine); ``policy``-carrying state is rejected.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import trace
from repro.core.hashing import hash_to_bins
from repro.kernels.ref import (MultiSourcePorcState, _porc_multisource_tail,
                               _snapshot_block, block_spans, count_sum,
                               multisource_state_init, salt_columns,
                               view_cap)

SOURCES_AXIS = "sources"


def _lane_sharding(mesh) -> NamedSharding:
    return NamedSharding(mesh, P(SOURCES_AXIS, None))


def shard_multisource_state(state: MultiSourcePorcState, mesh
                            ) -> MultiSourcePorcState:
    """Pin the per-source lanes of ``state`` onto the mesh: ``delta``
    shards row-wise over the ``sources`` axis (host h owns sources
    ``[h·S/H, (h+1)·S/H)``), the merged ``base`` and the scalars
    replicate. Sketch lanes are not supported on the mesh."""
    if state.sketch_base is not None or state.sketch_delta is not None:
        raise NotImplementedError(
            "heavy-hitter sketch lanes are not mesh-sharded; use the "
            "vmapped ref_porc_multisource for HHPolicy routing")
    S = state.delta.shape[0]
    H = mesh.shape[SOURCES_AXIS]
    if S % H != 0:
        raise ValueError(f"n_sources={S} not divisible by the mesh's "
                         f"{H} hosts")
    rep = NamedSharding(mesh, P())
    return state._replace(
        base=jax.device_put(state.base, rep),
        delta=jax.device_put(state.delta, _lane_sharding(mesh)))


@functools.lru_cache(maxsize=None)
def _mesh_scan(mesh, n_bins: int, n_sources: int, sync_every: int,
               block: int, eps: float, chunk: int):
    """Build (and cache) the jitted shard_map program for one
    ``(mesh, shape)`` cell. The scan body is the same per-block router
    as the vmapped engine (``_snapshot_block`` over the local sources);
    only the merge differs — a psum over the mesh axis instead of a
    ``delta.sum(0)`` over the vmap axis."""
    S = n_sources

    def body(base, delta, ticks0, kb):
        # kb: [S_local, nb, block] — this host's source substreams
        salts0 = jnp.arange(1, chunk + 1, dtype=jnp.uint32)
        cand0 = hash_to_bins(kb[..., None], salts0, n_bins)

        def blk(carry, xs):
            base, delta = carry
            b, kblk, cblk = xs                     # [S_local, block], ...
            # local-view capacity, identical to the vmapped engine: each
            # source can verify its cap against base + its own delta
            # without any cross-host traffic (see ref.py for why the
            # per-source invariant telescopes to the global envelope)
            mass = count_sum(base) + count_sum(delta, 1)
            cap = view_cap(eps, n_bins, mass, block / S)
            views = base[None, :] + delta
            assign = jax.vmap(
                lambda view, c, kk, cb: _snapshot_block(
                    view, c, kk, salt_columns(cb), n_bins, block, chunk))(
                views, cap, kblk, cblk)
            delta = jax.vmap(lambda d, a: d.at[a].add(1.0))(delta, assign)
            # piggyback merge = all-reduce of the lane deltas. The psum
            # runs every block (its operand is masked out on non-sync
            # blocks); counts are integer-valued f32, so the different
            # reduction order vs delta.sum(0) is still bit-exact.
            sync = ((ticks0 + b + 1) % sync_every) == 0
            with trace.scope(trace.MERGE):
                merged = jax.lax.psum(
                    jnp.where(sync, delta.sum(0), jnp.zeros((n_bins,))),
                    SOURCES_AXIS)
            base = jnp.where(sync, base + merged, base)
            delta = jnp.where(sync, jnp.zeros_like(delta), delta)
            return (base, delta), assign

        nb = kb.shape[1]
        (base, delta), assign = jax.lax.scan(
            blk, (base, delta),
            (jnp.arange(nb, dtype=jnp.int32), kb.transpose(1, 0, 2),
             cand0.transpose(1, 0, 2, 3)))
        return base, delta, assign.transpose(1, 0, 2)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(SOURCES_AXIS, None), P(), P(SOURCES_AXIS, None, None)),
        out_specs=(P(), P(SOURCES_AXIS, None), P(SOURCES_AXIS, None, None))))


def mesh_porc_multisource(keys: jnp.ndarray, n_bins: int, mesh, *,
                          n_sources: int | None = None,
                          sync_every: int = 1, block: int = 128,
                          eps: float = 0.05, chunk: int = 8,
                          state: MultiSourcePorcState | None = None):
    """Route a round-robin-interleaved key stream with the source lanes
    living on ``mesh``'s ``sources`` axis.

    Drop-in for ``ref_porc_multisource`` (snapshot engine, no policy):
    message i belongs to source ``i % S``, source s lives on host
    ``s // (S/H)``, and every semantic — local views, per-source caps,
    ``sync_every``-block delta merges, power-of-two remainder spans,
    the sub-S ragged tail publishing immediately — is inherited, so the
    result is bit-identical to the vmapped engine. The ragged tail
    (fewer than S messages) routes through the vmapped tail program;
    its lane state is re-pinned to the mesh afterwards.

    Returns (assignment [M] int32 in stream order, new state with
    mesh-sharded ``delta``).
    """
    if n_sources is None:
        if state is None:
            raise ValueError("need n_sources or a state to infer it from")
        n_sources = state.delta.shape[0]
    S = n_sources
    if state is None:
        state = multisource_state_init(n_bins, S)
    state = shard_multisource_state(state, mesh)
    base, delta, routed, ticks = (state.base, state.delta, state.routed,
                                  state.ticks)
    per = keys.shape[0] // S
    r = keys.shape[0] - per * S
    keys = jnp.asarray(keys)
    parts = []
    off = 0
    for _, length, blk in block_spans(per, block):
        span = keys[off: off + length * S]
        nb = length // blk
        # [S, nb, blk]: source s's substream, blocked — the sharded axis
        # leads so shard_map splits it across hosts
        kb = span.reshape(nb, blk, S).transpose(2, 0, 1)
        scan = _mesh_scan(mesh, n_bins, S, sync_every, blk, eps, chunk)
        base, delta, assign = scan(base, delta, ticks, kb)
        ticks = (ticks + nb) % sync_every
        routed = routed + length * S
        # [S, nb, blk] -> stream order: message (b·blk + k)·S + s
        parts.append(assign.transpose(1, 2, 0).reshape(-1))
        off += length * S
    if r:
        keys_pad = jnp.concatenate(
            [keys[off:], jnp.zeros((S - r,), keys.dtype)])
        a, base, delta, _, _ = _porc_multisource_tail(
            keys_pad, n_bins, S, eps, chunk, base, delta, jnp.float32(r))
        delta = jax.device_put(delta, _lane_sharding(mesh))
        routed = routed + r
        ticks = jnp.zeros_like(ticks)        # tail publish = a merge
        parts.append(a[:r])
    if not parts:
        assign = jnp.zeros((0,), jnp.int32)
    else:
        assign = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return assign, MultiSourcePorcState(base=base, delta=delta,
                                        routed=routed, ticks=ticks)
