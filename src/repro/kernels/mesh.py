"""Mesh-sharded multi-source PoRC — §V-C source lanes on real devices.

``ref_porc_multisource`` simulates the paper's distributed sources as a
vmap axis on one device; this module puts the same semantics on a JAX
device mesh via ``shard_map``: the mesh's ``sources`` axis owns the
per-source delta lanes (``delta [S_local, n_bins]`` per host), the
merged ``base`` view is replicated, and the delta-merge synchronization
is a ``jax.lax.psum`` across the axis — the collective the paper's
piggybacked load exchange becomes on hardware.

Exactness: each host scans the vmapped engine's own block step
(``ref.scan_blocks``) over its lanes, with the psum passed in as the
merge; delta counts are integer-valued f32 well below 2^24, so the
psum's different summation order is still exact, and
``mesh_porc_multisource`` is bit-identical to ``ref_porc_multisource``
at matching ``(n_sources, sync_every, block)`` — CI gates the
``sync_every=1`` case and the tests sweep wider. The span-and-tail
driver (``ref.route_multisource_spans``) is shared too.

The heavy-hitter sketch lanes are not mesh-sharded yet (the policy path
stays on the vmapped engine); ``policy``-carrying state is rejected.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import trace
# view_cap stays importable here: bench/faults.py's cap drift patches
# it on this module as on ``blocks`` and ``ref``
from repro.kernels.ref import (MultiSourcePorcState,  # noqa: F401
                               multisource_state_init,
                               route_multisource_spans, scan_blocks,
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


def _psum_merge(delta, sync):
    """Piggyback merge = all-reduce of the lane deltas. The psum runs
    every block, its operand masked out on non-sync blocks."""
    with trace.scope(trace.MERGE):
        return jax.lax.psum(
            jnp.where(sync, delta.sum(0), jnp.zeros((delta.shape[1],))),
            SOURCES_AXIS)


@functools.lru_cache(maxsize=None)
def _mesh_scan(mesh, n_bins: int, n_sources: int, sync_every: int,
               block: int, eps: float, chunk: int):
    """Build (and cache) the jitted shard_map program for one
    ``(mesh, shape)`` cell: the vmapped engine's block step over this
    host's lanes, merged by :func:`_psum_merge`."""

    def body(base, delta, ticks0, kb):
        # kb: [S_local, nb, block] — this host's source substreams
        (base, delta, _, _), assign = scan_blocks(
            kb.transpose(1, 0, 2), base, delta, ticks0, None, None,
            n_bins=n_bins, n_sources=n_sources, sync_every=sync_every,
            block=block, eps=eps, chunk=chunk, merge=_psum_merge)
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

    Drop-in for ``ref_porc_multisource`` (jnp engine, no policy):
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

    def scan(span, blk, base, delta, ticks, skb, skd):
        nb = span.shape[0] // (S * blk)
        # [S, nb, blk]: source s's substream, blocked — the sharded axis
        # leads so shard_map splits it across hosts
        kb = span.reshape(nb, blk, S).transpose(2, 0, 1)
        base, delta, assign = _mesh_scan(mesh, n_bins, S, sync_every, blk,
                                         eps, chunk)(base, delta, ticks, kb)
        # [S, nb, blk] -> stream order: message (b·blk + k)·S + s
        return (assign.transpose(1, 2, 0).reshape(-1), base, delta,
                (ticks + nb) % sync_every, skb, skd)

    assign, state = route_multisource_spans(jnp.asarray(keys), n_bins, S,
                                            block, eps, chunk, state, scan)
    if keys.shape[0] % S:
        # the ragged tail ran on the vmapped program: re-pin its lanes
        state = state._replace(
            delta=jax.device_put(state.delta, _lane_sharding(mesh)))
    return assign, state
