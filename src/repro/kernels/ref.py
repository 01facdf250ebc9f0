"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth the kernels are tested against
(``tests/test_kernels_*.py`` and ``tests/test_porc_snapshot_pallas.py``
sweep shapes and assert equality / allclose).

Oracles
-------
ref_porc_multisource  the PoRC block engine: S sources routing blocks
                      against local load views (§V-C); one source is S=1
ref_porc_route        its single-source form over ``PorcState``
ref_cg_dispatch       capacity-bounded MoE assignment with CG overflow
ref_ssd_scan          Mamba-2 SSD recurrence (exact sequential scan)
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.hashing import hash_to_bins

# The block-engine inner math lives in kernels/blocks.py so the jnp
# reference engine here and the Pallas engine in porc_snapshot.py
# consume literally the same implementation. Re-exported under the
# historical names — external import sites say
# ``from repro.kernels.ref import X`` and keep working (view_cap too:
# bench/faults.py's cap drift patches it on this module).
from .blocks import (  # noqa: F401  (re-exports)
    HHPolicy,
    hh_sketch_init,
    hh_sketch_query,
    hh_sketch_update,
    neutral_hh_policy,
    probe_salts,
    route_lanes,
    view_cap,
)
from .porc_snapshot import porc_multisource_scan

# the block engines a caller may name once ``backend.resolve_engine``
# has resolved its knob
ENGINES = ("ref", "pallas")


# ---------------------------------------------------------------------------
# PoRC state carried across blocks / calls (the block-parallel runtime)
# ---------------------------------------------------------------------------

class PorcState(NamedTuple):
    """Routing state of one source, threaded across blocks, slots, and
    batches.

    ``load`` is the (eventually-consistent) per-bin message count and
    ``routed`` the global message clock m_t — together they are
    everything Alg. 1 remembers. ``sketch`` is the count-min
    heavy-hitter sketch that drives the per-key probe depths when a
    :class:`HHPolicy` is active (``None`` otherwise — the default engine
    never materializes it).

    State-carry contract: every field continues across calls — splitting
    a stream over multiple ``ref_porc_route`` calls with the carried
    state is bit-identical to one call (block boundaries realign per
    call, the only alignment caveat). Nothing here resets at slot
    boundaries; the CG simulator carries the state through
    ``CGState.vw_load``/``t_offset``/``sketch`` instead.
    """
    load: jnp.ndarray     # [n_bins] f32
    routed: jnp.ndarray   # []       f32
    sketch: jnp.ndarray | None = None   # [depth, width] f32 count-min
                          # counts (only when an HHPolicy is active)


def porc_state_init(n_bins: int,
                    policy: "HHPolicy | None" = None) -> PorcState:
    return PorcState(load=jnp.zeros(n_bins, jnp.float32),
                     routed=jnp.zeros((), jnp.float32),
                     sketch=None if policy is None else hh_sketch_init(policy))


def block_spans(m: int, block: int) -> list[tuple[int, int, int]]:
    """(start, length, engine_block) spans covering an m-message stream.

    Full blocks come as one span; the trailing remainder is decomposed
    into powers of two. The jitted block engines specialize on
    (length, block), so this bounds the distinct remainder programs at
    O(log block) instead of one per possible remainder length — the
    serving path sees arbitrary batch sizes every call.
    """
    spans = []
    nb = m // block
    off = nb * block
    if nb:
        spans.append((0, off, block))
    rem = m - off
    while rem:
        p = 1 << (rem.bit_length() - 1)
        spans.append((off, p, p))
        off += p
        rem -= p
    return spans


def route_in_spans(keys: jnp.ndarray, block: int, carry, step):
    """Drive a jitted block engine over ``block_spans`` of a stream.

    ``step(sub_keys, engine_block, carry) -> (assignment, carry)`` is
    called per span with the threaded carry (load state). Returns the
    concatenated assignment and the final carry.
    """
    parts = []
    for start, length, blk in block_spans(keys.shape[0], block):
        a, carry = step(keys[start: start + length], blk, carry)
        parts.append(a)
    if not parts:
        return jnp.zeros((0,), jnp.int32), carry
    return (parts[0] if len(parts) == 1 else jnp.concatenate(parts)), carry


def ref_porc_route(keys: jnp.ndarray, n_bins: int, *, block: int = 128,
                   eps: float = 0.05, state: PorcState | None = None,
                   engine: str = "ref",
                   policy: HHPolicy | None = None):
    """Route an arbitrary-length key stream from one source.

    One source is the multi-source engine at S=1: this maps ``state``
    to a one-lane ``MultiSourcePorcState``, routes with
    ``ref_porc_multisource`` and maps the result back. Every block
    probes the load snapshot taken at its boundary against the capacity
    (1+eps)·m_t/n at block end; a trailing partial block routes as
    power-of-two sub-blocks (``block_spans``), so no padding keys ever
    pollute the load state. ``engine`` is ``"ref"`` (the jnp scan) or
    ``"pallas"`` (the Pallas kernel, bit-identical). With ``block=1``
    and no policy the result is bit-identical to the sequential oracle
    ``partitioners.power_of_random_choices``.

    ``policy`` turns on heavy-hitter-aware probe depths — D/W-Choices,
    see :class:`HHPolicy` — with the count-min sketch carried in
    ``state.sketch``. With a policy, ``block=1`` is *not* the
    sequential oracle: the probe budget is policy-defined, not Alg. 1's
    4·n chain.

    State-carry contract: ``state`` (load, clock, sketch) continues
    across calls — split-call == one-call with aligned block
    boundaries; nothing resets here.

    Returns (assignment [M] int32, new PorcState).
    """
    if state is None:
        state = porc_state_init(n_bins, policy)
    assign, ms = ref_porc_multisource(
        keys, n_bins, 1, block=block, eps=eps,
        state=published_state(state.load, state.routed, 1, state.sketch),
        engine=engine, policy=policy)
    ms = multisource_merge(ms)
    return assign, PorcState(load=ms.base, routed=ms.routed,
                             sketch=ms.sketch_base)


# ---------------------------------------------------------------------------
# Multi-source PoRC — §V-C distributed sources with local load views
# ---------------------------------------------------------------------------

class MultiSourcePorcState(NamedTuple):
    """Routing state of S sources sharing one bin population (§V-C).

    Each source routes against its *local* load view ``base + delta[s]``:
    the last synchronized global load plus its own unpublished counts.
    ``delta`` is merged into ``base`` every ``sync_every`` blocks — the
    paper's piggybacked load synchronization — so a source's view is
    stale by at most one sync period of the other sources' traffic.
    ``ticks`` carries the sync phase (blocks routed since the last
    merge) across calls, so a stream fed in batches shorter than one
    sync period still merges on schedule instead of never.

    When an :class:`HHPolicy` is active the count-min sketch shards the
    same way: ``sketch_base`` is the merged sketch and
    ``sketch_delta[s]`` source s's unpublished counts — a source
    classifies keys against its *local* sketch view ``sketch_base +
    sketch_delta[s]`` and the deltas merge (by addition — the sketch is
    linear) on the same schedule as the load deltas. Both stay ``None``
    without a policy.

    State-carry contract: every field continues across
    ``ref_porc_multisource`` calls (split-call == one-call, CI-gated);
    ``multisource_merge`` — and the sub-S ragged tail, which publishes
    immediately — fold the deltas into the bases and reset ``ticks``,
    which is what a monitoring-slot boundary does.
    """
    base: jnp.ndarray     # [n_bins]    f32 merged (synchronized) load
    delta: jnp.ndarray    # [S, n_bins] f32 per-source unpublished counts
    routed: jnp.ndarray   # []          f32 global message clock m_t
    ticks: jnp.ndarray    # []          i32 blocks since the last merge
    sketch_base: jnp.ndarray | None = None    # [depth, width] f32 merged
                          # count-min counts (HHPolicy only)
    sketch_delta: jnp.ndarray | None = None   # [S, depth, width] f32
                          # per-source unpublished sketch counts


def multisource_state_init(n_bins: int, n_sources: int,
                           policy: "HHPolicy | None" = None,
                           ) -> MultiSourcePorcState:
    return MultiSourcePorcState(
        base=jnp.zeros(n_bins, jnp.float32),
        delta=jnp.zeros((n_sources, n_bins), jnp.float32),
        routed=jnp.zeros((), jnp.float32),
        ticks=jnp.zeros((), jnp.int32),
        sketch_base=None if policy is None else hh_sketch_init(policy),
        sketch_delta=None if policy is None else jnp.zeros(
            (n_sources, policy.depth, policy.width), jnp.float32))


def published_state(load, routed, n_sources: int,
                    sketch=None) -> MultiSourcePorcState:
    """The state of ``n_sources`` sources that have all published: the
    merged ``load`` (and ``sketch``, when carried) with zero deltas, at
    the start of a sync period."""
    return MultiSourcePorcState(
        base=load,
        delta=jnp.zeros((n_sources,) + load.shape, jnp.float32),
        routed=routed,
        ticks=jnp.zeros((), jnp.int32),
        sketch_base=sketch,
        sketch_delta=None if sketch is None else jnp.zeros(
            (n_sources,) + sketch.shape, jnp.float32))


def lane_sum(delta, sync):
    """The merge of vmapped source lanes: the sum of their deltas."""
    return delta.sum(0)


def scan_blocks(kb, base, delta, ticks0, skb, skd, *, n_bins: int,
                n_sources: int, sync_every: int, block: int, eps: float,
                chunk: int, policy: HHPolicy | None = None,
                merge=lane_sum, weights=None):
    """The multi-source block step, scanned over ``kb`` [nb, S', block].

    Row ``kb[b, s]`` is lane s's b-th block. Per step every lane routes
    its block against its local view ``base + delta[s]`` and counts it
    into its delta (``blocks.route_lanes``); every ``sync_every`` steps
    (phase continued from ``ticks0``) ``merge(delta, sync)`` — the
    merged counts of every source — folds into the base and the deltas
    reset, and so do the sketch lanes under a ``policy``. ``n_sources``
    is the total S: the lanes here may be a shard of it
    (``kernels.mesh`` passes a psum across hosts as ``merge``; the
    vmapped engine the plain ``lane_sum``). ``weights`` [nb, S', block]
    (default all ones) scales what each message counts.

    With a policy the heavy/tail decision is one block stale, the same
    staleness license as the load snapshot itself; ``policy=None``
    traces to exactly the sketch-free engine.

    Returns ``((base, delta, skb, skd), assign [nb, S', block])``.
    """
    # without a policy the candidates are load-independent: hash them
    # for the whole stream ([nb, S', block, C]) outside the scan. The
    # policy chain can be n_bins deep, so it hashes per block instead.
    cand = (None if policy is not None
            else hash_to_bins(kb[..., None], probe_salts(chunk), n_bins))

    def blk(carry, xs):
        base, delta, skb, skd = carry
        b, kblk, cblk, w = xs
        assign, delta, skd = route_lanes(
            base, delta, skb, skd, kblk, cblk, n_bins=n_bins,
            n_sources=n_sources, block=block, eps=eps, chunk=chunk,
            policy=policy, weights=w)
        # piggyback merge — phase continues from ticks0 across calls
        sync = ((ticks0 + b + 1) % sync_every) == 0
        base = jnp.where(sync, base + merge(delta, sync), base)
        delta = jnp.where(sync, jnp.zeros_like(delta), delta)
        if policy is not None:
            skb = jnp.where(sync, skb + skd.sum(0), skb)
            skd = jnp.where(sync, jnp.zeros_like(skd), skd)
        return (base, delta, skb, skd), assign

    return jax.lax.scan(
        blk, (base, delta, skb, skd),
        (jnp.arange(kb.shape[0], dtype=jnp.int32), kb, cand, weights))


@functools.partial(jax.jit, static_argnames=(
    "n_bins", "n_sources", "sync_every", "block", "eps", "chunk", "policy"))
def _porc_multisource_scan(keys: jnp.ndarray, n_bins: int, n_sources: int,
                           sync_every: int, block: int, eps: float,
                           chunk: int, base0, delta0, ticks0,
                           skb0=None, skd0=None,
                           policy: HHPolicy | None = None):
    """Core multi-source scan over full per-source blocks (jnp).

    ``keys`` is the round-robin-interleaved global stream (message i
    belongs to source i % S); its length must be a multiple of S·block.
    Same arguments and ``(assign, base, delta, ticks, skb, skd)`` return
    as the Pallas ``porc_snapshot.porc_multisource_scan``.
    """
    S = n_sources
    M = keys.shape[0]
    assert M % (S * block) == 0, f"{M} % {S}*{block} != 0"
    nb = M // (S * block)
    # [nb, S, block]: element [b, s, k] = keys[(b·block + k)·S + s],
    # source s's k-th message of its b-th block
    kb = keys.reshape(nb, block, S).transpose(0, 2, 1)
    (base, delta, skb, skd), assign = scan_blocks(
        kb, base0, delta0, ticks0, skb0, skd0, n_bins=n_bins, n_sources=S,
        sync_every=sync_every, block=block, eps=eps, chunk=chunk,
        policy=policy)
    # invert the round-robin interleave back to global message order
    return (assign.transpose(0, 2, 1).reshape(-1), base, delta,
            (ticks0 + nb) % sync_every, skb, skd)


@functools.partial(jax.jit, static_argnames=("n_bins", "n_sources", "eps",
                                             "chunk", "policy"))
def _porc_multisource_tail(keys_pad: jnp.ndarray, n_bins: int, n_sources: int,
                           eps: float, chunk: int, base0, delta0, n_tail,
                           skb0=None, skd0=None,
                           policy: HHPolicy | None = None):
    """Ragged tail: the final r < S messages, one to each of sources
    0..r-1. ``keys_pad`` is padded to [S]; sources ≥ ``n_tail`` route a
    phantom key whose assignment is discarded and whose delta update is
    masked out, so one compiled program covers every r. The residue
    publishes immediately (merged base, zero deltas — and likewise the
    sketch, when a policy is active): it is less than one block, so it
    cannot advance the block-granular sync phase, and leaving it
    unpublished would let a stream fed in sub-S batches accumulate lane
    deltas that never merge — breaking the documented one-sync-period
    staleness bound.
    """
    S = n_sources
    active = (jnp.arange(S) < n_tail).astype(jnp.float32)
    # one step of a block of one per lane; sync_every=1 publishes it
    (base, delta, skb, skd), assign = scan_blocks(
        keys_pad[None, :, None], base0, delta0, 0, skb0, skd0,
        n_bins=n_bins, n_sources=S, sync_every=1, block=1, eps=eps,
        chunk=chunk, policy=policy, weights=active[None, :, None])
    return assign[0, :, 0], base, delta, skb, skd


def route_multisource_spans(keys: jnp.ndarray, n_bins: int, n_sources: int,
                            block: int, eps: float, chunk: int,
                            state: MultiSourcePorcState, scan,
                            policy: HHPolicy | None = None):
    """The span-and-tail driver of the multi-source engines.

    The full per-source span routes as ``block_spans`` (full blocks,
    then power-of-two sub-blocks), each through
    ``scan(span_keys, engine_block, base, delta, ticks, skb, skd) ->
    (assign, base, delta, ticks, skb, skd)``; a final sub-S ragged tail
    routes one message per source through ``_porc_multisource_tail``
    and publishes at once. ``state``'s sketch lanes must already match
    ``policy``.

    Returns (assignment [M] int32 in stream order, new state).
    """
    S = n_sources
    base, delta, routed, ticks, skb, skd = state
    per = keys.shape[0] // S             # full per-source span length
    r = keys.shape[0] - per * S
    parts = []
    off = 0
    for _, length, blk in block_spans(per, block):
        a, base, delta, ticks, skb, skd = scan(
            keys[off: off + length * S], blk, base, delta, ticks, skb, skd)
        routed = routed + length * S
        parts.append(a)
        off += length * S
    if r:
        keys_pad = jnp.concatenate(
            [keys[off:], jnp.zeros((S - r,), keys.dtype)])
        a, base, delta, skb, skd = _porc_multisource_tail(
            keys_pad, n_bins, S, eps, chunk, base, delta, jnp.float32(r),
            skb, skd, policy)
        routed = routed + r
        ticks = jnp.zeros_like(ticks)    # tail publish = a merge
        parts.append(a[:r])
    if not parts:
        assign = jnp.zeros((0,), jnp.int32)
    else:
        assign = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return assign, MultiSourcePorcState(base=base, delta=delta,
                                        routed=routed, ticks=ticks,
                                        sketch_base=skb, sketch_delta=skd)


def ref_porc_multisource(keys: jnp.ndarray, n_bins: int, n_sources: int, *,
                         sync_every: int = 1, block: int = 128,
                         eps: float = 0.05, chunk: int = 8,
                         state: MultiSourcePorcState | None = None,
                         engine: str = "ref",
                         policy: HHPolicy | None = None):
    """Multi-source block-parallel PoRC (§V-C distributed sources).

    The stream splits round-robin across ``n_sources`` sources (message
    i → source i % S, the paper's SG assignment of messages to sources);
    each source routes blocks of ``block`` messages against its local
    view ``base + own delta`` and the deltas merge into the shared base
    every ``sync_every`` blocks (piggybacked synchronization). Staleness
    is therefore bounded by one sync period: a source never misses more
    than the other S−1 sources' ``sync_every·block`` most recent
    messages.

    Each block probes its frozen local view: every message walks its
    salted-probe chain H(j‖1), H(j‖2), … and stops at the first bin
    below the source's capacity. At block=1 the full 4·n_bins chain of
    Alg. 1 runs, so S=1, block=1 is bit-identical to the sequential
    oracle ``partitioners.power_of_random_choices``; at block>1 each
    message gets a fixed budget of ``chunk`` probes per snapshot.
    Exhausting the budget falls back to the least-loaded view bin.

    ``engine`` is ``"ref"`` (the jnp scan) or ``"pallas"`` (the Pallas
    kernel ``porc_snapshot.porc_multisource_scan`` — bit-identical,
    delta/sketch lanes in VMEM scratch; the ragged tail and span driver
    stay shared); ``backend.resolve_engine`` maps the user-facing knob
    to one of them.

    Arbitrary stream lengths: the per-source remainder routes as
    power-of-two sub-blocks (``block_spans``), and a final sub-S ragged
    tail routes one message per source with the others masked (and
    publishes immediately — see ``_porc_multisource_tail``). The sync
    phase carries across spans and calls via ``state.ticks``
    (block-granular, so a stream fed in short batches still merges
    every ``sync_every`` blocks); block boundaries themselves realign
    per call.

    ``policy`` turns on heavy-hitter-aware probe depths (D/W-Choices):
    each source classifies keys against its local count-min sketch view
    and probes with per-key budgets; the sketch shards and delta-merges
    exactly like the load (see :class:`HHPolicy`). ``policy=None`` — the
    default — is bit-identical to the policy-free engine.

    Returns (assignment [M] int32 in original stream order,
    new MultiSourcePorcState).
    """
    S = n_sources
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}: expected one of "
                         f"{ENGINES} (resolve 'auto' with "
                         "kernels.resolve_engine)")
    if state is None:
        state = multisource_state_init(n_bins, S, policy)
    if policy is None:
        # the sketch is carried only with a policy
        state = state._replace(sketch_base=None, sketch_delta=None)
    elif state.sketch_base is None:
        # state predates the policy: start the sketch cold
        state = state._replace(
            sketch_base=hh_sketch_init(policy),
            sketch_delta=jnp.zeros((S, policy.depth, policy.width),
                                   jnp.float32))
    scan = porc_multisource_scan if engine == "pallas" \
        else _porc_multisource_scan

    def step(span, blk, *carry):
        return scan(span, n_bins, S, sync_every, blk, eps, chunk, *carry,
                    policy)

    return route_multisource_spans(keys, n_bins, S, block, eps, chunk,
                                   state, step, policy)


def multisource_merge(state: MultiSourcePorcState) -> MultiSourcePorcState:
    """Force a synchronization: publish every source's delta into the
    base (e.g. at a monitoring-slot boundary, where the paper's
    piggybacked signals all arrive) and restart the sync phase. The
    sketch lanes, when present, merge the same way (the sketch is
    linear, so this is exact)."""
    return MultiSourcePorcState(
        base=state.base + state.delta.sum(0),
        delta=jnp.zeros_like(state.delta),
        routed=state.routed,
        ticks=jnp.zeros_like(state.ticks),
        sketch_base=(None if state.sketch_base is None
                     else state.sketch_base + state.sketch_delta.sum(0)),
        sketch_delta=(None if state.sketch_delta is None
                      else jnp.zeros_like(state.sketch_delta)))


# ---------------------------------------------------------------------------
# CG MoE dispatch
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_experts", "k", "capacity", "block"))
def ref_cg_dispatch(pref: jnp.ndarray, gates: jnp.ndarray, *, n_experts: int,
                    k: int, capacity: int | None = None,
                    capacities: jnp.ndarray | None = None, block: int = 128):
    """Oracle for kernels.cg_dispatch.

    Args:
      pref: [T, D] experts per token sorted by gate desc (D ≥ k gives the
        overflow depth — the PoRC salted-probe sequence analogue).
      gates: [T, D] matching gate scores (softmax probs).
      capacity: uniform per-expert buffer size C (the scalar special
        case; bit-identical to ``capacities=full(E, C)``).
      capacities: [E] per-expert buffer sizes — the paper's
        heterogeneous-cluster capacities (Fig 15) on the expert axis.
        Exactly one of ``capacity`` / ``capacities`` must be given.
    Returns:
      expert_assign [T, k] int32 (-1 = unplaced), slot [T, k] int32
      (position in the expert's buffer, < cap_e), weights [T, k] f32
      (renormalized over placed slots), load [E] f32 final per-expert
      occupancy.
    """
    T, D = pref.shape
    assert T % block == 0
    if (capacity is None) == (capacities is None):
        raise ValueError("pass exactly one of capacity / capacities")
    cap_vec = (jnp.full((n_experts,), capacity, jnp.float32)
               if capacities is None
               else jnp.asarray(capacities, jnp.float32))

    def blk(load, xs):
        p, g = xs                                            # [B, D]
        B = p.shape[0]
        assign = jnp.full((B, k), -1, jnp.int32)
        slot = jnp.full((B, k), -1, jnp.int32)
        wts = jnp.zeros((B, k), jnp.float32)
        nacc = jnp.zeros((B,), jnp.int32)

        def rank_step(r, carry):
            load, assign, slot, wts, nacc = carry
            c = p[:, r]
            want = nacc < k
            onehot = (c[:, None] == jnp.arange(n_experts)[None, :]) & want[:, None]
            pos = jnp.cumsum(onehot.astype(jnp.float32), axis=0) - onehot
            mypos = jnp.take_along_axis(pos, c[:, None], axis=1)[:, 0]
            myload = load[c] + mypos
            accept = want & (myload < cap_vec[c])
            col = (jnp.arange(k)[None, :] == nacc[:, None]) & accept[:, None]
            assign = jnp.where(col, c[:, None], assign)
            slot = jnp.where(col, myload.astype(jnp.int32)[:, None], slot)
            wts = jnp.where(col, g[:, r][:, None], wts)
            load = load + jnp.sum(
                onehot.astype(jnp.float32) * accept[:, None], axis=0)
            return load, assign, slot, wts, nacc + accept.astype(jnp.int32)

        load, assign, slot, wts, nacc = jax.lax.fori_loop(
            0, D, rank_step, (load, assign, slot, wts, nacc))
        denom = jnp.maximum(jnp.sum(wts, -1, keepdims=True), 1e-9)
        return load, (assign, slot, wts / denom)

    load0 = jnp.zeros((n_experts,), jnp.float32)
    load, (assign, slot, wts) = jax.lax.scan(
        blk, load0, (pref.reshape(-1, block, D), gates.reshape(-1, block, D)))
    return (assign.reshape(T, k), slot.reshape(T, k),
            wts.reshape(T, k), load)


# ---------------------------------------------------------------------------
# Mamba-2 SSD
# ---------------------------------------------------------------------------

def ref_ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                 Bm: jnp.ndarray, Cm: jnp.ndarray) -> jnp.ndarray:
    """Exact sequential SSD recurrence (the gold semantics).

    h_t = exp(dt_t·A_h)·h_{t-1} + dt_t·(x_t ⊗ B_t);  y_t = h_t·C_t

    Args:
      x:  [B, L, H, P] inputs per head.
      dt: [B, L, H] positive step sizes.
      A:  [H] negative decay rates.
      Bm: [B, L, G, N] input projections (G groups, H % G == 0).
      Cm: [B, L, G, N] output projections.
    Returns y: [B, L, H, P].
    """
    Bsz, L, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2)                         # [B, L, H, N]
    Ch = jnp.repeat(Cm, rep, axis=2)

    def step(h, xs):
        xt, dtt, bt, ct = xs                                  # [B,H,P],[B,H],[B,H,N]x2
        decay = jnp.exp(dtt * A[None, :])[..., None, None]    # [B,H,1,1]
        h = decay * h + (dtt[..., None] * xt)[..., None] * bt[..., None, :]
        y = jnp.einsum("bhpn,bhn->bhp", h, ct)
        return h, y

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(Bh, 1, 0).astype(jnp.float32),
          jnp.moveaxis(Ch, 1, 0).astype(jnp.float32))
    _, y = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(y, 0, 1).astype(x.dtype)
