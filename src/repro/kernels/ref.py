"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth the kernels are tested against
(``tests/test_kernels_*.py`` sweeps shapes/dtypes and asserts
equality / allclose).

Oracles
-------
ref_porc_assign   block-synchronous PoRC (the TPU-adapted Alg. 1)
ref_cg_dispatch   capacity-bounded MoE assignment with CG overflow
ref_ssd_scan      Mamba-2 SSD recurrence (exact sequential scan)
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.hashing import hash_to_bins

# The block-engine inner math lives in kernels/blocks.py so the jnp
# reference engines here and the Pallas engines in porc_snapshot.py
# consume literally the same implementation. Re-exported under the
# historical names — every external import site says
# ``from repro.kernels.ref import X`` and keeps working.
from .blocks import (  # noqa: F401  (re-exports)
    HHPolicy,
    SKETCH_SALT0 as _SKETCH_SALT0,
    count_sum,
    hh_budgets as _hh_budgets,
    hh_chunk as _hh_chunk,
    hh_sketch_init,
    hh_sketch_query,
    hh_sketch_update,
    neutral_hh_policy,
    probe_salts,
    salt_columns,
    sketch_cols as _sketch_cols,
    snapshot_block as _snapshot_block,
    snapshot_block_hh as _snapshot_block_hh,
    snapshot_cap,
    view_cap,
)


# ---------------------------------------------------------------------------
# PoRC, block-synchronous semantics
# ---------------------------------------------------------------------------

def _porc_block(load, keys, cap, n_bins: int, d: int):
    """Assign one block of keys against running loads.

    Rank-sequential, key-vectorized: at rank r, every still-unassigned
    key bids for its r-th salted choice H(key‖r+1); the first
    ``cap − load`` bidders per bin (in block order) are accepted.
    Ranks advance until every key is placed (Alg. 1's unbounded probe),
    with a ceiling of d ranks; the rare leftovers are forced onto their
    rank-d choice.
    """
    B = keys.shape[0]
    assign = jnp.full((B,), -1, jnp.int32)
    unassigned = jnp.ones((B,), bool)

    def cond(carry):
        r, load, assign, unassigned = carry
        return (r < d) & jnp.any(unassigned)

    def rank_step(carry):
        r, load, assign, unassigned = carry
        c = hash_to_bins(keys, (r + 1).astype(jnp.uint32), n_bins)
        onehot = (c[:, None] == jnp.arange(n_bins)[None, :]) & unassigned[:, None]
        pos = jnp.cumsum(onehot.astype(jnp.float32), axis=0) - onehot
        mypos = jnp.take_along_axis(pos, c[:, None], axis=1)[:, 0]
        accept = unassigned & (load[c] + mypos < cap)
        assign = jnp.where(accept, c, assign)
        load = load + jnp.sum(
            onehot.astype(jnp.float32) * accept[:, None].astype(jnp.float32), axis=0)
        return r + 1, load, assign, unassigned & ~accept

    _, load, assign, unassigned = jax.lax.while_loop(
        cond, rank_step, (jnp.int32(0), load, assign, unassigned))
    # forced fallback at probe ceiling: spread leftovers round-robin over
    # the least-loaded bins (the vectorized analogue of Alg. 1's
    # argmin-load fallback; prevents pileup on any single bin).
    order = jnp.argsort(load).astype(jnp.int32)
    leftpos = jnp.cumsum(unassigned.astype(jnp.int32)) - 1
    fallback = order[leftpos % n_bins]
    assign = jnp.where(unassigned, fallback, assign)
    forced = jnp.zeros((n_bins,), jnp.float32).at[fallback].add(
        unassigned.astype(jnp.float32))
    return load + forced, assign


@functools.partial(jax.jit, static_argnames=("n_bins", "d", "block", "eps"))
def ref_porc_assign(keys: jnp.ndarray, n_bins: int, *, d: int | None = None,
                    block: int = 128, eps: float = 0.05,
                    load0: jnp.ndarray | None = None,
                    m0: float = 0.0):
    """Oracle for kernels.porc_assign. keys length must be a multiple of
    ``block``. Returns (assignment [M], final load [n_bins])."""
    if d is None:
        d = 4 * n_bins      # same probe ceiling as the sequential oracle
    M = keys.shape[0]
    assert M % block == 0
    nb = M // block
    kb = keys.reshape(nb, block)
    load = jnp.zeros(n_bins, jnp.float32) if load0 is None else load0

    def blk(load, xs):
        b, keys_blk = xs
        cap = snapshot_cap(eps, n_bins, m0, b, block)
        load, assign = _porc_block(load, keys_blk, cap, n_bins, d)
        return load, assign

    load, assign = jax.lax.scan(blk, load,
                                (jnp.arange(nb, dtype=jnp.float32), kb))
    return assign.reshape(-1), load


# ---------------------------------------------------------------------------
# PoRC state carried across blocks / calls (the block-parallel runtime)
# ---------------------------------------------------------------------------

class PorcState(NamedTuple):
    """Routing state threaded across blocks, slots, and batches.

    ``load`` is the (eventually-consistent) per-bin message count and
    ``routed`` the global message clock m_t that drives the capacity
    (1+eps)·m_t/n — together they are everything Alg. 1 remembers.
    ``sketch`` is the count-min heavy-hitter sketch that drives the
    per-key probe depths when a :class:`HHPolicy` is active (``None``
    otherwise — the default engine never materializes it).

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


@functools.partial(jax.jit, static_argnames=("n_bins", "block", "eps", "chunk"))
def ref_porc_snapshot(keys: jnp.ndarray, n_bins: int, *, block: int = 128,
                      eps: float = 0.05, chunk: int = 8,
                      load0: jnp.ndarray | None = None, m0: float = 0.0):
    """Snapshot-probing PoRC: the block-parallel *fast path*.

    Every message in a block independently walks its salted-probe chain
    H(j‖1), H(j‖2), … against the load snapshot taken at the block
    boundary and stops at the first bin below (1+eps)·m_t/n (m_t at
    block end); loads update once per block. This is the paper's §V-C
    eventual consistency — the same semantics as multiple sources
    routing with local load views — so a bin can overshoot the capacity
    by at most the number of duplicates of its keys inside one block.

    Unlike the rank-sequential ``ref_porc_assign`` (which resolves
    in-block contention rank by rank and therefore serializes ~max-key-
    multiplicity steps per block), every probe here is a vectorized
    gather, which is what makes the block path fast on CPU/TPU.

    Probe budget: at block=1 the full 4·n_bins salted chain of Alg. 1
    runs (lazily, in chunks of ``chunk`` salts) so the result is
    bit-identical to the sequential oracle — the snapshot *is* the true
    load. At block>1 each message gets a fixed budget of ``chunk``
    probes per snapshot (hoisted out of the block scan entirely, since
    they are load-independent); either way, exhausting the budget falls
    back to the least-loaded snapshot bin, Alg. 1's fallback. A fixed
    budget is the right trade at block>1 because a fresh snapshot
    resolves ~everything within a few probes — paying a data-dependent
    while-loop per block costs more than the rare deep chain saves.

    Returns (assignment [M] int32, final load [n_bins] f32).
    """
    M = keys.shape[0]
    assert M % block == 0, f"{M} % {block} != 0"
    nb = M // block
    kb = keys.reshape(nb, block)
    load = jnp.zeros(n_bins, jnp.float32) if load0 is None else load0
    # the first chunk of candidates is load-independent → hoist the
    # hashing for the whole stream out of the per-block scan
    salts0 = probe_salts(chunk)
    cand0 = hash_to_bins(kb[:, :, None], salts0[None, None, :], n_bins)

    def blk(load, xs):
        b, kblk, cblk = xs
        cap = snapshot_cap(eps, n_bins, m0, b, block)
        assign = _snapshot_block(load, cap, kblk, salt_columns(cblk), n_bins,
                                 block, chunk)
        return load.at[assign].add(1.0), assign

    load, assign = jax.lax.scan(blk, load,
                                (jnp.arange(nb, dtype=jnp.float32), kb, cand0))
    return assign.reshape(-1), load


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
                   engine: str = "snapshot",
                   policy: HHPolicy | None = None):
    """Route an arbitrary-length key stream in blocks of ``block``.

    ``engine="snapshot"`` (the fast path) probes block-boundary load
    snapshots via ``ref_porc_snapshot``; ``engine="pallas"`` runs the
    same semantics as the Pallas kernel
    (``porc_snapshot.porc_snapshot`` — bit-identical, load in VMEM
    scratch, compiled on TPU / interpreted elsewhere);
    ``engine="strict"`` uses the rank-sequential ``ref_porc_assign``,
    which never exceeds the (1+eps) cap but serializes in-block
    contention (slower — use it when the ε guarantee must hold exactly,
    e.g. tiny per-bin loads). The user-facing ``"ref"``/``"auto"``
    spellings resolve to these via ``kernels.backend.resolve_engine``.
    Either way a trailing partial block is routed as power-of-two
    sub-blocks (caps at each sub-block end, bounded recompilation —
    see ``block_spans``), so no padding keys ever pollute the load
    state. With ``block=1`` both engines are bit-identical to the
    sequential oracle ``partitioners.power_of_random_choices``.

    ``policy`` (snapshot engine only) turns on heavy-hitter-aware probe
    depths — D/W-Choices, see :class:`HHPolicy` — with the count-min
    sketch carried in ``state.sketch``; it routes through the
    multi-source engine at S=1 (bit-identical framing, CI-gated for the
    policy-free case). With a policy, ``block=1`` is *not* the
    sequential oracle: the probe budget is policy-defined, not Alg. 1's
    4·n chain.

    State-carry contract: ``state`` (load, clock, sketch) continues
    across calls — split-call == one-call with aligned block
    boundaries; nothing resets here.

    Returns (assignment [M] int32, new PorcState).
    """
    if state is None:
        state = porc_state_init(n_bins, policy)
    if policy is not None:
        if engine not in ("snapshot", "pallas"):
            raise ValueError("HHPolicy requires the snapshot engine")
        skb = state.sketch if state.sketch is not None \
            else hh_sketch_init(policy)
        ms = MultiSourcePorcState(
            base=state.load,
            delta=jnp.zeros((1, n_bins), jnp.float32),
            routed=state.routed,
            ticks=jnp.zeros((), jnp.int32),
            sketch_base=skb,
            sketch_delta=jnp.zeros((1,) + skb.shape, jnp.float32))
        assign, ms = ref_porc_multisource(
            keys, n_bins, 1, sync_every=1, block=block, eps=eps,
            state=ms, engine=engine, policy=policy)
        return assign, PorcState(
            load=ms.base + ms.delta.sum(0), routed=ms.routed,
            sketch=ms.sketch_base + ms.sketch_delta.sum(0))
    if engine == "pallas":
        from .porc_snapshot import porc_snapshot as eng  # deferred: pallas
    else:
        eng = {"snapshot": ref_porc_snapshot,
               "strict": ref_porc_assign}[engine]

    def step(sub, blk, carry):
        load, routed = carry
        a, load = eng(sub, n_bins, block=blk, eps=eps, load0=load, m0=routed)
        return a, (load, routed + sub.shape[0])

    assign, (load, routed) = route_in_spans(
        keys, block, (state.load, state.routed), step)
    return assign, PorcState(load=load, routed=routed)


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


@functools.partial(jax.jit, static_argnames=(
    "n_bins", "n_sources", "sync_every", "block", "eps", "chunk", "engine",
    "policy"))
def _porc_multisource_scan(keys: jnp.ndarray, n_bins: int, n_sources: int,
                           sync_every: int, block: int, eps: float,
                           chunk: int, engine: str, base0, delta0, ticks0,
                           skb0=None, skd0=None,
                           policy: HHPolicy | None = None):
    """Core multi-source scan over full per-source blocks.

    ``keys`` is the round-robin-interleaved global stream (message i
    belongs to source i % S); its length must be a multiple of S·block.
    Per scan step every source routes one block of its substream against
    ``base + delta[s]`` (``_snapshot_block`` or the rank-sequential
    ``_porc_block``, vmapped over sources); every ``sync_every`` steps
    the deltas merge into the base.

    With a ``policy`` (snapshot engine only) each source additionally
    classifies its block against its local sketch view at the block
    boundary, routes with per-key probe budgets
    (``_snapshot_block_hh``), and folds the block into its sketch delta
    afterwards — so the heavy/tail decision is one block stale, the
    same staleness license as the load snapshot itself. ``policy=None``
    traces to exactly the sketch-free engine (bit-identical).
    """
    S = n_sources
    M = keys.shape[0]
    assert M % (S * block) == 0, f"{M} % {S}*{block} != 0"
    nb = M // (S * block)
    # [nb, S, block]: element [b, s, k] = keys[(b·block + k)·S + s],
    # source s's k-th message of its b-th block
    kb = keys.reshape(nb, block, S).transpose(0, 2, 1)
    if engine == "snapshot":
        chunk_eff = (chunk if policy is None
                     else _hh_chunk(policy, chunk, n_bins))
        salts0 = probe_salts(chunk_eff)
        if policy is None:
            cand0 = hash_to_bins(kb[..., None], salts0, n_bins)
            xs_extra = (cand0,)             # [nb, S, block, C] hoisted
        else:
            # the policy chain can be n_bins deep — hash per block inside
            # the scan instead of hoisting [nb, S, block, n_bins] for the
            # whole stream
            xs_extra = ()
        route_block = jax.vmap(
            lambda view, cap, kblk, cblk: _snapshot_block(
                view, cap, kblk, salt_columns(cblk), n_bins, block, chunk),
            in_axes=(0, 0, 0, 0))
    else:        # "strict": in-block contention resolved rank by rank
        assert policy is None, "HHPolicy requires the snapshot engine"
        xs_extra = ()
        route_block = jax.vmap(
            lambda view, cap, kblk: _porc_block(
                view, kblk, cap, n_bins, 4 * n_bins)[1],
            in_axes=(0, 0, 0))

    def blk(carry, xs):
        base, delta, skb, skd = carry
        b, kblk, *extra = xs
        # Per-source capacity from the mass of its *local view* (merged
        # base + own delta) — not the global clock. A cap the source
        # cannot verify against its view would let all S sources fill a
        # hot bin to the global cap independently (S× overshoot at cold
        # start); the local-mass cap keeps the strict per-source
        # invariant load_view ≤ (1+eps)·mass_view/n, whose sum
        # telescopes to the global (1+eps)·m/n envelope — exactly why
        # the paper's independent-sources argument works. The arriving
        # block enters the mass as block/S so the *aggregate* lookahead
        # across sources is one block, matching the single-source m_t
        # (at S=1 this reduces bit-exactly to ``ref_porc_snapshot``'s
        # capacity); a full +block per source would hand the S sources
        # S·(1+eps)·block/n of joint slack on a shared hot bin.
        mass = count_sum(base) + count_sum(delta, 1)      # [S] local view
        cap = view_cap(eps, n_bins, mass, block / S)
        views = base[None, :] + delta                     # [S, n_bins]
        if policy is None:
            assign = route_block(views, cap, kblk, *extra)   # [S, block]
        else:
            # heavy/tail classification against the block-boundary local
            # sketch view, per-key budgets from the probe-depth schedule
            cand = hash_to_bins(kblk[..., None], salts0, n_bins)
            est = jax.vmap(lambda d, k: hh_sketch_query(policy, skb + d, k))(
                skd, kblk)                                # [S, block]
            bud = _hh_budgets(policy, n_bins, eps, est, mass[:, None])
            assign = jax.vmap(
                lambda view, c, kk, cblk, bd: _snapshot_block_hh(
                    view, c, kk, cblk, bd, n_bins,
                    policy.rotate_duplicates, policy.spread_fallback))(
                views, cap, kblk, cand, bud)
            skd = jax.vmap(lambda d, k: hh_sketch_update(policy, d, k))(
                skd, kblk)
        delta = jax.vmap(lambda d, a: d.at[a].add(1.0))(delta, assign)
        # piggyback merge — phase continues from ticks0 across calls
        sync = ((ticks0 + b + 1) % sync_every) == 0
        base = jnp.where(sync, base + delta.sum(0), base)
        delta = jnp.where(sync, jnp.zeros_like(delta), delta)
        if policy is not None:
            skb = jnp.where(sync, skb + skd.sum(0), skb)
            skd = jnp.where(sync, jnp.zeros_like(skd), skd)
        return (base, delta, skb, skd), assign

    (base, delta, skb, skd), assign = jax.lax.scan(
        blk, (base0, delta0, skb0, skd0),
        (jnp.arange(nb, dtype=jnp.int32), kb, *xs_extra))
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
    active = (jnp.arange(S) < n_tail)
    chunk_eff = chunk if policy is None else _hh_chunk(policy, chunk, n_bins)
    cand0 = hash_to_bins(keys_pad[:, None, None], probe_salts(chunk_eff),
                         n_bins)
    mass = count_sum(base0) + count_sum(delta0, 1)
    cap = view_cap(eps, n_bins, mass, 1.0 / S)
    if policy is None:
        assign = jax.vmap(
            lambda view, kblk, cblk, c: _snapshot_block(
                view, c, kblk, salt_columns(cblk), n_bins, 1, chunk))(
            base0[None, :] + delta0, keys_pad[:, None], cand0, cap)[:, 0]
        skb, skd = skb0, skd0
    else:
        est = jax.vmap(
            lambda d, k: hh_sketch_query(policy, skb0 + d, k))(
            skd0, keys_pad[:, None])                       # [S, 1]
        bud = _hh_budgets(policy, n_bins, eps, est, mass[:, None])
        assign = jax.vmap(
            lambda view, kk, cblk, c, bd: _snapshot_block_hh(
                view, c, kk, cblk, bd, n_bins,
                policy.rotate_duplicates, policy.spread_fallback))(
            base0[None, :] + delta0, keys_pad[:, None], cand0, cap,
            bud)[:, 0]
        skd = jax.vmap(
            lambda d, k, m: hh_sketch_update(policy, d, k, weights=m))(
            skd0, keys_pad[:, None], active.astype(jnp.float32)[:, None])
        skb = skb0 + skd.sum(0)
        skd = jnp.zeros_like(skd)
    delta = jax.vmap(lambda d, a, m: d.at[a].add(m))(
        delta0, assign, active.astype(jnp.float32))
    return assign, base0 + delta.sum(0), jnp.zeros_like(delta), skb, skd


def ref_porc_multisource(keys: jnp.ndarray, n_bins: int, n_sources: int, *,
                         sync_every: int = 1, block: int = 128,
                         eps: float = 0.05, chunk: int = 8,
                         state: MultiSourcePorcState | None = None,
                         engine: str = "snapshot",
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

    ``engine`` picks the per-block router, same choice as
    ``ref_porc_route``: ``"snapshot"`` (the fast path — each block
    probes a frozen local view), ``"pallas"`` (the same semantics as
    the Pallas kernel ``porc_snapshot.porc_multisource_scan`` —
    bit-identical, delta/sketch lanes in VMEM scratch; the ragged tail
    and span driver below stay shared) or ``"strict"`` (rank-sequential
    ``_porc_block`` — in-block contention resolved against the cap,
    slower but exact inside a block; use it when per-bin loads are a
    handful of messages, e.g. Fig 11's 100-source / 1000-VW point,
    where one block of snapshot staleness would dominate the ε
    mechanism).

    With ``n_sources=1, sync_every=1`` the local view *is* the running
    load, so the result is bit-identical to ``ref_porc_route`` with the
    same engine (and at ``block=1`` to the sequential oracle). Arbitrary
    stream lengths are handled like ``ref_porc_route``: the per-source
    remainder routes as power-of-two sub-blocks (``block_spans``), and a
    final sub-S ragged tail routes one message per source with the
    others masked (and publishes immediately — see
    ``_porc_multisource_tail``). The sync phase carries across spans and
    calls via ``state.ticks`` (block-granular, so a stream fed in short
    batches still merges every ``sync_every`` blocks); block boundaries
    themselves realign per call, the same alignment caveat as
    ``ref_porc_route``.

    ``policy`` (snapshot engine only) turns on heavy-hitter-aware probe
    depths (D/W-Choices): each source classifies keys against its local
    count-min sketch view and probes with per-key budgets; the sketch
    shards and delta-merges exactly like the load (see
    :class:`HHPolicy`). ``policy=None`` — the default — is bit-identical
    to the policy-free engine.

    Returns (assignment [M] int32 in original stream order,
    new MultiSourcePorcState).
    """
    S = n_sources
    if engine not in ("snapshot", "strict", "pallas"):
        raise ValueError(f"unknown engine {engine!r}")
    if policy is not None and engine not in ("snapshot", "pallas"):
        raise ValueError("HHPolicy requires the snapshot engine")
    if state is None:
        state = multisource_state_init(n_bins, S, policy)
    base, delta, routed, ticks, skb, skd = state
    if policy is not None and skb is None:
        # state predates the policy: start the sketch cold
        skb = hh_sketch_init(policy)
        skd = jnp.zeros((S, policy.depth, policy.width), jnp.float32)
    if policy is None:
        skb = skd = None                 # sketch is carried only with it
    per = keys.shape[0] // S             # full per-source span length
    r = keys.shape[0] - per * S
    parts = []
    off = 0
    for _, length, blk in block_spans(per, block):
        span = keys[off: off + length * S]
        if engine == "pallas":
            from .porc_snapshot import porc_multisource_scan  # deferred
            a, base, delta, ticks, skb, skd = porc_multisource_scan(
                span, n_bins, S, sync_every, blk, eps, chunk,
                base, delta, ticks, skb, skd, policy)
        else:
            a, base, delta, ticks, skb, skd = _porc_multisource_scan(
                span, n_bins, S, sync_every, blk, eps, chunk, engine,
                base, delta, ticks, skb, skd, policy)
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
