"""Shared block-engine math: one implementation for jnp ref + Pallas.

Everything here is the snapshot-probing block engine's inner math —
candidate resolution against a frozen load snapshot, the heavy-hitter
budget schedule, the count-min sketch, and the capacity schedule — in a
form that traces identically inside a ``jax.lax.scan`` body (the jnp
reference engine in ``kernels/ref.py``) and inside a Pallas kernel
body (``kernels/porc_snapshot.py``). The Pallas engine calls these
exact functions, which is what makes kernel-vs-ref bit-identity a
structural property instead of a test-enforced aspiration.

Kernel-traceability rules this module obeys (a Pallas kernel body
cannot close over concrete device arrays):

* no module-level jnp constants — scalars are plain Python ints/floats
  wrapped with ``jnp.uint32(...)``/float ops at the call site;
* no non-zero-start ``jnp.arange`` (it constant-folds to a concrete
  array; start-0 arange lowers to ``lax.iota`` and is fine) — salted
  probe chains come from :func:`probe_salts` instead.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.hashing import hash_to_bins


def probe_salts(count: int, start: int = 1) -> jnp.ndarray:
    """Salts ``start .. start+count-1`` as uint32 (Alg. 1: salt <- 1).

    Equals ``jnp.arange(start, start + count, dtype=uint32)`` but built
    from ``lax.iota`` so it traces inside a Pallas kernel body instead
    of constant-folding to a captured device array.
    """
    return jax.lax.iota(jnp.uint32, count) + jnp.uint32(start)


# ---------------------------------------------------------------------------
# Capacity schedule
# ---------------------------------------------------------------------------
# Both engines must evaluate the *same float expression* — float32
# addition/division are not associative, so a mathematically equal
# rearrangement would break bit-identity between ref and kernel.

def view_cap(eps: float, n_bins: int, mass, lookahead: float):
    """Per-source capacity from the local-view mass (multisource §V-C):
    (1+eps)·(mass + lookahead)/n with lookahead the source's share of
    the arriving block (block/S; 1/S for the ragged tail)."""
    return (1.0 + eps) * (mass + lookahead) / n_bins


def count_sum(x, axis=None, keepdims: bool = False):
    """Sum of per-bin message counts, independent of reduction order.

    A float32 sum of counts whose total passes 2^24 rounds, and XLA,
    Mosaic and a cross-device psum each add in their own order — so the
    jnp, Pallas and mesh engines would derive different local-view
    capacities from the same loads. Here each count splits into a
    multiple of 2^16 and a remainder, both summed as exact int32; the
    only rounding is the final conversion. Integer-valued counts (what
    the engines carry) therefore sum identically everywhere, and below
    2^24 the result equals the plain float sum. A fractional part (a
    restored non-integer load) is added back as a float sum.
    """
    hi = jnp.floor(x * (1.0 / 65536.0))
    rest = x - hi * 65536.0                       # exact, in [0, 65536)
    lo = jnp.floor(rest)
    total = (jnp.sum(hi.astype(jnp.int32), axis, keepdims=keepdims)
             .astype(jnp.float32) * 65536.0
             + jnp.sum(lo.astype(jnp.int32), axis, keepdims=keepdims)
             .astype(jnp.float32))
    return total + jnp.sum(rest - lo, axis, keepdims=keepdims)


# ---------------------------------------------------------------------------
# Per-bin table access
# ---------------------------------------------------------------------------

class Table(NamedTuple):
    """How block math reads a per-bin vector (load, view).

    The jnp engines index it directly (``GATHER``). A Pallas TPU kernel
    cannot gather on a vector, so it passes a compare-and-select
    implementation (``porc_snapshot.ONEHOT``); with one selected element
    per row both give the same bits.
    """
    take: Callable      # (table, idx) -> table[idx]
    argmin: Callable    # table -> first index of the minimum, int32


GATHER = Table(take=lambda t, i: t[i],
               argmin=lambda t: jnp.argmin(t).astype(jnp.int32))


def salt_columns(cand) -> list:
    """Split a hoisted candidate tensor [..., C] into its C per-salt
    arrays, the form :func:`snapshot_block` consumes."""
    return [cand[..., j] for j in range(cand.shape[-1])]


# ---------------------------------------------------------------------------
# Snapshot probing (the plain engine)
# ---------------------------------------------------------------------------

def snapshot_resolve(table: Table, load, cap, cands, salts, assign,
                     max_probes: int):
    """First under-cap candidate per key, respecting the probe ceiling.
    ``cands[j]`` (shaped like ``assign``) is every key's candidate at
    ``salts[j]``; keys already assigned keep their bin."""
    probes = [(c, s) for c, s in zip(cands, salts)
              if not (isinstance(s, int) and s > max_probes)]
    # last salt first, so the first under-cap candidate is written last;
    # each step reads ``pick`` once (a forward chain reading ``assign``
    # twice per salt makes XLA's CPU compile time grow steeply in chunk)
    pick = jnp.full_like(assign, -1)
    for cand, salt in reversed(probes):
        ok = table.take(load, cand) < cap
        if not isinstance(salt, int):
            ok = ok & (salt <= max_probes)
        pick = jnp.where(ok, cand, pick)
    return jnp.where(assign < 0, pick, assign)


def snapshot_block(load, cap, kblk, cand0, n_bins: int, block: int,
                   chunk: int, table: Table = GATHER):
    """Route one block of keys against a frozen load snapshot.

    The routing semantics of ``ref_porc_multisource`` and its Pallas
    kernel, one call per source (snapshot = merged base + own delta;
    with one source, the running load): each key walks its
    salted-probe chain against ``load`` and stops at the first bin below
    ``cap``. At block=1 the full 4·n_bins chain of Alg. 1 runs (lazily,
    in chunks of ``chunk`` salts); at block>1 the budget is the ``chunk``
    pre-hashed candidates in ``cand0`` (one array per salt, shaped like
    ``kblk``). Exhausting the budget falls back to the least-loaded
    snapshot bin (Alg. 1's fallback).
    """
    max_probes = 4 * n_bins
    assign = snapshot_resolve(table, load, cap, cand0, range(1, chunk + 1),
                              jnp.full(kblk.shape, -1, jnp.int32),
                              max_probes)

    if block == 1:
        # exactness: continue the salted chain to the oracle ceiling
        def cond(c):
            salt0, assign = c
            return (salt0 <= max_probes) & (jnp.min(assign) < 0)

        def probe_chunk(c):
            salt0, assign = c
            salts = [salt0 + jnp.uint32(j) for j in range(chunk)]
            cands = [hash_to_bins(kblk, s, n_bins) for s in salts]
            return salt0 + chunk, snapshot_resolve(table, load, cap, cands,
                                                   salts, assign, max_probes)

        _, assign = jax.lax.while_loop(
            cond, probe_chunk, (jnp.uint32(1 + chunk), assign))

    # probe budget exhausted: least-loaded snapshot bin (Alg. 1)
    return jnp.where(assign < 0, table.argmin(load), assign)


# ---------------------------------------------------------------------------
# Heavy-hitter-aware probe depth — D-Choices / W-Choices
# (arXiv:1510.05714 "When Two Choices Are not Enough")
# ---------------------------------------------------------------------------

class HHPolicy(NamedTuple):
    """Static per-key probe-depth policy driven by a count-min sketch.

    PoRC gives every key the same probe budget; at scale the few heavy
    keys need *many* choices while the long tail needs only two — that
    is what bounds imbalance and replication simultaneously. The policy
    classifies each key against a device-resident count-min sketch at
    the block boundary (snapshot semantics, like the load itself) and
    assigns a per-key probe budget:

    * **tail** (estimate < ``hot_fraction`` · routed mass): ``d_tail``
      salted choices; on cap exhaustion the key falls back to the
      least-loaded bin *among its own candidates* (PKG-style), so a
      tail key is ever stored on at most ``d_tail`` bins.
    * **heavy**: the probe-depth schedule
      ``d_tail + ceil(headroom · p̂ · n/(1+eps))`` — the Eq.-2 minimum
      spread a key of estimated share p̂ needs, with slack — clipped to
      ``d_heavy`` under scheme ``"d"`` (D-Choices) or to ``n_bins``
      under ``"w"`` (W-Choices: the full choice set).

    A key whose budget exceeds the materialized candidate chain is
    entitled to more choices than were hashed: it falls back to the
    *full* choice set (the least-loaded bins, spread in load order so a
    hot key's block never piles onto a single bin;
    ``spread_fallback=False`` keeps the plain engine's single-argmin
    fallback instead). That rule makes the *neutral* policy —
    ``hot_fraction >= 1`` (threshold off) with ``d_tail`` above the
    chain length and ``spread_fallback=False`` — bit-identical to the
    plain snapshot engine at block > 1: the CI parity gate.

    All fields are Python scalars, so the policy is hashable and rides
    as a static jit argument; ``None`` policy compiles to exactly the
    sketch-free engine.
    """
    scheme: str = "d"            # "d": heavy depth capped at d_heavy;
                                 # "w": cap lifted to n_bins (full set)
    depth: int = 4               # sketch rows (independent hashes)
    width: int = 4096            # sketch columns per row; keep width
                                 # >= ~4/hot_fraction so collision noise
                                 # (~m/width per row) stays below the
                                 # heavy threshold
    hot_fraction: float = 1e-3   # heavy when est >= hot_fraction * m_t
    d_heavy: int = 32            # probe-depth ceiling for heavy keys
                                 # under scheme "d"
    d_tail: int = 2              # probe budget for tail keys
    headroom: float = 2.0        # schedule slack over the Eq.-2
                                 # minimum spread ceil(p·n/(1+eps))
    chain: int = 0               # materialized candidates per key; 0 =
                                 # auto (the scheme ceiling, so every
                                 # budget is candidate-bounded). Budgets
                                 # beyond the chain fall back to the
                                 # full choice set.
    rotate_duplicates: bool = True  # the r-th in-block duplicate of a
                                 # key starts probing at candidate r of
                                 # its window, so a hot key's block
                                 # doesn't pile onto one snapshot bin
                                 # (False: plain first-fit — parity)
    spread_fallback: bool = True # full-choice-set fallback spreads over
                                 # the least-loaded bins in load order
                                 # (False: single argmin bin — the plain
                                 # engine's fallback, the parity config)


def neutral_hh_policy(n_bins: int, **kw) -> HHPolicy:
    """The policy that routes bit-identically to the plain engine at
    block > 1 (threshold off, tail budget beyond the chain, first-fit
    order, argmin fallback) while still exercising the whole
    sketch/budget machinery — the CI parity configuration."""
    return HHPolicy(scheme="w", hot_fraction=2.0, d_tail=4 * n_bins + 1,
                    chain=1, rotate_duplicates=False,
                    spread_fallback=False, **kw)


# sketch hashes live in their own salt space, disjoint from the probe
# chain's small consecutive salts (plain Python int: kernel-traceable)
SKETCH_SALT0 = 0x5EEDC0DE


def sketch_cols(policy: HHPolicy, keys: jnp.ndarray) -> jnp.ndarray:
    salts = probe_salts(policy.depth, start=SKETCH_SALT0)
    return hash_to_bins(keys[..., None], salts, policy.width)


def hh_sketch_init(policy: HHPolicy) -> jnp.ndarray:
    """Zeroed count-min counts [depth, width]."""
    return jnp.zeros((policy.depth, policy.width), jnp.float32)


def hh_sketch_update(policy: HHPolicy, counts: jnp.ndarray,
                     keys: jnp.ndarray,
                     weights: jnp.ndarray | None = None) -> jnp.ndarray:
    """Add ``keys`` (optionally weighted) into the sketch. The sketch is
    *linear*: updating with two streams in any order — or merging two
    sketches by addition — equals updating with the concatenation,
    which is exactly why it threads through the multi-source
    delta-merge path unchanged."""
    cols = sketch_cols(policy, keys)                        # [..., depth]
    w = (jnp.ones(keys.shape, jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    return counts.at[jnp.arange(policy.depth), cols].add(w[..., None])


def hh_sketch_query(policy: HHPolicy, counts: jnp.ndarray,
                    keys: jnp.ndarray) -> jnp.ndarray:
    """Estimated count per key: min over rows (never underestimates)."""
    cols = sketch_cols(policy, keys)
    return counts[jnp.arange(policy.depth), cols].min(-1)


def hh_budgets(policy: HHPolicy, n_bins: int, eps: float,
               est: jnp.ndarray, mass) -> jnp.ndarray:
    """Per-key probe budgets: the probe-depth schedule.

    ``est`` are sketch estimates, ``mass`` the routed message mass the
    estimates are measured against (broadcastable). Tail keys get
    ``d_tail``; heavy keys get the Eq.-2-derived spread, clipped to the
    scheme's ceiling (``d_heavy`` for "d", ``n_bins`` for "w").
    """
    mass = jnp.maximum(jnp.asarray(mass, jnp.float32), 1.0)
    heavy = est >= policy.hot_fraction * mass
    need = jnp.ceil(policy.headroom * (est / mass) * n_bins / (1.0 + eps))
    ceiling = max(n_bins if policy.scheme == "w" else policy.d_heavy,
                  policy.d_tail + 1)
    bud = jnp.clip(need.astype(jnp.int32) + policy.d_tail,
                   policy.d_tail + 1, ceiling)
    return jnp.where(heavy, bud, jnp.int32(policy.d_tail))


def hh_chunk(policy: HHPolicy, chunk: int, n_bins: int) -> int:
    """Candidates to materialize per key: by default the chain covers
    the scheme's budget ceiling (``d_heavy`` for "d", ``n_bins`` for
    "w") so every policy budget is candidate-bounded — a heavy key's
    replication then stays confined to its own salted chain instead of
    leaking onto whichever bins happen to be least loaded per block.
    ``policy.chain`` overrides the ceiling (the neutral/parity config
    pins it to the plain engine's chunk)."""
    ceiling = policy.chain or (n_bins if policy.scheme == "w"
                               else policy.d_heavy)
    return max(chunk, min(ceiling, n_bins))


def snapshot_block_hh(load, cap, kblk, cand, bud, n_bins: int,
                      rotate: bool, spread: bool):
    """Route one block against a frozen snapshot with per-key budgets.

    Each key probes its salted candidates in order and stops at the
    first bin below ``cap``, exactly like ``snapshot_block``, but only
    its first ``bud[k]`` candidates are admissible. With ``rotate``,
    the r-th in-block duplicate of a key starts probing at offset r of
    its admissible window (wrapping), so a hot key's block spreads over
    its under-cap candidates instead of piling onto the first one the
    frozen snapshot shows as free. On exhaustion:
    * budget within the materialized chain → least-loaded bins among
      the key's own admissible candidates, duplicates rotated across
      the load order (bounds its replication at bud),
    * budget beyond the chain (a tail budget set past the chain — the
      neutral/parity config) → the full choice set: least-loaded bins
      spread in load order (``spread``), or the single argmin bin.
    """
    B, C = cand.shape
    idx = jnp.arange(C)
    window = jnp.minimum(bud, C)                       # admissible width
    admissible = idx[None, :] < window[:, None]
    ok = (load[cand] < cap) & admissible
    if rotate:
        i = jnp.arange(B)
        eq = kblk[:, None] == kblk[None, :]
        dup = (eq & (i[None, :] < i[:, None])).sum(1)  # in-block dup rank
        count = eq.sum(1)                              # in-block copies
        # spread the key's copies evenly across its window — adjacent
        # offsets would collide on the same first under-cap candidate
        offset = (dup * window) // jnp.maximum(count, 1)
        pos = jnp.mod(idx[None, :] - offset[:, None],
                      jnp.maximum(window[:, None], 1))
    else:
        pos = jnp.broadcast_to(idx[None, :], (B, C))
    first = jnp.argmin(jnp.where(ok, pos, C + 1), axis=1)
    pick = jnp.take_along_axis(cand, first[:, None], 1)[:, 0]
    resolved = jnp.any(ok, axis=1)
    # bounded choice set: least-loaded among the key's own candidates.
    # With rotation the tie is broken by a potential score load + pos,
    # where pos is the candidate's rotated distance from the
    # duplicate's own offset measured in messages (one step forward =
    # one message of load) — duplicates settle into *distinct* light
    # bins without the per-row sort a "dup-th least loaded" pick needs.
    loadc = jnp.where(admissible, load[cand], jnp.inf)
    fbidx = jnp.argmin(loadc + pos if rotate else loadc, axis=1)
    candmin = jnp.take_along_axis(cand, fbidx[:, None], 1)[:, 0]
    over = bud > C                       # entitled to the full choice set
    if spread:
        border = jnp.argsort(load).astype(jnp.int32)
        leftpos = jnp.cumsum((~resolved & over).astype(jnp.int32)) - 1
        globpick = border[leftpos % n_bins]
    else:
        globpick = jnp.broadcast_to(jnp.argmin(load).astype(jnp.int32), (B,))
    fallback = jnp.where(over, globpick, candmin)
    return jnp.where(resolved, pick, fallback)


# ---------------------------------------------------------------------------
# One block of every source lane (§V-C local views)
# ---------------------------------------------------------------------------

def route_lanes(base, delta, skb, skd, kblk, cblk, *, n_bins: int,
                n_sources: int, block: int, eps: float, chunk: int,
                policy: HHPolicy | None = None, weights=None):
    """Route one block per source lane against its local view and count
    it into the lane.

    ``base`` [n_bins] is the merged load, ``delta`` [S', n_bins] the
    lanes' unpublished counts and ``kblk`` [S', block] their keys; the
    lanes may be a shard of the ``n_sources`` sources. ``cblk`` [S',
    block, C] are the hoisted salted candidates, or None to hash them
    here. Each lane's capacity comes from the mass of its *local view*
    (merged base + own delta), not the global clock: a cap the source
    cannot verify against its view would let all S sources fill a hot
    bin to the global cap independently (S× overshoot at cold start);
    the local-mass cap keeps the per-source invariant load_view ≤
    (1+eps)·mass_view/n, whose sum telescopes to the global
    (1+eps)·m/n envelope — exactly why the paper's independent-sources
    argument works. The arriving block enters the mass as block/S, so
    the *aggregate* lookahead across sources is one block, matching a
    single source's m_t at block end.

    With a ``policy`` each lane classifies its block against its local
    sketch view ``skb + skd[s]`` at the block boundary, routes with
    per-key probe budgets and counts the block into its sketch delta.
    ``weights`` [S', block] (default all ones) scales what each message
    counts, so a masked message routes without being counted.

    Returns (assign [S', block] int32, delta, skd).
    """
    mass = count_sum(base) + count_sum(delta, 1)          # [S'] local view
    cap = view_cap(eps, n_bins, mass, block / n_sources)
    views = base[None, :] + delta                         # [S', n_bins]
    if cblk is None:
        chunk_eff = chunk if policy is None else hh_chunk(policy, chunk,
                                                          n_bins)
        cblk = hash_to_bins(kblk[..., None], probe_salts(chunk_eff), n_bins)
    if policy is None:
        assign = jax.vmap(
            lambda view, c, kk, cb: snapshot_block(
                view, c, kk, salt_columns(cb), n_bins, block, chunk))(
            views, cap, kblk, cblk)
    else:
        est = jax.vmap(lambda d, k: hh_sketch_query(policy, skb + d, k))(
            skd, kblk)                                    # [S', block]
        bud = hh_budgets(policy, n_bins, eps, est, mass[:, None])
        assign = jax.vmap(
            lambda view, c, kk, cb, bd: snapshot_block_hh(
                view, c, kk, cb, bd, n_bins, policy.rotate_duplicates,
                policy.spread_fallback))(views, cap, kblk, cblk, bud)
        skd = jax.vmap(
            lambda d, k, w: hh_sketch_update(policy, d, k, weights=w))(
            skd, kblk, weights)
    lane = jax.lax.broadcasted_iota(jnp.int32, assign.shape, 0)
    delta = delta.at[lane, assign].add(1.0 if weights is None else weights)
    return assign, delta, skd
