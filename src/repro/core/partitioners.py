"""The seven stream-partitioning strategies of the paper (Table II).

Every partitioner consumes a key stream (int32 ids) and produces, one
message at a time (``jax.lax.scan`` — exactly the paper's "one message
per unit time" model), the bin index each message is routed to.

Bins are *virtual workers* when driven by ``repro.core.cg`` and physical
workers when used standalone (the paper's Figures 4/7/8 use them
standalone over n_bins = workers × VWs).

Schemes
-------
KG    key grouping                      H(j)                    stateless
SG    shuffle grouping                  round robin             stateless
PKG   partial key grouping              2 key-choices, argmin   load state
PoTC  power of two choices              2 msg-choices, argmin   load state
CH    consistent hashing bounded load   clockwise probe < cap   ring + load
PoRC  power of random choices (Alg. 1)  salted probe < cap      load state
GREEDY_D  Greedy-d (§VI-A-1)            d key-choices, argmin   load state
D-Choices  heavy keys ≤ d_heavy probes, tail keys 2   load + sketch
W-Choices  heavy keys ≤ n probes, tail keys 2         load + sketch

Each load-stateful scheme (PKG/PoTC/PoRC) also has a ``*_blocked``
block-parallel variant routing B messages per load snapshot —
bit-identical to the oracle at B=1, eventually consistent above (the
staleness license of PKG / "The Power of Both Choices"). The PoRC block
engine itself lives in ``repro.kernels`` (Pallas kernel + jnp oracle),
as does the multi-source engine behind
``power_of_random_choices_multisource`` (§V-C: S sources with local
load views, delta-merge synchronized).

D-Choices / W-Choices ("When Two Choices Are not Enough",
arXiv:1510.05714) ride the same block engine with a per-key probe-depth
policy: a count-min sketch classifies each key at the block boundary,
heavy keys get up to ``d_heavy`` (D) or ``n_bins`` (W) probe choices
while the tail keeps ``d_tail=2`` — bounding imbalance *and* key
replication at once. See ``repro.kernels.ref.HHPolicy`` and
``docs/partitioners.md`` for the playbook.

State-carry contract: every partitioner in this module routes the whole
stream it is given against *fresh* state (zero loads, empty sketch) and
discards that state on return — calls never observe each other. For
state that continues across calls (slots, serving), drive the kernel
engines via ``repro.core.cg`` or ``repro.serve`` instead.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .hashing import hash_to_bins, hash_u32, hash_unit_interval

# Cap on PoRC/CH probe chains. The analysis (§VI-B) shows a key never
# needs more than ~n probes once eps > 1/(n-1); 4·n is a safe ceiling.
_MAX_PROBES_FACTOR = 4


# ---------------------------------------------------------------------------
# Stateless schemes
# ---------------------------------------------------------------------------

def key_grouping(keys: jnp.ndarray, n_bins: int, salt: int = 1) -> jnp.ndarray:
    """KG: pure hash of the key."""
    return hash_to_bins(keys, salt, n_bins)


def shuffle_grouping(keys: jnp.ndarray, n_bins: int, offset: int = 0) -> jnp.ndarray:
    """SG: cyclic round robin, key-oblivious."""
    m = keys.shape[0]
    return ((jnp.arange(m, dtype=jnp.int32) + offset) % n_bins).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Greedy-d (covers PKG d=2 on keys, PoTC d=2 on message ids)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_bins", "d", "on_message_id"))
def greedy_d(keys: jnp.ndarray, n_bins: int, d: int = 2,
             on_message_id: bool = False) -> jnp.ndarray:
    """Greedy-d balls-and-bins (§VI-A-1): place on argmin-load choice.

    ``on_message_id=False`` hashes the *key* (PKG when d=2: key splitting);
    ``on_message_id=True`` hashes the *message index* (PoTC when d=2 —
    equivalent to fresh random choices per message).
    """
    m = keys.shape[0]
    ids = jnp.arange(m, dtype=jnp.int32) if on_message_id else keys
    salts = jnp.arange(1, d + 1, dtype=jnp.uint32)

    def step(load, x):
        cand = hash_to_bins(x, salts, n_bins)           # (d,)
        pick = cand[jnp.argmin(load[cand])]
        return load.at[pick].add(1), pick

    _, assignment = jax.lax.scan(step, jnp.zeros(n_bins, jnp.int32), ids)
    return assignment


def partial_key_grouping(keys: jnp.ndarray, n_bins: int) -> jnp.ndarray:
    """PKG = Greedy-2 over keys."""
    return greedy_d(keys, n_bins, d=2, on_message_id=False)


def power_of_two_choices(keys: jnp.ndarray, n_bins: int) -> jnp.ndarray:
    """PoTC = Greedy-2 over message ids."""
    return greedy_d(keys, n_bins, d=2, on_message_id=True)


# ---------------------------------------------------------------------------
# PoRC — Algorithm 1, exact sequential semantics
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_bins", "eps"))
def power_of_random_choices(keys: jnp.ndarray, n_bins: int,
                            eps: float = 0.01) -> jnp.ndarray:
    """PoRC (Alg. 1): probe H(j+salt), salt=1,2,… until load < (1+eps)·m_t/n.

    m_t counts the arriving message itself so the capacity is strictly
    positive from the first message on. A probe ceiling of 4·n_bins
    guards the (never observed once eps > 1/(n-1)) pathological chain;
    on exhaustion the least-loaded bin is used.
    """
    m = keys.shape[0]
    max_probes = _MAX_PROBES_FACTOR * n_bins

    def step(load, xt):
        key, t = xt
        cap = (1.0 + eps) * (t + 1.0) / n_bins

        def cond(c):
            salt, bin_, probes = c
            return (load[bin_] >= cap) & (probes < max_probes)

        def body(c):
            salt, _, probes = c
            salt = salt + 1
            return salt, hash_to_bins(key, salt, n_bins), probes + 1

        init = (jnp.uint32(1), hash_to_bins(key, jnp.uint32(1), n_bins),
                jnp.int32(0))
        _, bin_, probes = jax.lax.while_loop(cond, body, init)
        bin_ = jnp.where(probes >= max_probes, jnp.argmin(load).astype(jnp.int32), bin_)
        return load.at[bin_].add(1.0), bin_

    t = jnp.arange(m, dtype=jnp.float32)
    _, assignment = jax.lax.scan(step, jnp.zeros(n_bins, jnp.float32), (keys, t))
    return assignment


# ---------------------------------------------------------------------------
# Block-parallel variants — eventually-consistent load state
# ---------------------------------------------------------------------------
#
# Each block of B messages is routed against the load snapshot taken at
# the block boundary (PKG/"Power of Both Choices" show load-state routing
# tolerates slightly stale estimates). With block=1 every variant is
# bit-identical to its sequential oracle above; with block>1 the routing
# is the block-synchronous semantics of ``repro.kernels``.

@functools.partial(jax.jit, static_argnames=("n_bins", "d", "block"))
def _greedy_blocked_core(ids: jnp.ndarray, load0: jnp.ndarray, n_bins: int,
                         d: int, block: int):
    """Greedy-d over full blocks: every message in a block picks the
    argmin-load candidate against the block-start snapshot."""
    nb = ids.shape[0] // block
    salts = jnp.arange(1, d + 1, dtype=jnp.uint32)
    cand = hash_to_bins(ids[:, None], salts, n_bins).reshape(nb, block, d)

    def blk(load, c):
        pick = c[jnp.arange(c.shape[0]), jnp.argmin(load[c], axis=1)]
        return load.at[pick].add(1), pick

    load, picks = jax.lax.scan(blk, load0, cand)
    return picks.reshape(-1), load


def greedy_d_blocked(keys: jnp.ndarray, n_bins: int, d: int = 2,
                     on_message_id: bool = False,
                     block: int = 128) -> jnp.ndarray:
    """Block-parallel Greedy-d (batched PKG / PoTC). Any stream length;
    a trailing partial block runs as power-of-two sub-blocks (see
    ``repro.kernels.ref.block_spans``)."""
    from repro.kernels.ref import route_in_spans  # deferred: core ← kernels
    m = keys.shape[0]
    ids = (jnp.arange(m, dtype=jnp.int32) if on_message_id
           else keys.astype(jnp.int32))
    assign, _ = route_in_spans(
        ids, block, jnp.zeros(n_bins, jnp.int32),
        lambda sub, blk, load: _greedy_blocked_core(sub, load, n_bins, d, blk))
    return assign


def partial_key_grouping_blocked(keys: jnp.ndarray, n_bins: int,
                                 block: int = 128) -> jnp.ndarray:
    """Batched PKG = block-parallel Greedy-2 over keys."""
    return greedy_d_blocked(keys, n_bins, d=2, on_message_id=False, block=block)


def power_of_two_choices_blocked(keys: jnp.ndarray, n_bins: int,
                                 block: int = 128) -> jnp.ndarray:
    """Batched PoTC = block-parallel Greedy-2 over message ids."""
    return greedy_d_blocked(keys, n_bins, d=2, on_message_id=True, block=block)


def power_of_random_choices_blocked(keys: jnp.ndarray, n_bins: int,
                                    eps: float = 0.01,
                                    block: int = 128,
                                    engine: str = "ref") -> jnp.ndarray:
    """Batched PoRC: Alg. 1 against a per-block load snapshot, capacity
    evaluated at the block boundary — the kernel block engine
    (``repro.kernels.ref``) at one source, carrying state across
    blocks. ``engine``: "ref" (jnp scan) | "pallas" (Pallas kernel,
    bit-identical) | "auto" (Pallas on TPU, jnp elsewhere)."""
    return power_of_random_choices_multisource(keys, n_bins, 1, eps=eps,
                                               block=block, engine=engine)


def power_of_random_choices_multisource(keys: jnp.ndarray, n_bins: int,
                                        n_sources: int, eps: float = 0.01,
                                        block: int = 128,
                                        sync_every: int = 1,
                                        hh=None,
                                        engine: str = "ref") -> jnp.ndarray:
    """Multi-source PoRC (§V-C): the stream splits round-robin across
    ``n_sources`` sources, each routing blocks against its local load
    view (shared merged base + own unpublished delta); views synchronize
    by delta-merge every ``sync_every`` blocks. ``n_sources=1`` is the
    blocked single-source path. ``hh`` (an ``HHPolicy``) turns on
    heavy-hitter-aware probe depths; the per-source sketch deltas merge
    on the same sync cadence. ``engine`` selects the block engine
    ("ref" | "pallas" | "auto")."""
    from repro.kernels import resolve_engine  # deferred: core ← kernels
    from repro.kernels.ref import ref_porc_multisource
    assign, _ = ref_porc_multisource(keys, n_bins, n_sources,
                                     sync_every=sync_every, block=block,
                                     eps=eps, policy=hh,
                                     engine=resolve_engine(engine, hh))
    return assign


# ---------------------------------------------------------------------------
# D-Choices / W-Choices — heavy-hitter-aware probe depths (1510.05714)
# ---------------------------------------------------------------------------

def _hh_choices(keys: jnp.ndarray, n_bins: int, scheme: str, eps: float,
                block: int, hh, engine: str = "ref", sources: int = 1,
                sync_every: int = 1) -> jnp.ndarray:
    from repro.kernels.ref import HHPolicy  # deferred: core ← kernels
    policy = HHPolicy(scheme=scheme) if hh is None else hh._replace(scheme=scheme)
    return power_of_random_choices_multisource(
        keys, n_bins, sources, eps=eps, block=block, sync_every=sync_every,
        hh=policy, engine=engine)


def d_choices(keys: jnp.ndarray, n_bins: int, eps: float = 0.01,
              block: int = 128, hh=None,
              engine: str = "ref") -> jnp.ndarray:
    """D-Choices: PoRC block engine with per-key probe budgets — heavy
    keys (count-min estimate ≥ ``hot_fraction``·m_t) probe up to
    ``d_heavy`` salted choices, tail keys keep ``d_tail=2``. Caps the
    replication of *every* key at d_heavy; imbalance degrades once the
    hottest key's balanced spread ceil(p₁·n/(1+eps)) exceeds d_heavy —
    prefer W-Choices past that point (see docs/partitioners.md).
    ``hh`` overrides the default ``HHPolicy`` knobs (scheme is forced)."""
    return _hh_choices(keys, n_bins, "d", eps, block, hh, engine)


def w_choices(keys: jnp.ndarray, n_bins: int, eps: float = 0.01,
              block: int = 128, hh=None,
              engine: str = "ref") -> jnp.ndarray:
    """W-Choices: like D-Choices but a heavy key's probe ceiling is the
    full worker set, with the budget still set per key by the Eq.-2
    schedule ceil(headroom·p̂·n/(1+eps)) — tail replication stays at
    d_tail while the few heavy keys spread just wide enough to balance.
    ``hh`` overrides the default ``HHPolicy`` knobs (scheme is forced)."""
    return _hh_choices(keys, n_bins, "w", eps, block, hh, engine)


# ---------------------------------------------------------------------------
# CH — consistent hashing with bounded loads (Mirrokni et al.)
# ---------------------------------------------------------------------------

class _Ring(NamedTuple):
    order: jnp.ndarray      # bin ids sorted by ring position
    positions: jnp.ndarray  # sorted ring positions


def build_ring(n_bins: int, points_per_bin: int = 1, salt0: int = 7) -> _Ring:
    """Hash each bin onto the unit circle (points_per_bin replicas)."""
    bins = jnp.arange(n_bins, dtype=jnp.int32)
    salts = jnp.arange(salt0, salt0 + points_per_bin, dtype=jnp.uint32)
    pos = hash_unit_interval(bins[:, None], salts).reshape(-1)
    owners = jnp.tile(bins[:, None], (1, points_per_bin)).reshape(-1)
    idx = jnp.argsort(pos)
    return _Ring(order=owners[idx], positions=pos[idx])


@functools.partial(jax.jit, static_argnames=("n_bins", "eps", "points_per_bin"))
def consistent_hashing_bounded(keys: jnp.ndarray, n_bins: int,
                               eps: float = 0.01,
                               points_per_bin: int = 1) -> jnp.ndarray:
    """CH: walk clockwise from H(key)'s successor to first bin with
    load < (1+eps)·m_t/n (Consistent Hashing with Bounded Loads)."""
    ring = build_ring(n_bins, points_per_bin)
    n_points = ring.order.shape[0]
    m = keys.shape[0]
    max_probes = _MAX_PROBES_FACTOR * n_points

    def step(load, xt):
        key, t = xt
        cap = (1.0 + eps) * (t + 1.0) / n_bins
        p = hash_unit_interval(key, jnp.uint32(1))
        start = jnp.searchsorted(ring.positions, p) % n_points

        def cond(c):
            i, probes = c
            return (load[ring.order[i]] >= cap) & (probes < max_probes)

        def body(c):
            i, probes = c
            return (i + 1) % n_points, probes + 1

        i, probes = jax.lax.while_loop(cond, body, (start.astype(jnp.int32),
                                                    jnp.int32(0)))
        bin_ = jnp.where(probes >= max_probes,
                         jnp.argmin(load).astype(jnp.int32), ring.order[i])
        return load.at[bin_].add(1.0), bin_

    t = jnp.arange(m, dtype=jnp.float32)
    _, assignment = jax.lax.scan(step, jnp.zeros(n_bins, jnp.float32), (keys, t))
    return assignment


# ---------------------------------------------------------------------------
# Registry used by benchmarks and the CG runtime
# ---------------------------------------------------------------------------

def route(scheme: str, keys: jnp.ndarray, n_bins: int, *,
          eps: float = 0.01, block_size: int | None = None,
          sources: int = 1, sync_every: int = 1, hh=None,
          engine: str = "ref") -> jnp.ndarray:
    """Route a full stream with the named scheme (paper Table II symbols).

    ``block_size=None`` uses the exact sequential oracles (one message
    per unit time). Any ``block_size >= 1`` takes the block-parallel
    fast path for the load-stateful schemes (PKG/PoTC/PoRC) —
    bit-identical at block_size=1, eventually consistent above. KG/SG
    are stateless (already fully parallel); CH walks a ring sequentially
    and has no blocked variant, so both ignore ``block_size``.

    ``sources > 1`` models the paper's §V-C distributed sources for
    PoRC: the stream splits round-robin across that many sources, each
    with a local load view synchronized every ``sync_every`` blocks
    (requires the block path; KG/SG are source-oblivious and the other
    load-stateful schemes have no multi-source variant — they reject
    ``sources > 1``).

    ``DCHOICES`` / ``WCHOICES`` are block-native (the sketch classifies
    keys at block boundaries — there is no sequential oracle), so
    ``block_size=None`` means the default block of 128; both accept
    ``sources > 1``. ``hh`` (a ``kernels.ref.HHPolicy``) overrides the
    sketch/budget knobs for them and is rejected for every other scheme.

    ``engine`` selects the block-engine implementation for the PoRC
    family (PORC blocked/multisource and DCHOICES/WCHOICES): ``"ref"``
    (the jnp scan — the default), ``"pallas"`` (the Pallas kernel,
    bit-identical: load/delta/sketch lanes in VMEM scratch, compiled on
    TPU and interpreted elsewhere), or ``"auto"`` (Pallas on TPU, jnp
    elsewhere). The sequential oracles and the non-PoRC schemes have no
    kernel variant and reject a non-"ref" engine.
    """
    scheme = scheme.upper()
    if sources > 1 and scheme not in ("PORC", "KG", "SG") + HH_SCHEMES:
        raise ValueError(f"scheme {scheme!r} has no multi-source variant")
    if hh is not None and scheme not in HH_SCHEMES:
        raise ValueError(f"scheme {scheme!r} takes no heavy-hitter policy")
    if engine != "ref" and scheme not in ("PORC",) + HH_SCHEMES:
        raise ValueError(f"scheme {scheme!r} has no kernel engine variant")
    if engine != "ref" and scheme == "PORC" and not (block_size or sources > 1):
        raise ValueError("engine applies to the block path — pass "
                         "block_size (the sequential oracle is jnp-only)")
    if scheme in HH_SCHEMES:
        return _hh_choices(keys, n_bins, "d" if scheme == "DCHOICES" else "w",
                           eps, block_size or 128, hh, engine, sources,
                           sync_every)
    if scheme == "KG":
        return key_grouping(keys, n_bins)
    if scheme == "SG":
        return shuffle_grouping(keys, n_bins)
    if scheme == "PKG":
        if block_size:
            return partial_key_grouping_blocked(keys, n_bins, block=block_size)
        return partial_key_grouping(keys, n_bins)
    if scheme == "POTC":
        if block_size:
            return power_of_two_choices_blocked(keys, n_bins, block=block_size)
        return power_of_two_choices(keys, n_bins)
    if scheme == "PORC":
        if block_size or sources > 1:
            return power_of_random_choices_multisource(
                keys, n_bins, sources, eps=eps, block=block_size or 128,
                sync_every=sync_every, engine=engine)
        return power_of_random_choices(keys, n_bins, eps=eps)
    if scheme == "CH":
        return consistent_hashing_bounded(keys, n_bins, eps=eps)
    raise ValueError(f"unknown scheme {scheme!r}")


ALL_SCHEMES = ("KG", "SG", "PKG", "POTC", "CH", "PORC")
BLOCKED_SCHEMES = ("PKG", "POTC", "PORC")
HH_SCHEMES = ("DCHOICES", "WCHOICES")
