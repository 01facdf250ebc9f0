"""Plain reference for Consistent Grouping routing, in NumPy.

Independent of the program under test: it imports nothing from
``src/repro`` and recomputes from the keys alone what the benchmark's
timed path must produce.

* ``MultiSource`` routes a key stream the way the paper's distributed
  sources do (§V-C): message ``i`` goes to source ``i % S``; each source
  routes blocks of ``block`` messages against its local view (the merged
  load plus its own unpublished counts) and stops at the first of its
  salted candidates ``H(key, 1..8)`` whose view load is under the
  capacity ``(1+eps)·(mass + block/S)/V``, else takes the least-loaded
  bin of its view; the views merge every ``sync_every`` blocks. A
  per-source remainder routes as power-of-two sub-blocks, a block of one
  probes the whole ``4·V`` chain of Alg. 1, and fewer than ``S``
  messages at the end route one per source and publish at once.
* ``CGSlots`` adds the CG slot loop of the stream deployment on top:
  VW to worker through the owner map, the per-worker FIFO of §IV, the
  imbalance I(t) over capacity-normalised load, busy/idle signals at
  the slot's utilisation, and paired one-VW moves in severity order
  (the migrated VW is the busy worker's highest-rate one).

Counts are integer-valued float32, as the program carries them; every
float expression is evaluated in float32 in the order the semantics
state it, so the routing decisions come out exactly.
"""
from __future__ import annotations

import itertools
from typing import NamedTuple

import numpy as np

F32 = np.float32
_GAMMA_HI = np.uint32(0x9E3779B9)
_GAMMA_LO = np.uint32(0x7F4A7C15)
CHUNK = 8                      # salted candidates probed per block


def _mix32(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def hash_bins(keys: np.ndarray, salts: np.ndarray, n_bins: int) -> np.ndarray:
    """Salted splitmix-style hash of int keys into [0, n_bins);
    ``keys[..., None]`` against ``salts`` gives one column per salt."""
    with np.errstate(over="ignore"):
        k = np.asarray(keys).astype(np.int64).astype(np.uint32)[..., None]
        s = np.asarray(salts, np.uint32)
        h = _mix32(k + s * _GAMMA_HI)
        h = _mix32(h ^ (s * _GAMMA_LO + np.uint32(0x165667B1)))
    return (h % np.uint32(n_bins)).astype(np.int32)


def _mass(x: np.ndarray) -> np.ndarray:
    """Total of integer-valued counts along the last axis, rounded once
    to float32 from an exact split sum (high 2^16 part, low part)."""
    xi = x.astype(np.int64)
    hi = (xi >> 16).sum(-1)
    lo = (xi & 0xFFFF).sum(-1)
    return (hi * 65536).astype(F32) + lo.astype(F32)


def _spans(m: int, block: int):
    """(start, length, block) spans: full blocks, then powers of two."""
    out, nb = [], m // block
    off = nb * block
    if nb:
        out.append((0, off, block))
    rem = m - off
    while rem:
        p = 1 << (rem.bit_length() - 1)
        out.append((off, p, p))
        off += p
        rem -= p
    return out


class MultiSource:
    """Load state of ``n_sources`` sources routing onto ``n_bins`` bins."""

    def __init__(self, n_bins: int, n_sources: int, *, eps: float,
                 block: int, sync_every: int = 1):
        self.V, self.S = n_bins, n_sources
        self.eps1 = F32(1.0 + eps)
        self.block, self.sync_every = block, sync_every
        self.base = np.zeros(n_bins, F32)
        self.delta = np.zeros((n_sources, n_bins), F32)
        self.ticks = 0                  # blocks since the last merge
        self.rounding_choices = 0       # program's bins taken within rounding

    @property
    def load(self) -> np.ndarray:
        return self.base + self.delta.sum(0)

    def restart_phase(self) -> None:
        """Fold every delta into the base and restart the sync phase."""
        self.base = self.load
        self.delta[:] = 0
        self.ticks = 0

    def _probe(self, kb: np.ndarray, cand: np.ndarray, blk: int,
               shown=None) -> np.ndarray:
        """Bins for one block: ``kb`` is [S, blk] keys, one row per
        source, each probing its own frozen view; ``cand`` [S, blk, C]
        are their first C salted candidates. ``shown`` [S, blk] are the
        program's bins: where a probed load lies within rounding of the
        capacity (``ROUNDING`` ulps), the comparison may go either way,
        and the program's bin is taken if it is one of those outcomes."""
        S, V = self.S, self.V
        views = self.base[None, :] + self.delta                 # [S, V]
        mass = _mass(self.base)
        if self.ticks:                  # unpublished counts since a merge
            mass = mass + _mass(self.delta)
        cap = (self.eps1 * (mass + F32(blk / S)) / F32(V))
        cap = np.broadcast_to(cap, (S,))[:, None, None]
        rows = (np.arange(S) * V)[:, None, None]
        flat = views.ravel()
        probed = flat[rows + cand]
        if blk == 1:
            # a block of one walks the whole salted chain of Alg. 1
            salt = CHUNK + 1
            while not (probed < cap).any(-1).all() and salt <= 4 * V:
                more = hash_bins(kb, np.arange(salt, min(salt + 64, 4 * V + 1)),
                                 V)
                cand = np.concatenate([cand, more], -1)
                probed = np.concatenate([probed, flat[rows + more]], -1)
                salt += more.shape[-1]
        ok = probed < cap
        C = cand.shape[-1]
        flat_cand = cand.reshape(-1, C)
        fallback = views.argmin(1)[:, None]
        pick = flat_cand[np.arange(S * blk), ok.argmax(-1).ravel()]
        exact = np.where(ok.any(-1), pick.reshape(S, blk), fallback)
        if shown is None:
            return exact
        shown = np.asarray(shown)
        differ = np.nonzero(shown != exact)
        if not differ[0].size:
            return exact
        # only where the program differs: was its bin an outcome that
        # rounding allows? A candidate may be taken if it may be under
        # the capacity and no candidate before it is surely under.
        p, c, got = probed[differ], cand[differ], shown[differ][:, None]
        cp = cap[differ[0], 0]
        near = np.abs(p - cp) <= ROUNDING * np.spacing(cp)
        sure = (p < cp) & ~near
        free = np.cumsum(sure, -1) - sure == 0
        may = ((p < cp) | near) & free
        allowed = ((c == got) & may).any(-1)
        allowed |= ~sure.any(-1) & (got[:, 0] == fallback[differ[0], 0])
        self.rounding_choices += int(allowed.sum())
        out = exact.copy()
        out[tuple(d[allowed] for d in differ)] = got[allowed, 0]
        return out

    def _count(self, assign: np.ndarray) -> None:
        """Add each source's assignments [S, k] to its own delta."""
        S, V = self.S, self.V
        self.delta += np.bincount(
            (np.arange(S)[:, None] * V + assign).ravel(),
            minlength=S * V).reshape(S, V).astype(F32)

    def route(self, keys: np.ndarray, shown=None) -> np.ndarray:
        """Route a stream; returns the bin of every message, in order.
        ``shown`` is the program's bin of every message (see
        ``_probe``)."""
        keys = np.asarray(keys)
        S, V = self.S, self.V
        cands = hash_bins(keys, np.arange(1, CHUNK + 1), V)     # [m, C]
        per = len(keys) // S
        out = np.empty(len(keys), np.int32)
        off = 0
        for _, length, blk in _spans(per, self.block):
            nb = length // blk
            # [nb, S, blk]: source s's k-th message of block b
            span = keys[off: off + length * S].reshape(nb, blk, S)
            span = span.transpose(0, 2, 1)
            cspan = cands[off: off + length * S].reshape(nb, blk, S, CHUNK)
            cspan = cspan.transpose(0, 2, 1, 3)
            sspan = (None if shown is None else np.asarray(
                shown[off: off + length * S]).reshape(nb, blk, S)
                .transpose(0, 2, 1))
            got = np.empty((nb, S, blk), np.int32)
            for b in range(nb):
                got[b] = self._probe(span[b], cspan[b], blk,
                                     None if sspan is None else sspan[b])
                self._count(got[b])
                self.ticks += 1
                if self.ticks % self.sync_every == 0:
                    self.restart_phase()
            out[off: off + length * S] = got.transpose(0, 2, 1).ravel()
            off += length * S
        r = len(keys) - off
        if r:
            # fewer than S messages: one to each of sources 0..r-1, then
            # publish (a sub-block cannot advance the sync phase)
            pad = np.zeros((S, 1), keys.dtype)
            pad[:r, 0] = keys[off:]
            spad = None
            if shown is not None:
                spad = np.zeros((S, 1), np.int64)
                spad[:r, 0] = shown[off:]
            a = self._probe(pad, hash_bins(pad, np.arange(1, CHUNK + 1), V),
                            1, spad)[:r, 0]
            self.delta[np.arange(r), a] += 1
            self.restart_phase()
            out[off:] = a
        return out


def differ(got, want: np.ndarray) -> np.ndarray:
    """Where ``got`` differs from ``want``; all of it if the shapes do."""
    got = np.asarray(got)
    if got.shape != want.shape:
        return np.ones(want.shape, bool)
    return got != want


def capacities(n_workers: int, slow: list[int], slow_fraction: float,
               rho: float) -> np.ndarray:
    """Service rates in messages per unit time (arrivals are one per
    unit): capacity shares of the fleet over the provisioning point."""
    frac = np.ones(n_workers)
    frac[list(slow)] = slow_fraction
    return (frac / frac.sum() / rho).astype(F32)


def _rank(queued: np.ndarray, severity: np.ndarray) -> np.ndarray:
    """Queued workers first in ascending severity, ties by index."""
    return np.argsort(np.where(queued, severity, np.inf), kind="stable")


# Float32 division on a TPU is not correctly rounded: a quotient can come
# out an ulp or two off the IEEE one. Utilisations that lie this close
# to each other or to a threshold have no defined order, so the
# delegation of such a slot has several correct outcomes.
NEAR = 1e-6          # relative gap below which two utilisations tie
MAX_OUTCOMES = 256
ROUNDING = 4         # ulps within which a load may compare either way


def _near(x, y) -> bool:
    return abs(float(x) - float(y)) <= NEAR * max(abs(float(x)), abs(float(y)))


def _orders(rank: np.ndarray, queued: np.ndarray, util: np.ndarray,
            c: np.ndarray):
    """Every order of the queued workers in ``rank`` that rounding
    allows; the exact order first. Workers with equal arrivals and
    capacity get equal quotients on any platform, so they keep their
    index order; groups whose utilisations lie within rounding of each
    other may come in any order, or tie and interleave by index."""
    k = int(queued.sum())
    runs, i = [], 0
    while i < k:
        j = i
        while j + 1 < k and _near(util[rank[j]], util[rank[j + 1]]):
            j += 1
        if j > i:
            runs.append((i, j + 1))
        i = j + 1
    out = [rank]
    for lo, hi in runs:
        run = rank[lo:hi]
        groups: dict = {}
        for w in run:
            groups.setdefault((float(util[w]), float(c[w])), []).append(w)
        if len(groups) < 2:
            continue
        alts = [np.concatenate([groups[g] for g in p])
                for p in itertools.permutations(groups)]
        alts.append(np.sort(run))
        uniq = {a.tobytes(): a for a in alts}
        out = [np.concatenate([r[:lo], a, r[hi:]])
               for r in out for a in uniq.values()]
        if len(out) > MAX_OUTCOMES:
            return out[:MAX_OUTCOMES]
    return out


class _Story(NamedTuple):
    """One history of delegation outcomes: the owner map, the worker
    queues it led to, the moves made so far, and how many of its slots
    took an outcome other than the exact reading's (``departures``)."""
    owner: np.ndarray
    queues: np.ndarray
    moves: int
    departures: int = 0


MAX_STORIES = 64


class CGSlots:
    """The CG slot loop of the stream deployment, from a cold start.

    Where rounding leaves a slot's delegation more than one correct
    outcome (see ``NEAR``), every outcome is carried on as a story of
    its own. Given the program's assignment of each slot (``seen``), the
    stories that do not explain it are dropped, so the program passes
    exactly when some sequence of correct outcomes produces what it
    did. Without ``seen`` only the exact outcome is followed.

    The parameters are named as the program's ``CGConfig`` names them,
    so a configuration's ``cg`` group passes to both; a knob the
    reference does not model is refused as an unknown argument."""

    def __init__(self, *, n_workers: int, alpha: int, eps: float,
                 slot_len: int, block_size: int, n_sources: int,
                 sync_every: int, theta_busy: float, theta_idle: float,
                 max_moves_per_slot: int, caps: np.ndarray):
        n, V = n_workers, n_workers * alpha
        self.n, self.V, self.slot_len = n, V, slot_len
        self.eps, self.block = eps, block_size
        self.n_sources, self.sync_every = n_sources, sync_every
        self.theta_busy, self.theta_idle = F32(theta_busy), F32(theta_idle)
        self.max_moves = max_moves_per_slot
        self.c = np.asarray(caps, F32)
        self.load = np.zeros(V, F32)            # routing: one for all stories
        self.rate = np.zeros(V, F32)
        self.stories = [_Story(np.tile(np.arange(n, dtype=np.int32), alpha),
                               np.zeros(n, F32), 0)]
        self.open_slots = 0             # slots with more than one outcome
        self.rounding_choices = 0       # program's bins taken within rounding

    def slot(self, keys: np.ndarray, seen=None):
        """Route one slot; returns (workers, vws, imbalance, queue
        spread) and advances the state. ``seen`` is the program's
        (worker assignment, VW assignment) of this slot."""
        ms = MultiSource(self.V, self.n_sources, eps=self.eps,
                         block=self.block, sync_every=self.sync_every)
        ms.base = self.load.copy()
        vw = ms.route(keys, None if seen is None else seen[1])
        self.rounding_choices += ms.rounding_choices
        if seen is not None:
            seen = seen[0]
        load = ms.load
        self.rate = self.rate + (load - self.load)
        self.load = load
        stories = self.stories
        if seen is not None:
            fits = [s for s in stories if (s.owner[vw] == seen).all()]
            stories = fits or [min(stories, key=lambda s: int(
                (s.owner[vw] != seen).sum()))]
        self.fitting_moves = {s.moves for s in stories}
        c = self.c
        service = c * F32(self.slot_len)
        nxt, keep, out = [], {}, None
        for s in stories:
            workers = s.owner[vw]
            arrivals = np.bincount(workers, minlength=self.n).astype(F32)
            q1 = np.maximum(s.queues + arrivals - service, F32(0))
            util = arrivals / np.maximum(service, F32(1e-9))
            if out is None:
                norm = arrivals / np.maximum(c, F32(1e-9))
                mean = norm.mean(dtype=F32)
                imb = (norm.max() - mean) / max(mean, F32(1e-9))
                out = (workers, vw, F32(imb), F32(q1.max() - q1.min()))
            for j, (owner, done) in enumerate(self._outcomes(util, s.owner)):
                key = (owner.tobytes(), q1.tobytes(), s.moves + done)
                dep = s.departures + (j > 0)
                if key in keep:
                    i = keep[key]
                    if dep < nxt[i].departures:
                        nxt[i] = nxt[i]._replace(departures=dep)
                elif len(nxt) < MAX_STORIES:
                    keep[key] = len(nxt)
                    nxt.append(_Story(owner, q1, s.moves + done, dep))
            if seen is None:
                break                   # follow the exact outcome only
        self.open_slots += len(nxt) > len(stories)
        self.stories = nxt if seen is not None else nxt[:1]
        return out

    @property
    def departures(self) -> int:
        """The fewest slots, over the stories that explain the program,
        whose delegation took an outcome other than the exact one."""
        return min(s.departures for s in self.stories)

    def _outcomes(self, util: np.ndarray, owner: np.ndarray) -> list:
        """(owner map, moves) after this slot's delegation, for every
        reading of the utilisations that rounding allows, the exact
        reading first. Each busy worker (above theta_busy, most loaded
        first) hands its highest-rate VW to an idle worker (below
        theta_idle, least loaded first): one VW per pair, at most
        ``max_moves`` pairs."""
        busy = util > self.theta_busy
        idle = util < self.theta_idle
        masks = [(busy, idle)]
        for w in range(self.n):
            for theta, which in ((self.theta_busy, 0), (self.theta_idle, 1)):
                if _near(util[w], theta):
                    flipped = []
                    for m in masks:
                        m2 = [m[0].copy(), m[1].copy()]
                        m2[which][w] = not m2[which][w]
                        flipped.append(tuple(m2))
                    masks += flipped
        out, seen = [], set()
        for busy, idle in masks:
            for br in _orders(_rank(busy, -util), busy, util, self.c):
                for ir in _orders(_rank(idle, util), idle, util, self.c):
                    o, d = self._pair(owner, busy, idle, br, ir)
                    key = o.tobytes()
                    if key not in seen:
                        seen.add(key)
                        out.append((o, d))
                    if len(out) >= MAX_OUTCOMES:
                        return out
        return out

    def _pair(self, owner, busy, idle, busy_rank, idle_rank):
        owner = owner.copy()
        owned = np.bincount(owner, minlength=self.n)
        shed = np.where(busy, np.minimum(owned, 1), 0)
        absorb = idle.astype(np.int64)
        cs, ca = np.cumsum(shed[busy_rank]), np.cumsum(absorb[idle_rank])
        j = np.arange(self.max_moves)
        last = self.n - 1
        src = busy_rank[np.clip(np.searchsorted(cs, j, "right"), 0, last)]
        dst = idle_rank[np.clip(np.searchsorted(ca, j, "right"), 0, last)]
        done = 0
        for m in range(min(cs[-1], ca[-1], self.max_moves)):
            mine = owner == src[m]
            if mine.any():
                owner[np.argmax(np.where(mine, self.rate, -np.inf))] = dst[m]
                done += 1
        return owner, done

    def run(self, keys: np.ndarray, seen=None) -> dict:
        """Route whole slots of ``keys``: the assignment, VW assignment,
        per-slot imbalance and queue spread, and after each slot the
        move counts the surviving stories allow (``moves``: sets).
        ``seen`` is the program's (assignment, VW assignment) of the
        same slots."""
        keys = np.asarray(keys)
        L = self.slot_len
        slots = len(keys) // L
        assert slots * L == len(keys)
        a = np.empty(len(keys), np.int32)
        vw = np.empty(len(keys), np.int32)
        imb = np.empty(slots, F32)
        qs = np.empty(slots, F32)
        moves = []
        for t in range(slots):
            part = slice(t * L, (t + 1) * L)
            a[part], vw[part], imb[t], qs[t] = self.slot(
                keys[part], None if seen is None
                else (seen[0][part], seen[1][part]))
            if t:
                moves[-1] = self.fitting_moves
            moves.append({s.moves for s in self.stories})
        return dict(assignment=a, vw_assignment=vw, imbalance=imb,
                    queue_spread=qs, moves=moves)
