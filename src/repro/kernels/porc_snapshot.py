"""Pallas snapshot-probing PoRC block engine (single- and multi-source).

The fast-path semantics of ``ref.ref_porc_snapshot`` /
``ref.ref_porc_multisource`` as sequential-grid Pallas kernels: the
load vector (and, multisource, the per-source delta lanes and count-min
sketch lanes) lives in **VMEM scratch** and is carried across the grid,
so per block the only HBM traffic is the keys in and the assignments
out. Candidate hashing is *fused into the probe scan* — the salted
chain is hashed inside the kernel body right before it is resolved
against the snapshot, instead of materializing a [M, chain] candidate
tensor in HBM the way the jnp path hoists it. That fusion is what
removes the ROADMAP-flagged chain-width cost of the HH policy path: a
W-Choices chain of n_bins candidates never round-trips to memory.

Bit-identity with the jnp reference engines is structural, not
aspirational: the kernel bodies call the *same* block math
(``kernels.blocks``: ``snapshot_block``, the order-independent
``count_sum`` and the shared capacity schedule
``snapshot_cap``/``view_cap``) that ``kernels/ref.py`` scans over, and
the hash family in ``core.hashing`` is written to trace inside a kernel
body. Only table access differs: Mosaic has no vector gather or
scatter, so the kernels read and count bins by compare-and-select
against a bin iota (``ONEHOT``), which is exact for integer-valued
counts. Layout follows the TPU's tiles: keys ride the lanes (a block
is a ``[1, block]`` row, or ``[S, block]`` for S sources), per-bin
vectors ride the sublanes (``[n_bins, 1]`` columns; the S delta lanes
are ``[n_bins, S]``), and the source lanes are a static loop. The
parity tests (``tests/test_porc_snapshot_pallas.py``) pin this in
interpret mode; ``tests/test_tpu_compile.py`` compiles it for v5e.

The heavy-hitter policy path of the multi-source kernel (count-min
sketch lanes, per-key budgets) still uses gathers, sorts and prefix
sums that Mosaic does not lower; it runs in interpret mode only, and
``backend.resolve_engine("auto")`` routes policy traffic to the jnp
engine.

Grid: (M // block,), sequential. Scratch: load [n_bins, 1] f32 (+
delta [n_bins, S], sketch lanes when multisource / HH policy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import hash_to_bins

from . import blocks
from .backend import resolve_interpret
from .blocks import HHPolicy


# ---------------------------------------------------------------------------
# Table access by compare-and-select (no vector gather/scatter on TPU)
# ---------------------------------------------------------------------------

def _bin_hits(table, idx):
    """[n_bins, B] mask: bin j (sublane) is row ``idx``'s entry (lane)."""
    bins = jax.lax.broadcasted_iota(jnp.int32, (table.shape[0],
                                                idx.shape[-1]), 0)
    return idx == bins


def _take(table, idx):
    """``table[idx]`` for a [n_bins, 1] column and a [1, B] index row."""
    return jnp.sum(jnp.where(_bin_hits(table, idx), table, 0.0), axis=0,
                   keepdims=True)


def _count(table, idx):
    """``table.at[idx].add(1.0)``: the row's histogram, added per bin."""
    return table + jnp.sum(jnp.where(_bin_hits(table, idx), 1.0, 0.0),
                           axis=1, keepdims=True)


def _argmin(table):
    """First bin of the minimum of a [n_bins, 1] column (int32)."""
    bins = jax.lax.broadcasted_iota(jnp.int32, table.shape, 0)
    return jnp.min(jnp.where(table == jnp.min(table), bins, table.shape[0]))


ONEHOT = blocks.Table(take=_take, argmin=_argmin)


def _salted(krow, chunk: int, n_bins: int) -> list:
    """The first ``chunk`` salted candidates of a key row, one per salt
    (hashed in-kernel: the jnp path hoists the same values to HBM)."""
    return [hash_to_bins(krow, jnp.uint32(salt), n_bins)
            for salt in range(1, chunk + 1)]


_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


# ---------------------------------------------------------------------------
# Single source — the ``ref_porc_snapshot`` kernel
# ---------------------------------------------------------------------------

def _snapshot_kernel(m0_ref, load0_ref, keys_ref, assign_ref, loadout_ref,
                     load_scr, *,
                     n_bins: int, block: int, eps: float, chunk: int,
                     n_blocks: int):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        load_scr[...] = load0_ref[...]

    load = load_scr[...]                               # [n_bins, 1]
    kblk = keys_ref[...]                               # [1, block]
    cap = blocks.snapshot_cap(eps, n_bins, m0_ref[0],
                              b.astype(jnp.float32), block)
    assign = blocks.snapshot_block(load, cap, kblk,
                                   _salted(kblk, chunk, n_bins), n_bins,
                                   block, chunk, ONEHOT)
    assign_ref[...] = assign
    load_scr[...] = _count(load, assign)

    @pl.when(b == n_blocks - 1)
    def _flush():
        loadout_ref[...] = load_scr[...]


@functools.partial(jax.jit, static_argnames=("n_bins", "block", "eps",
                                             "chunk", "interpret"))
def porc_snapshot(keys: jnp.ndarray, n_bins: int, *, block: int = 128,
                  eps: float = 0.05, chunk: int = 8,
                  load0: jnp.ndarray | None = None, m0: float = 0.0,
                  interpret: bool | None = None):
    """Snapshot-probing PoRC as a Pallas kernel — drop-in for
    ``ref.ref_porc_snapshot`` (same signature, bit-identical result).

    Every block probes the frozen VMEM load snapshot with its salted
    chain (hashed in-kernel) against the capacity
    (1+eps)·m_t/n_bins at block end; at block=1 the full 4·n_bins lazy
    chain of Alg. 1 runs, so the kernel is bit-identical to the
    sequential oracle. ``interpret=None`` → auto (compiled on TPU).

    Returns (assignment [M] int32, final load [n_bins] f32).
    """
    M = keys.shape[0]
    assert M % block == 0, f"{M} % {block} != 0"
    n_blocks = M // block
    load0_arr = (jnp.zeros((n_bins,), jnp.float32) if load0 is None
                 else load0.astype(jnp.float32))
    if n_blocks == 0:
        return jnp.zeros((0,), jnp.int32), load0_arr
    kernel = functools.partial(_snapshot_kernel, n_bins=n_bins, block=block,
                               eps=eps, chunk=chunk, n_blocks=n_blocks)
    m0_arr = jnp.reshape(jnp.asarray(m0, jnp.float32), (1,))
    row = pl.BlockSpec((None, 1, block), lambda b: (b, 0, 0))
    column = pl.BlockSpec((n_bins, 1), lambda b: (0, 0))
    assign, load = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), column, row],
        out_specs=[row, column],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, 1, block), jnp.int32),
            jax.ShapeDtypeStruct((n_bins, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n_bins, 1), jnp.float32)],
        compiler_params=_SEQUENTIAL,
        interpret=resolve_interpret(interpret),
        name="porc_snapshot",
    )(m0_arr, load0_arr.reshape(n_bins, 1), keys.reshape(n_blocks, 1, block))
    return assign.reshape(M), load.reshape(n_bins)


# ---------------------------------------------------------------------------
# Multi-source — the ``_porc_multisource_scan`` kernel (delta + sketch
# lanes in scratch, piggyback merge on the sync cadence)
# ---------------------------------------------------------------------------

def _route_lanes(base, delta, kblk, *, n_bins: int, n_sources: int,
                 block: int, eps: float, chunk: int):
    """Route one block per source lane against its local view.

    ``base`` [n_bins, 1], ``delta`` [n_bins, S], ``kblk`` [S, block].
    Same per-source math as the vmapped jnp scan — local-view mass
    (``count_sum``), ``view_cap``, ``snapshot_block`` — unrolled over
    the S lanes. Returns (assign [S, block], delta with the block
    counted into each lane)."""
    S = n_sources
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_bins, S), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (S, block), 0)
    base_mass = blocks.count_sum(base)
    assign = jnp.zeros((S, block), jnp.int32)
    counted = delta
    for s in range(S):
        own = jnp.sum(jnp.where(lane == s, delta, 0.0), axis=1,
                      keepdims=True)                   # [n_bins, 1]
        cap = blocks.view_cap(eps, n_bins,
                              base_mass + blocks.count_sum(own), block / S)
        krow = kblk[s:s + 1, :]
        a = blocks.snapshot_block(base + own, cap, krow,
                                  _salted(krow, chunk, n_bins), n_bins,
                                  block, chunk, ONEHOT)
        assign = jnp.where(row == s, a, assign)
        counted = jnp.where(lane == s, _count(own, a), counted)
    return assign, counted


def _route_lanes_hh(base, delta, skb, skd, kblk, *, n_bins: int,
                    n_sources: int, block: int, eps: float, chunk_eff: int,
                    policy: HHPolicy):
    """The heavy-hitter policy counterpart of :func:`_route_lanes`
    (interpret mode only: gathers, sorts and prefix sums). Works on the
    jnp engine's row layout and converts back."""
    S = n_sources
    base, delta = base[:, 0], delta.T                  # [n], [S, n]
    mass = blocks.count_sum(base) + blocks.count_sum(delta, 1)   # [S]
    cap = blocks.view_cap(eps, n_bins, mass, block / S)
    views = base[None, :] + delta                      # [S, n_bins]
    cand = hash_to_bins(kblk[..., None], blocks.probe_salts(chunk_eff),
                        n_bins)
    est = jax.vmap(
        lambda d, k: blocks.hh_sketch_query(policy, skb + d, k))(
        skd, kblk)                                     # [S, block]
    bud = blocks.hh_budgets(policy, n_bins, eps, est, mass[:, None])
    assign = jax.vmap(
        lambda view, c, kk, cb, bd: blocks.snapshot_block_hh(
            view, c, kk, cb, bd, n_bins,
            policy.rotate_duplicates, policy.spread_fallback))(
        views, cap, kblk, cand, bud)
    skd = jax.vmap(lambda d, k: blocks.hh_sketch_update(policy, d, k))(
        skd, kblk)
    delta = jax.vmap(lambda d, a: d.at[a].add(1.0))(delta, assign)
    return assign, delta.T, skd


def _multisource_kernel(*refs, n_bins: int, n_sources: int, block: int,
                        sync_every: int, eps: float, chunk: int,
                        chunk_eff: int, n_blocks: int,
                        policy: HHPolicy | None):
    S = n_sources
    if policy is None:
        (ticks_ref, base0_ref, delta0_ref, keys_ref,
         assign_ref, baseout_ref, deltaout_ref,
         base_scr, delta_scr) = refs
    else:
        (ticks_ref, base0_ref, delta0_ref, skb0_ref, skd0_ref, keys_ref,
         assign_ref, baseout_ref, deltaout_ref, skbout_ref, skdout_ref,
         base_scr, delta_scr, skb_scr, skd_scr) = refs
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        base_scr[...] = base0_ref[...]
        delta_scr[...] = delta0_ref[...]
        if policy is not None:
            skb_scr[...] = skb0_ref[...]
            skd_scr[...] = skd0_ref[...]

    base, delta = base_scr[...], delta_scr[...]        # [n, 1], [n, S]
    kblk = keys_ref[...]                               # [S, block]
    # piggyback merge — phase continues from ticks across calls
    sync = ((ticks_ref[0] + b + 1) % sync_every) == 0
    if policy is None:
        assign, delta = _route_lanes(base, delta, kblk, n_bins=n_bins,
                                     n_sources=S, block=block, eps=eps,
                                     chunk=chunk)
    else:
        skb, skd = skb_scr[...], skd_scr[...]
        assign, delta, skd = _route_lanes_hh(
            base, delta, skb, skd, kblk, n_bins=n_bins, n_sources=S,
            block=block, eps=eps, chunk_eff=chunk_eff, policy=policy)
        skb = jnp.where(sync, skb + skd.sum(0), skb)
        skd = jnp.where(sync, jnp.zeros_like(skd), skd)
        skb_scr[...], skd_scr[...] = skb, skd
    base = jnp.where(sync, base + jnp.sum(delta, axis=1, keepdims=True), base)
    delta = jnp.where(sync, jnp.zeros_like(delta), delta)
    assign_ref[...] = assign
    base_scr[...], delta_scr[...] = base, delta

    @pl.when(b == n_blocks - 1)
    def _flush():
        baseout_ref[...] = base_scr[...]
        deltaout_ref[...] = delta_scr[...]
        if policy is not None:
            skbout_ref[...] = skb_scr[...]
            skdout_ref[...] = skd_scr[...]


@functools.partial(jax.jit, static_argnames=(
    "n_bins", "n_sources", "sync_every", "block", "eps", "chunk", "policy",
    "interpret"))
def porc_multisource_scan(keys: jnp.ndarray, n_bins: int, n_sources: int,
                          sync_every: int, block: int, eps: float,
                          chunk: int, base0, delta0, ticks0,
                          skb0=None, skd0=None,
                          policy: HHPolicy | None = None,
                          interpret: bool | None = None):
    """Pallas counterpart of ``ref._porc_multisource_scan``: the core
    multi-source scan over full per-source blocks, same argument order
    and the same ``(assign, base, delta, ticks, skb, skd)`` return, so
    ``ref_porc_multisource(engine="pallas")`` swaps it in per span.

    One grid step routes one block per source against its local view
    ``base + delta[s]`` (delta lanes in VMEM scratch), merges the lanes
    every ``sync_every`` steps, and — with a ``policy`` — carries the
    count-min sketch base/delta lanes in scratch on the same cadence.
    The policy path runs in interpret mode only (see the module
    docstring); asking to compile it raises.
    """
    S = n_sources
    M = keys.shape[0]
    assert M % (S * block) == 0, f"{M} % {S}*{block} != 0"
    interpret = resolve_interpret(interpret)
    if policy is not None and not interpret:
        raise NotImplementedError(
            "the heavy-hitter policy kernel does not lower to Mosaic; "
            "route HHPolicy traffic with engine='ref'")
    nb = M // (S * block)
    # [nb, S, block]: source s's k-th message of its b-th block
    kb = keys.reshape(nb, block, S).transpose(0, 2, 1)
    chunk_eff = (chunk if policy is None
                 else blocks.hh_chunk(policy, chunk, n_bins))
    kernel = functools.partial(
        _multisource_kernel, n_bins=n_bins, n_sources=S, block=block,
        sync_every=sync_every, eps=eps, chunk=chunk, chunk_eff=chunk_eff,
        n_blocks=nb, policy=policy)
    ticks_arr = jnp.reshape(jnp.asarray(ticks0, jnp.int32), (1,))
    column = pl.BlockSpec((n_bins, 1), lambda b: (0, 0))
    lanes = pl.BlockSpec((n_bins, S), lambda b: (0, 0))
    blk = pl.BlockSpec((None, S, block), lambda b: (b, 0, 0))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), column, lanes]
    out_specs = [blk, column, lanes]
    out_shape = [
        jax.ShapeDtypeStruct((nb, S, block), jnp.int32),
        jax.ShapeDtypeStruct((n_bins, 1), jnp.float32),
        jax.ShapeDtypeStruct((n_bins, S), jnp.float32),
    ]
    scratch = [pltpu.VMEM((n_bins, 1), jnp.float32),
               pltpu.VMEM((n_bins, S), jnp.float32)]
    operands = [ticks_arr, base0.reshape(n_bins, 1), delta0.T]
    if policy is not None:
        D, W = policy.depth, policy.width
        in_specs += [pl.BlockSpec((D, W), lambda b: (0, 0)),
                     pl.BlockSpec((S, D, W), lambda b: (0, 0, 0))]
        out_specs += [pl.BlockSpec((D, W), lambda b: (0, 0)),
                      pl.BlockSpec((S, D, W), lambda b: (0, 0, 0))]
        out_shape += [jax.ShapeDtypeStruct((D, W), jnp.float32),
                      jax.ShapeDtypeStruct((S, D, W), jnp.float32)]
        scratch += [pltpu.VMEM((D, W), jnp.float32),
                    pltpu.VMEM((S, D, W), jnp.float32)]
        operands += [skb0, skd0]
    in_specs.append(blk)
    operands.append(kb)
    outs = pl.pallas_call(
        kernel, grid=(nb,),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch, compiler_params=_SEQUENTIAL,
        interpret=interpret, name="porc_multisource_scan",
    )(*operands)
    if policy is None:
        assign, base, delta = outs
        skb = skd = None
    else:
        assign, base, delta, skb, skd = outs
    # invert the round-robin interleave back to global message order
    return (assign.transpose(0, 2, 1).reshape(-1), base.reshape(n_bins),
            delta.T, (ticks0 + nb) % sync_every, skb, skd)
