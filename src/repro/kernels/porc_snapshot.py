"""Pallas snapshot-probing PoRC block engine (one kernel, S source lanes).

The semantics of ``ref.ref_porc_multisource`` as a sequential-grid
Pallas kernel; one source is the S=1 case. The merged load, the
per-source delta lanes and, with a heavy-hitter policy, the count-min
sketch lanes live in **VMEM scratch** and are carried across the grid,
so per block the only HBM traffic is the keys in and the assignments
out. Candidate hashing is *fused into the probe scan* — the salted
chain is hashed inside the kernel body right before it is resolved
against the snapshot, instead of materializing a [M, chain] candidate
tensor in HBM the way the jnp path hoists it. That fusion is what
removes the chain-width cost of the HH policy path: a W-Choices chain
of n_bins candidates never round-trips to memory.

Bit-identity with the jnp reference engine is structural, not
aspirational: the kernel body calls the *same* block math
(``kernels.blocks``: ``snapshot_block``, the order-independent
``count_sum`` and the capacity schedule ``view_cap``) that
``kernels/ref.py`` scans over, and the hash family in ``core.hashing``
is written to trace inside a kernel body. Only table access differs:
Mosaic has no vector gather or scatter, so the kernel reads and counts
bins by compare-and-select against a bin iota (``ONEHOT``), which is
exact for integer-valued counts. Layout follows the TPU's tiles: keys
ride the lanes (a block is an ``[S, block]`` tile), per-bin vectors
ride the sublanes (``[n_bins, 1]`` columns; the S delta lanes are
``[n_bins, S]``), and the source lanes are a static loop. The parity
tests (``tests/test_porc_snapshot_pallas.py``) pin this in interpret
mode; ``tests/test_tpu_compile.py`` compiles it for v5e.

The heavy-hitter policy path (count-min sketch lanes, per-key budgets)
still uses gathers, sorts and prefix sums that Mosaic does not lower;
it runs in interpret mode only, and ``backend.resolve_engine("auto")``
routes policy traffic to the jnp engine.

Grid: (M // (S·block),), sequential. Scratch: base [n_bins, 1] f32,
delta [n_bins, S] f32 (+ sketch lanes under an HH policy).
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
# The kernel — ``ref._porc_multisource_scan`` with the delta and sketch
# lanes in scratch and the piggyback merge on the sync cadence
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


def _multisource_kernel(*refs, n_bins: int, n_sources: int, block: int,
                        sync_every: int, eps: float, chunk: int,
                        n_blocks: int, policy: HHPolicy | None):
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
        # interpret mode only (gathers, sorts and prefix sums): the jnp
        # engine's own block step, on its [S, n_bins] lane layout
        skb, skd = skb_scr[...], skd_scr[...]
        assign, delta, skd = blocks.route_lanes(
            base[:, 0], delta.T, skb, skd, kblk, None, n_bins=n_bins,
            n_sources=S, block=block, eps=eps, chunk=chunk, policy=policy)
        delta = delta.T
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
    kernel = functools.partial(
        _multisource_kernel, n_bins=n_bins, n_sources=S, block=block,
        sync_every=sync_every, eps=eps, chunk=chunk, n_blocks=nb,
        policy=policy)
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
