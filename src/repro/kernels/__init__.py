"""Pallas TPU kernels for the perf-critical hot spots.

porc_snapshot — the PoRC block engine: S source lanes routing blocks
                against local load views (one source is S=1),
                HH-policy aware — bit-identical to ``ref``.
cg_dispatch   — CG MoE dispatch: capacity-bounded with overflow.
ssd_scan      — Mamba-2 SSD chunked recurrence (assigned ssm/hybrid archs).

``ops`` holds the public jit'd wrappers; ``ref`` the pure-jnp oracles
and the block engine's span driver; ``blocks`` the block math the jnp
and Pallas engines share; ``mesh`` the engine's lanes on a device
mesh; ``backend`` the engine/interpret auto-resolution.
"""
from . import backend, blocks, ops, ref  # noqa: F401
from .backend import resolve_engine  # noqa: F401
from .ops import cg_dispatch, ssd_scan  # noqa: F401
