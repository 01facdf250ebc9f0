"""Public jit'd entry points for the Pallas kernels.

On TPU the kernels compile to Mosaic; everywhere else (this CPU
container, unit tests) they run in interpret mode, which executes the
kernel body with real JAX ops — same semantics, validated against the
``ref`` oracles. The backend choice lives in the kernels themselves
now (``interpret=None`` → auto, see ``kernels.backend``); these
wrappers just re-export the auto-mode call. The PoRC block engine is
driven through ``ref.ref_porc_multisource(engine="pallas")``.
"""
from __future__ import annotations

import jax.numpy as jnp

from .cg_dispatch import cg_dispatch as _cg_dispatch
from .ssd_scan import ssd_scan as _ssd_scan


def cg_dispatch(pref: jnp.ndarray, gates: jnp.ndarray, *, n_experts: int,
                k: int, capacity: int, block: int = 128):
    """Capacity-bounded MoE assignment with CG overflow."""
    return _cg_dispatch(pref, gates, n_experts=n_experts, k=k,
                        capacity=capacity, block=block)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128):
    """Mamba-2 SSD chunked scan."""
    return _ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
