"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required for the dry-run's 512 placeholder
devices to be configured first.

TPU v5e constants used by the roofline (benchmarks/roofline.py):
197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_smoke_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over the real local device(s) for integration tests."""
    return _auto_mesh(shape, axes)


def make_source_mesh(n_hosts: int | None = None):
    """1-D mesh whose single ``sources`` axis carries the serving
    runtime's source lanes (``repro.kernels.mesh`` /
    ``repro.serve.mesh``). Defaults to every local device — under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` that is the
    N simulated hosts the multihost bench and CI job use."""
    n = n_hosts or len(jax.devices())
    return _auto_mesh((n,), ("sources",))


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the programs here
    place data with ``NamedSharding``/``with_sharding_constraint`` and
    let the partitioner propagate the rest (``make_mesh`` would
    otherwise default to ``Explicit`` axes, under which a sharding
    constraint is an assertion)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple:
    """The combined batch-sharding axes for this mesh."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def n_chips(mesh) -> int:
    return mesh.devices.size
