"""Backend detection shared by the Pallas kernel wrappers.

Two knobs resolve here:

* ``interpret`` — every Pallas entry point takes ``interpret=None``
  meaning *auto*: compile to Mosaic on TPU, run the kernel body under
  the Pallas interpreter everywhere else (CPU CI, unit tests). Passing
  an explicit bool still forces either mode (the parity tests pin
  ``interpret=True`` so they exercise the kernel path on any backend).
* ``engine`` — the user-facing routing-engine selector
  (``partitioners.route``, ``CGConfig.engine``,
  ``serve.CGRequestRouter``), resolved once at that boundary:
  ``"ref"`` is the jnp block engine, ``"pallas"`` the Pallas block
  engine, ``"auto"`` picks Pallas on TPU and jnp elsewhere (on CPU the
  interpreted kernel is strictly slower than the jnp scan — same math,
  per-op interpreter overhead — so auto never pays it). Heavy-hitter
  policy traffic is the exception: its kernel body does not lower to
  Mosaic, so ``"auto"`` with a policy names the jnp engine on every
  platform. The kernels layer (``ref.ref_porc_multisource``) takes the
  resolved ``"ref"`` or ``"pallas"``.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → auto: compiled on TPU, interpreter elsewhere."""
    return not on_tpu() if interpret is None else interpret


def resolve_engine(engine: str, policy=None) -> str:
    """Map an engine knob to the block engine (``"ref"`` or
    ``"pallas"``) that runs traffic routed with ``policy`` (an
    ``HHPolicy`` or None)."""
    if engine == "auto":
        return "pallas" if on_tpu() and policy is None else "ref"
    if engine in ("ref", "pallas"):
        return engine
    raise ValueError(
        f"unknown engine {engine!r}: expected 'ref' | 'pallas' | 'auto'")
