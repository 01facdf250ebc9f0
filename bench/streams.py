"""Key streams of the benchmark's deployments, sampled on the device.

The shape is that of the paper's Table I traces: ``n_keys`` distinct
keys, the most frequent carrying ``p1`` of the messages and the rest a
Zipf(``z_tail``) tail over ranks 2..n_keys, rescaled to ``1 - p1``.
Keys are int32 ranks (0 = hottest). The same arithmetic as the
program's own generator (``repro.core.streams.trace_probs`` and
``sample_trace``), kept here so that the yardstick does not move with
the program.
"""
from __future__ import annotations

import functools

import numpy as np


def trace_probs(n_keys: int, p1: float, z_tail: float) -> np.ndarray:
    """Probability of each key rank: ``p1`` for rank 0, then Zipf."""
    w = np.arange(1, n_keys + 1, dtype=np.float64) ** (-z_tail)
    tail = w[1:] * (1.0 - p1) / w[1:].sum()
    return np.concatenate([[p1], tail])


def seed_key(seed: int, stream: int):
    """PRNG key for stream ``stream`` of a run seeded with ``seed``; all
    64 bits of the seed count, so no two seeds share a stream."""
    import jax
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, stream):
        key = jax.random.fold_in(key, np.uint32(word))
    return key


@functools.lru_cache(maxsize=None)
def _sampler(n: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sample(key, p):
        return jax.random.choice(key, p.shape[0], shape=(n,), p=p
                                 ).astype(jnp.int32)
    return sample


def sample_keys(seed: int, stream: int, spec: dict, n: int):
    """``n`` keys of the stream ``spec`` (``keys``, ``p1``, ``z_tail``),
    as one int32 device array drawn in one jitted call."""
    import jax.numpy as jnp
    p = jnp.asarray(trace_probs(spec["keys"], spec["p1"], spec["z_tail"]),
                    jnp.float32)
    return _sampler(n)(seed_key(seed, stream), p)
