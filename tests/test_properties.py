"""Hypothesis property-based tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (pip install -e .[test])")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import metrics, partitioners as P
from repro.kernels.ref import ref_cg_dispatch, ref_porc_route

SETTINGS = dict(max_examples=20, deadline=None)


@given(st.integers(2, 64), st.integers(0, 2**31 - 1),
       st.floats(0.01, 0.5))
@settings(**SETTINGS)
def test_porc_capacity_invariant(n_bins, seed, eps):
    """∀ streams: PoRC sequential load ≤ (1+eps)·m/n + 1."""
    m = 1024
    keys = jax.random.randint(jax.random.PRNGKey(seed), (m,), 0, 100)
    a = P.power_of_random_choices(keys, n_bins, eps=round(eps, 3))
    L = np.asarray(metrics.loads(a, n_bins))
    assert L.max() <= (1 + eps) * m / n_bins + 1
    assert L.sum() == m                      # every message placed


@given(st.integers(2, 32), st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_kernel_equals_ref_random(n_bins, seed):
    """The Pallas block engine (interpret mode) equals the jnp scan at
    random bin counts."""
    keys = jax.random.randint(jax.random.PRNGKey(seed), (512,), 0, 200)
    a_ref, s_ref = ref_porc_route(keys, n_bins, block=128, eps=0.05)
    a_k, s_k = ref_porc_route(keys, n_bins, block=128, eps=0.05,
                              engine="pallas")
    np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a_k))
    np.testing.assert_array_equal(np.asarray(s_ref.load),
                                  np.asarray(s_k.load))


@given(st.integers(0, 2**31 - 1), st.integers(1, 4),
       st.floats(1.05, 2.0))
@settings(**SETTINGS)
def test_dispatch_conservation(seed, k, cf):
    """Placed slots == total expert load; capacity never exceeded."""
    T, E, D = 256, 8, 6
    r1, r2 = jax.random.split(jax.random.PRNGKey(seed))
    probs = jax.nn.softmax(
        jax.random.normal(r1, (T, E)) + 3 * jax.random.normal(r2, (1, E)), -1)
    gates, pref = jax.lax.top_k(probs, D)
    cap = max(1, int(cf * T * k / E))
    assign, slot, wts, load = ref_cg_dispatch(
        pref.astype(jnp.int32), gates, n_experts=E, k=k, capacity=cap)
    assign, slot, load = map(np.asarray, (assign, slot, load))
    assert load.max() <= cap
    assert (assign >= 0).sum() == load.sum()
    valid = assign >= 0
    pairs = assign[valid] * 100_000 + slot[valid]
    assert len(np.unique(pairs)) == valid.sum()
    # a token never gets the same expert twice
    for t in range(0, T, 37):
        ex = assign[t][assign[t] >= 0]
        assert len(np.unique(ex)) == len(ex)


@given(st.integers(2, 40), st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_imbalance_nonnegative(n, seed):
    a = jax.random.randint(jax.random.PRNGKey(seed), (500,), 0, n)
    caps = jnp.ones(n) / n
    assert float(metrics.imbalance(a, caps)) >= -1e-5


@given(st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_memory_bounds(seed):
    """unique_keys ≤ memory footprint ≤ min(m, unique·n)."""
    n, n_keys = 8, 50
    keys = jax.random.randint(jax.random.PRNGKey(seed), (400,), 0, n_keys)
    a = P.shuffle_grouping(keys, n)
    mem = int(metrics.memory_footprint(a, keys, n, n_keys))
    uniq = len(np.unique(np.asarray(keys)))
    assert uniq <= mem <= min(400, uniq * n)


@given(st.integers(1, 6), st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_greedy_d_imbalance_decreases_in_d(d, seed):
    """More choices → (weakly) better balance, PoTC-style."""
    keys = jax.random.randint(jax.random.PRNGKey(seed), (2000,), 0, 500)
    n = 16
    caps = jnp.ones(n) / n
    i1 = float(metrics.normalized_imbalance(
        P.greedy_d(keys, n, d=1, on_message_id=True), caps))
    id_ = float(metrics.normalized_imbalance(
        P.greedy_d(keys, n, d=d, on_message_id=True), caps))
    assert id_ <= i1 + 1e-6


@given(st.integers(2, 6), st.integers(64, 512), st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_hh_sketch_recall_property(depth, width, seed):
    """∀ zipf streams and sketch geometries: count-min never
    underestimates, and the top key is always recalled as heavy once the
    stream mass clears the collision noise (≤ m/width per row)."""
    from repro.core.streams import sample_zipf_stream
    from repro.kernels.ref import (HHPolicy, hh_sketch_init,
                                   hh_sketch_query, hh_sketch_update)
    m = 4096
    keys = sample_zipf_stream(jax.random.PRNGKey(seed), m, 2000, 1.5)
    pol = HHPolicy(depth=depth, width=width)
    counts = hh_sketch_update(pol, hh_sketch_init(pol), keys)
    uniq, true = np.unique(np.asarray(keys), return_counts=True)
    est = np.asarray(hh_sketch_query(pol, counts, jnp.asarray(uniq)))
    assert (est >= true).all()
    assert (est <= true + m / width + 1e-6).all()
    top = int(np.argmax(true))
    assert est[top] >= true[top] >= m / 50      # the head is unmissable
