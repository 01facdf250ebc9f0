"""The PoRC block engine (``ref_porc_multisource``; one source is S=1,
``ref_porc_route``), jnp and Pallas: parity sweeps, bounds, state carry."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import metrics, streams
from repro.kernels.ref import ref_porc_multisource, ref_porc_route

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import reference as R  # noqa: E402  (the NumPy reference)


@pytest.mark.parametrize("n_bins", [8, 16, 100, 256])
@pytest.mark.parametrize("block", [64, 128])
@pytest.mark.parametrize("n_sources", [1, 3])
def test_kernel_matches_ref(n_bins, block, n_sources):
    """The Pallas kernel (interpret mode) equals the jnp scan: the same
    assignments and bit-equal float base and delta lanes."""
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(1), 4096, 1000, 1.3)
    a_ref, s_ref = ref_porc_multisource(keys, n_bins, n_sources, block=block,
                                        eps=0.05)
    a_k, s_k = ref_porc_multisource(keys, n_bins, n_sources, block=block,
                                    eps=0.05, engine="pallas")
    np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a_k))
    np.testing.assert_array_equal(np.asarray(s_ref.base), np.asarray(s_k.base))
    np.testing.assert_array_equal(np.asarray(s_ref.delta),
                                  np.asarray(s_k.delta))


@pytest.mark.parametrize("z", [0.5, 1.0, 1.6])
def test_imbalance_bounded(z):
    n, m, eps = 64, 8192, 0.05
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(2), m, 2000, z)
    a, state = ref_porc_route(keys, n, eps=eps)
    # capacity bound holds up to block staleness (≤ 1 block per bin)
    assert float(state.load.max()) <= (1 + eps) * m / n + 128


def _continuation(engine):
    """Routing in two calls with the carried state == one call."""
    n = 32
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(3), 2048, 500, 1.2)
    a_full, s_full = ref_porc_route(keys, n, eps=0.05, engine=engine)
    a1, s1 = ref_porc_route(keys[:1024], n, eps=0.05, engine=engine)
    a2, s2 = ref_porc_route(keys[1024:], n, eps=0.05, state=s1,
                            engine=engine)
    np.testing.assert_array_equal(np.asarray(a_full),
                                  np.concatenate([a1, a2]))
    np.testing.assert_array_equal(np.asarray(s_full.load),
                                  np.asarray(s2.load))
    assert float(s_full.routed) == float(s2.routed) == 2048.0


def test_continuation_equals_one_shot():
    """The jnp engine at S=1 carries (load, clock) across calls."""
    _continuation("ref")


def test_pallas_kernel_continuation_equals_one_shot():
    """The Pallas kernel at S=1 carries (load, clock) like the jnp scan."""
    _continuation("pallas")


# ---------------------------------------------------------------------------
# single source (ref_porc_route: the engine at S=1)
# ---------------------------------------------------------------------------

def test_snapshot_b1_equals_sequential_oracle():
    from repro.core import partitioners as P
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(6), 3000, 800, 1.3)
    for eps in (0.01, 0.05):
        a_seq = np.asarray(P.power_of_random_choices(keys, 24, eps=eps))
        a_b1, _ = ref_porc_route(keys, 24, block=1, eps=eps)
        np.testing.assert_array_equal(a_seq, np.asarray(a_b1))


@pytest.mark.parametrize("block", [32, 128])
def test_snapshot_envelope_and_conservation(block):
    n, m, eps = 64, 8192, 0.05
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(7), m, 2000, 1.4)
    a, state = ref_porc_route(keys, n, block=block, eps=eps)
    load = state.load
    assert float(load.max()) <= (1 + eps) * m / n + block
    np.testing.assert_allclose(np.asarray(load),
                               np.asarray(metrics.loads(a, n)))


@pytest.mark.parametrize("m", [0, 1, 127, 128, 301, 1000])
def test_block_spans_cover_stream(m):
    from repro.kernels.ref import block_spans
    spans = block_spans(m, 128)
    covered = 0
    for start, length, blk in spans:
        assert start == covered
        assert length % blk == 0 and 1 <= blk <= 128
        covered += length
    assert covered == m
    # remainder decomposition is bounded: at most log2(block)+1 spans
    assert len(spans) <= 1 + 8


def test_porc_route_state_threading():
    """ref_porc_route: split calls with carried PorcState == one call
    (blocks aligned), and partial blocks route exactly len(keys)."""
    n = 32
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(8), 1000, 300, 1.2)
    a_full, s_full = ref_porc_route(keys, n, block=128, eps=0.05)
    a1, s1 = ref_porc_route(keys[:512], n, block=128, eps=0.05)
    a2, s2 = ref_porc_route(keys[512:], n, block=128, eps=0.05, state=s1)
    np.testing.assert_array_equal(np.asarray(a_full),
                                  np.concatenate([a1, a2]))
    np.testing.assert_allclose(np.asarray(s_full.load), np.asarray(s2.load))
    assert float(s_full.routed) == float(s2.routed) == 1000.0
    assert float(s_full.load.sum()) == 1000.0


# ---------------------------------------------------------------------------
# multi-source engine (ref_porc_multisource)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [1, 64, 128])
@pytest.mark.parametrize("engine", ["ref", "pallas"])
def test_multisource_s1_matches_numpy_reference(block, engine):
    """One source (S=1) routes like ``bench.reference.MultiSource(n, 1)``,
    the independent NumPy reference, on a ragged stream (a
    non-block-multiple tail), with either engine."""
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(9), 1777, 400, 1.3)
    want = R.MultiSource(24, 1, eps=0.05, block=block)
    a, st = ref_porc_multisource(keys, 24, 1, block=block, eps=0.05,
                                 engine=engine)
    np.testing.assert_array_equal(np.asarray(a), want.route(np.asarray(keys)))
    np.testing.assert_array_equal(np.asarray(st.base + st.delta.sum(0)),
                                  want.load)
    a_route, s_route = ref_porc_route(keys, 24, block=block, eps=0.05,
                                      engine=engine)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a_route))
    assert float(s_route.routed) == float(st.routed) == 1777.0


@pytest.mark.parametrize("n_sources", [3, 10, 100])
@pytest.mark.parametrize("m", [4096, 3001])
def test_multisource_conservation(n_sources, m):
    """Every message lands in exactly one bin and every source's count
    is accounted: base + Σ deltas == assignment histogram == m (holds
    through syncs, partial blocks, and the ragged sub-S tail)."""
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(10), m, 900, 1.2)
    a, st = ref_porc_multisource(keys, 50, n_sources, sync_every=2,
                                 block=64, eps=0.05)
    a = np.asarray(a)
    assert a.shape == (m,) and a.min() >= 0 and a.max() < 50
    total = np.asarray(st.base + st.delta.sum(0))
    np.testing.assert_allclose(total, np.bincount(a, minlength=50))
    assert float(st.routed) == m
    assert float(total.sum()) == m


def test_multisource_state_carries_across_calls():
    """Two calls with the carried state == one call over the
    concatenation (spans and sync boundaries aligned)."""
    S, block = 4, 64
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(11), 2048, 500, 1.2)
    a_full, s_full = ref_porc_multisource(keys, 32, S, sync_every=2,
                                          block=block)
    a1, s1 = ref_porc_multisource(keys[:1024], 32, S, sync_every=2,
                                  block=block)
    a2, s2 = ref_porc_multisource(keys[1024:], 32, S, sync_every=2,
                                  block=block, state=s1)
    np.testing.assert_array_equal(np.asarray(a_full),
                                  np.concatenate([a1, a2]))
    np.testing.assert_allclose(np.asarray(s_full.base), np.asarray(s2.base))
    np.testing.assert_allclose(np.asarray(s_full.delta), np.asarray(s2.delta))
    assert float(s_full.routed) == float(s2.routed) == 2048.0


@pytest.mark.parametrize("n_sources", [1, 10, 50, 100])
def test_multisource_imbalance_within_staleness_envelope(n_sources):
    """The Fig 11 claim: as S grows 1→100 the max load stays inside the
    (1+eps) envelope up to one sync window of staleness (the other
    sources' unseen S·sync_every·block messages + the cap lookahead)."""
    n, m, eps, block, sync_every = 20, 20_000, 0.05, 4, 2
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(12), m, 2000, 1.2)
    a, st = ref_porc_multisource(keys, n, n_sources, sync_every=sync_every,
                                 block=block, eps=eps)
    load = np.asarray(st.base + st.delta.sum(0))
    window = n_sources * sync_every * block
    assert load.max() <= (1 + eps) * m / n + window + 1
    assert load.sum() == m


def test_multisource_sync_phase_carries_across_calls():
    """The sync counter must not restart per call: feeding one block at
    a time with sync_every=4 still merges every 4th block, bit-equal to
    the one-shot stream (and the deltas do eventually publish)."""
    S, block, sync_every = 4, 16, 4
    step = S * block                      # one scan step per call
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(14),
                                      8 * step, 300, 1.2)
    a_full, s_full = ref_porc_multisource(keys, 16, S,
                                          sync_every=sync_every, block=block)
    st, parts = None, []
    for i in range(8):
        a, st = ref_porc_multisource(keys[i * step:(i + 1) * step], 16, S,
                                     sync_every=sync_every, block=block,
                                     state=st)
        parts.append(a)
    np.testing.assert_array_equal(np.asarray(a_full), np.concatenate(parts))
    np.testing.assert_allclose(np.asarray(s_full.base), np.asarray(st.base))
    np.testing.assert_allclose(np.asarray(s_full.delta), np.asarray(st.delta))
    assert int(st.ticks) == 0             # 8 blocks = 2 full sync periods
    assert float(np.asarray(st.delta).sum()) == 0.0   # deltas published


def test_multisource_empty_stream():
    a, st = ref_porc_multisource(jnp.zeros((0,), jnp.int32), 8, 4)
    assert a.shape == (0,)
    assert float(st.routed) == 0.0


def test_multisource_tail_only_call():
    """A call shorter than S routes the ragged tail path alone: one
    message per source, the rest masked — no phantom load."""
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(13), 5, 50, 1.2)
    a, st = ref_porc_multisource(keys, 8, 16, block=64, eps=0.05)
    a = np.asarray(a)
    assert a.shape == (5,) and a.min() >= 0 and a.max() < 8
    total = np.asarray(st.base + st.delta.sum(0))
    np.testing.assert_allclose(total, np.bincount(a, minlength=8))
    assert float(st.routed) == 5.0


def test_load_equals_histogram():
    n = 16
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(4), 1024, 200, 1.0)
    a, state = ref_porc_route(keys, n)
    hist = np.asarray(metrics.loads(a, n))
    np.testing.assert_allclose(np.asarray(state.load), hist)


def test_memory_vs_shuffle():
    """PoRC replication stays well below shuffle grouping."""
    from repro.core import partitioners as P
    n, m = 50, 16384
    keys = streams.sample_zipf_stream(jax.random.PRNGKey(5), m, 1000, 1.2)
    a, _ = ref_porc_route(keys, n, eps=0.05)
    mem_porc = int(metrics.memory_footprint(a, keys, n, 1000))
    mem_sg = int(metrics.memory_footprint(
        P.shuffle_grouping(keys, n), keys, n, 1000))
    assert mem_porc < 0.6 * mem_sg
