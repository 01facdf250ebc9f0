"""Heavy-hitter-aware probing (D/W-Choices): sketch correctness, the
neutral-policy parity gate, budget/replication bounds, and the wiring
through partitioners, CG and the serving router."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cg
from repro.core import partitioners as P
from repro.core.streams import sample_zipf_stream
from repro.kernels.ref import (HHPolicy, MultiSourcePorcState,
                               hh_sketch_init, hh_sketch_query,
                               hh_sketch_update, multisource_state_init,
                               neutral_hh_policy, porc_state_init,
                               ref_porc_multisource, ref_porc_route)


def zipf_keys(m, z=1.6, n_keys=50_000, seed=0):
    return sample_zipf_stream(jax.random.PRNGKey(seed), m, n_keys, z)


# ---------------------------------------------------------------------------
# count-min sketch
# ---------------------------------------------------------------------------

def test_sketch_never_underestimates():
    pol = HHPolicy(width=512)
    keys = zipf_keys(8192, z=1.2)
    counts = hh_sketch_update(pol, hh_sketch_init(pol), keys)
    uniq, true = np.unique(np.asarray(keys), return_counts=True)
    est = np.asarray(hh_sketch_query(pol, counts, jnp.asarray(uniq)))
    assert (est >= true).all()                  # CMS one-sided error
    assert counts.sum() == pol.depth * 8192     # every row counts all mass


def test_sketch_topk_recall_zipf():
    """The heads of a zipf stream are always classified heavy: estimates
    overshoot by at most m/width per row (CMS bound), far below the head
    counts at default width."""
    pol = HHPolicy()            # width 4096
    keys = zipf_keys(65536, z=1.4)
    counts = hh_sketch_update(pol, hh_sketch_init(pol), keys)
    uniq, true = np.unique(np.asarray(keys), return_counts=True)
    top = uniq[np.argsort(true)[-10:]]
    est = np.asarray(hh_sketch_query(pol, counts, jnp.asarray(top)))
    true_top = np.sort(true)[-10:]
    assert (est >= true_top).all()
    assert (est <= true_top + 4 * 65536 / pol.width).all()


def test_sketch_weighted_update_masks():
    pol = HHPolicy(width=256)
    keys = jnp.asarray([3, 3, 7, 9], jnp.int32)
    w = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    counts = hh_sketch_update(pol, hh_sketch_init(pol), keys, weights=w)
    assert float(counts.sum()) == pol.depth * 3.0
    assert float(hh_sketch_query(pol, counts, jnp.asarray([7]))[0]) <= 1.0


def test_sketch_merge_linearity():
    """CMS is linear: sharded updates summed == one-shot update — the
    property that makes the multisource delta-merge exact."""
    pol = HHPolicy(width=1024)
    keys = zipf_keys(4096, z=1.0)
    whole = hh_sketch_update(pol, hh_sketch_init(pol), keys)
    parts = sum(hh_sketch_update(pol, hh_sketch_init(pol), keys[s::4])
                for s in range(4))
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(parts))


# ---------------------------------------------------------------------------
# neutral-policy bit-parity (the CI gate's test twin)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [64, 128])
def test_neutral_policy_bit_parity(block):
    n = 64
    keys = zipf_keys(16384)
    plain, st_p = ref_porc_route(keys, n, block=block)
    neut, st_n = ref_porc_route(keys, n, block=block,
                                policy=neutral_hh_policy(n))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(neut))
    np.testing.assert_array_equal(np.asarray(st_p.load), np.asarray(st_n.load))
    assert st_p.sketch is None and st_n.sketch is not None


def test_neutral_policy_bit_parity_multisource():
    n, S = 64, 4
    keys = zipf_keys(16128)        # exercises the ragged sub-S tail too
    plain, _ = ref_porc_multisource(keys, n, S, sync_every=2, block=64)
    neut, st = ref_porc_multisource(keys, n, S, sync_every=2, block=64,
                                    policy=neutral_hh_policy(n))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(neut))
    # the sketch still counted every message while routing identically
    assert float(st.sketch_base.sum() + st.sketch_delta.sum()) == 4 * 16128


def test_policy_none_state_has_no_sketch():
    keys = zipf_keys(4096)
    _, st = ref_porc_route(keys, 32, block=128)
    assert st.sketch is None
    assert porc_state_init(32).sketch is None
    ms = multisource_state_init(32, 2)
    assert ms.sketch_base is None and ms.sketch_delta is None


# ---------------------------------------------------------------------------
# budgets and replication bounds
# ---------------------------------------------------------------------------

def test_tail_budget_bounds_replication():
    """hot_fraction >= 1 turns every key into a tail key: each key's
    replication is capped at d_tail even under heavy skew."""
    n = 64
    keys = zipf_keys(32768, z=1.8)
    pol = HHPolicy(scheme="d", hot_fraction=2.0, d_tail=2)
    a, _ = ref_porc_route(keys, n, policy=pol)
    k, b = np.asarray(keys), np.asarray(a)
    for key in np.unique(k):
        assert len(np.unique(b[k == key])) <= 2


def test_heavy_keys_spread_wider_than_tail():
    n = 200
    keys = zipf_keys(65536, z=1.8)
    pol = HHPolicy(scheme="w")
    a, st = ref_porc_route(keys, n, policy=pol)
    k, b = np.asarray(keys), np.asarray(a)
    uniq, counts = np.unique(k, return_counts=True)
    head = uniq[np.argmax(counts)]
    spread_head = len(np.unique(b[k == head]))
    assert spread_head > pol.d_tail            # heavy keys got more choices
    # tail keys (single occurrence) sit on one bin
    singles = uniq[counts == 1]
    assert all(len(np.unique(b[k == s])) == 1 for s in singles[:50])


def test_w_choices_beats_porc_on_skew():
    """The headline property: under skew, W-Choices cuts replication
    while holding (here: improving) imbalance vs plain PoRC."""
    from repro.core.metrics import memory_footprint
    n, m = 200, 131072
    keys = zipf_keys(m, z=1.6, n_keys=65536, seed=3)
    uniq = len(np.unique(np.asarray(keys)))

    def run(policy):
        a, _ = ref_porc_route(keys, n, policy=policy)
        load = np.bincount(np.asarray(a), minlength=n)
        imb = (load.max() - load.mean()) / load.mean()
        repl = float(memory_footprint(a, keys, n, 65536)) / uniq
        return imb, repl

    imb_p, repl_p = run(None)
    imb_w, repl_w = run(HHPolicy(scheme="w"))
    assert repl_w < repl_p
    assert imb_w <= imb_p + 0.05


# ---------------------------------------------------------------------------
# state carry and the multisource sketch merge path
# ---------------------------------------------------------------------------

def test_policy_state_carry_split_equals_whole():
    n = 64
    keys = zipf_keys(16384)
    pol = HHPolicy(scheme="w")
    whole, st_w = ref_porc_route(keys, n, policy=pol)
    a1, st1 = ref_porc_route(keys[:8192], n, policy=pol)
    a2, st2 = ref_porc_route(keys[8192:], n, policy=pol, state=st1)
    np.testing.assert_array_equal(
        np.asarray(whole),
        np.concatenate([np.asarray(a1), np.asarray(a2)]))
    np.testing.assert_array_equal(np.asarray(st_w.sketch),
                                  np.asarray(st2.sketch))


def test_multisource_sketch_merge_exact_s1():
    """S=1 multisource with policy == single-source with policy, sketch
    included (the delta-merge path is exact at S=1)."""
    n = 64
    keys = zipf_keys(16384)
    pol = HHPolicy(scheme="w")
    a_single, st_s = ref_porc_route(keys, n, policy=pol)
    a_multi, st_m = ref_porc_multisource(keys, n, 1, sync_every=1,
                                         block=128, policy=pol)
    np.testing.assert_array_equal(np.asarray(a_single), np.asarray(a_multi))
    np.testing.assert_array_equal(
        np.asarray(st_s.sketch),
        np.asarray(st_m.sketch_base + st_m.sketch_delta.sum(0)))


def test_multisource_sketch_mass_conserved():
    """Across S sources and sync periods the merged sketch counts every
    routed message exactly (f32 integer sums stay exact here)."""
    n, S, m = 64, 4, 16128
    keys = zipf_keys(m)
    pol = HHPolicy(scheme="d")
    _, st = ref_porc_multisource(keys, n, S, sync_every=4, block=64,
                                 policy=pol)
    total = float(st.sketch_base.sum() + st.sketch_delta.sum())
    assert total == pol.depth * m


def test_multisource_policy_state_cold_start():
    """A policy-on call over a state that predates the policy (no sketch
    lanes) cold-starts the sketch instead of failing."""
    n, S = 32, 2
    keys = zipf_keys(8192)
    _, st0 = ref_porc_multisource(keys, n, S, block=64)   # no policy
    assert st0.sketch_base is None
    pol = HHPolicy(scheme="w")
    _, st1 = ref_porc_multisource(keys, n, S, block=64, state=st0,
                                  policy=pol)
    assert float(st1.sketch_base.sum() + st1.sketch_delta.sum()) \
        == pol.depth * 8192


def test_policy_rejects_strict_engine():
    """The kernels layer takes only a resolved engine: an unknown
    spelling (the strict engine is gone) or an unresolved ``"auto"``
    is rejected, with a policy or without."""
    for engine in ("strict", "snapshot", "auto"):
        for policy in (HHPolicy(), None):
            with pytest.raises(ValueError, match="unknown engine"):
                ref_porc_multisource(zipf_keys(1024), 16, 2, engine=engine,
                                     policy=policy)


# ---------------------------------------------------------------------------
# partitioners registry
# ---------------------------------------------------------------------------

def test_route_registry_hh_schemes():
    keys = zipf_keys(8192)
    for scheme in P.HH_SCHEMES:
        a = P.route(scheme, keys, 32)
        assert a.shape == (8192,)
        assert int(np.bincount(np.asarray(a), minlength=32).sum()) == 8192
    # multi-source variant exists
    a = P.route("WCHOICES", keys, 32, sources=4, sync_every=2)
    assert a.shape == (8192,)


def test_route_registry_rejects_hh_elsewhere():
    keys = zipf_keys(256)
    with pytest.raises(ValueError):
        P.route("PORC", keys, 32, hh=HHPolicy())


def test_d_w_choices_override_policy():
    keys = zipf_keys(8192, z=1.8)
    # the hh override keeps its knobs but the scheme letter is forced
    a = P.d_choices(keys, 32, hh=HHPolicy(scheme="w", d_tail=3))
    assert a.shape == (8192,)


# ---------------------------------------------------------------------------
# CG runtime
# ---------------------------------------------------------------------------

def test_cg_hh_runs_and_carries_sketch():
    cfg = cg.CGConfig(n_workers=8, slot_len=4096, block_size=128,
                      hh_scheme="w")
    keys = zipf_keys(16384)
    caps = jnp.ones(8, jnp.float32) / 8
    res = cg.run(cfg, keys, caps)
    assert float(res.state.sketch.sum()) == cfg.sketch_depth * 16384
    # split == whole with the sketch riding along
    r1 = cg.run(cfg, keys[:8192], caps)
    r2 = cg.run(cfg, keys[8192:], caps, state=r1.state)
    np.testing.assert_array_equal(
        np.asarray(res.assignment),
        np.concatenate([np.asarray(r1.assignment), np.asarray(r2.assignment)]))


def test_cg_hh_off_state_has_no_sketch():
    cfg = cg.CGConfig(n_workers=4, slot_len=2048, block_size=128)
    res = cg.run(cfg, zipf_keys(4096), jnp.ones(4, jnp.float32) / 4)
    assert res.state.sketch is None


def test_cg_hh_requires_block_path():
    with pytest.raises(ValueError):
        cg.hh_policy(cg.CGConfig(n_workers=4, hh_scheme="d", block_size=0))
    with pytest.raises(ValueError):
        cg.hh_policy(cg.CGConfig(n_workers=4, hh_scheme="d", inner="KG"))


def test_hh_scheme_spellings_normalize_to_kernel_letter():
    # regression: "WCHOICES" must not silently degrade to D semantics
    # (the kernel ceiling switch compares scheme == "w")
    for spelled, letter in [("w", "w"), ("WCHOICES", "w"),
                            ("wchoices", "w"), ("d", "d"),
                            ("DCHOICES", "d")]:
        pol = cg.hh_policy(cg.CGConfig(n_workers=4, hh_scheme=spelled))
        assert pol.scheme == letter, (spelled, pol.scheme)
    with pytest.raises(ValueError):
        cg.hh_policy(cg.CGConfig(n_workers=4, hh_scheme="PORC"))
    from repro.serve.engine import CGRequestRouter
    rt = CGRequestRouter(n_replicas=4, hh_scheme="WCHOICES")
    assert rt._policy.scheme == "w"
    with pytest.raises(ValueError):
        CGRequestRouter(n_replicas=4, hh_scheme="x")


def test_cg_hh_cold_start_from_policy_off_state():
    cfg_off = cg.CGConfig(n_workers=4, slot_len=2048, block_size=128)
    caps = jnp.ones(4, jnp.float32) / 4
    r0 = cg.run(cfg_off, zipf_keys(4096), caps)
    cfg_on = cfg_off._replace(hh_scheme="w")
    r1 = cg.run(cfg_on, zipf_keys(4096, seed=1), caps, state=r0.state)
    assert float(r1.state.sketch.sum()) == cfg_on.sketch_depth * 4096


# ---------------------------------------------------------------------------
# serving router
# ---------------------------------------------------------------------------

def test_serve_router_hh_conservation_and_single_route():
    from repro.serve.engine import CGRequestRouter
    keys = np.asarray(zipf_keys(9000), np.int32)
    rt = CGRequestRouter(n_replicas=8, hh_scheme="w")
    assign = rt.route_batch(keys)
    assert assign.shape == (9000,)
    assert (0 <= assign).all() and (assign < 8).all()
    assert float(rt.vw_load.sum()) == 9000.0
    # single-request path delegates to the batch engine under a policy
    r = rt.route(int(keys[0]))
    assert 0 <= r < 8
    assert rt.routed == 9001
    assert float(rt._state.sketch_base.sum()
                 + rt._state.sketch_delta.sum()) == rt.sketch_depth * 9001


def test_serve_router_hh_off_is_policy_free():
    from repro.serve.engine import CGRequestRouter
    keys = np.asarray(zipf_keys(4096), np.int32)
    rt_off = CGRequestRouter(n_replicas=4)
    rt_on = CGRequestRouter(n_replicas=4, hh_scheme="")
    np.testing.assert_array_equal(rt_off.route_batch(keys),
                                  rt_on.route_batch(keys))
    assert rt_on._policy is None and rt_on._state.sketch_base is None


def test_serve_router_vw_load_restore_rescales_sketch():
    from repro.serve.engine import CGRequestRouter
    keys = np.asarray(zipf_keys(8192), np.int32)
    rt = CGRequestRouter(n_replicas=4, hh_scheme="w")
    rt.route_batch(keys)
    restored = rt.vw_load / 2.0
    rt.vw_load = restored
    assert rt.routed == int(restored.sum())
    mass = float(rt._state.sketch_base.sum()) / rt.sketch_depth
    assert abs(mass - rt.routed) <= 1.0
