"""The reduction of the program's own spans and scopes (``bench.scopes``).

``recorded_serve_scopes.json``, ``recorded_route_scopes.json`` and
``recorded_mesh_scopes.json`` are excerpts of traces recorded on TPU
v5e of ``storm-wp.serve`` (80 ms, about four engine ticks; its ops carry
no scopes), ``storm-wp.route`` (1.2 ms, about four slots) and
``storm-wp-mesh4.serve`` (60 ms on four chips, two ticks), reduced by
``bench.scopes.from_xplane`` and stored with ``ScopedTrace.to_json``
(op names cut to the instruction, except the custom calls the kernel
pattern reads).
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as R  # noqa: E402
from bench import scopes as S  # noqa: E402
from bench import trace as T  # noqa: E402

HERE = Path(__file__).resolve().parent
PEAKS = T.peaks("TPU v5 lite")


def recorded(name, cls=S.ScopedTrace):
    return cls.from_json(json.loads((HERE / name).read_text()))


def read(metric, trace, **work):
    return R.load("metrics", metric).read(R.Reading(trace, {}, work, PEAKS))


def spans():
    """A tick on the main thread, and a collection on another line."""
    return S.ScopedTrace(
        spans=[("bench.window", 0.0, 10.0), ("cg.step", 1.0, 6.0),
               ("cg.admit", 1.5, 2.0), ("cg.finalize", 2.0, 1.0),
               ("cg.device_wait", 2.5, 0.25), ("cg.rebalance", 4.0, 2.0),
               ("cg.device_wait", 5.0, 0.5), ("cg.gc", 2.0, 1.0),
               ("cg.step", 9.0, 3.0)],
        span_lines=["h/main"] * 7 + ["h/other", "h/main"])


def test_span_total_clips_to_the_window():
    tr = spans()
    assert tr.span_count("cg.step") == 2
    # [1, 7) and [9, 10) of [9, 12)
    assert tr.span_total("cg.step") == pytest.approx(7.0)
    assert tr.span_total("cg.device_wait") == pytest.approx(0.75)
    assert tr.span_total("cg.nothing") == 0.0


def test_span_self_takes_out_nested_spans_of_its_line():
    tr = spans()
    # admit [1.5, 3.5) holds finalize [2, 3), which holds a wait
    assert tr.span_self("cg.admit") == pytest.approx(1.0)
    assert tr.span_self("cg.finalize") == pytest.approx(0.75)
    assert tr.span_self("cg.rebalance") == pytest.approx(1.5)
    # step [1, 7): admit and rebalance are its children; gc ran on
    # another line and is not; the clipped second step has none
    assert tr.span_self("cg.step") == pytest.approx(2.0 + 1.0)
    children = sum(tr.span_total(n) for n in ("cg.admit", "cg.rebalance"))
    assert children + tr.span_self("cg.step") == pytest.approx(
        tr.span_total("cg.step"))


def test_program_spans_name_idle_gaps():
    tr = spans()
    tr.ops = {"/device:TPU:0": [("%fusion", 0.0, 1.0), ("%fusion", 7.0, 2.0)]}
    tr.op_scopes = {"/device:TPU:0": ["", ""]}
    gaps = dict(tr.idle_gaps())
    # [1, 7) falls in cg.step (mid 4: rebalance is innermost), [9, 10) in
    # the second step
    assert gaps == pytest.approx({"cg.rebalance": 6.0, "cg.step": 1.0})


def scoped_ops():
    """A while op enclosing its body, on two devices, and a collective
    on the asynchronous line."""
    deleg = "jit(run)/while/body/jit(rebalance_step)/cg.delegation"
    return S.ScopedTrace(
        ops={"/device:TPU:0": [("%while.142", 0.0, 10.0),
                               ("%while.147", 1.0, 4.0),
                               ("%fusion.9", 2.0, 1.0),
                               ("%fusion.74", 6.0, 1.0),
                               ("%fusion.80", 8.0, 1.0)],
             "/device:TPU:1": [("%fusion.80", 0.0, 2.0)]},
        op_scopes={"/device:TPU:0": ["jit(run)/while", deleg + "/while",
                                     deleg + "/gather",
                                     "jit(run)/while/body/cg.bind/scatter-add",
                                     deleg + "/select_n"],
                   "/device:TPU:1": [deleg + "/select_n"]},
        async_ops={"/device:TPU:0": [("%psum.1", 3.0, 2.0)],
                   "/device:TPU:1": [("%psum.1", 3.0, 1.0)]},
        async_scopes={"/device:TPU:0": ["jit(body)/cg.merge/psum"],
                      "/device:TPU:1": ["jit(body)/cg.merge/psum"]},
        spans=[("bench.window", 0.0, 10.0)])


def test_scope_time_is_the_union_under_the_scope():
    tr = scoped_ops()
    # device 0: [1, 5) holds [2, 3), plus [8, 9): 5; device 1: 2
    assert tr.scope_s("cg.delegation") == pytest.approx((5.0 + 2.0) / 2)
    assert tr.scope_s("cg.bind") == pytest.approx(0.5)
    # a component, not a substring
    assert tr.scope_s("cg.deleg") == 0.0
    assert tr.scope_s("cg.merge") == 0.0
    assert tr.scope_s("cg.merge", asynchronous=True) == pytest.approx(1.5)


def test_json_round_trip_keeps_lines_and_scopes():
    tr = scoped_ops()
    tr.spans, tr.span_lines = spans().spans, spans().span_lines
    back = S.ScopedTrace.from_json(json.loads(json.dumps(tr.to_json())))
    assert back.scope_s("cg.delegation") == tr.scope_s("cg.delegation")
    assert back.span_self("cg.step") == tr.span_self("cg.step")


def test_span_names_drop_their_arguments():
    assert S.span_name("cg.dispatch#batch=3#") == "cg.dispatch"
    assert S.span_name("cg.step") == "cg.step"


def test_op_names_come_from_the_traced_programs(tmp_path):
    """Each program's HLO in the trace names its instructions' scopes."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("cg.bind"):
            y = x * 2.0 + 1.0
        return jnp.sum(y)

    x = jnp.ones(64)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    names = S.op_names(next(tmp_path.rglob("*.xplane.pb")).read_bytes())
    program = [p for p in names if p.startswith("jit_f(")]
    assert len(program) == 1
    scoped = [n for n in names[program[0]].values()
              if S._under(n, "cg.bind")]
    assert scoped and all(n.startswith("jit(f)/") for n in scoped)


# -- the readers on recorded chip traces -------------------------------------

# what the readers of the first benchmark read from the recorded route
# excerpt with bench/trace.py's reduction, before this module existed
BEFORE = {"porc_kernel_us.route": 41.4766000000001,
          "porc_roofline.route": 0.2355065209783286,
          "slot_other_us.route": 222.10669999999993,
          "device_idle_pct.route": 12.138899999999996,
          "porc_kernel_us.serve": 414.766000000001,
          "device_idle_pct.serve": 12.138899999999996}


@pytest.mark.parametrize("cls", [T.Trace, S.ScopedTrace])
@pytest.mark.parametrize("metric", sorted(BEFORE))
def test_existing_readers_read_as_before(metric, cls):
    tr = recorded("recorded_route_trace.json", cls)
    got = read(metric, tr, slots=10, messages=100_000, batches=1)
    assert got == pytest.approx(BEFORE[metric], rel=1e-12)


def test_new_readers_are_silent_without_the_program_trace():
    """On a reduction without the program's spans and scopes (the base
    reduction, or a trace of a program that records none) the readers
    report nothing, and raise nothing."""
    for cls in (T.Trace, S.ScopedTrace):
        tr = recorded("recorded_route_trace.json", cls)
        for metric in ("admit_ms.serve", "serve_replicas_ms.serve",
                       "rebalance_ms.serve", "device_wait_ms.serve",
                       "gc_ms.serve", "bind_us.route", "delegation_us.route",
                       "controller_us.route", "mesh_psum_us.serve"):
            assert read(metric, tr, slots=10, ticks=4, batches=4) is None


def test_serve_readers_on_a_recorded_chip_excerpt():
    tr = recorded("recorded_serve_scopes.json")
    ticks = tr.span_count("cg.step")
    assert ticks >= 2
    got = {m: read(m, tr, ticks=ticks) for m in (
        "admit_ms.serve", "serve_replicas_ms.serve", "rebalance_ms.serve",
        "device_wait_ms.serve", "gc_ms.serve")}
    assert all(v is not None and v >= 0.0 for v in got.values()), got
    assert got["admit_ms.serve"] > 0 and got["serve_replicas_ms.serve"] > 0
    assert got["admit_ms.serve"] == pytest.approx(
        tr.span_self("cg.admit") / ticks * 1e3)
    # the tick is its children and its own time
    kids = sum(tr.span_total(n) for n in (
        "cg.admit", "cg.serve_replicas", "cg.rebalance"))
    assert kids + tr.span_self("cg.step") <= tr.span_total("cg.step") + 1e-9
    # what the harness's span around step times, the program's span
    # times from inside
    assert tr.span_total("cg.step") <= tr.span_total("bench.step") + 1e-9
    assert tr.span_total("cg.step") > 0.9 * tr.span_total("bench.step")


def test_route_readers_on_a_recorded_chip_excerpt():
    tr = recorded("recorded_route_scopes.json")
    slots = 10
    got = {m: read(m, tr, slots=slots) for m in (
        "bind_us.route", "delegation_us.route", "controller_us.route")}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["bind_us.route"] == pytest.approx(
        tr.scope_s("cg.bind") / slots * 1e6)
    kernel = T.kernel_s(tr)
    assert kernel
    # the layers are disjoint parts of the busy time
    assert sum(got.values()) * slots * 1e-6 + kernel <= tr.busy_s() + 1e-9
    # the serve readers find no engine tick here
    assert read("admit_ms.serve", tr, ticks=1) is None


def test_mesh_reader_on_a_recorded_chip_excerpt():
    tr = recorded("recorded_mesh_scopes.json")
    assert len(tr.ops) == 4
    merge = read("mesh_psum_us.serve", tr, batches=1)
    assert merge == pytest.approx(tr.scope_s("cg.merge", asynchronous=True)
                                  * 1e6)
    # the psum is one all-reduce op per block on each chip's op line
    assert 0 < merge and tr.scope_s("cg.merge") * 1e6 == pytest.approx(merge)
    assert merge * 1e-6 < tr.busy_s()
    # the mesh router's tick carries the same host spans
    ticks = tr.span_count("cg.step")
    assert read("admit_ms.serve", tr, ticks=ticks) > 0
