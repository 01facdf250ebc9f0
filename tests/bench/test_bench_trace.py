"""The trace reducer, the peak table and the benchmark's layout.

``recorded_route_trace.json`` is an excerpt (the first 50 ms of the
window) of a trace of ``storm-wp.route`` recorded on a TPU v5e, reduced
by ``bench.trace.from_xplane`` and stored with ``Trace.to_json``.
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace as T  # noqa: E402

HERE = Path(__file__).resolve().parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def synthetic():
    """Two devices over a 10 s window; known busy time and gaps."""
    return T.Trace(
        ops={"/device:TPU:0": [("kernel.1", 1.0, 2.0), ("add", 2.5, 1.0),
                               ("kernel.1", 6.0, 1.0), ("late", 9.5, 2.0)],
             "/device:TPU:1": [("kernel.1", 0.0, 1.0), ("all-reduce", 4.0, 1.0)]},
        spans=[("bench.window", 0.0, 10.0), ("bench.step", 3.0, 4.0),
               ("bench.submit", 7.0, 3.0)])


def test_busy_time_is_the_union_inside_the_window():
    tr = synthetic()
    assert tr.window() == (0.0, 10.0)
    # device 0: [1, 3.5) + [6, 7) + [9.5, 10) = 4.0; device 1: 2.0
    assert tr.busy_s() == pytest.approx(3.0)
    assert tr.op_s(r"^kernel") == pytest.approx((3.0 + 1.0) / 2)
    assert tr.op_count(r"^kernel") == pytest.approx(1.5)
    assert tr.op_s(r"all-reduce") == pytest.approx(0.5)


def test_top_ops_and_idle_gaps():
    tr = synthetic()
    top = dict(tr.top_ops(10))
    assert top["kernel.1"] == pytest.approx(2.0)
    assert list(dict(tr.top_ops(1))) == ["kernel.1"]
    gaps = dict(tr.idle_gaps(10))
    # device 0 idles [0, 1) (no span: host), [3.5, 6) (step), [7, 9.5)
    # (submit: its midpoint is inside submit only)
    assert gaps == pytest.approx({"host": 1.0, "bench.step": 2.5,
                                  "bench.submit": 2.5})


def test_round_trip_json():
    tr = synthetic()
    back = T.Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert back.busy_s() == pytest.approx(tr.busy_s())
    assert back.idle_gaps() == tr.idle_gaps()


def test_recorded_chip_trace():
    """The reduction of a real TPU trace: the block kernel is found, and
    every share it feeds lies between 0 and 100%."""
    tr = T.Trace.from_json(json.loads(
        (HERE / "recorded_route_trace.json").read_text()))
    kernel = T.PORC_KERNEL
    assert tr.op_count(kernel) > 0
    busy, kern = tr.busy_s(), tr.op_s(kernel)
    assert 0 < kern < busy <= tr.window_s()
    assert 0 < 100 * (1 - busy / tr.window_s()) < 100
    assert tr.top_ops(10) and len(tr.top_ops(10)) <= 10


def test_unknown_device_kind_is_an_error():
    assert T.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        T.peaks("TPU v9 imaginary")


def test_every_cell_and_metric_has_its_files():
    """The harness finds everything by name: a configuration file, a
    traffic file whose mode names a loop file, a reader per per-layer
    metric."""
    from bench import run
    configs = {c["name"]: c for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert (ROOT / configs[w["config"]]["file"]).is_file()
        traffic = json.loads(
            (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert callable(run.load("loops", traffic["mode"]).run)
    names = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert callable(run.load("metrics", m["name"]).read)
        assert set(m["workloads"]) <= names
    for m in SPEC["end_to_end"]:
        assert set(m.get("workloads", names)) <= names
