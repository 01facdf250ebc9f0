"""The benchmark's comparison catches a broken timed path.

Each test drives a whole run of a cell at the rehearsal size on the
CPU (``--rehearse`` skips the look for a chip) with the program broken
underneath, and expects ``correct`` to come out false:

* the control: the program run with the sources' sync period doubled,
  a staler view than the configuration states;
* a call that returns its state unchanged;
* half of a call's work left out, the rest standing in for it;
* one answer altered where it is produced;
* on the four-chip configuration, the exchange between chips (the psum
  of the lane deltas) left out.

The faults themselves are in ``bench/faults.py``.

A sound run of each cell must come out correct.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import faults  # noqa: E402
from bench import run as bench_run  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def run_cell(capsys, cell, *extra):
    rc = bench_run.main(["--workload", cell, "--seed", "2147483659",
                         "--seconds", "0.4", "--rehearse", *extra])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# -- storm-wp.route: cg.run ------------------------------------------------

def test_route_sound_run_is_correct(capsys):
    out = run_cell(capsys, "storm-wp.route")
    assert out["correct"] and out["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


def test_route_control_is_not_correct(capsys):
    out = run_cell(capsys, "storm-wp.route", "--control")
    assert not out["correct"]
    assert out["checks"]["vw_mismatch"]["value"] > 0


def config(cell):
    return bench_run.load_cell(cell, SPEC)[1]


@pytest.mark.parametrize("fault", [faults.state_unchanged,
                                   faults.half_left_out,
                                   faults.answer_altered])
def test_route_fault_is_not_correct(capsys, monkeypatch, fault):
    fault(monkeypatch.setattr, config("storm-wp.route"))
    out = run_cell(capsys, "storm-wp.route")
    assert not out["correct"] and out["failed"] > 0


# -- storm-wp.serve: ServingEngine over CGRequestRouter ------------------------

def test_serve_sound_run_is_correct(capsys):
    out = run_cell(capsys, "storm-wp.serve")
    assert out["correct"] and out["failed"] == 0
    assert out["metrics"]["serve_req_per_s"]["value"] > 0


def test_serve_control_is_not_correct(capsys):
    out = run_cell(capsys, "storm-wp.serve", "--control")
    assert not out["correct"]
    assert out["checks"]["vw_mismatch"]["value"] > 0


@pytest.mark.parametrize("fault", [faults.router_state_unchanged,
                                   faults.router_half_left_out,
                                   faults.router_answer_altered])
def test_serve_fault_is_not_correct(capsys, monkeypatch, fault):
    fault(monkeypatch.setattr, config("storm-wp.serve"))
    out = run_cell(capsys, "storm-wp.serve")
    assert not out["correct"] and out["failed"] > 0


# -- storm-wp-mesh4: the serve loop on a mesh of four devices -----------------

MESH = """
import json, sys
sys.path.insert(0, {root!r})
from bench import faults, run
spec = json.load(open({spec!r}))
spec["configs"].append({{"name": "storm-wp-mesh4",
                        "file": "bench/configs/storm-wp-mesh4.json"}})
spec["workloads"].append({{"name": "storm-wp-mesh4.serve", "chips": 4,
                          "config": "storm-wp-mesh4", "traffic": "serve"}})
if {fault!r}:
    getattr(faults, {fault!r})(setattr, run.load_cell("storm-wp-mesh4.serve", spec)[1])
sys.exit(run.main(["--workload", "storm-wp-mesh4.serve", "--seed",
                   "2147483659", "--seconds", "0.4", "--rehearse"], spec))
"""


@pytest.mark.parametrize("fault", ["", "psum_dropped"])
def test_mesh_serve_rehearses(fault):
    """The four-chip configuration, not yet a cell, runs through the
    serve loop on four CPU devices: sound, it is correct; with the
    exchange between chips left out, it is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = MESH.format(root=str(ROOT), spec=str(ROOT / "BENCHMARK.json"),
                       fault=fault)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    if fault:
        assert not out["correct"] and out["failed"] > 0
        assert out["checks"]["vw_mismatch"]["value"] > 0
    else:
        assert out["correct"] and out["failed"] == 0, out["checks"]
