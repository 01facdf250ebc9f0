"""Each cell's command runs end to end at the rehearsal size on the CPU,
and refuses to measure anything but a TPU.

The command is run as the benchmark's users run it, in a process of its
own: ``python3 bench/run.py --workload <cell> ...``. Nothing here
touches a TPU.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def command(cell, *extra, cwd=ROOT, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload", cell,
         "--seed", "3000000017", "--seconds", "0.5", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def chips(cell):
    return next(w["chips"] for w in SPEC["workloads"] if w["name"] == cell)


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, trace):
    p = command(cell, "--trace", trace, "--rehearse", devices=chips(cell))
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(out) == KEYS | ({"breakdown"} & set(out))
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == chips(cell)
    if trace == "0":
        want = {m["name"] for m in SPEC["end_to_end"]
                if cell in m.get("workloads", [cell])}
        assert set(out["metrics"]) == want
    else:
        # no device plane on the CPU: only host-clock metrics are read
        allowed = {m["name"] for m in SPEC["per_layer"]
                   if cell in m.get("workloads", [cell])
                   and m["source"] == "host_clock"}
        assert set(out["metrics"]) == allowed
    # the numbers compared, with their limits, end standard error
    tail = p.stderr.strip().splitlines()[-len(out["checks"]):]
    assert [line.split()[1] for line in tail] == list(out["checks"])


def test_cpu_without_rehearsal_exits_nonzero():
    p = command(CELLS[0])
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """Without the program beside it the benchmark prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in SPEC["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = command(CELLS[0], "--rehearse", cwd=tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()
