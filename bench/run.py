#!/usr/bin/env python3
"""The chip benchmark of CG routing and serving: one cell, one run.

    python bench/run.py --workload storm-wp.route --seed 7 --seconds 10 --trace 0
    JAX_PLATFORMS=cpu python bench/run.py --workload storm-wp.serve --seed 7 \\
        --seconds 2 --trace 0 --rehearse

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix. Everything of a cell is found by name: the configuration
is the file ``BENCHMARK.json`` gives it, the mix
``bench/traffic/<traffic>.json``, whose ``mode`` names the loop
``bench/loops/<mode>.py`` that drives it, and each per-layer metric a
reader ``bench/metrics/<metric>.py``. A new cell, mix, loop or metric
is a new file and an entry in ``BENCHMARK.json``.

A run builds its inputs from ``--seed``, warms up every program the
window uses (set-up, reported as ``setup_s``), drives the timed path for
``--seconds``, reads the device's peak memory, then compares what the
window produced with the plain reference (``bench/reference.py``).
With ``--trace 1`` the window runs under the JAX profiler and the
per-layer metrics are reported instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), then ``checks``: each number compared, with its
limit. The checks are also the last lines of standard error.

Only a TPU is measured. ``--rehearse`` shrinks the sizes and allows any
platform (the CPU, for tests); it is never the default. ``--control``
runs the program with the sync period of the source views doubled, a
staler view than the configuration states, which must come out not
correct.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import log  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"     # fixed: the path is part of the key


def load_cell(name: str, spec: dict) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic) of cell ``name`` in ``spec``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def sized(obj: dict, rehearse: bool) -> dict:
    """``obj`` with its ``rehearsal`` overrides applied when rehearsing."""
    out = {k: v for k, v in obj.items() if k != "rehearsal"}
    if rehearse:
        for k, v in obj.get("rehearsal", {}).items():
            out[k] = {**out[k], **v} if isinstance(v, dict) else v
    return out


class Spans:
    """Host spans the harness records around its calls into the program
    (``--trace 1`` only): durations in memory, and a
    ``jax.profiler.TraceAnnotation`` so the device trace shows them."""

    def __init__(self, on: bool):
        self.on = on
        self.seconds: dict[str, list[float]] = {}

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        return self._span(name)

    @contextlib.contextmanager
    def _span(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation("bench." + name):
            t = time.perf_counter()
            try:
                yield
            finally:
                self.seconds.setdefault(name, []).append(
                    time.perf_counter() - t)

    def clear(self) -> None:
        self.seconds.clear()


class CompileCounter:
    """Counts traces, compiles and loads from the compilation cache: a
    warm-up runs until they stop, and the window should have none."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.count += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.count += 1


class Run:
    """What a loop (``bench/loops/<mode>.py``) gets, and fills in."""

    def __init__(self, args, cell, config, traffic, devices):
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.control = args.control
        self.cell, self.config, self.traffic = cell, config, traffic
        self.devices = devices
        self.spans = Spans(self.trace)
        self.compiles = CompileCounter()
        self.window_compiles = 0
        self.trace_dir: str | None = None
        # filled in by the loop
        self.setup_s = None
        self.end_to_end: dict[str, float] = {}
        self.work: dict[str, float] = {}
        self.checks: dict[str, tuple[float, float]] = {}
        self.attempted = 0
        self.failed = 0
        self.memory_peak = None

    @property
    def window_seconds(self) -> float:
        """The window's length: ``--seconds``, or with ``--trace 1`` at
        most the mix's ``trace_seconds`` (a dense trace grows fast)."""
        if self.trace:
            return min(self.seconds, self.traffic.get("trace_seconds",
                                                      self.seconds))
        return self.seconds

    def sync(self, group: dict) -> int:
        """The sync period the program runs with: ``group``'s
        ``sync_every``, doubled for the control (the reference always
        keeps the configuration's)."""
        return group["sync_every"] * (2 if self.control else 1)

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T_START

    @contextlib.contextmanager
    def window(self):
        """The measured window: no compile inside it; under the profiler
        with ``--trace 1``."""
        import jax
        self.spans.clear()
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            # device ops and the harness's spans; no Python call events,
            # which would slow the host path the serve cells measure
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        before = self.compiles.count
        try:
            with self.spans("window"):
                yield
        finally:
            self.window_compiles = self.compiles.count - before
            if self.trace:
                jax.profiler.stop_trace()
            self.memory_peak = memory_peak(self.devices)

    def check(self, name: str, value, limit) -> None:
        self.checks[name] = (value, limit)


def memory_peak(devices):
    """Peak bytes in use on the fullest device, where JAX reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``: a loop or a metric reader."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Reading:
    """What a per-layer reader reads: the reduced trace (None off the
    chip), the harness's host spans, the window's work counts and the
    device's peaks."""

    def __init__(self, trace, spans, work, peaks):
        self.trace, self.spans, self.work = trace, spans, work
        self.peaks = peaks

    def span_mean(self, name: str):
        xs = self.spans.get(name)
        return sum(xs) / len(xs) if xs else None

    def span_total(self, name: str):
        xs = self.spans.get(name)
        return sum(xs) if xs else None


def main(argv=None, spec: dict | None = None) -> int:
    """One run; ``spec`` stands in for ``BENCHMARK.json`` (tests)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="small sizes on any platform (CPU tests); never "
                         "a measurement")
    ap.add_argument("--control", action="store_true",
                    help="the program with the sync period doubled; must "
                         "come out not correct")
    args = ap.parse_args(argv)

    if spec is None:
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, config, traffic = load_cell(args.workload, spec)
    config = sized(config, args.rehearse)
    traffic = sized(traffic, args.rehearse)

    import jax
    if not args.rehearse:
        # every program the cell uses comes from the cache after the
        # first run in a checkout, however quickly it compiles
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devices = jax.devices()
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}")
    if dev.platform != "tpu" and not args.rehearse:
        log(f"no TPU: JAX found {dev.platform!r}; the benchmark measures "
            f"only the chip (--rehearse runs small sizes anywhere)")
        return 3
    chips = cell["chips"]
    if len(devices) < chips:
        log(f"{args.workload} needs {chips} devices; JAX found "
            f"{len(devices)}")
        return 3
    devices = devices[:chips]
    peaks = None
    if dev.platform == "tpu":
        from bench.trace import peaks as peak_table
        peaks = peak_table(dev.device_kind)

    run = Run(args, cell, config, traffic, devices)
    load("loops", traffic["mode"]).run(run)

    if args.trace:
        metrics, breakdown, dev_times = per_layer(spec, run, peaks)
    else:
        run.end_to_end["setup_s"] = run.setup_s
        metrics = {m["name"]: {"value": run.end_to_end[m["name"]],
                               "unit": m["unit"]}
                   for m in spec["end_to_end"]
                   if args.workload in m.get("workloads", [args.workload])}
        breakdown, dev_times = None, {}

    correct = all(v <= lim for v, lim in run.checks.values())
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": run.memory_peak, **dev_times}}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in run.checks.items()}
    log(f"compiles in the window: {run.window_compiles}; "
        f"setup {run.setup_s:.3f} s")
    print(json.dumps(out), flush=True)
    for k, (v, lim) in run.checks.items():
        log(f"check {k} = {v} (limit {lim})")
    return 0


def per_layer(spec, run, peaks):
    """Per-layer metrics of the cell, the breakdown and the device's
    busy and window seconds, from the traced window."""
    from bench.trace import find_xplane, from_xplane
    trace = None
    if run.trace_dir:
        path = find_xplane(run.trace_dir)
        if path is not None:
            trace = from_xplane(path)
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    reading = Reading(trace, run.spans.seconds, run.work, peaks)
    metrics = {}
    for m in spec["per_layer"]:
        if run.cell["name"] not in m.get("workloads", [run.cell["name"]]):
            continue
        value = load("metrics", m["name"]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev_times, breakdown = {}, None
    if trace is not None and trace.ops:
        dev_times = {"busy_s": trace.busy_s(), "window_s": trace.window_s()}
        breakdown = {"device_ops": trace.top_ops(10),
                     "idle_gaps": trace.idle_gaps(10)}
    return metrics, breakdown, dev_times


if __name__ == "__main__":
    sys.exit(main())
