"""Reduce a profiler trace to device time, per-op time and idle gaps.

A run with ``--trace 1`` records the measured window with the JAX
profiler. ``from_xplane`` reads the ``.xplane.pb`` it writes with
nothing but JAX and keeps two things:

* device operations: every event on a device plane's op line
  (``XLA Ops`` on a TPU), as ``(name, start_s, duration_s)`` per device,
  and those of its ``Async XLA Ops`` line (collectives, copies) apart;
* host spans: the harness's own ``jax.profiler.TraceAnnotation`` spans
  (names starting ``bench.``), on the same clock.

``Trace`` then gives busy time (the union of op intervals inside the
window), the time of the ops a metric names, the ops that took most
time, and the idle gaps with the host span that was open in each.
Per-layer metric readers (``bench/metrics/<name>.py``) compute from it
with the helpers at the end of this file, which hold the kernels'
names in one place. Peaks come from ``peaks.json``, keyed by device kind; a kind that is
not in the table is an error.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")
OP_LINE = "XLA Ops"          # the device plane's line of executed ops
ASYNC_LINE = "Async XLA Ops"  # asynchronous ops (collectives, copies)
SPAN_PREFIX = "bench."


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; KeyError if unknown."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add the device with its source")
    return table[device_kind]


def short(name: str) -> str:
    """An op's name without its HLO text: ``%fusion.74``."""
    return name.split(" = ", 1)[0]


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(s + d, hi)) for s, d in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Trace:
    """Device ops per device and host spans, in seconds on one clock."""
    ops: dict[str, list[tuple[str, float, float]]] = field(default_factory=dict)
    async_ops: dict[str, list[tuple[str, float, float]]] = field(
        default_factory=dict)
    spans: list[tuple[str, float, float]] = field(default_factory=list)

    # -- the window ----------------------------------------------------
    def window(self) -> tuple[float, float]:
        """The ``bench.window`` span, or the extent of everything."""
        for name, s, d in self.spans:
            if name == SPAN_PREFIX + "window":
                return s, s + d
        ends = [(s, s + d) for evs in self.ops.values() for _, s, d in evs]
        ends += [(s, s + d) for _, s, d in self.spans]
        if not ends:
            return 0.0, 0.0
        return min(s for s, _ in ends), max(e for _, e in ends)

    def window_s(self) -> float:
        lo, hi = self.window()
        return hi - lo

    # -- device time ----------------------------------------------------
    def busy_s(self) -> float:
        """Seconds in which some op ran, inside the window, averaged
        over the devices that ran any op."""
        lo, hi = self.window()
        per = [sum(e - s for s, e in _union([(s, d) for _, s, d in evs],
                                            lo, hi))
               for evs in self.ops.values() if evs]
        return sum(per) / len(per) if per else 0.0

    def op_s(self, pattern: str, asynchronous: bool = False) -> float:
        """Seconds of the ops whose name matches the regular expression
        ``pattern`` (searched), inside the window, averaged over the
        devices that ran any op; of the asynchronous ops' line if
        ``asynchronous``."""
        lo, hi = self.window()
        rx = re.compile(pattern)
        devs = [evs for evs in (self.async_ops if asynchronous
                                else self.ops).values() if evs]
        tot = sum(min(s + d, hi) - max(s, lo)
                  for evs in devs for name, s, d in evs
                  if rx.search(name) and s + d > lo and s < hi)
        return tot / len(devs) if devs else 0.0

    def op_count(self, pattern: str, asynchronous: bool = False) -> float:
        """Occurrences of the matching ops in the window, per device."""
        lo, hi = self.window()
        rx = re.compile(pattern)
        devs = [evs for evs in (self.async_ops if asynchronous
                                else self.ops).values() if evs]
        n = sum(1 for evs in devs for name, s, d in evs
                if rx.search(name) and s + d > lo and s < hi)
        return n / len(devs) if devs else 0.0

    def top_ops(self, k: int = 10) -> list[list]:
        """The ``k`` ops (short names) with the most time, per device."""
        lo, hi = self.window()
        devs = [evs for evs in self.ops.values() if evs]
        tot: dict[str, float] = {}
        for evs in devs:
            for name, s, d in evs:
                if s + d > lo and s < hi:
                    tot[short(name)] = (tot.get(short(name), 0.0)
                                        + min(s + d, hi) - max(s, lo))
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, t / len(devs)] for name, t in top]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """Idle time of the first device by the innermost host span open
        at each gap's midpoint (``host`` where none is), longest first."""
        lo, hi = self.window()
        devs = [evs for evs in self.ops.values() if evs]
        if not devs:
            return []
        busy = _union([(s, d) for _, s, d in devs[0]], lo, hi)
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        spans = [(n, s, d) for n, s, d in self.spans
                 if n != SPAN_PREFIX + "window"]
        tot: dict[str, float] = {}
        for s, e in gaps:
            mid = 0.5 * (s + e)
            open_ = [(d, n) for n, ss, d in spans if ss <= mid < ss + d]
            name = min(open_)[1] if open_ else "host"
            tot[name] = tot.get(name, 0.0) + (e - s)
        return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    # -- storage (small recorded traces for tests) ------------------------
    def to_json(self) -> dict:
        return {"ops": {d: [list(e) for e in evs] for d, evs in self.ops.items()},
                "async_ops": {d: [list(e) for e in evs]
                              for d, evs in self.async_ops.items()},
                "spans": [list(s) for s in self.spans]}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls(ops={d: [tuple(e) for e in evs]
                        for d, evs in obj["ops"].items()},
                   async_ops={d: [tuple(e) for e in evs]
                              for d, evs in obj.get("async_ops", {}).items()},
                   spans=[tuple(s) for s in obj["spans"]])


def find_xplane(trace_dir) -> Path | None:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None


def from_xplane(path) -> Trace:
    """Device ops (the ``XLA Ops`` and ``Async XLA Ops`` lines of each
    ``/device:`` plane) and the harness's host spans from one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                into = {OP_LINE: tr.ops, ASYNC_LINE: tr.async_ops}.get(line.name)
                if into is not None:
                    into[plane.name] = [
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans += [(ev.name, ev.start_ns * 1e-9,
                              ev.duration_ns * 1e-9)
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX)]
    return tr


# -- what the per-layer readers share -------------------------------------

# The block engine's Pallas kernel. Its ``pallas_call`` carries no
# ``name=``, so the custom call takes the wrapping function's name.
PORC_KERNEL = (r'^%porc_multisource_scan[.0-9]* = '
               r'.*custom_call_target="tpu_custom_call"')


def idle_pct(tr: Trace | None) -> float | None:
    """Share of the traced window in which no op ran on the device (%);
    None without a device trace."""
    if tr is None or not tr.ops:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s())


def kernel_s(tr: Trace | None, pattern: str = PORC_KERNEL) -> float | None:
    """Device seconds of the kernel's events in the window; None where
    the trace holds none."""
    if tr is None or not tr.op_count(pattern):
        return None
    return tr.op_s(pattern)
