"""The program's own spans and device scopes, read from a profiler trace.

``bench/trace.py`` reduces a trace to device ops by name and the
harness's spans (``bench.``). This module extends that reduction,
additively, with what the program records itself (``repro.trace``):

* host spans named ``cg.<what>``, matched by the name before any ``#``
  that ``TraceAnnotation`` arguments add, each with the line (the
  thread) it ran on, so spans nest;
* each device op's scope path: the name stack its HLO instruction
  carries (``jit(run)/while/body/cg.bind/gather``), which the trace
  keeps in each program's HLO on its host metadata plane (an ``XLA
  Ops`` event names only the instruction).

``ScopedTrace`` is a ``bench.trace.Trace``: every helper of the base
class reads it as it reads the base reduction of the same file, except
``idle_gaps``, which may now name the program's spans. It adds the
readers of the new per-layer metrics (``bench/metrics/``):

* ``span_total``: the time of a span name in the window;
* ``span_self``: that less, for each span, the union of the spans nested
  in it on the same line;
* ``scope_s``: device seconds of the ops under a scope, as the union of
  their intervals per device, averaged over the devices. A union,
  because a ``while`` op's event encloses the events of its body.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from pathlib import Path

from bench.trace import (ASYNC_LINE, OP_LINE, SPAN_PREFIX, Trace, _union,
                         short)

PROGRAM_PREFIX = "cg."
MODULE_LINE = "XLA Modules"   # a device plane's line of program runs


def span_name(name: str) -> str:
    """A host event's name without the ``#key=value#`` arguments."""
    return name.split("#", 1)[0]


def _message_classes():
    """The parts of the profiler's ``XSpace`` and of XLA's ``HloProto``
    that name an op's scope, declared field by field (the wire format
    of ``xplane.proto`` and ``hlo.proto``): the host metadata plane
    keeps each program's optimized HLO, whose instructions carry their
    name stack as ``metadata.op_name``."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="cg_trace.proto",
                                           package="cgtrace")
    i64, s, b, m = F.TYPE_INT64, F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE

    def msg(name, *fields):
        # (field, number, type, message type; "*" before it: repeated)
        d = f.message_type.add(name=name)
        for field, number, kind, of in fields:
            fd = d.field.add(name=field, number=number, type=kind,
                             label=F.LABEL_REPEATED if of and of[0] == "*"
                             else F.LABEL_OPTIONAL)
            if of:
                fd.type_name = ".cgtrace." + of.lstrip("*")

    msg("Stat", ("metadata_id", 1, i64, None), ("bytes_value", 6, b, None))
    msg("EventMetadata", ("name", 2, s, None), ("stats", 5, m, "*Stat"))
    msg("StatMetadata", ("name", 2, s, None))
    msg("EventEntry", ("key", 1, i64, None),
        ("value", 2, m, "EventMetadata"))
    msg("StatEntry", ("key", 1, i64, None), ("value", 2, m, "StatMetadata"))
    msg("Plane", ("name", 2, s, None), ("event_metadata", 4, m, "*EventEntry"),
        ("stat_metadata", 5, m, "*StatEntry"))
    msg("Space", ("planes", 1, m, "*Plane"))
    msg("OpMetadata", ("op_name", 2, s, None))
    msg("Instruction", ("name", 1, s, None), ("metadata", 7, m, "OpMetadata"))
    msg("Computation", ("instructions", 2, m, "*Instruction"))
    msg("Module", ("computations", 3, m, "*Computation"))
    msg("Hlo", ("module", 1, m, "Module"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return [message_factory.GetMessageClass(
        pool.FindMessageTypeByName("cgtrace." + n)) for n in ("Space", "Hlo")]


def op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """The name stack of every HLO instruction the trace's programs
    ran: ``{program: {instruction: op_name}}``, a program named as the
    device planes' ``XLA Modules`` events name it (``jit_run(8593…)``)."""
    space_cls, hlo_cls = _message_classes()
    space = space_cls.FromString(xspace)
    out: dict[str, dict[str, str]] = {}
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            for stat in entry.value.stats:
                if stat_names.get(stat.metadata_id) != "Hlo Proto":
                    continue
                hlo = hlo_cls.FromString(stat.bytes_value)
                out[entry.value.name] = {
                    ins.name: ins.metadata.op_name
                    for comp in hlo.module.computations
                    for ins in comp.instructions}
    return out


def _under(path: str, scope: str) -> bool:
    return scope in path.split("/")


@dataclass
class ScopedTrace(Trace):
    """A ``Trace`` with the line of each span (parallel to ``spans``)
    and the scope path of each op (parallel to ``ops``/``async_ops``)."""
    span_lines: list[str] = field(default_factory=list)
    op_scopes: dict[str, list[str]] = field(default_factory=dict)
    async_scopes: dict[str, list[str]] = field(default_factory=dict)

    # -- host spans -----------------------------------------------------
    def _clipped(self, name: str):
        """``(index, start, end)`` of the spans of ``name`` that overlap
        the window, clipped to it."""
        lo, hi = self.window()
        for i, (n, s, d) in enumerate(self.spans):
            if n == name and s + d > lo and s < hi:
                yield i, max(s, lo), min(s + d, hi)

    def span_count(self, name: str) -> int:
        """Spans of ``name`` that overlap the window."""
        return sum(1 for _ in self._clipped(name))

    def span_total(self, name: str) -> float:
        """Seconds of the spans of ``name``, inside the window."""
        return sum(e - s for _, s, e in self._clipped(name))

    def span_self(self, name: str) -> float:
        """``span_total`` less, in each span, the union of the other
        spans on its line that lie inside it."""
        lines = self.span_lines or [""] * len(self.spans)
        total = 0.0
        for i, s, e in self._clipped(name):
            _, ps, pd = self.spans[i]
            inner = [(cs, cd) for j, (_, cs, cd) in enumerate(self.spans)
                     if j != i and lines[j] == lines[i]
                     and cs >= ps and cs + cd <= ps + pd]
            total += (e - s) - sum(b - a for a, b in _union(inner, s, e))
        return total

    # -- device scopes ----------------------------------------------------
    def scope_s(self, scope: str, asynchronous: bool = False) -> float:
        """Device seconds under ``scope``: per device, the union of the
        intervals of its ops (with ``asynchronous``, of both op lines)
        whose scope path holds ``scope``, inside the window; averaged
        over the devices that ran any op."""
        lo, hi = self.window()
        sources = [(self.ops, self.op_scopes)]
        if asynchronous:
            sources.append((self.async_ops, self.async_scopes))
        per: dict[str, list[tuple[float, float]]] = {}
        for ops, scopes in sources:
            for dev, evs in ops.items():
                if not evs:
                    continue
                paths = scopes.get(dev, [])
                sel = per.setdefault(dev, [])
                sel += [(s, d) for (_, s, d), p in zip(evs, paths)
                        if _under(p, scope)]
        if not per:
            return 0.0
        return sum(sum(e - s for s, e in _union(iv, lo, hi))
                   for iv in per.values()) / len(per)

    # -- storage ----------------------------------------------------------
    def to_json(self) -> dict:
        return {**super().to_json(), "span_lines": list(self.span_lines),
                "op_scopes": self.op_scopes,
                "async_scopes": self.async_scopes}

    @classmethod
    def from_json(cls, obj: dict) -> "ScopedTrace":
        base = Trace.from_json(obj)
        return cls(ops=base.ops, async_ops=base.async_ops, spans=base.spans,
                   span_lines=list(obj.get("span_lines", [])),
                   op_scopes=obj.get("op_scopes", {}),
                   async_scopes=obj.get("async_scopes", {}))


def from_xplane(path) -> ScopedTrace:
    """What ``bench.trace.from_xplane`` keeps of one ``.xplane.pb``,
    with the program's ``cg.`` spans, the line of every span and the
    scope path of every device op: the ``op_name`` of its instruction in
    the program that ran it (the ``XLA Modules`` event it falls in)."""
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    names = op_names(raw)
    data = ProfileData.from_serialized_xspace(raw)
    tr = ScopedTrace()
    lines = {OP_LINE: (tr.ops, tr.op_scopes),
             ASYNC_LINE: (tr.async_ops, tr.async_scopes)}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            runs = sorted((ev.start_ns, ev.end_ns, ev.name)
                          for line in plane.lines if line.name == MODULE_LINE
                          for ev in line.events)
            starts = [r[0] for r in runs]
            for line in plane.lines:
                into = lines.get(line.name)
                if into is None:
                    continue
                evs = list(line.events)
                into[0][plane.name] = [
                    (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    for ev in evs]
                paths = []
                for ev in evs:
                    k = bisect.bisect_right(starts, ev.start_ns) - 1
                    program = runs[k][2] if k >= 0 else ""
                    op = short(ev.name).lstrip("%")
                    paths.append(names.get(program, {}).get(op, ""))
                into[1][plane.name] = paths
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = span_name(ev.name)
                    if name.startswith((SPAN_PREFIX, PROGRAM_PREFIX)):
                        tr.spans.append((name, ev.start_ns * 1e-9,
                                         ev.duration_ns * 1e-9))
                        tr.span_lines.append(f"{plane.name}/{line.name}")
    return tr


# -- what the new per-layer readers share -----------------------------------

def scoped(r) -> ScopedTrace | None:
    """The reading's trace where it holds the program's spans and
    scopes; None where the reduction did not keep them."""
    return r.trace if isinstance(r.trace, ScopedTrace) else None


def per_tick_ms(r, name: str, self_time: bool = True) -> float | None:
    """Host milliseconds of span ``name`` per engine tick (its self
    time, or its total); None where the trace holds no ``cg.step``."""
    tr = scoped(r)
    if tr is None or not tr.span_count(PROGRAM_PREFIX + "step"):
        return None
    t = tr.span_self(name) if self_time else tr.span_total(name)
    return t / r.work["ticks"] * 1e3


def scope_us(r, scope: str, per: str, asynchronous: bool = False
             ) -> float | None:
    """Device microseconds under ``scope`` per unit of work ``per``
    (slots, batches); None where no op of the trace ran under it."""
    tr = scoped(r)
    if tr is None or not tr.ops:
        return None
    t = tr.scope_s(scope, asynchronous)
    if not t:
        return None
    return t / r.work[per] * 1e6
