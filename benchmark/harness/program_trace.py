"""The program's own names, read after the window: the fifth kind of reader.

The other four kinds read what the BENCHMARK wrote (its spans, counters,
the ``bench:`` annotations, trace events by HLO text).  This file reads
what the PROGRAM wrote, by three roads, and every function returns
``None`` where the program under test has nothing to read (the parent
commit of the PR that added a name has none of them):

(a) the ``.xplane.pb`` of a traced run (``obs.facts["xplane"]``):

    - ``uigc:<phase>`` annotations on the host planes, which
      ``uigc_tpu/telemetry/profile.py`` writes around a collector wake
      (``uigc:wake``) and each of its phases, with the wake's ordinal as
      the ``wake`` stat;
    - the named scope of every device operation.  The wake program wraps
      its phases in ``jax.named_scope`` (``uigc.wake/closure``, ...,
      ``ops/pallas_decremental.py``); XLA keeps the scope path as the
      instruction's ``op_name``, and the TPU profiler stores it as the
      ``tf_op`` stat of the operation's *event metadata* in the device
      plane.  ``jax.profiler.ProfileData`` shows an event's own stats
      only, not its metadata's, so the metadata tables are read here
      from the protobuf wire format directly (five message types, forty
      lines; no schema package is imported), and an event is joined to
      its metadata by the ``metadata_id`` it carries.

(b) ``obs.facts["program_wakes"]``: whole ``WakeProfiler`` records, as
    ``drivers/served.py`` polls them during the window;

(c) ``uigc_tpu.ops.pallas_decremental.live_tracers()``: the tracers alive
    in this process, whose ``wake_stats()`` reads back the sweep counters
    that every wake program leaves on the device.

Device seconds are summed as SELF time (the op line nests: a ``while``
holds the operations of its body), inside the traced interval, over the
events of the wake module (``jit_wake_fn``); "per wake" divides by the
``bench:wake`` spans that lie wholly inside the interval, as
``layers/kernel_ms.py`` does.
"""

from __future__ import annotations

import bisect
import re
import struct
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from .stats import percentile
from .trace import (
    DEVICE_PLANE, OP_LINE, TRACED, Interval, clip, gaps, length, merge, self_seconds,
)

#: prefix of the annotations the program's wake profiler writes
PROGRAM_PREFIX = "uigc:"
#: root of the wake program's named scopes
WAKE_SCOPE = "uigc.wake"
#: the jitted wake's module, as the device plane's module line names it
WAKE_MODULE = re.compile(r"^jit_wake_fn\(")
MODULE_LINE = "XLA Modules"
#: the propagate kernel's events, as ``layers/kernel_ms.py`` finds them
KERNEL_EVENT = re.compile(r" custom-call\(")

# --------------------------------------------------------------------- #
# protobuf wire format, as much of it as an XSpace needs
# --------------------------------------------------------------------- #
# XSpace{1: planes}; XPlane{2: name, 3: lines, 4: event_metadata (map),
# 5: stat_metadata (map), 6: stats}; XLine{2: name, 3: timestamp_ns,
# 4: events}; XEvent{1: metadata_id, 2: offset_ps, 3: duration_ps,
# 4: stats}; XEventMetadata{2: name, 5: stats}; XStatMetadata{2: name};
# XStat{1: metadata_id, 2: double, 3: uint64, 4: int64, 5: str, 6: bytes,
# 7: ref (a stat_metadata id whose name is the value)}.


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _fields(buf: bytes, at: int, end: int) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a
    varint, raw bytes for a fixed width, ``(start, end)`` for a
    length-delimited field (nothing is copied until somebody asks)."""
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = (at, at + size), at + size
        elif kind == 1:
            value, at = buf[at:at + 8], at + 8
        elif kind == 5:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {kind} at byte {at}")
        yield key >> 3, kind, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stat(buf: bytes, span, stat_names: Dict[int, str]) -> Tuple[str, object]:
    name, value = "", None
    for num, kind, v in _fields(buf, *span):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num in (5, 6):
            value = _text(buf, v)
        elif num == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf: bytes, span) -> Tuple[int, Optional[Tuple[int, int]]]:
    key, value = 0, None
    for num, _, v in _fields(buf, *span):
        if num == 1:
            key = _signed(v)
        elif num == 2:
            value = v
    return key, value


class Plane:
    """One XPlane: its lines as ``[(metadata_id, start_s, end_s, stats)]``
    and its event metadata as ``{id: (name, {stat: value})}``."""

    def __init__(self, buf: bytes, span, want_events) -> None:
        self.name = ""
        line_spans, meta_spans, stat_spans = [], [], []
        for num, _, v in _fields(buf, *span):
            if num == 2:
                self.name = _text(buf, v)
            elif num == 3:
                line_spans.append(v)
            elif num == 4:
                meta_spans.append(v)
            elif num == 5:
                stat_spans.append(v)
        self.lines: Dict[str, List[Tuple[int, float, float, Dict[str, object]]]] = {}
        self.metadata: Dict[int, Tuple[str, Dict[str, object]]] = {}
        if not want_events(self.name):
            return
        stat_names: Dict[int, str] = {}
        for sp in stat_spans:
            key, value = _map_entry(buf, sp)
            for num, _, v in _fields(buf, *value):
                if num == 2:
                    stat_names[key] = _text(buf, v)
        for sp in meta_spans:
            key, value = _map_entry(buf, sp)
            name, stats = "", {}
            for num, _, v in _fields(buf, *value):
                if num == 2:
                    name = _text(buf, v)
                elif num == 5:
                    k, val = _stat(buf, v, stat_names)
                    stats[k] = val
            self.metadata[key] = (name, stats)
        for sp in line_spans:
            name, t0_ns, event_spans = "", 0, []
            for num, _, v in _fields(buf, *sp):
                if num == 2:
                    name = _text(buf, v)
                elif num == 3:
                    t0_ns = _signed(v)
                elif num == 4:
                    event_spans.append(v)
            events = self.lines.setdefault(name, [])
            for ev in event_spans:
                mid = offset_ps = duration_ps = 0
                stats: Dict[str, object] = {}
                for num, _, v in _fields(buf, *ev):
                    if num == 1:
                        mid = _signed(v)
                    elif num == 2:
                        offset_ps = _signed(v)
                    elif num == 3:
                        duration_ps = _signed(v)
                    elif num == 4:
                        k, val = _stat(buf, v, stat_names)
                        stats[k] = val
                start = t0_ns * 1e-9 + offset_ps * 1e-12
                events.append((mid, start, start + duration_ps * 1e-12, stats))

    def event_name(self, mid: int) -> str:
        return self.metadata.get(mid, ("", {}))[0]


def read_planes(path: str, want_events=lambda name: True) -> List[Plane]:
    with open(path, "rb") as fh:
        buf = fh.read()
    return [Plane(buf, v, want_events) for num, _, v in _fields(buf, 0, len(buf)) if num == 1]


# --------------------------------------------------------------------- #
# what a traced run's trace holds of the program's names
# --------------------------------------------------------------------- #


class DeviceOp:
    __slots__ = ("name", "scope", "start", "end", "own_s")

    def __init__(self, name: str, scope: str, start: float, end: float):
        self.name, self.scope, self.start, self.end = name, scope, start, end
        self.own_s = end - start

    @property
    def is_kernel(self) -> bool:
        return bool(KERNEL_EVENT.search(self.name))

    def under(self, component: str) -> bool:
        """Is ``component`` (``jump``, or a path like ``uigc.wake/closure``)
        on this operation's scope path?"""
        return f"/{component}/" in f"/{self.scope}/"


class ProgramTrace:
    """The traced interval, the ``uigc:`` annotations inside it, and the
    wake module's device operations with their scopes."""

    def __init__(self, path: str) -> None:
        planes = read_planes(
            path, lambda name: bool(DEVICE_PLANE.match(name)) or name.startswith("/host:")
        )
        marks: List[Interval] = []
        #: ``uigc:<name>`` -> [(start_s, end_s, wake ordinal or None)]
        self.annotations: Dict[str, List[Tuple[float, float, Optional[int]]]] = {}
        for plane in planes:
            if not plane.name.startswith("/host:"):
                continue
            for events in plane.lines.values():
                for mid, a, b, stats in events:
                    name = plane.event_name(mid)
                    if name == TRACED:
                        marks.append((a, b))
                    elif name.startswith(PROGRAM_PREFIX):
                        wake = stats.get("wake")
                        self.annotations.setdefault(name, []).append(
                            (a, b, int(wake) if wake is not None else None)
                        )
        if len(marks) < 2:
            raise ValueError(f"{path}: no pair of {TRACED!r} marks: not a benchmark trace")
        self.lo = min(a for a, _ in marks)
        self.hi = max(b for _, b in marks)
        for name, spans in self.annotations.items():
            self.annotations[name] = sorted(
                s for s in spans if s[0] >= self.lo and s[1] <= self.hi
            )
        #: per device plane: every op-line interval, clipped (for busy time)
        self.busy: List[List[Interval]] = []
        #: per device plane: the wake module's operations, clipped
        self.wake_ops: List[List[DeviceOp]] = []
        for plane in planes:
            if not DEVICE_PLANE.match(plane.name):
                continue
            ops = plane.lines.get(OP_LINE, [])
            self.busy.append(merge(clip([(a, b) for _, a, b, _ in ops], self.lo, self.hi)))
            modules = sorted(
                (a, b) for mid, a, b, _ in plane.lines.get(MODULE_LINE, [])
                if WAKE_MODULE.match(plane.event_name(mid))
            )
            starts = [a for a, _ in modules]
            inside = []
            for mid, a, b, _ in ops:
                at = bisect.bisect_right(starts, a) - 1  # the module begun last before it
                if b <= self.lo or a >= self.hi or at < 0 or b > modules[at][1]:
                    continue
                name, stats = plane.metadata.get(mid, ("", {}))
                inside.append(DeviceOp(
                    name, str(stats.get("tf_op") or ""), max(a, self.lo), min(b, self.hi)
                ))
            own = self_seconds([(op.name, op.start, op.end) for op in inside])
            for op, s in zip(inside, own):
                op.own_s = s
            self.wake_ops.append(inside)

    # -- device seconds by scope --------------------------------------- #

    @property
    def has_scopes(self) -> bool:
        return any(op.under(WAKE_SCOPE) for ops in self.wake_ops for op in ops)

    def seconds(self, pick) -> float:
        """Self seconds of the wake module's operations that ``pick``
        takes, mean over the device planes."""
        if not self.wake_ops:
            return 0.0
        return sum(op.own_s for ops in self.wake_ops for op in ops if pick(op)) / len(self.wake_ops)

    # -- the device against the collector's annotations ---------------- #

    def busy_inside(self, span: Interval) -> float:
        """Device-0 busy seconds inside ``span``."""
        return length(clip(self.busy[0], *span)) if self.busy else 0.0

    def idle_share_inside(self, name: str) -> Optional[float]:
        """Of device 0's idle seconds in the traced interval, the share
        that lies inside the union of the ``name`` annotations."""
        if not self.busy:
            return None
        idle = gaps(self.busy[0], self.lo, self.hi)
        total = length(idle)
        if total <= 0:
            return None
        covered = merge((a, b) for a, b, _ in self.annotations.get(name, ()))
        inside = sum(length(clip(covered, a, b)) for a, b in idle)
        return inside / total


_parsed: Dict[str, ProgramTrace] = {}


def program_trace(obs) -> Optional[ProgramTrace]:
    """The traced run's trace (parsed once per file), or ``None``."""
    path = obs.facts.get("xplane")
    if not path:
        return None
    if path not in _parsed:
        _parsed[path] = ProgramTrace(path)
    return _parsed[path]


# --------------------------------------------------------------------- #
# the readers' one-liners
# --------------------------------------------------------------------- #

KINDS = {
    # the three kinds partition the scoped operations of the wake module
    "kernel": lambda op: op.is_kernel,
    "jump": lambda op: not op.is_kernel and op.under("jump"),
    "frontier": lambda op: not op.is_kernel and not op.under("jump") and op.under(WAKE_SCOPE),
    # by phase of the wake instead, kernel calls included
    "closure": lambda op: op.under(WAKE_SCOPE + "/closure"),
    "repair": lambda op: op.under(WAKE_SCOPE + "/repair"),
    "all": lambda op: True,
}


def scope_ms_per_wake(obs, kind: str) -> Optional[float]:
    """Device milliseconds per wake of the wake module's operations of
    ``kind`` (``KINDS``); ``None`` without a trace, without whole wakes
    in it, or where the program names no scopes."""
    trace = program_trace(obs)
    if trace is None or obs.trace is None or not trace.has_scopes:
        return None
    wakes = obs.trace.spans_inside("wake")
    if not wakes:
        return None
    return trace.seconds(KINDS[kind]) * 1e3 / wakes


def coverage(obs) -> Optional[float]:
    """kernel + jump + frontier over all of the wake module's device
    seconds: what is left carries no ``uigc.wake`` scope."""
    trace = program_trace(obs)
    if trace is None or not trace.has_scopes:
        return None
    total = trace.seconds(KINDS["all"])
    named = sum(trace.seconds(KINDS[k]) for k in ("kernel", "jump", "frontier"))
    return named / total if total > 0 else None


def device_wakes(obs) -> List[dict]:
    """Road (b): the window's ``WakeProfiler`` records that called the device."""
    return [r for r in obs.facts.get("program_wakes") or () if r.get("device_s", 0) > 0]


def phase_ms(obs, phase: str) -> Optional[float]:
    """Median of one ``WakeProfiler`` phase over the window's wakes that
    called the device; ``None`` where the records have no such phase."""
    values = [r["phases"][phase] * 1e3 for r in device_wakes(obs) if phase in r.get("phases", {})]
    return percentile(values, 50) if values else None


def window_wake_stats(obs) -> Optional[List[dict]]:
    """Road (c): the sweep counters of the window's wakes, from the one
    tracer a 10M cell's driver holds (as many wakes as the window has
    ``wake`` spans, the last ones the tracer ran)."""
    try:
        from uigc_tpu.ops import pallas_decremental
    except ImportError:
        return None
    live = getattr(pallas_decremental, "live_tracers", None)
    n = len(obs.span_ms("wake"))
    if live is None or not n:
        return None
    tracers = [t for t in live() if len(t.wake_stats(1))]
    if len(tracers) != 1:
        return None
    return tracers[0].wake_stats(n)


def sweeps_per_wake(obs, key: str) -> Optional[float]:
    stats = window_wake_stats(obs)
    return percentile([w[key] for w in stats], 50) if stats else None


def annotation_busy_ms(obs, name: str) -> Optional[float]:
    """Median over the ``name`` annotations of the device's busy
    milliseconds inside each."""
    trace = program_trace(obs)
    spans: Sequence = trace.annotations.get(name, ()) if trace is not None else ()
    if not spans or not trace.busy:
        return None
    return percentile([trace.busy_inside((a, b)) * 1e3 for a, b, _ in spans], 50)
