"""Profiler trace: capture inside the window, and the reduction to numbers.

The reduction reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``
and nothing else.  What it takes from a trace:

- the traced interval: from the first to the last ``bench:traced``
  annotation, which the capture writes right after ``start_trace``
  returns and right before it calls ``stop_trace`` (host plane, the
  trace's own clock; two short marks, because an annotation must end on
  the thread it began on);
- per device plane (``/device:TPU:<i>``), the events of its op line
  (``XLA Ops``), clipped to that interval: busy seconds are the length of
  the union of their intervals, averaged over the device planes;
- device seconds by operation, as SELF time: the op line nests (a
  ``while`` holds the operations of its body), so an operation's seconds
  are its duration less what its children cover; names are cut to the
  instruction's name and opcode (``%fusion.132 fusion``);
- the idle gaps of device 0, named by the ``bench:<span>`` annotation
  that covers most of each gap.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .obs import ANNOTATION_PREFIX, Obs

TRACED = ANNOTATION_PREFIX + "traced"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
NO_ANNOTATION = "(outside every benchmark span)"

Interval = Tuple[float, float]


# --------------------------------------------------------------------- #
# interval arithmetic
# --------------------------------------------------------------------- #


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def length(merged: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merged)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What ``[lo, hi]`` holds besides the disjoint sorted ``merged``."""
    out, at = [], lo
    for a, b in merged:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def self_seconds(events: Sequence[Tuple[str, float, float]]) -> List[float]:
    """Per event of one line, its duration less what the events nested
    directly inside it cover."""
    out = [b - a for _, a, b in events]
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    stack: List[int] = []
    for i in order:
        _, a, b = events[i]
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            out[stack[-1]] -= min(b, events[stack[-1]][2]) - a
        stack.append(i)
    return out


_HLO = re.compile(r"^(%[^ ]+) = .*?(?<![\w.])([a-z][a-z\-]*)\(")


def short_name(name: str, limit: int = 80) -> str:
    """``%fusion.132 = s32[...] fusion(...)`` -> ``%fusion.132 fusion``."""
    m = _HLO.match(name)
    return (f"{m.group(1)} {m.group(2)}" if m else name)[:limit]


# --------------------------------------------------------------------- #
# reduction
# --------------------------------------------------------------------- #


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over the device planes
    n_devices: int
    #: per device plane: [(name, start_s, end_s)] clipped to the window
    device_events: List[List[Tuple[str, float, float]]] = field(default_factory=list)
    #: benchmark annotations on the host plane: name -> [(start_s, end_s)]
    annotations: Dict[str, List[Interval]] = field(default_factory=dict)
    device_ops: List[List[object]] = field(default_factory=list)
    idle_gaps: List[List[object]] = field(default_factory=list)

    def seconds_of(self, pattern: str) -> Tuple[float, int]:
        """(summed device seconds, event count) of the operations whose
        name matches ``pattern``, over all device planes."""
        rx = re.compile(pattern)
        total, k = 0.0, 0
        for events in self.device_events:
            for name, a, b in events:
                if rx.search(name):
                    total += b - a
                    k += 1
        return total, k

    def spans_inside(self, name: str) -> int:
        """How many ``bench:<name>`` annotations lie wholly inside the
        traced interval."""
        return len(self.annotations.get(ANNOTATION_PREFIX + name, ()))


def _planes(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path).planes


def summarize(path: str, top: int = 10) -> TraceSummary:
    """Reduce one ``.xplane.pb`` (module docstring)."""
    device_lines, notes = [], {}
    for plane in _planes(path):
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OP_LINE:
                    device_lines.append(
                        [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9) for e in line.events]
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        notes.setdefault(e.name, []).append(
                            (e.start_ns * 1e-9, e.end_ns * 1e-9)
                        )
    if len(notes.get(TRACED, ())) < 2:
        raise ValueError(f"{path}: no pair of {TRACED!r} marks: not a benchmark trace")
    lo = min(a for a, _ in notes[TRACED])
    hi = max(b for _, b in notes[TRACED])
    window = hi - lo

    events = [
        [(n, max(a, lo), min(b, hi)) for n, a, b in line if b > lo and a < hi]
        for line in device_lines
    ]
    busy = [length(merge((a, b) for _, a, b in ev)) for ev in events]
    inside = {
        name: [(a - lo, b - lo) for a, b in spans if a >= lo and b <= hi]
        for name, spans in notes.items()
        if name != TRACED
    }

    by_op: Dict[str, float] = {}
    for ev in events:
        for (name, _, _), own in zip(ev, self_seconds(ev)):
            key = short_name(name)
            by_op[key] = by_op.get(key, 0.0) + own
    n_dev = max(1, len(events))
    device_ops = [
        [name, s / n_dev]
        for name, s in sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    ]

    by_gap: Dict[str, float] = {}
    if events:
        spans = [
            (name, (a, b))
            for name, lst in notes.items()
            if name != TRACED
            for a, b in clip(lst, lo, hi)
        ]
        for gap in gaps(merge((a, b) for _, a, b in events[0]), lo, hi):
            best, best_s = NO_ANNOTATION, 0.0
            for name, span in spans:
                s = overlap(gap, span)
                if s > best_s:
                    best, best_s = name, s
            by_gap[best] = by_gap.get(best, 0.0) + (gap[1] - gap[0])
    idle_gaps = [
        [name, s] for name, s in sorted(by_gap.items(), key=lambda kv: -kv[1])[:top]
    ]

    return TraceSummary(
        window_s=window,
        busy_s=sum(busy) / n_dev if events else 0.0,
        n_devices=len(events),
        device_events=[[(n, a - lo, b - lo) for n, a, b in ev] for ev in events],
        annotations=inside,
        device_ops=device_ops,
        idle_gaps=idle_gaps,
    )


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


# --------------------------------------------------------------------- #
# capture
# --------------------------------------------------------------------- #


class TraceCapture:
    """Trace ``length_s`` seconds of the window, from the first tick at
    or after ``start_after_s`` to the first tick ``length_s`` later, so
    that a closed loop's traced interval holds whole units of work."""

    def __init__(self, obs: Obs, trace_dir: str, start_after_s: float, length_s: float):
        self.obs = obs
        self.dir = trace_dir
        self.start_after_s = start_after_s
        self.length_s = length_s
        self.state = "waiting"
        self._t_on = 0.0
        self.overhead_s = 0.0  # host seconds spent starting and stopping
        obs.on_tick = self.on_tick

    def on_tick(self, now: float) -> None:
        if self.state == "waiting":
            if self.obs.window_t0 is not None and now - self.obs.window_t0 >= self.start_after_s:
                self._start()
        elif self.state == "on" and now - self._t_on >= self.length_s:
            self.stop()

    def _start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the served cell runs many Python threads
        options.host_tracer_level = 2
        t0 = time.perf_counter()
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.obs.annotate = jax.profiler.TraceAnnotation
        with jax.profiler.TraceAnnotation(TRACED):
            pass
        self._t_on = time.perf_counter()
        self.overhead_s += self._t_on - t0
        self.state = "on"

    def stop(self) -> None:
        """Stop the profiler (if it runs) and reduce the trace."""
        if self.state != "on":
            return
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(TRACED):
            pass
        self.obs.annotate = None
        jax.profiler.stop_trace()
        self.overhead_s += time.perf_counter() - t0
        self.state = "done"
        path = newest_xplane(self.dir)
        if path is None:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under {self.dir}")
        self.obs.facts["xplane"] = path
        self.obs.trace = summarize(path)
