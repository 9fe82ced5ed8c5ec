"""Collector wake profiler: per-phase, device-vs-host wake attribution.

One Bookkeeper wake (``engines/crgc/collector.py collect()``) is the
unit of collection latency, but a single wall-clock number cannot say
*where* a slow wake went.  This profiler breaks every wake into the
pipeline's named phases:

- ``ingest``     draining the mutator entry queue + packed rows
- ``fold``       merging the drained batch into the shadow graph
- ``trace``      the liveness trace: what of the mark computation the
                 phases below do not cover (all of it on host backends)
- ``layout``     kernel-layout maintenance of the device backends
                 (``apply_log`` of the pair log, or a ``rebuild``)
- ``upload``     host -> device: layout deltas, suspect id words, flags
                 and receive counts
- ``device``     dispatch of the wake program until its result is ready
- ``readback``   device -> host: the verdict words (slots in use and
                 unmarked, a bit a slot) and the count of marks
- ``sweep``      kill decisions + slot frees (its record carries the
                 ``kills`` and ``freed`` counts, ``kill_uids``: the
                 foreign uids handed to the engine's sink to stop, and
                 ``sweep_edge_slots``: the edge slots examined to find
                 the edges that hang on the dead, the edge capacity
                 where the sweep scanned)
- ``broadcast``  delta-graph serialization + peer broadcast (multi-node)

A wake's record also carries ``fold_rows`` (packed rows folded),
``uids_interned`` (uids the fold interned, local or foreign) and
``upload_bytes`` (what the device call's ``device_put``s were handed):
0 where a backend has nothing to count.

Phases are exclusive: a nested phase pauses the enclosing one, so the
phases of a wake add up to its ``wall_s`` less the few statements
between brackets.  The collector hands the active wake to its backend
(``profile_wake``), which brackets its own steps; that handle is the one
road by which a backend reaches the profiler.

Every bracket is also written onto a profiler trace's clock: while a
``jax.profiler`` trace runs, a wake shows as a ``uigc:wake`` annotation
on the collector's thread and its phases as ``uigc:<phase>`` inside it,
all carrying the wake's ordinal (``wake=<n>``), so the device's op line
can be read against what the collector was doing.  Annotations exist
only where a profiler is attached; with no trace running one costs well
under a microsecond.

``device_s`` is the host clock around the whole device CALL (the
``tpu.device_trace`` event: layout, upload, run and readback together):
the profiler registers as a recorder listener and credits the durations
committed on the wake's thread to the active wake.

Dumps are BENCH-style JSON (one ``wake_profile`` document per node),
matching the ``tools/*_bench.py`` artifact convention.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..utils import events

PHASES = ("ingest", "fold", "trace", "layout", "upload", "device",
          "readback", "sweep", "broadcast")

#: prefix of every annotation the profiler writes into a trace
ANNOTATION_PREFIX = "uigc:"
WAKE_ANNOTATION = ANNOTATION_PREFIX + "wake"


def trace_annotation(name: str, **args: Any):
    """The default ``annotate`` hook: a ``jax.profiler.TraceAnnotation``
    (jax is imported here, as everywhere in the package, on first use)."""
    import jax

    return jax.profiler.TraceAnnotation(name, **args)


class _PhaseFrame:
    __slots__ = ("name", "acc", "last_start")

    def __init__(self, name: str, now: float):
        self.name = name
        self.acc = 0.0
        self.last_start = now


class _Phase:
    """Context manager charging exclusive time to one named phase; a
    nested phase pauses the enclosing one (so ``broadcast`` inside the
    ingest drain loop is never double-counted)."""

    __slots__ = ("wake", "name", "mark")

    def __init__(self, wake: "_Wake", name: str):
        self.wake = wake
        self.name = name
        self.mark = None

    def __enter__(self) -> "_Phase":
        self.mark = self.wake.annotate(ANNOTATION_PREFIX + self.name)
        now = time.perf_counter()
        stack = self.wake.stack
        if stack:
            top = stack[-1]
            top.acc += now - top.last_start
        stack.append(_PhaseFrame(self.name, now))
        return self

    def __exit__(self, *exc: Any) -> None:
        now = time.perf_counter()
        stack = self.wake.stack
        frame = stack.pop()
        frame.acc += now - frame.last_start
        self.wake.phases[frame.name] = (
            self.wake.phases.get(frame.name, 0.0) + frame.acc
        )
        if stack:
            stack[-1].last_start = now
        self.mark.__exit__(None, None, None)


class _Wake:
    """Accounting for one in-flight collector wake."""

    __slots__ = ("profiler", "thread", "ordinal", "t0", "start", "phases",
                 "stack", "device_s", "fields", "mark")

    def __init__(self, profiler: "WakeProfiler", ordinal: int):
        self.profiler = profiler
        self.thread = threading.get_ident()
        self.ordinal = ordinal
        self.phases: Dict[str, float] = {}
        self.stack: List[_PhaseFrame] = []
        self.device_s = 0.0
        self.fields: Dict[str, Any] = {}
        self.mark = profiler.annotate(WAKE_ANNOTATION, wake=ordinal)
        self.mark.__enter__()
        self.t0 = time.time()
        self.start = time.perf_counter()

    def annotate(self, name: str):
        mark = self.profiler.annotate(name, wake=self.ordinal)
        mark.__enter__()
        return mark

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name)

    def note(self, **fields: Any) -> None:
        """Fields for this wake's record, from whoever holds the wake
        (the backend: sweep counters, ``kills``, ``freed``)."""
        self.fields.update(fields)

    def end(self, **fields: Any) -> None:
        wall_s = time.perf_counter() - self.start
        self.mark.__exit__(None, None, None)
        self.profiler._finish(self, wall_s, fields)


class WakeProfiler:
    """Per-system wake profiler.  Install as the engine's
    ``wake_profiler`` (the collector consults it each wake) and as a
    recorder listener (device/sweep attribution); both are done by
    :meth:`uigc_tpu.telemetry.Telemetry.attach`."""

    def __init__(self, node: str, max_recent: int = 256, registry=None,
                 annotate=trace_annotation):
        self.node = node
        #: ``annotate(name, **args)`` -> context manager bracketing the
        #: wake and each phase on a profiler trace's clock
        self.annotate = annotate
        self._lock = threading.Lock()
        self._active: Optional[_Wake] = None
        #: Prometheus face (optional): per-phase wake durations as one
        #: histogram labelled by phase, plus the device share — so the
        #: BENCH-JSON dump is no longer the only way to read the
        #: profiler (uigc.telemetry.metrics + wake-profile together).
        self._phase_hist = None
        self._device_hist = None
        if registry is not None:
            self._phase_hist = registry.histogram(
                "uigc_wake_phase_seconds",
                "Exclusive time of one collector-wake phase, by phase "
                "(" + "/".join(PHASES) + ").",
            )
            self._device_hist = registry.histogram(
                "uigc_wake_device_seconds",
                "Device-kernel share of one collector wake.",
            )
        self._wakes = 0
        self._wall_total = 0.0
        self._wall_max = 0.0
        self._totals: Dict[str, Dict[str, float]] = {
            name: {"total_s": 0.0, "max_s": 0.0, "device_total_s": 0.0}
            for name in PHASES
        }
        self._recent: deque = deque(maxlen=max_recent)
        self._entries_total = 0
        self._garbage_total = 0

    # -- wake lifecycle (called from the Bookkeeper thread) ---------- #

    def begin_wake(self) -> _Wake:
        # wakes follow one another on the collector's thread, so the
        # count of finished ones numbers the next
        wake = _Wake(self, self._wakes)
        self._active = wake
        return wake

    def _finish(self, wake: _Wake, wall_s: float, fields: Dict[str, Any]) -> None:
        self._active = None
        phases = {name: wake.phases.get(name, 0.0) for name in PHASES}
        record = {
            "t": wake.t0,
            "wake": wake.ordinal,
            "wall_s": wall_s,
            "device_s": wake.device_s,
            "phases": phases,
            **wake.fields,
            **fields,
        }
        if self._phase_hist is not None:
            for name in PHASES:
                self._phase_hist.observe(phases[name], phase=name)
            if self._device_hist is not None:
                self._device_hist.observe(wake.device_s)
        with self._lock:
            self._wakes += 1
            self._wall_total += wall_s
            if wall_s > self._wall_max:
                self._wall_max = wall_s
            self._entries_total += int(fields.get("entries", 0) or 0)
            self._garbage_total += int(fields.get("garbage", 0) or 0)
            for name in PHASES:
                totals = self._totals[name]
                totals["total_s"] += phases[name]
                if phases[name] > totals["max_s"]:
                    totals["max_s"] = phases[name]
            self._totals["trace"]["device_total_s"] += wake.device_s
            self._recent.append(record)

    # -- recorder listener (the device call) ------------------------- #

    def __call__(self, name: str, fields: Dict[str, Any]) -> None:
        if name != events.DEVICE_TRACE:
            return
        wake = self._active
        if wake is None or wake.thread != threading.get_ident():
            return
        wake.device_s += fields.get("duration_s") or 0.0
        if "trace_mode" in fields:
            wake.fields.setdefault("trace_mode", fields["trace_mode"])

    # -- reading ----------------------------------------------------- #

    def wakes_since(self, t0: float) -> List[Dict[str, Any]]:
        """Recent wake records newer than ``t0`` (their ``t`` stamp),
        oldest first — the time-plane sampler's feed
        (uigc_tpu/telemetry/timeseries.py): each call hands over only
        the wakes completed since the last tick."""
        with self._lock:
            return [dict(r) for r in self._recent if r["t"] > t0]

    # -- export ------------------------------------------------------ #

    def to_json(self) -> Dict[str, Any]:
        """BENCH-style document: per-phase totals plus the recent wakes."""
        with self._lock:
            return {
                "bench": "wake_profile",
                "node": self.node,
                "wakes": self._wakes,
                "wall_total_s": self._wall_total,
                "wall_max_s": self._wall_max,
                "entries_total": self._entries_total,
                "garbage_total": self._garbage_total,
                "phases": {k: dict(v) for k, v in self._totals.items()},
                "recent": list(self._recent),
            }

    def dump(self, path: str) -> Dict[str, Any]:
        doc = self.to_json()
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
        return doc
