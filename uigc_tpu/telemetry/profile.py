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
``upload_bytes`` (what the upload handed the device for node features:
the padded patch of the slots of ``flags`` and ``recv_count`` written
since the wake before, both arrays at capacity where a wake uploaded
them whole, 0 where no slot was written), and ``layout_rows`` with
``layout_rebuilt`` (the pair transitions the ``layout`` phase folded; 1
where it packed the layout from the graph): 0 where a backend has nothing
to count; on the mesh backends ``bucket_cols`` and ``bucket_fill`` (the
insert bucket tier's columns a shard, which every sweep pays for, and
the fullest shard's columns in use) with ``bucket_writes`` and
``base_masks`` (the bucket columns and the packed-base slots the wake's
two layout scatters write: what ``layout`` and ``upload`` moved); and,
where a sweep ran,
``actors_local`` and ``actors_foreign``: the slots in use after it by
kind (actors with a cell; actors held by uid alone), from the graph's
running counts.

Around and inside the phases, all on ``time.perf_counter()`` (a reader
puts them on a trace's clock through the ``uigc:wake`` annotation of the
record's ordinal; ``None`` where there was nothing to time):

- ``gap_s``          from the end of the wake before to this one's start:
                     the collector not in a wake
- ``ingest_wait_s``  how long the oldest flush this wake drained had
                     waited, a packed row or an object ``Entry`` (the
                     planes take the time of the first write after a
                     drain: ``PackedPlane.timed``, ``CRGC.send_entry``)
- ``stage_s``        inside ``upload``: ``DecrementalTracer.stage_wake``
                     (annotation ``uigc:stage``, nested)
- ``dispatch_s``     inside ``device``: until the wake program's call
                     returned (``uigc:dispatch``, nested); the rest of
                     the phase waits for the result
- ``sweep_end_s``    from the wake's start to the end of its ``sweep``
- ``freed_local``, ``stopped``, ``last_stop_s``, ``cascade_s``  the
                     local cells the sweep freed that had yet to
                     terminate, how many of them have since (as of the
                     record's last touch), from the wake's start to the
                     last of them, and what of that lies after the end
                     of ``sweep`` (0 where the cascade ended inside the
                     sweep, which sends the ``StopMsg``s first and then
                     shares the GIL with what they started).  Reported
                     from the dispatchers' threads (``ActorCell.
                     _finalize`` -> :meth:`WakeProfiler.cell_terminated`)
                     and counted in after the wake, so the cascade has
                     no annotation; the two times stay ``None`` until
                     every cell has stopped

The wake program's own counters (``n_sweeps``, ``closure_sweeps``,
``closure_bailed``, ``gated_tiles``, ``sweep_*``...) stay on the device
when the wake ends; the backend
leaves a handle (:meth:`_Wake.defer`) and the records get them when they
are read (:meth:`WakeProfiler.wakes_since`, :meth:`WakeProfiler.
to_json`), on the reader's thread.

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

``device_s`` is the host clock around the whole device CALL (layout,
upload, run and readback together): the backend brackets it on the wake
it holds, beside its ``tpu.device_trace`` event.  The profiler is no
recorder listener: attached alone it leaves the process recorder off.

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
        if frame.name == "sweep":
            self.wake.sweep_end = now  # where the stop cascade is timed from
        self.mark.__exit__(None, None, None)


class _Part:
    """Context manager timing a stretch INSIDE a phase into the record's
    ``field`` (summed over the wake), under ``uigc:<annotation>`` on the
    trace's clock where one is given.  No phase: the clock of the phase
    around it runs on, so the phases keep adding up to the wall."""

    __slots__ = ("wake", "field", "annotation", "mark", "t0")

    def __init__(self, wake: "_Wake", field: str, annotation: Optional[str]):
        self.wake = wake
        self.field = field
        self.annotation = annotation
        self.mark = None
        self.t0 = 0.0

    def __enter__(self) -> "_Part":
        if self.annotation is not None:
            self.mark = self.wake.annotate(ANNOTATION_PREFIX + self.annotation)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        took = time.perf_counter() - self.t0
        fields = self.wake.fields
        fields[self.field] = fields.get(self.field, 0.0) + took
        if self.mark is not None:
            self.mark.__exit__(None, None, None)


class _Wake:
    """Accounting for one in-flight collector wake."""

    __slots__ = ("profiler", "thread", "ordinal", "t0", "start", "phases",
                 "stack", "fields", "mark", "sweep_end", "deferred")

    def __init__(self, profiler: "WakeProfiler", ordinal: int):
        self.profiler = profiler
        self.thread = threading.get_ident()
        self.ordinal = ordinal
        self.phases: Dict[str, float] = {}
        self.stack: List[_PhaseFrame] = []
        self.fields: Dict[str, Any] = {}
        self.sweep_end: Optional[float] = None
        self.deferred: Optional[tuple] = None
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

    def part(self, field: str, annotation: Optional[str] = None) -> _Part:
        return _Part(self, field, annotation)

    def note(self, **fields: Any) -> None:
        """Fields for this wake's record, from whoever holds the wake
        (the backend: sweep counters, ``kills``, ``freed``)."""
        self.fields.update(fields)

    def defer(self, read: Any, handle: Any) -> None:
        """Fields this wake cannot have without waiting (counters its
        device program left on the device): ``read([handle, ...])``
        gives a dict of fields a handle, and is called when records are
        read, on the reader's thread, with the handles of all the wakes
        that left it the same ``read``."""
        self.deferred = (read, handle)

    def end(self, **fields: Any) -> None:
        now = time.perf_counter()
        self.mark.__exit__(None, None, None)
        self.profiler._finish(self, now, fields)


class _Cascade:
    """The terminations of the local cells one wake freed, counted in
    until the wake's record knows how many there are to come."""

    __slots__ = ("stopped", "last", "record", "start")

    def __init__(self) -> None:
        self.stopped = 0
        self.last = 0.0
        self.record: Optional[Dict[str, Any]] = None
        self.start = 0.0  # the wake's, on perf_counter


class WakeProfiler:
    """Per-system wake profiler.  Installed as the engine's
    ``wake_profiler`` (the collector consults it each wake) by
    :meth:`uigc_tpu.telemetry.Telemetry.attach`, which also switches on
    the planes' clocks; ``ActorCell._finalize`` finds it as
    ``system.telemetry.profiler``."""

    def __init__(self, node: str, max_recent: int = 256, registry=None,
                 annotate=trace_annotation):
        self.node = node
        #: ``annotate(name, **args)`` -> context manager bracketing the
        #: wake and each phase on a profiler trace's clock
        self.annotate = annotate
        self._lock = threading.Lock()
        self._active: Optional[_Wake] = None
        #: ``perf_counter`` at the end of the wake before (``gap_s``)
        self._last_end: Optional[float] = None
        #: (wake ordinal, ``perf_counter``) of the terminations not yet
        #: counted in: appended from the dispatchers' threads, which
        #: take no lock for it (``deque.append`` is atomic)
        self._stops: deque = deque()
        #: wake ordinal -> the terminations of what that wake freed,
        #: while they are still coming in (under ``_lock``)
        self._cascades: Dict[int, _Cascade] = {}
        #: (record, read, handle) of the wakes whose deferred fields
        #: nobody has read yet; bounded like the records themselves
        self._deferred: deque = deque(maxlen=max_recent)
        self._settling = threading.Lock()
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

    def _finish(self, wake: _Wake, end: float, fields: Dict[str, Any]) -> None:
        self._active = None
        wall_s = end - wake.start
        phases = {name: wake.phases.get(name, 0.0) for name in PHASES}
        record = {
            "t": wake.t0,
            "wake": wake.ordinal,
            "wall_s": wall_s,
            "device_s": 0.0,
            "gap_s": None if self._last_end is None else wake.start - self._last_end,
            "phases": phases,
            **wake.fields,
            **fields,
        }
        self._last_end = end
        if wake.sweep_end is not None:
            record["sweep_end_s"] = wake.sweep_end - wake.start
        local = record.get("freed_local")
        if local:
            record["stopped"], record["last_stop_s"], record["cascade_s"] = 0, None, None
        device_s = record["device_s"]
        if self._phase_hist is not None:
            for name in PHASES:
                self._phase_hist.observe(phases[name], phase=name)
            if self._device_hist is not None:
                self._device_hist.observe(device_s)
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
            self._totals["trace"]["device_total_s"] += device_s
            self._recent.append(record)
            if wake.deferred is not None:
                self._deferred.append((record, *wake.deferred))
            if local:
                cascade = self._cascades.setdefault(wake.ordinal, _Cascade())
                cascade.record, cascade.start = record, wake.start
            self._count_stops()
            # a cascade that never ends goes when its record does
            self._cascades.pop(wake.ordinal - self._recent.maxlen, None)

    # -- the stop cascade (called from the dispatchers' threads) ------ #

    def cell_terminated(self, ordinal: int, now: float) -> None:
        """A local cell that wake ``ordinal``'s sweep freed has
        terminated, at ``now`` on ``time.perf_counter()``.  It may well
        come before the wake's own end: a cell stops while the sweep
        still frees the others.  Counted in by the collector's next
        wake or the next reader, whoever comes first."""
        self._stops.append((ordinal, now))

    def _count_stops(self) -> None:
        """Count the reported terminations into their wakes' records
        (under ``_lock``)."""
        stops, cascades = self._stops, self._cascades
        while stops:
            ordinal, now = stops.popleft()
            cascade = cascades.get(ordinal)
            if cascade is None:
                cascade = cascades[ordinal] = _Cascade()
            cascade.stopped += 1
            if now > cascade.last:
                cascade.last = now
        # the open cascades are few: the last wakes' that freed cells
        for ordinal, cascade in list(cascades.items()):
            record = cascade.record
            if record is None:
                continue  # its wake has not ended yet
            record["stopped"] = cascade.stopped
            if cascade.stopped >= record["freed_local"]:
                record["last_stop_s"] = cascade.last - cascade.start
                record["cascade_s"] = max(
                    0.0, record["last_stop_s"] - record.get("sweep_end_s", 0.0)
                )
                del cascades[ordinal]

    def _settle(self) -> None:
        """Bring the records up to date for a reader: count in the
        terminations reported since, and read what wakes deferred
        (:meth:`_Wake.defer`), one ``read`` for all the handles a reader
        left."""
        with self._settling:
            with self._lock:
                self._count_stops()
                waiting = list(self._deferred)
                self._deferred.clear()
            by_read: Dict[Any, List[tuple]] = {}
            for record, read, handle in waiting:
                by_read.setdefault(read, []).append((record, handle))
            for read, items in by_read.items():
                try:
                    got = read([handle for _, handle in items])
                except Exception:
                    # what the handles point into is gone (a poisoned
                    # wake's device state): those records stay without
                    events.recorder.commit(
                        events.LISTENER_ERROR, listener="wake_profiler.deferred"
                    )
                    continue
                with self._lock:
                    for (record, _), fields in zip(items, got):
                        record.update(fields)

    def wakes_since(self, t0: float) -> List[Dict[str, Any]]:
        """Recent wake records newer than ``t0`` (their ``t`` stamp),
        oldest first — the time-plane sampler's feed
        (uigc_tpu/telemetry/timeseries.py): each call hands over only
        the wakes completed since the last tick.  Copies: a record's
        ``stopped`` and ``cascade_s`` may still move after."""
        self._settle()
        with self._lock:
            return [dict(r) for r in self._recent if r["t"] > t0]

    # -- export ------------------------------------------------------ #

    def to_json(self) -> Dict[str, Any]:
        """BENCH-style document: per-phase totals plus the recent wakes."""
        self._settle()
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
                "recent": [dict(r) for r in self._recent],
            }

    def dump(self, path: str) -> Dict[str, Any]:
        doc = self.to_json()
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
        return doc
