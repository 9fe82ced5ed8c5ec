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

Beside the wall clock, CPU clocks: who had the host.  All of it only
while a profiler is attached; nothing is read on a dispatcher's path.

- ``cpu_s``, ``phases_cpu``  ``time.thread_time()`` of the collector's
                     thread over the wake and, exclusive exactly as
                     ``phases`` is, over each phase: ``phases_cpu`` adds
                     up to ``cpu_s`` as ``phases`` does to ``wall_s``.  A
                     part has its twin (``stage_cpu_s``, ``dispatch_cpu_s``,
                     ``device_cpu_s``).  A phase's ``wall - cpu`` is the
                     time the collector's thread did not run: in
                     ``device`` the wait for the chip (``device`` less
                     ``dispatch_s``: a check of the clock itself), in
                     ``sweep``, ``fold``, ``layout``, ``upload`` the GIL
                     in other hands, or the host
- ``workers_cpu_s``, ``workers_cpu_sweep_s``, ``workers_cpu_gap_s``,
  ``workers_busy_max_s``  the dispatcher workers' CPU clocks, read from
                     OUTSIDE the threads (``time.pthread_getcpuclockid``
                     of the idents ``Dispatcher.thread_idents()`` gives,
                     learned once when the profiler starts) at the
                     wake's start, the ``sweep``'s start and end and the
                     wake's end: summed over the wake, inside ``sweep``,
                     over ``gap_s`` since the wake before, and the
                     busiest single worker over the wake (eight threads
                     under one GIL should share it).  ``sweep`` less
                     ``phases_cpu["sweep"]`` less ``workers_cpu_sweep_s``
                     is time NOBODY of the runtime ran.  ``None``
                     without ``pthread_getcpuclockid`` or where nobody
                     named the threads; a thread that has exited is
                     dropped
- ``process_cpu_s``  ``time.process_time()`` over the wake: every thread
                     of the process, XLA's and numpy's included
- ``gc_s``, ``gc_sweep_s``, ``gc_full``  CPython's collections of
                     generation >= 1 that ended while the wake was in
                     flight, on whatever thread (every thread stands
                     still meanwhile): their pause, the part inside
                     ``sweep``, the full ones.  From ONE ``gc.callbacks``
                     entry, which also writes each as a ``uigc:gc``
                     annotation (``gen=``) on the thread that collects

How fine these clocks are is the host's affair: nanoseconds on a plain
Linux kernel, TICKS of 10 ms under gVisor, where one wake's reading is
a multiple of the tick and only sums over many wakes tell (PROFILING.md
"Who had the host").

A watchdog thread (``uigc-stallwatch``) ticks every 100 ms; a tick more
than 0.5 s late is a STALL, kept in :attr:`WakeProfiler.stalls` (and in
:meth:`WakeProfiler.to_json`) and written as ``uigc:stall``
(``late_ms=``): ``at`` (``perf_counter``), ``late_s``, the advance over
the stall of ``process_cpu_s`` and of the collector's, the workers' and
the timer's CPU clocks, and the ``wake`` and ``phase`` in flight.  Two
readings: ``process_cpu_s`` ~ 0 means nobody ran, the host's (the
process not scheduled); ``process_cpu_s`` ~ ``late_s`` means a thread
of the program held on (the GIL through a full collection or a C call),
and the class that has it names the thread.  The STACKS are opt-in,
:meth:`WakeProfiler.dump_stalls_to`: ``faulthandler``'s timer thread
needs no GIL, but a process has one such timer
(``benchmark/run.py`` uses it as the run's limit), and a dump taken
while the GIL's holder runs Python can crash the process.

:data:`record_sink` is the record's way out of a run
(``tools/telemetry_dump.py --wakes``).

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

import faulthandler
import gc
import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from ..utils import events

PHASES = ("ingest", "fold", "trace", "layout", "upload", "device",
          "readback", "sweep", "broadcast")

#: prefix of every annotation the profiler writes into a trace
ANNOTATION_PREFIX = "uigc:"
WAKE_ANNOTATION = ANNOTATION_PREFIX + "wake"
GC_ANNOTATION = ANNOTATION_PREFIX + "gc"
STALL_ANNOTATION = ANNOTATION_PREFIX + "stall"

#: the classes of runtime threads whose CPU clocks are read from outside
THREAD_CLASSES = ("workers", "timer", "collector")

#: ``record_sink(record)``: where a finished wake's record and a stall's
#: go besides the profiler's own deques, as they are at that instant (the
#: deferred counters and the cascade come later: :meth:`WakeProfiler.
#: to_json`).  Called after the wake has ended, outside every phase, on
#: the collector's thread (a stall: on the watchdog's).  ``None``: nobody.
#: ``tools/telemetry_dump.py --wakes`` sets it for a run.
record_sink: Optional[Callable[[Dict[str, Any]], None]] = None


def trace_annotation(name: str, **args: Any):
    """The default ``annotate`` hook: a ``jax.profiler.TraceAnnotation``
    (jax is imported here, as everywhere in the package, on first use)."""
    import jax

    return jax.profiler.TraceAnnotation(name, **args)


class _ThreadClocks:
    """The CPU clocks of the runtime's threads, by class, read from
    outside the threads they measure (``pthread_getcpuclockid``: a clock
    read, nothing on the measured thread's path).  The ids are taken once,
    from threads that are alive; the clock of a thread that has exited
    since fails to read and is dropped."""

    def __init__(self, idents: Dict[str, List[int]]):
        self.clocks: Dict[str, List[int]] = {
            name: [time.pthread_getcpuclockid(ident) for ident in idents.get(name, ())]
            for name in THREAD_CLASSES
        }

    def read(self, name: str) -> Dict[int, float]:
        """CPU seconds so far of each thread of the class, by clock id."""
        out, dead = {}, []
        for clock in self.clocks[name]:
            try:
                out[clock] = time.clock_gettime(clock)
            except OSError:
                dead.append(clock)
        if dead:
            self.clocks[name] = [c for c in self.clocks[name] if c not in dead]
        return out


def _hand_over(record: Dict[str, Any]) -> None:
    """Give :data:`record_sink` a copy of a finished record; a sink that
    raises must not take the collector's wake (or the watchdog) with it."""
    sink = record_sink
    if sink is None:
        return
    try:
        sink(dict(record))
    except Exception:
        events.recorder.commit(events.LISTENER_ERROR, listener="wake_profiler.record_sink")


def _advance(before: Dict[int, float], after: Dict[int, float]) -> List[float]:
    """What each clock read on both sides advanced by."""
    return [now - before[clock] for clock, now in after.items() if clock in before]


class _PhaseFrame:
    __slots__ = ("name", "acc", "last_start", "cpu", "cpu_start")

    def __init__(self, name: str, now: float, cpu: float):
        self.name = name
        self.acc = 0.0
        self.last_start = now
        self.cpu = 0.0
        self.cpu_start = cpu


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
        wake = self.wake
        self.mark = wake.annotate(ANNOTATION_PREFIX + self.name)
        if self.name == "sweep":
            # the others' clocks, outside the phase's own two
            wake.workers_sweep = wake.profiler._read_workers()
        now = time.perf_counter()
        cpu = time.thread_time()
        stack = wake.stack
        if stack:
            top = stack[-1]
            top.acc += now - top.last_start
            top.cpu += cpu - top.cpu_start
        stack.append(_PhaseFrame(self.name, now, cpu))
        return self

    def __exit__(self, *exc: Any) -> None:
        now = time.perf_counter()
        cpu = time.thread_time()
        wake = self.wake
        stack = wake.stack
        frame = stack.pop()
        frame.acc += now - frame.last_start
        frame.cpu += cpu - frame.cpu_start
        wake.phases[frame.name] = wake.phases.get(frame.name, 0.0) + frame.acc
        wake.phases_cpu[frame.name] = wake.phases_cpu.get(frame.name, 0.0) + frame.cpu
        if stack:
            stack[-1].last_start = now
            stack[-1].cpu_start = cpu
        if frame.name == "sweep":
            wake.sweep_end = now  # where the stop cascade is timed from
            before = wake.workers_sweep
            if before is not None:
                wake.workers_cpu_sweep_s += sum(
                    _advance(before, wake.profiler._read_workers())
                )
        self.mark.__exit__(None, None, None)


class _Part:
    """Context manager timing a stretch INSIDE a phase into the record's
    ``field`` (summed over the wake), under ``uigc:<annotation>`` on the
    trace's clock where one is given.  No phase: the clock of the phase
    around it runs on, so the phases keep adding up to the wall."""

    __slots__ = ("wake", "field", "annotation", "mark", "t0", "cpu0")

    def __init__(self, wake: "_Wake", field: str, annotation: Optional[str]):
        self.wake = wake
        self.field = field
        self.annotation = annotation
        self.mark = None
        self.t0 = 0.0
        self.cpu0 = 0.0

    def __enter__(self) -> "_Part":
        if self.annotation is not None:
            self.mark = self.wake.annotate(ANNOTATION_PREFIX + self.annotation)
        self.t0 = time.perf_counter()
        self.cpu0 = time.thread_time()
        return self

    def __exit__(self, *exc: Any) -> None:
        took = time.perf_counter() - self.t0
        ran = time.thread_time() - self.cpu0
        fields = self.wake.fields
        fields[self.field] = fields.get(self.field, 0.0) + took
        # ``stage_s`` -> ``stage_cpu_s``: the part's thread CPU beside its wall
        twin = self.field[:-2] + "_cpu_s"
        fields[twin] = fields.get(twin, 0.0) + ran
        if self.mark is not None:
            self.mark.__exit__(None, None, None)


class _Wake:
    """Accounting for one in-flight collector wake."""

    __slots__ = ("profiler", "thread", "ordinal", "t0", "start", "phases",
                 "stack", "fields", "mark", "sweep_end", "deferred",
                 "cpu_start", "cpu_end", "phases_cpu", "process_start",
                 "workers_start", "workers_sweep", "workers_cpu_sweep_s",
                 "gc_s", "gc_sweep_s", "gc_full")

    def __init__(self, profiler: "WakeProfiler", ordinal: int):
        self.profiler = profiler
        self.thread = threading.get_ident()
        self.ordinal = ordinal
        self.phases: Dict[str, float] = {}
        self.phases_cpu: Dict[str, float] = {}
        self.stack: List[_PhaseFrame] = []
        self.fields: Dict[str, Any] = {}
        self.sweep_end: Optional[float] = None
        self.deferred: Optional[tuple] = None
        #: the workers' clocks at the last ``sweep``'s start, and their
        #: advance inside the ``sweep`` brackets so far
        self.workers_sweep: Optional[Dict[int, float]] = None
        self.workers_cpu_sweep_s = 0.0
        #: CPython's collections of generation >= 1 that ended while this
        #: wake was in flight, on whatever thread (``WakeProfiler._on_gc``)
        self.gc_s = 0.0
        self.gc_sweep_s = 0.0
        self.gc_full = 0
        self.cpu_end = 0.0
        self.mark = profiler.annotate(WAKE_ANNOTATION, wake=ordinal)
        self.mark.__enter__()
        self.workers_start = profiler._read_workers()
        self.process_start = time.process_time()
        self.t0 = time.time()
        self.start = time.perf_counter()
        self.cpu_start = time.thread_time()

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
        self.cpu_end = time.thread_time()
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

    #: where the stalls' stacks go (:meth:`dump_stalls_to`): the open
    #: file, for every profiler of the process, as ``faulthandler``'s one
    #: timer is
    _stall_dump: Optional[Any] = None

    def __init__(self, node: str, max_recent: int = 256, registry=None,
                 annotate=trace_annotation,
                 threads: Optional[Callable[[], Dict[str, List[int]]]] = None,
                 watch_period_s: float = 0.1, stall_threshold_s: float = 0.5):
        self.node = node
        #: ``annotate(name, **args)`` -> context manager bracketing the
        #: wake and each phase on a profiler trace's clock
        self.annotate = annotate
        #: ``threads()`` -> ``{class: [Thread.ident, ...]}`` of the
        #: runtime's threads (``THREAD_CLASSES``), asked once, by
        #: :meth:`start`; without it (or without the platform's
        #: ``pthread_getcpuclockid``) the workers' fields read ``None``
        self.threads = threads
        self._clocks: Optional[_ThreadClocks] = None
        #: the workers' clocks at the end of the wake before
        #: (``workers_cpu_gap_s``)
        self._last_workers: Optional[Dict[int, float]] = None
        #: the watchdog: a tick every ``watch_period_s``, a stall where
        #: one comes more than ``stall_threshold_s`` late
        self.watch_period_s = watch_period_s
        self.stall_threshold_s = stall_threshold_s
        self.stalls: deque = deque(maxlen=max_recent)
        self._watchdog: Optional[threading.Thread] = None
        self._stop_watching = threading.Event()
        #: ``perf_counter`` and annotation of the collection of
        #: generation >= 1 in flight (one at a time: CPython's collector
        #: does not re-enter)
        self._gc_open: Optional[tuple] = None
        #: collections and stalls are annotated once a wake has been: the
        #: default hook imports jax on first use, and a collection can
        #: strike INSIDE that import, on the importing thread
        self._annotating = False
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
            name: {"total_s": 0.0, "max_s": 0.0, "cpu_total_s": 0.0,
                   "device_total_s": 0.0}
            for name in PHASES
        }
        self._recent: deque = deque(maxlen=max_recent)
        self._entries_total = 0
        self._garbage_total = 0

    # -- attached and detached (telemetry.Telemetry) ----------------- #

    def start(self) -> None:
        """Attached: learn the runtime's threads, hold one
        ``gc.callbacks`` entry and start the watchdog.  Until
        :meth:`close` undoes all three."""
        if self._watchdog is not None:
            return
        if self.threads is not None and hasattr(time, "pthread_getcpuclockid"):
            self._clocks = _ThreadClocks(self.threads())
        gc.callbacks.append(self._on_gc)
        self._stop_watching.clear()
        self._watchdog = threading.Thread(
            target=self._watch, name="uigc-stallwatch", daemon=True
        )
        self._watchdog.start()

    def close(self) -> None:
        watchdog, self._watchdog = self._watchdog, None
        if watchdog is None:
            return
        self._stop_watching.set()
        watchdog.join(timeout=5)
        gc.callbacks.remove(self._on_gc)
        self._gc_open = None

    def _read_workers(self) -> Optional[Dict[int, float]]:
        clocks = self._clocks
        return clocks.read("workers") if clocks is not None else None

    # -- CPython's collector (whatever thread it runs on) ------------- #

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        """A collection of generation >= 1 as a ``uigc:gc`` annotation on
        the thread that runs it, and its pause on the wake in flight
        when it ends (every thread stands still meanwhile: the
        collector holds the GIL)."""
        generation = info["generation"]
        if generation < 1:
            return
        if phase == "start":
            mark = None
            if self._annotating:
                mark = self.annotate(GC_ANNOTATION, gen=generation)
                mark.__enter__()
            self._gc_open = (time.perf_counter(), mark)
            return
        opened, self._gc_open = self._gc_open, None
        if opened is None:
            return  # attached while it ran
        t0, mark = opened
        pause = time.perf_counter() - t0
        if mark is not None:
            mark.__exit__(None, None, None)
        wake = self._active
        if wake is not None:
            wake.gc_s += pause
            if any(frame.name == "sweep" for frame in wake.stack):
                wake.gc_sweep_s += pause
            if generation == 2:
                wake.gc_full += 1

    # -- stalls (the watchdog's thread) ------------------------------- #

    @classmethod
    def dump_stalls_to(cls, path: Optional[str]) -> None:
        """From now on every watchdog tick of every profiler of this
        process also re-arms ``faulthandler.dump_traceback_later`` onto
        ``path``: its C thread needs no GIL, so every thread's Python
        stack is written WHILE the process stands still, a profiler's
        threshold plus two periods (0.7 s) after its last tick.  A
        stall's record then carries
        ``dump_offset``, where in ``path`` its dump begins.  ``None``
        ends it.  A process has ONE such timer: whoever else armed it
        (``benchmark/run.py`` does, as the run's time limit) loses it.
        And a dump can KILL the process: that thread walks every
        thread's frames without the GIL, which is safe while they stand
        still (the case it is for: a collection, a long C call) and a
        race while the holder runs Python (one chip run in twelve
        died of SIGSEGV inside a dump, its holder tracing a jax program:
        PERF.md section 6, PR 54).  Which is why nothing asks for this
        but a tool, by name, for a hunt."""
        if cls._stall_dump is not None:
            faulthandler.cancel_dump_traceback_later()
            cls._stall_dump.close()
            cls._stall_dump = None
        if path is not None:
            cls._stall_dump = open(path, "a")

    def _snapshot(self) -> Dict[str, Any]:
        """The CPU clocks a stall is read against: the process's and
        each thread class's."""
        clocks = self._clocks
        snap: Dict[str, Any] = {"process": time.process_time()}
        if clocks is not None:
            for name in THREAD_CLASSES:
                if clocks.clocks[name]:
                    snap[name] = clocks.read(name)
        return snap

    def _in_flight(self) -> tuple:
        wake = self._active
        if wake is None:
            return None, None
        stack = wake.stack
        # the collector's thread may pop meanwhile
        try:
            return wake.ordinal, stack[-1].name
        except IndexError:
            return wake.ordinal, None

    def _watch(self) -> None:
        period, threshold = self.watch_period_s, self.stall_threshold_s
        stop = self._stop_watching
        last = time.perf_counter()
        before = self._snapshot()
        flight = self._in_flight()
        dump_end = None
        while True:
            dump = WakeProfiler._stall_dump
            if dump is not None:
                dump_end = os.fstat(dump.fileno()).st_size
                faulthandler.dump_traceback_later(threshold + 2 * period, file=dump)
            if stop.wait(period):
                if dump is not None:
                    faulthandler.cancel_dump_traceback_later()  # nobody ticks after this
                return
            now = time.perf_counter()
            late = now - last - period
            snap = self._snapshot()
            if late > threshold:
                if self._annotating:
                    with self.annotate(STALL_ANNOTATION, late_ms=round(late * 1e3)):
                        pass
                # the wake in flight now, else the one at the tick before
                ordinal, phase = self._in_flight()
                if ordinal is None:
                    ordinal, phase = flight
                stall = {
                    "t": time.time(),
                    "at": now,
                    "late_s": late,
                    "process_cpu_s": snap["process"] - before["process"],
                    "wake": ordinal,
                    "phase": phase,
                }
                for name in THREAD_CLASSES:
                    stall[name + "_cpu_s"] = (
                        sum(_advance(before[name], snap[name]), 0.0)
                        if name in snap and name in before else None
                    )
                if dump is not None:
                    grew = os.fstat(dump.fileno()).st_size > dump_end
                    stall["dump_offset"] = dump_end if grew else None
                self.stalls.append(stall)
                _hand_over(stall)
            last, before, flight = now, snap, self._in_flight()

    # -- wake lifecycle (called from the Bookkeeper thread) ---------- #

    def begin_wake(self) -> _Wake:
        # wakes follow one another on the collector's thread, so the
        # count of finished ones numbers the next
        wake = _Wake(self, self._wakes)
        self._active = wake
        self._annotating = True
        return wake

    def _finish(self, wake: _Wake, end: float, fields: Dict[str, Any]) -> None:
        self._active = None
        wall_s = end - wake.start
        phases = {name: wake.phases.get(name, 0.0) for name in PHASES}
        phases_cpu = {name: wake.phases_cpu.get(name, 0.0) for name in PHASES}
        record = {
            "t": wake.t0,
            "wake": wake.ordinal,
            "wall_s": wall_s,
            "cpu_s": wake.cpu_end - wake.cpu_start,
            "process_cpu_s": time.process_time() - wake.process_start,
            "device_s": 0.0,
            "gap_s": None if self._last_end is None else wake.start - self._last_end,
            "phases": phases,
            "phases_cpu": phases_cpu,
            **self._workers_fields(wake),
            "gc_s": wake.gc_s,
            "gc_sweep_s": wake.gc_sweep_s,
            "gc_full": wake.gc_full,
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
                totals["cpu_total_s"] += phases_cpu[name]
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
        _hand_over(record)

    def _workers_fields(self, wake: _Wake) -> Dict[str, Any]:
        """The dispatcher workers' CPU over the wake, inside its
        ``sweep`` and over the gap before it, summed over the threads,
        and the busiest single one's over the wake."""
        start, end = wake.workers_start, self._read_workers()
        gap_from, self._last_workers = self._last_workers, end
        if start is None or end is None:
            return dict.fromkeys(
                ("workers_cpu_s", "workers_cpu_sweep_s", "workers_cpu_gap_s",
                 "workers_busy_max_s")
            )
        ran = _advance(start, end)
        return {
            "workers_cpu_s": sum(ran),
            "workers_cpu_sweep_s": wake.workers_cpu_sweep_s,
            "workers_cpu_gap_s": None if gap_from is None else sum(_advance(gap_from, start)),
            "workers_busy_max_s": max(ran, default=0.0),
        }

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
                # (the watchdog appends without the lock: copy in one step)
                "stalls": [dict(s) for s in list(self.stalls)],
            }

    def dump(self, path: str) -> Dict[str, Any]:
        doc = self.to_json()
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
        return doc
