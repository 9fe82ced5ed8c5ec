"""Device-plane observatory: HBM/array ledger, compile-cache telemetry,
per-sweep kernel attribution.

The wake profiler (:mod:`uigc_tpu.telemetry.profile`) says *which phase*
of a wake was slow; this module answers the device-plane questions the
phase brackets cannot: which array family holds how many bytes (and what
the high-water mark was), whether a jit/pjit cache is being missed every
wake (the recompile-storm class of bug — the PR 5 multi-system pjit
deadlock was found by hand; the ``recompile_storm`` alert exists so the
next one fires a page instead of hanging tier-1), whether a
supposedly-donated buffer silently copied, and how many bytes crossed
device->host on a hot path.  It is the measurement substrate the
adaptive-strategy work (ROADMAP items 1 and 5) presupposes: per-sweep,
per-pass numbers, not per-wake wall clock.

Three planes, all fed through the existing recorder-listener
architecture (no engine imports — the observatory reads graphs
duck-typed, like the metrics gauges, and everything else arrives as
structured events):

- **memory ledger** — :func:`ledger_families` walks a shadow graph's
  known array families (host mirrors, device-resident operands, the
  bookkeeping maps) read-only and tallies bytes per family;
  :meth:`DeviceObservatory.on_wake` samples it on the collector thread
  (fold-consistent) and tracks per-family peak watermarks.  Exposed as
  ``uigc_device_ledger_bytes{family=...}`` callback gauges.
- **compile-cache telemetry** — the engine/ops compile caches commit
  ``tpu.compile`` events (tag + geometry key + hit/miss); the
  observatory folds them into ``uigc_compile_{hits,misses}_total{tag}``
  and a ``uigc_compile_seconds`` histogram (real XLA compile seconds
  additionally ride ``jax.monitoring`` when that API exists).  The
  ``recompile_storm`` built-in alert is a rate rule over the miss
  counter.
- **host-transfer accounting + donation audit** — the annotated
  readback sites in ``engines/crgc`` commit ``tpu.host_transfer``
  (site, bytes); donating call sites audit their operands after the
  call and commit ``tpu.donation_copy`` when a donated buffer survived
  (XLA copied instead of aliasing).  Transfers are attributed to the
  active wake's open profiler phase — the listener runs synchronously
  on the committing thread, so reading the profiler's active-wake stack
  is race-free.

Per-sweep counts: the fixpoint runs all its sweeps inside one XLA
program and counts them there (``n_sweeps``, ``closure_sweeps`` and the
per-sweep dirty chunks in each wake record).  Device TIME per phase of
that program is not estimated here: it is read from a profiler trace,
where every operation carries its ``uigc.wake/<phase>`` scope
(PROFILING.md "The wake on the profiler's clock").

``tools/device_report.py`` renders :meth:`DeviceObservatory.to_doc`
(also served as ``/device`` on the metrics HTTP server) into the
wake-budget attribution report; ``tools/uigc_top.py`` shows the same
doc as a device panel.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

from ..utils import events

#: Per-entry byte estimate for the bookkeeping maps the ledger cannot
#: measure exactly (CPython dict overhead; coarse on purpose —
#: the ledger's job is catching growth that never comes back down, and
#: a constant factor cancels in that comparison).
_DICT_ENTRY_EST = 96


def _array_bytes(x: Any) -> Tuple[int, bool]:
    """(nbytes, is_device) of one array-like; (0, False) for anything
    else.  Device-ness is duck-typed: jax arrays carry ``is_deleted``,
    numpy does not."""
    nbytes = getattr(x, "nbytes", None)
    if nbytes is None or isinstance(x, (bytes, bytearray, memoryview)):
        return 0, False
    try:
        return int(nbytes), hasattr(x, "is_deleted")
    except Exception:
        return 0, False


def _tally(out: Dict[str, int], x: Any, depth: int = 0) -> None:
    """Fold one object (array, or a dict/list/tuple of arrays) into a
    {host, device, items} tally."""
    nbytes, device = _array_bytes(x)
    if nbytes:
        out["device" if device else "host"] += nbytes
        out["items"] += 1
        return
    if depth >= 2:
        return
    if isinstance(x, dict):
        for v in list(x.values()):
            _tally(out, v, depth + 1)
    elif isinstance(x, (list, tuple)):
        for v in list(x):
            _tally(out, v, depth + 1)


#: (family, attribute names) groups duck-typed off the shadow graph.
#: Missing attributes contribute nothing — the same walk serves the
#: host array graph, the decremental graph and the mesh graph.
_FAMILY_ATTRS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("node_features", (
        "flags", "recv_count", "supervisor", "_br_seq", "_sup_seq",
        "_slot_uid", "_uid_to_slot", "_recv_synced",
    )),
    ("edges", ("edge_src", "edge_dst", "edge_weight")),
    ("parents", ("last_parents", "last_parents_mark")),
    ("jump", ("_jump_parent", "_jump_dev")),
    ("device_nodes", ("_dev_flags", "_dev_recv", "_resident")),
    ("device_layout", ("_dev_stacked", "_stacked")),
    ("device_buckets", ("_dev_psrc", "_dev_pdst", "_pb_src", "_pb_dst")),
    ("wake_state", (
        "_wake_state", "_zero_words", "_verdict_dev", "last_verdict_words",
    )),
)

#: sub-objects whose ``vars()`` are scanned generically for arrays —
#: the decremental tracer and its incremental layout own device mirrors
#: the graph only references indirectly.
_SCAN_ATTRS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("decremental_tracer", ("_dec",)),
)


def _scan_object(out: Dict[str, int], obj: Any, depth: int = 0) -> None:
    """Tally every array reachable through one object's ``__dict__``
    (one level of nested layout objects)."""
    d = getattr(obj, "__dict__", None)
    if not isinstance(d, dict):
        return
    for value in list(d.values()):
        nbytes, _device = _array_bytes(value)
        if nbytes or isinstance(value, (dict, list, tuple)):
            _tally(out, value)
        elif depth < 1 and hasattr(value, "__dict__"):
            _scan_object(out, value, depth + 1)


def ledger_families(graph: Any) -> Dict[str, Dict[str, int]]:
    """Read-only walk of one shadow graph's array families ->
    ``{family: {host, device, items}}`` byte tallies.  Tolerates
    concurrent folds (torn reads of a growing container cost one family
    sample, never an exception) and unknown backends (missing
    attributes contribute nothing)."""
    out: Dict[str, Dict[str, int]] = {}

    def family(name: str) -> Dict[str, int]:
        return out.setdefault(name, {"host": 0, "device": 0, "items": 0})

    for name, attrs in _FAMILY_ATTRS:
        tally = family(name)
        for attr in attrs:
            try:
                _tally(tally, getattr(graph, attr, None))
            except Exception:
                continue
    for name, attrs in _SCAN_ATTRS:
        tally = family(name)
        for attr in attrs:
            try:
                _scan_object(tally, getattr(graph, attr, None))
            except Exception:
                continue
    # The bookkeeping maps: measured by entry-count estimate (documented
    # constants above) — what the "no ledger leak" check watches, since
    # these are exactly the structures that shrink when a sweep frees
    # slots (slot_of pops, edge_of pops, send-matrix purge).
    maps = family("maps")
    for attr, per_entry in (
        ("slot_of", _DICT_ENTRY_EST),
        ("send_matrix", _DICT_ENTRY_EST),
        ("_jump_writes", _DICT_ENTRY_EST),
    ):
        try:
            container = getattr(graph, attr, None)
            if container is not None and hasattr(container, "__len__"):
                maps["host"] += len(container) * per_entry
                maps["items"] += 1
        except Exception:
            continue
    # the pair log is columns (ops/slotmap.PairLog) and knows its bytes
    log_bytes = getattr(getattr(graph, "_pair_log", None), "nbytes", None)
    if log_bytes is not None:
        maps["host"] += log_bytes
        maps["items"] += 1
    try:
        edge_of = getattr(graph, "edge_of", None)
        if edge_of is not None:
            scanned = {"host": 0, "device": 0, "items": 0}
            _scan_object(scanned, edge_of)
            if scanned["host"]:
                maps["host"] += scanned["host"]
            elif hasattr(edge_of, "__len__"):
                maps["host"] += len(edge_of) * _DICT_ENTRY_EST
            maps["items"] += 1
    except Exception:
        pass
    return out


#: compile-cache geometry labelling lives with the event vocabulary so
#: the emitting sites (engines/ops) never import this package.
geom_key = events.compile_geom


# ------------------------------------------------------------------- #
# jax.monitoring hookup (real XLA compile seconds, process-global)
# ------------------------------------------------------------------- #

_MONITOR_LOCK = threading.Lock()
#: weakrefs to live observatories — weak so a system torn down without
#: reaching Telemetry.close() (crash paths, aborted tests) cannot be
#: pinned for the process lifetime through graph_fn's bookkeeper
#: closure; dead refs are pruned on the next fan-out.
_MONITOR_TARGETS: "set" = set()
_MONITOR_REGISTERED = False


def _ensure_jax_monitor() -> None:
    """Register ONE process-global jax.monitoring duration listener (the
    API has no per-listener removal) that fans backend-compile durations
    out to the live observatories.  Silently a no-op on jax versions
    without the API."""
    global _MONITOR_REGISTERED
    with _MONITOR_LOCK:
        if _MONITOR_REGISTERED:
            return
        try:
            from jax import monitoring
        except Exception:  # pragma: no cover - jax absent/ancient
            return

        def _listener(name: str, duration: float, **_kw: Any) -> None:
            if "backend_compile" not in name:
                return
            with _MONITOR_LOCK:
                refs = list(_MONITOR_TARGETS)
            for ref in refs:
                obs = ref()
                if obs is None:
                    with _MONITOR_LOCK:
                        _MONITOR_TARGETS.discard(ref)
                else:
                    obs._on_jax_compile(float(duration))

        try:
            monitoring.register_event_duration_secs_listener(_listener)
        except Exception:  # pragma: no cover - API drift
            return
        _MONITOR_REGISTERED = True


class DeviceObservatory:
    """Per-system device-plane observatory (see module docstring).

    Install as a recorder listener AND as the engine's
    ``device_observatory`` (the collector feeds :meth:`on_wake` once per
    wake on its own thread); both are done by
    :class:`uigc_tpu.telemetry.Telemetry`.  Works registry-less too
    (offline JSONL replay builds one and feeds it events)."""

    def __init__(
        self,
        node: str = "",
        registry: Any = None,
        profiler: Any = None,
        graph_fn: Any = None,
    ):
        self.node = node
        self.profiler = profiler
        self.graph_fn = graph_fn
        self._lock = threading.Lock()
        self.wakes = 0
        #: family -> latest {host, device, items} sample (collector thread)
        self.ledger: Dict[str, Dict[str, int]] = {}
        #: family -> peak host+device bytes ever sampled
        self.peaks: Dict[str, int] = {}
        #: (tag, geom) -> {hits, misses, compile_s}
        self.compiles: Dict[Tuple[str, str], Dict[str, float]] = {}
        #: (site, phase) -> {count, bytes}
        self.transfers: Dict[Tuple[str, str], Dict[str, float]] = {}
        #: site -> donation-copy count
        self.donations: Dict[str, int] = {}
        self._jax_compile = {"n": 0, "total_s": 0.0, "max_s": 0.0}

        self._m_transfers = self._m_transfer_bytes = None
        self._m_donations = None
        self._m_hits = self._m_misses = self._m_compile_s = None
        if registry is not None:
            self._m_transfers = registry.counter(
                "uigc_host_transfers_total",
                "Device->host value crossings on collector paths, by "
                "readback site and the wake phase they landed in.",
            )
            self._m_transfer_bytes = registry.counter(
                "uigc_host_transfer_bytes_total",
                "Bytes moved device->host on collector paths.",
            )
            self._m_donations = registry.counter(
                "uigc_donation_copies_total",
                "Donated buffers that survived their donating call "
                "(XLA copied instead of aliasing), by site.",
            )
            self._m_misses = registry.counter(
                "uigc_compile_misses_total",
                "Compile-cache misses (a program was (re)built), by tag. "
                "A sustained per-wake rate is a recompile storm.",
            )
            self._m_hits = registry.counter(
                "uigc_compile_hits_total",
                "Compile-cache hits, by tag.",
            )
            self._m_compile_s = registry.histogram(
                "uigc_compile_seconds",
                "Seconds spent building/compiling one cached program "
                "(timed misses; real XLA compiles additionally ride "
                "jax.monitoring when available).",
            )
            registry.gauge(
                "uigc_device_ledger_bytes",
                "Live bytes per shadow-graph array family (host mirrors "
                "+ device-resident operands), sampled per wake.",
                fn=self._gauge_ledger,
                label_name="family",
            )
            registry.gauge(
                "uigc_device_ledger_peak_bytes",
                "Peak watermark of uigc_device_ledger_bytes per family.",
                fn=self._gauge_peaks,
                label_name="family",
            )
        _ensure_jax_monitor()
        with _MONITOR_LOCK:
            _MONITOR_TARGETS.add(weakref.ref(self))

    # -- recorder listener ------------------------------------------- #

    def __call__(self, name: str, fields: Dict[str, Any]) -> None:
        if self.node:
            # The recorder is process-global: in a multi-system process
            # accept only this node's threads (origin-less events — user
            # and test threads — are unscoped and accepted), the same
            # scoping discipline as the EventMetricsBridge.
            origin = fields.get("origin")
            if origin is not None and origin != self.node:
                return
        if name == events.HOST_TRANSFER:
            self._on_transfer(fields)
        elif name == events.COMPILE:
            self._on_compile(fields)
        elif name == events.DONATION_COPY:
            self._on_donation(fields)

    def _active_phase(self) -> str:
        """The open profiler phase of the active wake, when the event
        committed on the collector thread (listeners run synchronously
        on the committing thread, so this read cannot race the wake that
        owns the stack)."""
        profiler = self.profiler
        wake = getattr(profiler, "_active", None)
        if wake is None or wake.thread != threading.get_ident():
            return ""
        stack = wake.stack
        return stack[-1].name if stack else ""

    def _on_transfer(self, fields: Dict[str, Any]) -> None:
        site = str(fields.get("site", "?"))
        nbytes = int(fields.get("bytes", 0) or 0)
        phase = str(fields.get("phase", "") or self._active_phase())
        with self._lock:
            slot = self.transfers.setdefault(
                (site, phase), {"count": 0, "bytes": 0}
            )
            slot["count"] += 1
            slot["bytes"] += nbytes
        if self._m_transfers is not None:
            self._m_transfers.inc(site=site, phase=phase)
            self._m_transfer_bytes.inc(nbytes, phase=phase)

    #: per-tag geometry-stream bound: past it, further geometries fold
    #: into one ``geom="overflow"`` stream.  The recompile-storm
    #: pathology mints a FRESH geometry per wake, so without the bound
    #: the observatory's own state would grow without limit during
    #: exactly the incident it exists to diagnose (the same discipline
    #: as the registry's max-labelsets).  The storm stays visible: the
    #: overflow stream keeps counting misses per tag.
    MAX_GEOMS_PER_TAG = 256

    def _on_compile(self, fields: Dict[str, Any]) -> None:
        tag = str(fields.get("tag", "?"))
        geom = str(fields.get("geom", ""))
        hit = bool(fields.get("hit"))
        duration = fields.get("duration_s")
        with self._lock:
            slot = self.compiles.get((tag, geom))
            if slot is None:
                tag_geoms = sum(1 for t, _g in self.compiles if t == tag)
                if tag_geoms >= self.MAX_GEOMS_PER_TAG:
                    geom = "overflow"
                slot = self.compiles.setdefault(
                    (tag, geom), {"hits": 0, "misses": 0, "compile_s": 0.0}
                )
            slot["hits" if hit else "misses"] += 1
            if duration and not hit:
                slot["compile_s"] += float(duration)
        if hit:
            if self._m_hits is not None:
                self._m_hits.inc(tag=tag)
        else:
            if self._m_misses is not None:
                self._m_misses.inc(tag=tag)
            if duration and self._m_compile_s is not None:
                self._m_compile_s.observe(float(duration), tag=tag)

    def _on_donation(self, fields: Dict[str, Any]) -> None:
        site = str(fields.get("site", "?"))
        with self._lock:
            self.donations[site] = self.donations.get(site, 0) + 1
        if self._m_donations is not None:
            self._m_donations.inc(site=site)

    def _on_jax_compile(self, duration_s: float) -> None:
        with self._lock:
            j = self._jax_compile
            j["n"] += 1
            j["total_s"] += duration_s
            if duration_s > j["max_s"]:
                j["max_s"] = duration_s
        if self._m_compile_s is not None:
            self._m_compile_s.observe(duration_s, tag="jax_backend")

    # -- per-wake sampling (collector thread) ------------------------- #

    def on_wake(self, graph: Any) -> None:
        """Sample the memory ledger against one fold-consistent graph
        view and roll the peak watermarks.  Called by the collector
        after each wake (exception-isolated there, like the liveness
        inspector's hook)."""
        sample = ledger_families(graph)
        with self._lock:
            self.wakes += 1
            self.ledger = sample
            for fam, tally in sample.items():
                total = tally["host"] + tally["device"]
                if total > self.peaks.get(fam, 0):
                    self.peaks[fam] = total

    # -- gauges -------------------------------------------------------- #

    def _gauge_ledger(self) -> Optional[Dict[str, int]]:
        graph = None
        if self.graph_fn is not None:
            try:
                graph = self.graph_fn()
            except Exception:
                graph = None
        if graph is not None:
            # Lazy scrape-time sample (concurrent-fold tolerant); also
            # refreshes the wake-sampled copy for headless readers and
            # rolls the peaks — live must never read above peak in one
            # exposition (the leak heuristic compares the two).
            sample = ledger_families(graph)
            with self._lock:
                self.ledger = sample
                for fam, tally in sample.items():
                    total = tally["host"] + tally["device"]
                    if total > self.peaks.get(fam, 0):
                        self.peaks[fam] = total
        else:
            with self._lock:
                sample = dict(self.ledger)
        return {
            fam: tally["host"] + tally["device"] for fam, tally in sample.items()
        } or None

    def _gauge_peaks(self) -> Optional[Dict[str, int]]:
        with self._lock:
            return dict(self.peaks) or None

    # -- reading / export --------------------------------------------- #

    def recent_wakes(self, limit: int = 32) -> List[Dict[str, Any]]:
        """The profiler's newest per-wake records (with the per-sweep
        device attribution profile.py stamps), newest last.  Prefers
        wakes that actually dispatched device work — a healthy idle
        system's newest wakes all skip the trace (the ``_graph_dirty``
        gate), and a report full of idle records would hide the sweeps
        the regression explainer exists to decompose."""
        profiler = self.profiler
        if profiler is None or not hasattr(profiler, "wakes_since"):
            return []
        records = profiler.wakes_since(0.0)
        active = [
            r for r in records if r.get("device_s") or r.get("n_sweeps")
        ]
        return (active or records)[-limit:]

    def to_doc(self) -> Dict[str, Any]:
        """The ``/device`` document: every plane, JSON-able.  The shape
        ``tools/device_report.py`` renders and validates."""
        with self._lock:
            ledger = {
                fam: dict(tally) for fam, tally in sorted(self.ledger.items())
            }
            peaks = dict(self.peaks)
            compiles = [
                {"tag": tag, "geom": geom, **{k: v for k, v in slot.items()}}
                for (tag, geom), slot in sorted(self.compiles.items())
            ]
            transfers = [
                {"site": site, "phase": phase, **slot}
                for (site, phase), slot in sorted(self.transfers.items())
            ]
            donations = dict(self.donations)
            jax_compile = dict(self._jax_compile)
            wakes = self.wakes
        return {
            "version": 1,
            "bench": "device_observatory",
            "node": self.node,
            "t": time.time(),
            "wakes": wakes,
            "ledger": {
                "families": ledger,
                "peaks": peaks,
                "total_bytes": sum(
                    t["host"] + t["device"] for t in ledger.values()
                ),
                "device_bytes": sum(t["device"] for t in ledger.values()),
            },
            "compile": {
                "entries": compiles,
                "misses_total": sum(c["misses"] for c in compiles),
                "hits_total": sum(c["hits"] for c in compiles),
                "jax_backend": jax_compile,
            },
            "transfers": {
                "sites": transfers,
                "total_count": sum(t["count"] for t in transfers),
                "total_bytes": sum(t["bytes"] for t in transfers),
            },
            "donation": {
                "sites": donations,
                "copies_total": sum(donations.values()),
            },
            "recent_wakes": self.recent_wakes(),
        }

    def close(self) -> None:
        with _MONITOR_LOCK:
            _MONITOR_TARGETS.discard(weakref.ref(self))


def validate_device_doc(doc: Any) -> List[str]:
    """Schema check of one observatory document; returns the problems
    (empty = valid).  Used by ``device_report --selfcheck`` and the
    tests, so the wire shape cannot drift silently."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return ["document is not an object"]
    for key, kind in (
        ("version", int), ("node", str), ("wakes", int),
        ("ledger", dict), ("compile", dict), ("transfers", dict),
        ("donation", dict), ("recent_wakes", list),
    ):
        if not isinstance(doc.get(key), kind):
            problems.append(f"missing/typed-wrong key {key!r}")
    ledger = doc.get("ledger") or {}
    if not isinstance(ledger.get("families"), dict):
        problems.append("ledger.families is not an object")
    else:
        for fam, tally in ledger["families"].items():
            if not isinstance(tally, dict) or not {
                "host", "device", "items"
            } <= set(tally):
                problems.append(f"ledger family {fam!r} malformed")
    compile_doc = doc.get("compile") or {}
    if not isinstance(compile_doc.get("entries"), list):
        problems.append("compile.entries is not a list")
    else:
        for entry in compile_doc["entries"]:
            if not isinstance(entry, dict) or "tag" not in entry:
                problems.append("compile entry without a tag")
                break
    transfers = doc.get("transfers") or {}
    if not isinstance(transfers.get("sites"), list):
        problems.append("transfers.sites is not a list")
    for rec in doc.get("recent_wakes") or []:
        if not isinstance(rec, dict):
            problems.append("recent_wakes entry is not an object")
            break
        n = rec.get("n_sweeps")
        dirty = rec.get("sweep_dirty_chunks")
        if dirty is not None:
            if not isinstance(dirty, list) or (n and len(dirty) > int(n)):
                problems.append("sweep_dirty_chunks does not match n_sweeps")
                break
    return problems
