"""What one run observed: spans, samples and counters on the host clock.

The driver brackets its calls into each layer of the program with
``obs.span(name)``; in a traced run the same bracket is written into the
profiler's trace as a ``jax.profiler.TraceAnnotation`` named
``bench:<name>``, so that an idle gap of the device can be named by what
the benchmark was doing in it.  Nothing here knows a metric: the readers
under ``layers/`` and ``metrics/`` pick their series by name.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Tuple

#: prefix of every annotation the benchmark writes into the trace
ANNOTATION_PREFIX = "bench:"


class Obs:
    def __init__(self) -> None:
        # re-entrant: a hook that runs wherever the interpreter likes
        # (a ``gc.callbacks`` entry, a ``__del__``) may record from inside
        # a thread that is recording; with a plain lock that thread waits
        # for itself, and every other recorder behind it
        self._lock = threading.RLock()
        self.spans: Dict[str, List[Tuple[float, float]]] = {}
        self.samples: Dict[str, List[float]] = {}
        self.counters: Dict[str, float] = {}
        self.facts: Dict[str, object] = {}
        #: host clock at the window's start and end (perf_counter)
        self.window_t0: Optional[float] = None
        self.window_t1: Optional[float] = None
        #: set by the harness in a traced run
        self.annotate: Optional[Callable[[str], object]] = None
        self.on_tick: Optional[Callable[[float], None]] = None
        self.trace = None  # harness.trace.TraceSummary after a traced run
        self.recording = False

    # -- the window ---------------------------------------------------- #

    def open_window(self) -> float:
        """Drop what the warm-up recorded and start the measured window."""
        with self._lock:
            self.spans.clear()
            self.samples.clear()
            self.counters.clear()
        self.recording = True
        self.window_t0 = time.perf_counter()
        return self.window_t0

    def close_window(self) -> float:
        """End the window; a driver whose requests may finish in a grace
        period calls this itself, and the harness's later call is void."""
        if self.recording:
            self.window_t1 = time.perf_counter()
            self.recording = False
        return self.window_t1

    @property
    def window_s(self) -> float:
        return self.window_t1 - self.window_t0

    def tick(self) -> None:
        """Called by the driver between units of work: the harness
        starts and stops the profiler here, so that a traced interval
        holds whole wakes."""
        if self.on_tick is not None:
            self.on_tick(time.perf_counter())

    # -- recording ----------------------------------------------------- #

    @contextmanager
    def span(self, name: str):
        note = self.annotate(ANNOTATION_PREFIX + name) if self.annotate else None
        if note is not None:
            note.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if note is not None:
                note.__exit__(None, None, None)
            if self.recording:
                with self._lock:
                    self.spans.setdefault(name, []).append((t0, t1))

    def sample(self, name: str, value: float) -> None:
        if self.recording:
            with self._lock:
                self.samples.setdefault(name, []).append(value)

    def late_sample(self, name: str, value: float) -> None:
        """A sample of a request that began inside the window and ended
        in the grace period after it: a tail is the tail of all requests."""
        with self._lock:
            self.samples.setdefault(name, []).append(value)

    def count(self, name: str, k: float = 1) -> None:
        if self.recording:
            with self._lock:
                self.counters[name] = self.counters.get(name, 0) + k

    # -- reading (the metric readers' side) ----------------------------- #

    def series(self, name: str) -> List[float]:
        return list(self.samples.get(name, ()))

    def span_ms(self, name: str) -> List[float]:
        return [(t1 - t0) * 1e3 for t0, t1 in self.spans.get(name, ())]

    def counter(self, name: str) -> float:
        return self.counters.get(name, 0)
