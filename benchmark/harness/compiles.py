"""Count what compiles, or loads from the persistent cache, and when.

``jax.monitoring`` calls the listeners for every backend compile request
(``/jax/core/compile/backend_compile_duration`` brackets both a real
compile and a load from the persistent cache) and for every persistent
cache hit.  The window must see none: every shape is warmed in set-up.
"""

from __future__ import annotations

import time
from typing import List, Tuple

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileWatch:
    def __init__(self) -> None:
        #: (host clock at the end of the request, its seconds, the function's name)
        self.requests: List[Tuple[float, float, str]] = []
        self.cache_hits: List[float] = []

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.requests.append((time.perf_counter(), seconds, str(kw.get("fun_name", "?"))))

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            self.cache_hits.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> Tuple[int, int, float, List[str]]:
        """(compile requests, of which cache hits, their seconds, the
        functions' names) that ended inside ``[t0, t1]``."""
        inside = [(s, name) for t, s, name in self.requests if t0 <= t <= t1]
        hits = sum(1 for t in self.cache_hits if t0 <= t <= t1)
        return len(inside), hits, sum(s for s, _ in inside), [name for _, name in inside]
