"""``dirty_chunks_pct.kron``: of the walk chunks a derivation's sweeps could have walked (sweeps x the layout's walk
chunks), the share that was dirty (``100 * sum(dirty_chunks) / (n_sweeps * walk_chunks)``; median over the window's
wakes).  ``dirty_chunks`` is counted per repair sweep by the wake program itself (``ops/pallas_decremental.py``: ``n_dirty``
in ``r_body``'s carry), read back after the window through ``DecrementalTracer.wake_stats()``; the layout's walk chunks
are left in ``obs.facts["walk_chunks"]`` by the driver (``drivers/tracer_wake_kron.py``, from the packed layout's rows).
The frontier's own number: on a graph whose slot order follows liveness few chunks are dirty in a sweep and the grid
skips the blocks over the clean ones; here nearly all are (98 of 7 x 16 simulated).  A wake of more sweeps than the
program keeps rows for (``pt.MAX_SWEEP_STATS``) folds the later ones into the last row: it is left out."""

from harness.program_trace import percentile, window_wake_stats


def of(stats, walk_chunks):
    if not stats or not walk_chunks or "dirty_chunks" not in stats[0]:
        return None
    shares = [100.0 * sum(w["dirty_chunks"]) / (w["n_sweeps"] * walk_chunks)
              for w in stats if 0 < w["n_sweeps"] == len(w["dirty_chunks"])]
    return percentile(shares, 50) if shares else None


def read(obs):
    return of(window_wake_stats(obs), obs.facts.get("walk_chunks"))
