"""``tiles_skipped.kron``: destination supertiles the pull gate skipped, summed over a wake's repair sweeps (a tile counts
once for every sweep that skipped it), median over the window's wakes.  ``tiles_skipped`` is kept per sweep by the wake
program itself (``ops/pallas_decremental.py``: the saturated tiles of a sweep that pulled), read back after the window
through ``DecrementalTracer.wake_stats()``.  What the pull side saves on a graph the dirty-chunk frontier cannot thin:
a skipped tile's blocks are not walked.  Nothing on a program whose wakes carry no such row."""

from harness.program_trace import percentile, window_wake_stats


def of(stats):
    if not stats or "tiles_skipped" not in stats[0]:
        return None
    return percentile([sum(w["tiles_skipped"]) for w in stats], 50)


def read(obs):
    return of(window_wake_stats(obs))
