"""``pull_sweeps.kron``: repair sweeps that ran with the pull gate on (saturated destination tiles skipped), median over
the window's wakes.  ``pull_on`` is kept per sweep by the wake program itself (``ops/pallas_decremental.py``: under
``auto`` a sweep pulls when its dirty chunks reach the pull cut), read back after the window through
``DecrementalTracer.wake_stats()``.  Nothing on a program whose wakes carry no such row."""

from harness.program_trace import percentile, window_wake_stats


def of(stats):
    if not stats or "pull_on" not in stats[0]:
        return None
    return percentile([sum(w["pull_on"]) for w in stats], 50)


def read(obs):
    return of(window_wake_stats(obs))
