"""``closure_bailed``: wakes whose suspect closure gave up at its price and took the derivation from the seeds instead
(0 or 1 a wake; median over the window's wakes).  Counted by the wake program itself, every wake
(``ops/pallas_decremental.py``: the ``closure_bailed`` it leaves on the device beside ``closure_sweeps``), read back
after the window through ``DecrementalTracer.wake_stats()``.  Nothing on a program whose wakes carry no such counter."""

from harness.program_trace import percentile, window_wake_stats


def read(obs):
    stats = window_wake_stats(obs)
    if not stats or "closure_bailed" not in stats[0]:
        return None
    return percentile([w["closure_bailed"] for w in stats], 50)
