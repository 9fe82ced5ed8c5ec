"""``kernel_steps_pct``: the grid steps the propagate kernels took in a wake, as a share of what as many launches over
every block would take (``100 * kernel_steps / kernel_steps_full``; median over the window's wakes).  Both are counted
by the wake program itself, every wake, in the carries of its closure and repair loops (``ops/pallas_decremental.py``:
the list of blocks with work is as long as ``kernel_steps``), and read back after the window through
``DecrementalTracer.wake_stats()``.  Nothing on a program whose wakes carry no such counter: its grid visits every
block, 100 by construction."""

from harness.program_trace import percentile, window_wake_stats


def read(obs):
    stats = window_wake_stats(obs)
    if not stats or "kernel_steps" not in stats[0]:
        return None
    shares = [100.0 * w["kernel_steps"] / w["kernel_steps_full"] for w in stats if w["kernel_steps_full"]]
    return percentile(shares, 50) if shares else None
