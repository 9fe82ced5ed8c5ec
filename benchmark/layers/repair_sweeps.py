"""``repair_sweeps``: sweeps of the repair fixpoint (``r_body`` iterations), median over the window's wakes.  Counted by
the wake program itself, every wake (``ops/pallas_decremental.py``: the
``n_sweeps`` it leaves on the device), read back after the window through
``DecrementalTracer.wake_stats()`` of the tracer that
``pallas_decremental.live_tracers()`` finds."""

from harness.program_trace import sweeps_per_wake


def read(obs):
    return sweeps_per_wake(obs, "n_sweeps")
