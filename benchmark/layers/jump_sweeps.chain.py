"""``jump_sweeps.chain``: repair sweeps that ran the pointer jump (the
sweeps of ``r_body`` whose ``lax.cond`` was taken), median over the
window's wakes.  Counted by the wake program itself, every wake
(``ops/pallas_decremental.py``: the ``jump_sweeps`` it leaves on the
device), read back after the window through
``DecrementalTracer.wake_stats()``, as ``repair_sweeps`` is."""

from harness.program_trace import sweeps_per_wake


def read(obs):
    return sweeps_per_wake(obs, "jump_sweeps")
