"""Bytes the pointer jump moves, counted from shapes: the numerator of
``jump_hbm_pct.chain``.

What ONE engaged sweep of the wake program asks of the memory
(``ops/pallas_trace.py jump_sweep`` and the pack of its hits in
``ops/pallas_decremental.py run_jump``), in elements over the ``n``
actors, each counted at 4 bytes whether it is read in a stream, gathered
or written (the planes of bools are smaller; the count is what the
algorithm asks for, not the transactions the memory serves, and a gather
of 4 bytes costs a transaction of 32 or more):

- ``jump/hits``: the parents read (n), their bits gathered from the
  table (n), the hit plane written (n): 3 n;
- ``jump/double``, per doubling: the parents read (n), their parents
  gathered (n), their transparency bits gathered (n), the parents written
  (n): 4 n;
- ``jump/pack``: the hit plane read (n), and three word tables of n / 32
  read (the packed hits' mask, the marks, the in-use bits) and one
  written: n + 4 n / 32.

With ``steps`` doublings a sweep that is (4.125 + 4 x steps) x n elements,
of which 1 + 2 x steps are gathers: 12.125 n, 48.5 bytes an actor, at the
program's ``JUMP_STEPS`` of 2.
"""

from __future__ import annotations

from typing import Optional

BYTES_PER_ELEMENT = 4
#: doublings a jump sweep where the program does not say (its JUMP_STEPS)
DEFAULT_STEPS = 2


def program_steps() -> int:
    try:
        from uigc_tpu.ops.pallas_trace import JUMP_STEPS
    except ImportError:
        return DEFAULT_STEPS
    return int(JUMP_STEPS)


def elements_per_sweep(n: int, steps: int) -> float:
    hits = 3 * n
    double = 4 * n * steps
    pack = n + 4 * n / 32
    return hits + double + pack


def jump_bytes(n: int, jump_sweeps: float, steps: Optional[int] = None) -> float:
    """Bytes that ``jump_sweeps`` engaged sweeps over ``n`` actors ask for."""
    if steps is None:
        steps = program_steps()
    return BYTES_PER_ELEMENT * elements_per_sweep(n, steps) * jump_sweeps


def traced_actors() -> Optional[int]:
    """Actors of the one tracer alive in this process that has run a wake
    (found as ``harness/program_trace.py window_wake_stats`` finds it)."""
    try:
        from uigc_tpu.ops import pallas_decremental
    except ImportError:
        return None
    live = getattr(pallas_decremental, "live_tracers", None)
    if live is None:
        return None
    tracers = [t for t in live() if len(t.wake_stats(1))]
    return int(tracers[0].n) if len(tracers) == 1 else None
