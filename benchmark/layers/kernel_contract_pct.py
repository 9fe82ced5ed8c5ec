"""``kernel_contract_pct``: of the grid steps the propagate kernels took in a wake, the share that paid for a contraction
(``100 * kernel_contractions / kernel_steps``; median over the window's wakes).  A walked block builds its one-hot
operands and runs the MXU only when its gather found a bit that is new since the sweep before; the kernel counts those
steps itself (an SMEM scalar, ``ops/pallas_trace.py build_propagate``), the wake program sums them in the carries of its
closure and repair loops beside ``kernel_steps`` (``ops/pallas_decremental.py``), and both are read back after the window
through ``DecrementalTracer.wake_stats()``.  Nothing on a program whose wakes carry no such counter: every step of its
grid contracts, 100 by construction."""

from harness.program_trace import percentile, window_wake_stats


def read(obs):
    stats = window_wake_stats(obs)
    if not stats or "kernel_contractions" not in stats[0]:
        return None
    shares = [100.0 * w["kernel_contractions"] / w["kernel_steps"] for w in stats if w["kernel_steps"]]
    return percentile(shares, 50) if shares else None
