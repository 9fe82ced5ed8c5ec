"""``kernel_chunk_walks.kron``: the chunk-iterations the propagate kernels' walks took in a wake (per grid step, the dirty
chunks in the block's span: one dynamic-row table read, the lane gathers and the compare-selects each), median over the
window's wakes.  New in PR 44: summed by the wake program in the carries of its closure and repair loops
(``ops/pallas_decremental.py``) from the vector the list of active blocks is made from (``ops/pallas_trace.py
build_propagate``: ``block_iters``), read back after the window through ``DecrementalTracer.wake_stats()``.  The largest
term of the kernel's cost (0.213 us an iteration, PERF.md section 7), which until now only the simulator
(``tools/sweep_profile.py simulate_sweeps``: ``chunk_iterations``) could count.  Nothing on a program without the counter."""

from harness.program_trace import percentile, window_wake_stats


def of(stats):
    if not stats or "kernel_chunk_walks" not in stats[0]:
        return None
    return percentile([w["kernel_chunk_walks"] for w in stats], 50)


def read(obs):
    return of(window_wake_stats(obs))
