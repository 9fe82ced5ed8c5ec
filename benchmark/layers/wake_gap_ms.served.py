"""``wake_gap_ms.served``: the collector between two wakes: ``WakeProfiler`` record field ``gap_s``, from the end of the wake before to the start of this one (the ``WAKEUP`` through the mailbox, the dispatcher's scheduling, the GIL, or the timer), on ``time.perf_counter()``.
Median over the window's wakes that called the device; nothing on a program
whose records lack the field."""

from harness.program_trace import device_wakes
from harness.stats import percentile


def read(obs):
    values = [r["gap_s"] * 1e3 for r in device_wakes(obs) if r.get("gap_s") is not None]
    return percentile(values, 50)
