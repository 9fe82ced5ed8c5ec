"""``dispatch_ms.served``: the host's side of the wake program's dispatch: ``WakeProfiler`` record field ``dispatch_s``, inside the ``device`` phase the time until ``DecrementalTracer.wake_device`` returned (annotation ``uigc:dispatch`` inside ``uigc:device``); ``device_run_ms.served`` less this is the wait for the result.
Median over the window's wakes that called the device; nothing on a program
whose records lack the field."""

from harness.program_trace import device_wakes
from harness.stats import percentile


def read(obs):
    values = [r["dispatch_s"] * 1e3 for r in device_wakes(obs) if r.get("dispatch_s") is not None]
    return percentile(values, 50)
