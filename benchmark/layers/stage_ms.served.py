"""``stage_ms.served``: ``DecrementalTracer.stage_wake`` inside the ``upload`` phase (the layout's tier deltas and jump-parent writes, the suspect id words scattered on the host and put): ``WakeProfiler`` record field ``stage_s`` (annotation ``uigc:stage`` inside ``uigc:upload``); ``upload_ms.served`` less this is the two whole-array ``device_put``s.
Median over the window's wakes that called the device; nothing on a program
whose records lack the field."""

from harness.program_trace import device_wakes
from harness.stats import percentile


def read(obs):
    values = [r["stage_s"] * 1e3 for r in device_wakes(obs) if r.get("stage_s") is not None]
    return percentile(values, 50)
