"""``device_call_ms.served``: ``WakeProfiler`` ``device_s``: the host clock around upload + run + readback of the device call (``engines/crgc/arrays.py compute_marks``), so a device CALL, not device time, median per wake over the wakes of the
window that called the device (a wake with nothing new to trace takes
microseconds); the driver polls the profiler once a second."""

from harness.stats import percentile


def read(obs):
    wakes = [r for r in obs.facts.get("program_wakes") or () if r["device_s"] > 0]
    if not wakes:
        return None
    return percentile([r["device_s"] * 1e3 for r in wakes], 50)
