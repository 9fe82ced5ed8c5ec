"""``wake_wall_ms.served``: ``WakeProfiler`` ``wall_s`` of a collector wake (``engines/crgc/collector.py collect()``), median per wake over the wakes of the
window that called the device (a wake with nothing new to trace takes
microseconds); the driver polls the profiler once a second."""

from harness.stats import percentile


def read(obs):
    wakes = [r for r in obs.facts.get("program_wakes") or () if r["device_s"] > 0]
    if not wakes:
        return None
    return percentile([r["wall_s"] * 1e3 for r in wakes], 50)
