"""``fold_ms.served``: ``WakeProfiler`` ``fold`` phase: merging the drained batch into the shadow graph (``engines/crgc/arrays.py``), median per wake over the wakes of the
window that called the device (a wake with nothing new to trace takes
microseconds); the driver polls the profiler once a second."""

from harness.stats import percentile


def read(obs):
    wakes = [r for r in obs.facts.get("program_wakes") or () if r["device_s"] > 0]
    if not wakes:
        return None
    return percentile([r["phases"]["fold"] * 1e3 for r in wakes], 50)
