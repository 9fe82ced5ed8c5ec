"""``ingest_wait_ms.served``: how long the oldest flush a wake drained had waited: ``WakeProfiler`` record field ``ingest_wait_s`` (``engines/crgc/collector.py _ingest_wait``: the ``perf_counter`` of the first ring write or queued ``Entry`` since the drain before, taken by the writers while a profiler is attached, against the start of the wake's ``ingest`` phase).
Median over the window's wakes that called the device and drained something;
nothing on a program whose records lack the field."""

from harness.program_trace import device_wakes
from harness.stats import percentile


def read(obs):
    values = [r["ingest_wait_s"] * 1e3 for r in device_wakes(obs) if r.get("ingest_wait_s") is not None]
    return percentile(values, 50)
