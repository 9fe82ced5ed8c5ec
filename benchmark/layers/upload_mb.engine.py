"""``upload_mb.engine``: what the wake's ``jax.device_put`` calls were handed (``_compute_marks_decremental`` notes ``upload_bytes``: the whole ``flags`` and ``recv_count`` arrays at the graph's capacity, every wake), in MB.
Median over the window's wakes that called the device, from the program's
``WakeProfiler`` records (``obs.facts["program_wakes"]``); nothing on a
program whose records carry no such counter."""

from harness.program_trace import device_wakes, percentile


def read(obs):
    values = [r["upload_bytes"] for r in device_wakes(obs) if "upload_bytes" in r]
    return percentile(values, 50) * 1e-06 if values else None
