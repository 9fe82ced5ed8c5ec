"""``ingest_ms.served``: ``WakeProfiler`` ``ingest`` phase: draining the mutator entry queue and the packed rows (``collector.py _collect_inner``); exclusive host-clock
bracket (``uigc_tpu/telemetry/profile.py``), median per wake over the
wakes of the window that called the device; the driver polls the
profiler once a second."""

from harness.program_trace import phase_ms


def read(obs):
    return phase_ms(obs, "ingest")
