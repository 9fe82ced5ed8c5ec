"""``upload_ms.served``: ``WakeProfiler`` ``upload`` phase: layout deltas, suspect id words, flags and receive counts to the device (``DecrementalTracer.stage_wake`` and two ``device_put``); exclusive host-clock
bracket (``uigc_tpu/telemetry/profile.py``), median per wake over the
wakes of the window that called the device; the driver polls the
profiler once a second."""

from harness.program_trace import phase_ms


def read(obs):
    return phase_ms(obs, "upload")
