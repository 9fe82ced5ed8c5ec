"""``readback_ms.served``: ``WakeProfiler`` ``readback`` phase: the verdicts unpacked on the device and copied to the host, with the wake's sweep counters; exclusive host-clock
bracket (``uigc_tpu/telemetry/profile.py``), median per wake over the
wakes of the window that called the device; the driver polls the
profiler once a second."""

from harness.program_trace import phase_ms


def read(obs):
    return phase_ms(obs, "readback")
