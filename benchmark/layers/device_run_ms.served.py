"""``device_run_ms.served``: ``WakeProfiler`` ``device`` phase: the wake program from its dispatch to ``block_until_ready`` (``engines/crgc/arrays.py _compute_marks_decremental``); exclusive host-clock
bracket (``uigc_tpu/telemetry/profile.py``), median per wake over the
wakes of the window that called the device; the driver polls the
profiler once a second."""

from harness.program_trace import phase_ms


def read(obs):
    return phase_ms(obs, "device")
