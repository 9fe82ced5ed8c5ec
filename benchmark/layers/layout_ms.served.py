"""``layout_ms.served``: ``WakeProfiler`` ``layout`` phase: kernel-layout maintenance of the backend (``engines/crgc/arrays.py _sync_layout``: ``apply_log`` or a ``rebuild``); exclusive host-clock
bracket (``uigc_tpu/telemetry/profile.py``), median per wake over the
wakes of the window that called the device; the driver polls the
profiler once a second."""

from harness.program_trace import phase_ms


def read(obs):
    return phase_ms(obs, "layout")
