"""``sweep_ms.served``: ``WakeProfiler`` ``sweep`` phase: kill decisions, ``StopMsg`` to the killed and slot frees (``ArrayShadowGraph.trace``); exclusive host-clock
bracket (``uigc_tpu/telemetry/profile.py``), median per wake over the
wakes of the window that called the device; the driver polls the
profiler once a second."""

from harness.program_trace import phase_ms


def read(obs):
    return phase_ms(obs, "sweep")
