"""``stop_cascade_ms.served``: the stop cascade under the GIL: ``WakeProfiler`` record field ``cascade_s``, from the end of a wake's ``sweep`` phase to the last termination of the local cells that sweep freed (``ArrayShadowGraph._sweep`` leaves the wake's ordinal on each, ``ActorCell._finalize`` reports to ``WakeProfiler.cell_terminated``; 0 where the last cell stopped before the sweep had ended).
Median over the window's wakes that called the device and whose freed cells
had ALL terminated when the driver polled the record (the others keep
``None`` and are left out); nothing on a program whose records lack the field."""

from harness.program_trace import device_wakes
from harness.stats import percentile


def read(obs):
    values = [r["cascade_s"] * 1e3 for r in device_wakes(obs) if r.get("cascade_s") is not None]
    return percentile(values, 50)
