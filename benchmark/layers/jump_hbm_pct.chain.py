"""``jump_hbm_pct.chain``: the pointer jump's share of the memory
roofline: the bytes its sweeps ask for per wake (``roofline_jump.py
jump_bytes``: 4 bytes an element read, gathered or written, counted from
the shapes) over ``jump_ms.chain``, against the device's published
``hbm_bytes_per_s`` (``harness/peaks.json``).  The jump is a string of
data-dependent gathers, so this reads far under 100%: latency bounds it,
not bandwidth.  Nothing without a traced run, without jump sweeps, or on
a device that is not in the table of peaks."""

from harness.cell import reader_of
from harness.device import device_info, peaks_for
from harness.program_trace import sweeps_per_wake
from roofline_jump import jump_bytes, traced_actors

jump_ms = reader_of("layers", "jump_ms")


def read(obs):
    ms = jump_ms(obs)
    sweeps = sweeps_per_wake(obs, "jump_sweeps")
    n = traced_actors()
    if not ms or not sweeps or n is None:
        return None
    try:
        peak = peaks_for(device_info()["kind"])["hbm_bytes_per_s"]
    except SystemExit:  # the CPU of a rehearsal has no peak
        return None
    return 100.0 * jump_bytes(n, sweeps) / (ms * 1e-3) / peak
