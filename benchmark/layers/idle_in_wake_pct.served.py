"""``idle_in_wake_pct.served``: of the device's idle seconds in the traced
interval, the share that lies inside a ``uigc:wake`` annotation: the
collector was awake and on the host (ingest, fold, layout, upload, sweep,
or waiting for the GIL inside a wake).  The rest is idle while the
collector slept or waited to be scheduled."""

from harness.program_trace import PROGRAM_PREFIX, program_trace


def read(obs):
    trace = program_trace(obs)
    if trace is None:
        return None
    share = trace.idle_share_inside(PROGRAM_PREFIX + "wake")
    return None if share is None or not trace.annotations else 100.0 * share
