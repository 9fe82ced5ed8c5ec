"""``device_busy_ms.served``: device time of a served wake: the device's busy
milliseconds (union of its op-line events) inside each ``uigc:device``
annotation, which the program's wake profiler writes from the wake
program's dispatch to ``block_until_ready``; median over the annotations
in the traced interval.  Beside ``device_run_ms.served``, the host clock of
the same bracket."""

from harness.program_trace import PROGRAM_PREFIX, annotation_busy_ms


def read(obs):
    return annotation_busy_ms(obs, PROGRAM_PREFIX + "device")
