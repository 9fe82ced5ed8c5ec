"""``kernel_ms.mesh4``: device milliseconds of the propagate kernel per wake on the SLOWEST shard of the mesh: per device
plane the summed durations of the Mosaic custom-call events in the traced interval (found as ``layers/kernel_ms.py``
finds them), the maximum over the planes, over the wakes that lie wholly inside the interval.  The shards run one
program in step and meet in an all-gather every sweep, so a wake's kernel time is its fullest shard's; ``layers/kernel_ms.py``
sums the planes (``harness/trace.py seconds_of``), which on four chips reads four chips' time as one's."""

from harness.mesh_trace import kernel_ms_slowest_plane as read
