"""``detect_ms.engine``: median of the ``detect_ms`` samples (``metrics/detect_p50_ms.py``'s number): the host clock from
a batch of rows being handed to the plane until the wake's uids are in the driver's hands.  A per-layer metric in the
engine-fold cell, beside the throughput it moves: four fifths of such a wake are numpy passes on the host, the six runs'
medians spread by 1.4% (my chip runs, PR 33), and ``detect_p50_ms`` carries a bound of 1% that was set for a wake that
waits on the device."""

from harness.cell import reader_of

read = reader_of("metrics", "detect_p50_ms")
