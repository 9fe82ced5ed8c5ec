"""``wake_gap_ms.engine``: ``wake_gap_ms.served`` (``layers/wake_gap_ms.served.py``) in the engine-fold cell,
where the gap is the driver's own ``generate`` between two wakes; it moves that cell's end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "wake_gap_ms.served")
