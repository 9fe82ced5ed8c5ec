"""``ingest_wait_ms.engine``: ``ingest_wait_ms.served`` (``layers/ingest_wait_ms.served.py``) in the engine-fold cell,
where the flush is ``PackedPlane.write_foreign``'s block, timed from when it was handed over: the ring write and the mailbox hop; it moves that cell's end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "ingest_wait_ms.served")
