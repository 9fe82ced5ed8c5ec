"""``stage_ms.engine``: ``stage_ms.served`` (``layers/stage_ms.served.py``) in the engine-fold cell,
where ``upload_ms.engine`` is 75 ms and nobody knew how much of it this is; it moves that cell's end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "stage_ms.served")
