"""``dispatch_ms.engine``: ``dispatch_ms.served`` (``layers/dispatch_ms.served.py``) in the engine-fold cell,
where the wake is the collector's own (``drivers/engine_fold.py``); it moves that cell's end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "dispatch_ms.served")
