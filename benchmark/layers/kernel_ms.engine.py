"""``kernel_ms.engine``: ``kernel_ms`` (``layers/kernel_ms.py``) in the engine-fold cell,
where the wake is the collector's own (``drivers/engine_fold.py``) and moves that cell's end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "kernel_ms")
