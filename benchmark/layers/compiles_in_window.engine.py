"""``compiles_in_window.engine``: ``compiles_in_window`` (``layers/compiles_in_window.py``) in the engine-fold cell,
where the wake is the collector's own (``drivers/engine_fold.py``) and moves that cell's end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "compiles_in_window")
