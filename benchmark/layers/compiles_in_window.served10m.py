"""``compiles_in_window.served10m``: ``compiles_in_window`` (``layers/compiles_in_window.py``) in the ``served-10m`` cell,
where the wake is the collector's own, on its timer, beside 5M residents held by uid (``drivers/served_fold.py``)."""

from harness.cell import reader_of

read = reader_of("layers", "compiles_in_window")
