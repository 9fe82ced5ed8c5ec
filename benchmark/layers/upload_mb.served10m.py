"""``upload_mb.served10m``: ``upload_mb.engine`` (``layers/upload_mb.engine.py``) in the ``served-10m`` cell,
where the wake is the collector's own, on its timer, beside 5M residents held by uid (``drivers/served_fold.py``)."""

from harness.cell import reader_of

read = reader_of("layers", "upload_mb.engine")
