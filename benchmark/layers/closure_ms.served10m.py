"""``closure_ms.served10m``: ``closure_ms`` (``layers/closure_ms.py``) in the ``served-10m`` cell,
where the wake is the collector's own, on its timer, beside 5M residents held by uid (``drivers/served_fold.py``).  The wakes it counts are the ``bench:wake`` spans that driver writes around the backend's device call."""

from harness.cell import reader_of

read = reader_of("layers", "closure_ms")
