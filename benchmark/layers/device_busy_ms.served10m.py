"""``device_busy_ms.served10m``: ``device_busy_ms.served`` (``layers/device_busy_ms.served.py``) in the ``served-10m`` cell,
where the wake is the collector's own, on its timer, beside 5M residents held by uid (``drivers/served_fold.py``)."""

from harness.cell import reader_of

read = reader_of("layers", "device_busy_ms.served")
