"""``idle_in_wake_pct.served10m``: ``idle_in_wake_pct.served`` (``layers/idle_in_wake_pct.served.py``) in the ``served-10m`` cell,
where the wake is the collector's own, on its timer, beside 5M residents held by uid (``drivers/served_fold.py``)."""

from harness.cell import reader_of

read = reader_of("layers", "idle_in_wake_pct.served")
