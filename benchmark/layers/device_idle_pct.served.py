"""``device_idle_pct.served``: ``device_idle_pct`` (``layers/device_idle_pct.py``)
in the served cell, where it moves ``stopped_per_s``."""

from harness.cell import reader_of

read = reader_of("layers", "device_idle_pct")
