"""``device_idle_pct.chain``: ``device_idle_pct`` (``layers/device_idle_pct.py``) in the deep chain's
re-derivation cell, where it moves that cell's own end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "device_idle_pct")
