"""``device_idle_pct.kron``: ``device_idle_pct`` (``layers/device_idle_pct.py``) in the Kronecker graph's
re-derivation cell, where it moves that cell's own end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "device_idle_pct")
