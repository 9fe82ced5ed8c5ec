"""``frontier_ms.kron``: ``frontier_ms`` (``layers/frontier_ms.py``) in the Kronecker graph's
re-derivation cell, where it moves that cell's own end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "frontier_ms")
