"""``kernel_ms.kron``: ``kernel_ms`` (``layers/kernel_ms.py``) in the Kronecker graph's
re-derivation cell, where it moves that cell's own end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "kernel_ms")
