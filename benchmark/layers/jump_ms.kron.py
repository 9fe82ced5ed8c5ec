"""``jump_ms.kron``: ``jump_ms`` (``layers/jump_ms.py``) in the Kronecker graph's
re-derivation cell, where it moves that cell's own end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "jump_ms")
