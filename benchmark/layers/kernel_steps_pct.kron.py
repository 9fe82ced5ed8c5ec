"""``kernel_steps_pct.kron``: ``kernel_steps_pct`` (``layers/kernel_steps_pct.py``) in the Kronecker graph's
re-derivation cell, where it moves that cell's own end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "kernel_steps_pct")
