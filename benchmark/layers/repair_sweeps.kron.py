"""``repair_sweeps.kron``: ``repair_sweeps`` (``layers/repair_sweeps.py``) in the Kronecker graph's
re-derivation cell, where it moves that cell's own end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "repair_sweeps")
