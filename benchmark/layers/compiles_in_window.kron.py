"""``compiles_in_window.kron``: ``compiles_in_window`` (``layers/compiles_in_window.py``) in the Kronecker graph's
re-derivation cell, where it moves that cell's own end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "compiles_in_window")
