"""``jump_sweeps.kron``: ``jump_sweeps.chain`` (``layers/jump_sweeps.chain.py``) in the Kronecker graph's
re-derivation cell, where it moves that cell's own end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "jump_sweeps.chain")
