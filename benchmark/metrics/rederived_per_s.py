"""``rederived_per_s``: ``collected_per_s`` (``metrics/collected_per_s.py``) in
the re-derivation cell: garbage actors in the verdicts of the window's full
derivations, over the whole window: the ceiling for garbage actors/s."""

from harness.cell import reader_of

read = reader_of("metrics", "collected_per_s")
