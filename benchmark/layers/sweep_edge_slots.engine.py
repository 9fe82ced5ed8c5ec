"""``sweep_edge_slots.engine``: ``sweep_edge_slots.served`` (``layers/sweep_edge_slots.served.py``) in the engine-fold cell,
where 250 actors die a wake in a graph of 2^26 edge slots and the counter moves that cell's end-to-end metric."""

from harness.cell import reader_of

read = reader_of("layers", "sweep_edge_slots.served")
