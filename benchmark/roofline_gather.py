"""Bytes a chip receives in the sharded wake's all-gathers, counted from
shapes and from the program's own counters: the numerator of
``gather_ici_pct.mesh4``.

The sharded wake (``uigc_tpu/parallel/sharded_trace.py
make_sharded_decremental_wake``) keeps the packed mark table, one bit a
padded slot, sharded by slot range over the ``D`` chips of the mesh, and
rebuilds it whole on every chip by ``all_gather`` (scope
``uigc.wake/.../gather``): once a sweep of either loop and three or four
times around them, which the program counts itself (``gathers`` in
``MeshShadowGraph.wake_stats()``).  In one all-gather a chip holds
``n_pad / 8 / D`` bytes of the table and receives the other ``D - 1``
shards':

    (D - 1) / D  x  n_pad / 8   bytes a chip an all-gather.

At 2^24 padded slots on four chips that is 1,572,864 bytes; against a
chip's published interconnect of 1,600 Gbit/s (``harness/peaks.json``
``ici_bits_per_s``, all links of a chip together: on a 2x2 mesh not all
of them are wired, so the share this gives is a floor) 7.9 us.  The
all-gather is small: latency, not bandwidth, sets its time, and the share
reads far under 100%.
"""

from __future__ import annotations


def gather_bytes(n_pad: int, devices: int, gathers: float) -> float:
    """Bytes ONE chip receives in ``gathers`` all-gathers of the packed
    table of ``n_pad`` slots over ``devices`` chips."""
    return (devices - 1) / devices * (n_pad / 8) * gathers
