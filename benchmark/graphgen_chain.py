"""The deep chain's generator, for configuration ``chain-1m``.

A copy of ``uigc_tpu/models/graphgen.py chain_actor_graph`` as it stood
at PR 29, kept here because traffic generation belongs to the yardstick
(as ``graphgen.py``'s ``powerlaw`` is a copy of ``powerlaw_actor_graph``).
It is a file of its own, and ``drivers/tracer_wake_chain.py`` puts it into
``graphgen.GENERATORS`` at import, because a PR that is not a
``benchmark`` PR may only add files (``README-chain.md``).

``BASELINE.json`` configs[0] (upstream's default test workload: an
acyclic chain, every actor spawned by, supervised by and referenced from
the one before it) beside a released ring (configs[2]) as its garbage
half.  Slots ``[0, n_live)`` are the chain: slot 0 the only root,
``supervisor[i] = i - 1``, one reference ``i - 1 -> i``.  Slots
``[n_live, n)`` are one ring with the same supervisor pointers inside it;
its head is supervised by slot 0 (a child keeps its supervisor alive, not
the other way round) and nothing outside refers to it.  Slot order is
spawn order.  The graph has no randomness: ``seed`` is taken and unused.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from reference import FLAG_IN_USE, FLAG_INTERNED, FLAG_LOCAL, FLAG_ROOT


def chain(actors: int, seed: int, garbage_fraction: float) -> Dict[str, np.ndarray]:
    del seed  # nothing is drawn
    n = actors
    n_garbage = int(n * garbage_fraction)
    n_live = n - n_garbage
    if n_live < 1:
        n_live, n_garbage = 1, n - 1

    flags = np.full(n, FLAG_IN_USE | FLAG_INTERNED | FLAG_LOCAL, dtype=np.uint8)
    flags[0] |= FLAG_ROOT
    supervisor = np.arange(-1, n - 1, dtype=np.int32)
    if n_garbage > 0:
        supervisor[n_live] = 0

    links = np.arange(n_live, dtype=np.int32)
    ring = np.arange(n_live, n, dtype=np.int32)
    ring_dst = np.roll(ring, -1) if n_garbage > 1 else ring[:0]
    edge_src = np.concatenate([links[:-1], ring[: ring_dst.size]])
    edge_dst = np.concatenate([links[1:], ring_dst])

    expected_garbage = np.zeros(n, dtype=bool)
    expected_garbage[n_live:] = True
    return {
        "flags": flags,
        "recv_count": np.zeros(n, dtype=np.int64),
        "supervisor": supervisor,
        "edge_src": edge_src,
        "edge_dst": edge_dst,
        "edge_weight": np.ones(edge_src.shape[0], dtype=np.int64),
        "expected_garbage": expected_garbage,
        "n_live": n_live,
        "n_garbage": n_garbage,
    }
