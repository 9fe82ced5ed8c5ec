"""Graph generators for the benchmark's configurations, from a seed.

``powerlaw`` is a copy of ``uigc_tpu/models/graphgen.py
powerlaw_actor_graph`` as it stood at PR 24 (BASELINE config 5, the
"10M-actor power-law refob graph"): traffic generation belongs to the
yardstick.  Slots ``[0, n_live)`` are the live partition (roots first),
reachable from the roots by construction; the rest is garbage that is
only internally connected, cycles included.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from reference import FLAG_IN_USE, FLAG_INTERNED, FLAG_LOCAL, FLAG_ROOT


def powerlaw(
    actors: int,
    seed: int,
    garbage_fraction: float,
    avg_degree: float,
    zipf_alpha: float,
    roots: int,
) -> Dict[str, np.ndarray]:
    n = actors
    rng = np.random.default_rng(seed)
    n_garbage = int(n * garbage_fraction)
    n_live = n - n_garbage
    if n_live < 1:
        n_live, n_garbage = 1, n - 1
    roots = max(1, min(roots, n_live))

    flags = np.full(n, FLAG_IN_USE | FLAG_INTERNED | FLAG_LOCAL, dtype=np.uint8)
    flags[:roots] |= FLAG_ROOT
    recv_count = np.zeros(n, dtype=np.int64)
    supervisor = np.full(n, -1, dtype=np.int32)

    # Supervision forest: a live actor under a lower live slot, a garbage
    # actor under a lower garbage slot; the garbage head under live slot 0.
    live_ids = np.arange(1, n_live)
    supervisor[live_ids] = (rng.random(n_live - 1) * live_ids).astype(np.int32)
    if n_garbage > 1:
        g_ids = np.arange(n_live + 1, n)
        supervisor[g_ids] = (
            n_live + (rng.random(n_garbage - 1) * (g_ids - n_live))
        ).astype(np.int32)
    if n_garbage > 0:
        supervisor[n_live] = 0

    degrees = np.minimum(rng.zipf(zipf_alpha, size=n), 1000)
    scale = avg_degree / max(degrees.mean(), 1e-9)
    degrees = np.maximum(1, (degrees * scale)).astype(np.int64)
    total_edges = int(degrees.sum())

    src = np.repeat(np.arange(n, dtype=np.int32), degrees)
    # preferential attachment inside each partition: floor(u^2 * size)
    u = rng.random(total_edges)
    tgt_live = (u * u * n_live).astype(np.int32)
    tgt_garbage = (n_live + (u * u * n_garbage)).astype(np.int32)
    dst = np.where(src < n_live, tgt_live, tgt_garbage).astype(np.int32)

    # one guaranteed reference from each live actor's supervisor down to it
    chain_src = supervisor[1:n_live].astype(np.int32)
    chain_dst = np.arange(1, n_live, dtype=np.int32)
    # a cycle through the whole garbage partition
    if n_garbage > 1:
        spine_src = np.arange(n_live, n, dtype=np.int32)
        spine_dst = np.roll(spine_src, -1)
    else:
        spine_src = spine_dst = np.empty(0, dtype=np.int32)

    edge_src = np.concatenate([src, chain_src, spine_src])
    edge_dst = np.concatenate([dst, chain_dst, spine_dst])
    expected_garbage = np.zeros(n, dtype=bool)
    expected_garbage[n_live:] = True
    return {
        "flags": flags,
        "recv_count": recv_count,
        "supervisor": supervisor,
        "edge_src": edge_src,
        "edge_dst": edge_dst,
        "edge_weight": np.ones(edge_src.shape[0], dtype=np.int64),
        "expected_garbage": expected_garbage,
        "n_live": n_live,
        "n_garbage": n_garbage,
    }


GENERATORS = {"powerlaw": powerlaw}
