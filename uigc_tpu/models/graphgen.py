"""Synthetic actor-reference graph generators (the benchmark workloads).

Produces graphs directly in the kernel layout (ops/trace.py arrays):
power-law out-degree actor graphs with a controllable garbage fraction —
the BASELINE config-5 workload ("10M-actor power-law refob graph") — plus
the ring/clique cyclic-garbage topologies of config 3 and the deep chain
of config 1.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from ..ops import trace as trace_ops

_F = trace_ops


def powerlaw_actor_graph(
    n: int,
    seed: int = 0,
    garbage_fraction: float = 0.5,
    avg_degree: float = 3.0,
    alpha: float = 2.1,
    num_roots: int = 64,
) -> Dict[str, np.ndarray]:
    """A power-law refob graph of ``n`` actors.

    The live partition is reachable from ``num_roots`` root actors; the
    garbage partition (about ``garbage_fraction`` of actors) is only
    internally connected — including cycles — so a correct trace must
    leave it unmarked.  Out-degrees follow a zipf(alpha) distribution
    clipped to [1, 1000]; targets are biased toward low slot indices
    (preferential attachment), giving the hub-heavy shape of real actor
    systems.

    Returns dict of kernel arrays plus ``expected_garbage`` (bool[n]).
    """
    rng = np.random.default_rng(seed)
    n_garbage = int(n * garbage_fraction)
    n_live = n - n_garbage
    if n_live < 1:
        n_live, n_garbage = 1, n - 1
    num_roots = max(1, min(num_roots, n_live))

    # Slots [0, n_live) are the live partition (roots first), the rest is
    # the garbage partition.
    flags = np.full(n, _F.FLAG_IN_USE | _F.FLAG_INTERNED | _F.FLAG_LOCAL, dtype=np.uint8)
    flags[:num_roots] |= _F.FLAG_ROOT
    recv_count = np.zeros(n, dtype=np.int64)
    supervisor = np.full(n, -1, dtype=np.int32)

    # Supervision forest: every non-root live actor is supervised by a
    # lower live slot; garbage actors by a lower garbage slot (or the
    # garbage partition head, supervised by a live actor — the cascade
    # ancestor).
    live_ids = np.arange(1, n_live)
    supervisor[live_ids] = (rng.random(n_live - 1) * live_ids).astype(np.int32)
    if n_garbage > 1:
        g_ids = np.arange(n_live + 1, n)
        rel = g_ids - n_live
        supervisor[g_ids] = (n_live + (rng.random(n_garbage - 1) * rel)).astype(
            np.int32
        )
    if n_garbage > 0:
        supervisor[n_live] = 0  # oldest garbage ancestor, supervised live

    # Power-law out-degrees.
    degrees = np.minimum(rng.zipf(alpha, size=n), 1000)
    scale = avg_degree / max(degrees.mean(), 1e-9)
    degrees = np.maximum(1, (degrees * scale)).astype(np.int64)
    total_edges = int(degrees.sum())

    src = np.repeat(np.arange(n, dtype=np.int32), degrees)
    # Preferential attachment within each partition: target = floor(u^2 *
    # partition_size) biases toward low slots (hubs).
    u = rng.random(total_edges)
    src_is_live = src < n_live
    tgt_live = (u * u * n_live).astype(np.int32)
    tgt_garbage = (n_live + (u * u * n_garbage)).astype(np.int32)
    dst = np.where(src_is_live, tgt_live, tgt_garbage).astype(np.int32)

    # Make the live partition actually reachable from the roots: chain
    # each live actor to its supervisor's slot via one guaranteed edge
    # (supervision edges don't propagate; add real ref edges downward).
    chain_src = supervisor[1:n_live].astype(np.int32)
    chain_dst = np.arange(1, n_live, dtype=np.int32)
    # And a garbage-internal cycle spine so garbage is cyclic, not just
    # disconnected: g_i -> g_{i+1} -> ... -> g_0.
    if n_garbage > 1:
        g = np.arange(n_live, n, dtype=np.int32)
        spine_src = g
        spine_dst = np.roll(g, -1)
    else:
        spine_src = np.empty(0, dtype=np.int32)
        spine_dst = np.empty(0, dtype=np.int32)

    edge_src = np.concatenate([src, chain_src, spine_src])
    edge_dst = np.concatenate([dst, chain_dst, spine_dst])
    edge_weight = np.ones(edge_src.shape[0], dtype=np.int64)

    expected_garbage = np.zeros(n, dtype=bool)
    expected_garbage[n_live:] = True

    return {
        "flags": flags,
        "recv_count": recv_count,
        "supervisor": supervisor,
        "edge_src": edge_src,
        "edge_dst": edge_dst,
        "edge_weight": edge_weight,
        "expected_garbage": expected_garbage,
        "n_live": n_live,
        "n_garbage": n_garbage,
    }


def chain_actor_graph(n: int, garbage_fraction: float = 0.5) -> Dict[str, np.ndarray]:
    """A graph as deep as it is long (BASELINE config 1, the upstream
    default test workload: an acyclic chain, every actor spawned by,
    supervised by and referenced from the one before it), beside a
    released ring (config 3) as its garbage half.

    Slots ``[0, n_live)`` are one chain: slot 0 is the only root,
    ``supervisor[i] = i - 1`` and one reference ``i - 1 -> i``.  Slots
    ``[n_live, n)`` are one ring ``g -> g + 1 -> ... -> n_live`` with the
    same supervisor pointers inside it; its head is supervised by slot 0
    (a child keeps its supervisor alive, not the other way round) and has
    no reference from outside, so the ring is garbage.  Slot order is
    spawn order, as the runtime interns.  A push fixpoint needs one sweep
    per hop here (``n_live - 1`` of them): the graph the pointer jump is
    for.  No randomness; same return dict as ``powerlaw_actor_graph``."""
    n_garbage = int(n * garbage_fraction)
    n_live = n - n_garbage
    if n_live < 1:
        n_live, n_garbage = 1, n - 1

    flags = np.full(n, _F.FLAG_IN_USE | _F.FLAG_INTERNED | _F.FLAG_LOCAL, dtype=np.uint8)
    flags[0] |= _F.FLAG_ROOT
    supervisor = np.arange(-1, n - 1, dtype=np.int32)
    if n_garbage > 0:
        supervisor[n_live] = 0

    chain = np.arange(n_live, dtype=np.int32)
    ring = np.arange(n_live, n, dtype=np.int32)
    ring_dst = np.roll(ring, -1) if n_garbage > 1 else ring[:0]
    edge_src = np.concatenate([chain[:-1], ring[: ring_dst.size]])
    edge_dst = np.concatenate([chain[1:], ring_dst])

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


def ring_graph(n_rings: int, ring_size: int, live: bool = False) -> Dict[str, np.ndarray]:
    """Mutually-referencing actor rings (BASELINE config 3: cyclic
    garbage).  If ``live`` is False the rings have no owners and are all
    garbage; otherwise slot 0 is a root owning one member of each ring."""
    n = n_rings * ring_size + 1
    flags = np.full(n, _F.FLAG_IN_USE | _F.FLAG_INTERNED | _F.FLAG_LOCAL, dtype=np.uint8)
    flags[0] |= _F.FLAG_ROOT
    recv_count = np.zeros(n, dtype=np.int64)
    supervisor = np.full(n, -1, dtype=np.int32)
    supervisor[1:] = 0

    members = np.arange(1, n, dtype=np.int32).reshape(n_rings, ring_size)
    src = members.reshape(-1)
    dst = np.roll(members, -1, axis=1).reshape(-1)
    if live:
        root_src = np.zeros(n_rings, dtype=np.int32)
        root_dst = members[:, 0]
        src = np.concatenate([src, root_src])
        dst = np.concatenate([dst, root_dst])
    weight = np.ones(src.shape[0], dtype=np.int64)

    expected_garbage = np.zeros(n, dtype=bool)
    if not live:
        expected_garbage[1:] = True
    return {
        "flags": flags,
        "recv_count": recv_count,
        "supervisor": supervisor,
        "edge_src": src,
        "edge_dst": dst,
        "edge_weight": weight,
        "expected_garbage": expected_garbage,
        "n_live": n if live else 1,
        "n_garbage": 0 if live else n - 1,
    }
