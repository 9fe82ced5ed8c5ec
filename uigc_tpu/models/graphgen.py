"""Synthetic actor-reference graph generators (the benchmark workloads).

Produces graphs directly in the kernel layout (ops/trace.py arrays):
power-law out-degree actor graphs with a controllable garbage fraction —
the BASELINE config-5 workload ("10M-actor power-law refob graph") — plus
the ring/clique cyclic-garbage topologies of config 3, the deep chain
of config 1, and the Graph500 Kronecker graph, whose slot order knows
nothing of liveness.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
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


#: the Graph500 specification's R-MAT initiator (D = 1 - A - B - C = 0.05)
KRON_A, KRON_B, KRON_C = 0.57, 0.19, 0.19
#: draws per random stream: a chunk has a stream of its own, so the graph
#: of a seed does not depend on how many threads drew it
_KRON_CHUNK = 1 << 20


def _kron_draw(scale: int, seed: int, lo: int, hi: int):
    """Draws ``[lo, hi)`` of the Kronecker recursion, as the spec's
    reference generator makes them: one quadrant of the initiator per bit
    of the vertex id, two float32 uniforms a bit."""
    rng = np.random.default_rng([seed, lo // _KRON_CHUNK])
    ab = np.float32(KRON_A + KRON_B)
    c_norm = np.float32(KRON_C / (1.0 - (KRON_A + KRON_B)))
    a_norm = np.float32(KRON_A / (KRON_A + KRON_B))
    src = np.zeros(hi - lo, np.int32)
    dst = np.zeros(hi - lo, np.int32)
    for b in range(scale):
        ii = rng.random(hi - lo, dtype=np.float32) > ab
        jj = rng.random(hi - lo, dtype=np.float32) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.int32) << b
        dst |= jj.astype(np.int32) << b
    return src, dst


def _run_heads(a: np.ndarray) -> np.ndarray:
    """Where each run of equal values in the sorted ``a`` starts."""
    head = np.ones(a.size, bool)
    np.not_equal(a[1:], a[:-1], out=head[1:])
    return head


def kron_pairs(scale: int, seed: int, edgefactor: int = 16, roots: int = 64):
    """The Graph500 Kronecker generator (R-MAT A .57, B .19, C .19,
    ``edgefactor * 2**scale`` draws, vertex labels and edge order
    permuted) as a refob graph: ``(edge_src, edge_dst, edge_weight,
    supervisor, roots)``.

    What the spec does not say (its edges have no direction, its graph no
    supervisor): a drawn pair ``(src, dst)`` is a reference held by
    ``src`` to ``dst``; self-pairs are dropped; a pair drawn k times is
    one reference of weight k; the search keys, ``roots`` actors drawn
    among those with a reference in or out, are the roots;
    ``supervisor[v]`` is the smallest-labelled actor below ``v`` that
    holds a reference to ``v``, else ``roots[v mod len(roots)]``, and a
    root has none: every parent's label is below its child's or the
    parent is a root, so the pointers are a forest.  The edge order is
    permuted after the duplicates are merged (the one sort that merges
    them would undo a permutation made before it)."""
    n, m = 1 << scale, edgefactor << scale
    if 2 * scale >= 63:
        raise ValueError("a pair's key is dst << scale | src in an int64")
    spans = [(lo, min(lo + _KRON_CHUNK, m)) for lo in range(0, m, _KRON_CHUNK)]
    with ThreadPoolExecutor(8) as pool:  # numpy draws without the GIL
        parts = list(pool.map(lambda span: _kron_draw(scale, seed, *span), spans))
    rng = np.random.default_rng([seed, 1 << 30])
    label = rng.permutation(n).astype(np.int32)  # the permuted label IS the slot id
    src = label[np.concatenate([p[0] for p in parts])]
    dst = label[np.concatenate([p[1] for p in parts])]
    del parts

    # one sort, by (dst, src): duplicates become one reference of their
    # count, and an actor's holders stand together, smallest label first
    key = dst.astype(np.int64)
    key <<= scale
    key |= src
    key = key[src != dst]
    del src, dst
    key.sort()
    at = np.flatnonzero(_run_heads(key))
    edge_weight = np.diff(at, append=key.size)
    key = key[at]
    edge_dst = (key >> scale).astype(np.int32)
    edge_src = (key & (n - 1)).astype(np.int32)
    del key, at

    degree = np.bincount(edge_src, minlength=n) + np.bincount(edge_dst, minlength=n)
    held = np.flatnonzero(degree > 0)
    root_ids = np.sort(rng.choice(held, min(roots, held.size), replace=False)).astype(np.int32)

    supervisor = root_ids[np.arange(n) % root_ids.size]
    # an actor's first holder is its smallest
    below = _run_heads(edge_dst) & (edge_src < edge_dst)
    supervisor[edge_dst[below]] = edge_src[below]
    supervisor[root_ids] = -1

    order = rng.permutation(edge_src.size)
    return edge_src[order], edge_dst[order], edge_weight[order], supervisor, root_ids


def kron_actor_graph(
    scale: int, seed: int = 0, edgefactor: int = 16, roots: int = 64
) -> Dict[str, np.ndarray]:
    """A Graph500 Kronecker reference graph of ``2**scale`` actors
    (:func:`kron_pairs`): hubs that a large share of the actors hold, a
    heavy tail, a small diameter, and slot ids that say nothing about who
    is alive.  The roots are all ``FLAG_ROOT`` in one trace, nobody is
    busy and no message is undelivered; garbage is whatever the roots do
    not reach, about half the actors, most of them holding and held by
    nothing, scattered over every walk chunk.  So ``expected_garbage``
    comes from the oracle (``trace_marks_np``) and ``n_live`` is a count
    and no slot boundary.  Same return dict as ``powerlaw_actor_graph``."""
    edge_src, edge_dst, edge_weight, supervisor, root_ids = kron_pairs(
        scale, seed, edgefactor, roots
    )
    n = 1 << scale
    flags = np.full(n, _F.FLAG_IN_USE | _F.FLAG_INTERNED | _F.FLAG_LOCAL, dtype=np.uint8)
    flags[root_ids] |= _F.FLAG_ROOT
    recv_count = np.zeros(n, dtype=np.int64)
    marks = _F.trace_marks_np(
        flags, recv_count, supervisor, edge_src, edge_dst, edge_weight
    )
    n_live = int(marks.sum())
    return {
        "flags": flags,
        "recv_count": recv_count,
        "supervisor": supervisor,
        "edge_src": edge_src,
        "edge_dst": edge_dst,
        "edge_weight": edge_weight,
        "expected_garbage": ~marks,
        "n_live": n_live,
        "n_garbage": n - n_live,
    }
