"""The Graph500 Kronecker graph's generator, for configuration ``kron-s22``.

A copy of ``uigc_tpu/models/graphgen.py kron_pairs`` / ``kron_actor_graph``
as they stood at PR 44, kept here because traffic generation belongs to
the yardstick (as ``graphgen.py``'s ``powerlaw`` is a copy of
``powerlaw_actor_graph`` and ``graphgen_chain.py``'s ``chain`` of
``chain_actor_graph``).  It is a file of its own, and
``drivers/tracer_wake_kron.py`` puts it into ``graphgen.GENERATORS`` at
import, because a PR that is not a ``benchmark`` PR may only add files
(``README-kron.md``).

Source: the Graph500 benchmark specification, Kronecker generator: R-MAT
initiator A 0.57, B 0.19, C 0.19, D 0.05, ``edgefactor * 2**scale`` draws
(edgefactor 16), vertex labels and edge order permuted, 64 search keys
drawn among vertices of degree > 0.  Written from memory; what the spec
does not say is in ``kron_pairs``'s docstring and in the configuration
file's ``assumed``.  The permuted label IS the slot id: the slot order
says nothing about who is alive, so this generator, unlike the other two,
returns no ``expected_garbage`` and no ``n_live``: garbage is whatever
the reference says (the driver asks ``reference_bfs``).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

from reference import FLAG_IN_USE, FLAG_INTERNED, FLAG_LOCAL, FLAG_ROOT

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


def kron(scale: int, seed: int, edgefactor: int = 16, roots: int = 64) -> Dict[str, np.ndarray]:
    """The graph as kernel arrays: the roots all ``FLAG_ROOT`` in one
    trace, nobody busy, no message undelivered."""
    edge_src, edge_dst, edge_weight, supervisor, root_ids = kron_pairs(
        scale, seed, edgefactor, roots
    )
    n = 1 << scale
    flags = np.full(n, FLAG_IN_USE | FLAG_INTERNED | FLAG_LOCAL, dtype=np.uint8)
    flags[root_ids] |= FLAG_ROOT
    return {
        "flags": flags,
        "recv_count": np.zeros(n, dtype=np.int64),
        "supervisor": supervisor,
        "edge_src": edge_src,
        "edge_dst": edge_dst,
        "edge_weight": edge_weight,
    }
