"""The Graph500 Kronecker graph (``models/graphgen.py kron_actor_graph``):
hubs, a heavy tail and a slot order that knows nothing of liveness, so
garbage is whatever the oracle says and never a partition of the slots.
Its shape, the tracer's verdicts on it in every push/pull mode, the wake
program's kernel counters against ``tools/sweep_profile.py
simulate_sweeps`` (the oracle of ``kernel_steps``, ``kernel_contractions``
and ``kernel_chunk_walks``), and the graph under churn."""

import os
import sys

import numpy as np
import pytest

from uigc_tpu.models.graphgen import KRON_A, KRON_B, KRON_C, kron_actor_graph, kron_pairs
from uigc_tpu.ops import pallas_decremental as pd
from uigc_tpu.ops import pallas_trace as pt
from uigc_tpu.ops import trace as F
from uigc_tpu.ops.pallas_incremental import EDGE

CHUNK = 8 * 128 * 32  # actors in one walk chunk at the interpreted geometry
SMALL, TWO_CHUNKS = 12, 16  # scales: under one walk chunk, and two of them


def _sweep_profile():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        import sweep_profile
    finally:
        sys.path.pop(0)
    return sweep_profile


def _oracle(g):
    return F.trace_marks_np(
        g["flags"], g["recv_count"], g["supervisor"],
        g["edge_src"], g["edge_dst"], g["edge_weight"],
    )


@pytest.fixture(scope="module")
def two_chunks():
    return kron_actor_graph(TWO_CHUNKS, seed=0)


@pytest.mark.parametrize("scale,seed,edgefactor", [(10, 0, 16), (SMALL, 3, 16), (11, 2**31 + 5, 8)])
def test_kron_graph_shape(scale, seed, edgefactor):
    g = kron_actor_graph(scale, seed=seed, edgefactor=edgefactor)
    n = 1 << scale
    src, dst, weight, sup = g["edge_src"], g["edge_dst"], g["edge_weight"], g["supervisor"]
    assert g["flags"].shape == g["recv_count"].shape == sup.shape == (n,)
    assert (src.dtype, dst.dtype, weight.dtype, sup.dtype) == (np.int32, np.int32, np.int64, np.int32)
    assert src.min() >= 0 and dst.min() >= 0 and max(src.max(), dst.max()) < n
    # no self-pair, no pair twice; a pair drawn k times is one reference of
    # weight k, so the weights add up to the draws less the self-pairs
    assert (src != dst).all()
    keys = src.astype(np.int64) << 32 | dst
    assert np.unique(keys).size == keys.size
    assert (weight >= 1).all() and weight.max() > 1
    drawn = edgefactor << scale
    assert drawn - 0.02 * drawn < weight.sum() < drawn
    # the edge order is permuted: the list is sorted by neither end
    assert (np.diff(keys) < 0).any() and (np.diff(dst) < 0).any()
    # 64 roots, each with a reference in or out, and nobody busy
    roots = np.flatnonzero(g["flags"] & F.FLAG_ROOT)
    degree = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    assert roots.size == 64 and (degree[roots] > 0).all()
    assert not (g["flags"] & (F.FLAG_BUSY | F.FLAG_HALTED)).any() and not g["recv_count"].any()
    assert (g["flags"] & F.FLAG_IN_USE).all() and (g["flags"] & F.FLAG_INTERNED).all()
    # the supervisor pointers: a root has none; every other actor's is the
    # smallest-labelled holder below it, else its root by label
    assert (sup[roots] == -1).all() and (np.delete(sup, roots) >= 0).all()
    is_root = np.zeros(n, bool)
    is_root[roots] = True
    child = np.flatnonzero(~is_root)
    lowest = np.full(n, n, np.int64)
    np.minimum.at(lowest, dst, src)
    by_holder = lowest[child] < child
    assert np.array_equal(sup[child][by_holder], lowest[child][by_holder])
    assert np.array_equal(sup[child][~by_holder], roots[child[~by_holder] % 64])
    # a forest: a parent's label is below its child's, or the parent is a root
    assert ((sup[child] < child) | is_root[sup[child]]).all()
    # same seed, same graph; another seed, another
    again = kron_actor_graph(scale, seed=seed, edgefactor=edgefactor)
    assert all(np.array_equal(g[k], again[k]) for k in g)
    other = kron_actor_graph(scale, seed=seed + 1, edgefactor=edgefactor)
    assert not np.array_equal(other["supervisor"], sup)


def test_kron_graph_is_the_initiators():
    """The draws follow the R-MAT initiator: an end's bit is set with
    probability C + D (source) and B + D (target), whatever the label
    permutation hides, so the degrees are skewed and hubs exist."""
    assert (KRON_A, KRON_B, KRON_C) == (0.57, 0.19, 0.19)
    scale = 14
    src, dst, weight, _, roots = kron_pairs(scale, seed=1)
    n = 1 << scale
    held_by = np.bincount(dst, weights=weight, minlength=n)
    holds = np.bincount(src, weights=weight, minlength=n)
    # the largest hub: the vertex of all-zero bits, (A + B)^scale of the draws
    expected_hub = (16 << scale) * (KRON_A + KRON_B) ** scale
    assert 0.8 * expected_hub < holds.max() < 1.25 * expected_hub
    assert 0.8 * expected_hub < held_by.max() < 1.25 * expected_hub
    # a heavy tail: most actors far under the mean degree, many with none
    assert np.median(holds) < 16 / 4 and (holds + held_by == 0).mean() > 0.2
    assert roots.size == 64 and np.unique(roots).size == 64


@pytest.mark.parametrize("mode", [pt.MODE_AUTO, pt.MODE_PUSH, pt.MODE_PULL])
def test_kron_through_the_tracer_and_the_simulator(two_chunks, mode):
    """Through ``DecrementalTracer`` the derived verdicts equal the
    oracle's on every actor, and garbage is no slot range: in every walk
    chunk of the interpreted geometry some garbage slot lies below some
    live one.  The wake program's kernel counters equal what
    ``simulate_sweeps`` counts from the tracer's own packed layout: the
    grid steps, those that contracted, and the chunk-iterations of the
    walks, which no other reader sees."""
    g, n = two_chunks, 1 << TWO_CHUNKS
    oracle = _oracle(g)
    assert np.array_equal(g["expected_garbage"], ~oracle) and g["n_live"] == oracle.sum()
    assert n == 2 * CHUNK
    for chunk in range(n // CHUNK):
        garbage = g["expected_garbage"][chunk * CHUNK:(chunk + 1) * CHUNK]
        assert 0.2 < garbage.mean() < 0.8
        assert np.flatnonzero(garbage).min() < np.flatnonzero(~garbage).max()
        # and finely interleaved: thousands of runs, not two
        assert np.count_nonzero(np.diff(garbage)) > CHUNK // 8

    tracer = pd.DecrementalTracer(n, mode=mode)
    tracer.rebuild(g["edge_src"], g["edge_dst"], g["edge_weight"], g["supervisor"])
    for wake in range(2):
        if wake:
            tracer.invalidate()
        assert np.array_equal(tracer.marks(g["flags"], g["recv_count"]), oracle), wake
    assert tracer.layout.stats["anomalies"] == 0
    first, s = tracer.wake_stats()
    assert first == s
    assert 0 < s["kernel_contractions"] < s["kernel_steps"] <= s["kernel_chunk_walks"]

    preps, _ = tracer.layout.prepare_device_wake()
    (layout,) = [p for p in preps if "xla_src" not in p]
    sim = _sweep_profile().simulate_sweeps(g, n, [mode], layout=layout)[mode]
    assert sim["sweeps"] == s["n_sweeps"] and sim["dirty_chunks"] == s["dirty_chunks"]
    assert sum(sim["steps"]) == s["kernel_steps"]
    assert sum(sim["contracting"]) == s["kernel_contractions"]
    assert sum(sim["chunk_iterations"]) == s["kernel_chunk_walks"]
    assert sum(sim["walk_trips"]) == s["kernel_walk_trips"]
    # no slot locality to skip by: every sweep but the last dirties both chunks
    assert s["dirty_chunks"][:-1] == [2] * (s["n_sweeps"] - 1)
    if mode == pt.MODE_AUTO:
        assert s["jump_sweeps"] == 0


def test_churned_kron_graph_equals_the_oracle_after_every_wake():
    """A few hundred releases and new references a wake through
    ``apply_log``, the largest hubs' among them: the decremental wake
    from the previous fixpoint equals the oracle on the graph as churned."""
    rng = np.random.default_rng(5)
    g = kron_actor_graph(SMALL, seed=1)
    n = 1 << SMALL
    src, dst = g["edge_src"].copy(), g["edge_dst"].copy()
    weight = g["edge_weight"].copy()
    tracer = pd.DecrementalTracer(n, freeze_threshold=64, max_frozen=2)
    tracer.rebuild(src, dst, weight, g["supervisor"])
    assert np.array_equal(tracer.marks(g["flags"], g["recv_count"]), _oracle(g))
    hubs = np.argsort(np.bincount(dst, minlength=n))[-4:]
    held = set(zip(src.tolist(), dst.tolist()))
    garbage_seen = {int(g["n_garbage"])}
    for wake in range(5):
        # releases: 100 references drawn at large and 100 held of a hub
        at_large = rng.choice(np.flatnonzero(weight > 0), 100, replace=False)
        of_hub = np.flatnonzero((weight > 0) & np.isin(dst, hubs))
        gone = np.union1d(at_large, rng.choice(of_hub, min(100, of_hub.size), replace=False))
        weight[gone] = 0
        log = [(False, int(src[e]), int(dst[e]), EDGE) for e in gone]
        for e in gone:
            held.discard((int(src[e]), int(dst[e])))
        # new references: 100 between any two actors, 50 from a hub
        new_src = np.concatenate([rng.integers(0, n, 100), rng.choice(hubs, 50)])
        new_dst = rng.integers(0, n, 150)
        fresh = [(int(a), int(b)) for a, b in zip(new_src, new_dst) if a != b]
        fresh = [p for p in dict.fromkeys(fresh) if p not in held]
        held.update(fresh)
        log += [(True, a, b, EDGE) for a, b in fresh]
        src = np.concatenate([src, np.array([p[0] for p in fresh], np.int32)])
        dst = np.concatenate([dst, np.array([p[1] for p in fresh], np.int32)])
        weight = np.concatenate([weight, np.ones(len(fresh), np.int64)])
        tracer.apply_log(log)
        got = tracer.marks(g["flags"], g["recv_count"])
        expected = F.trace_marks_np(g["flags"], g["recv_count"], g["supervisor"], src, dst, weight)
        assert np.array_equal(got, expected), wake
        garbage_seen.add(int((~expected).sum()))
        s = tracer.wake_stats(1)[0]
        assert 0 <= s["kernel_contractions"] <= s["kernel_steps"] <= s["kernel_chunk_walks"]
        # two chunks a trip, one where a block's count is odd
        assert s["kernel_steps"] <= s["kernel_walk_trips"] <= s["kernel_chunk_walks"]
        assert s["kernel_chunk_walks"] <= 2 * s["kernel_walk_trips"]
    assert tracer.layout.stats["anomalies"] == 0
    assert len(garbage_seen) > 2  # the churn moved the verdict
