"""Differential tests for the incremental (base+delta) Pallas layout.

The layout must produce byte-identical mark vectors to the numpy oracle
at every point of a random mutation history — inserts into the delta,
in-place base masking on delete, supervisor retargeting, forced repacks,
and delete-then-reinsert of the same pair (the masked-slot path).  The
layout is read through the one program that walks it, the decremental
wake: a ``DecrementalTracer`` invalidated before each derivation, so that
every verdict is derived from nothing over the layout as it stands and
the tracer's own repair plays no part.  On CPU the kernel runs in Pallas
interpret mode; the graph-level test also drives the whole engine fold
path through it (reference semantics: ShadowGraph.java:205-289).
"""

import numpy as np
import pytest

from uigc_tpu.ops import pallas_incremental as pinc
from uigc_tpu.ops import trace as trace_ops
from uigc_tpu.ops.pallas_decremental import DecrementalTracer

F = trace_ops


def derive(tracer, gt):
    """Marks from nothing over the tracer's layout as it stands."""
    tracer.invalidate()
    return tracer.marks(gt.flags, gt.recv)


class GroundTruth:
    """Plain dict/array mirror of the live pair set."""

    def __init__(self, rng, n):
        self.rng = rng
        self.n = n
        self.edges = {}  # (src, dst) -> True
        self.supervisor = np.full(n, -1, dtype=np.int32)
        self.flags = np.zeros(n, dtype=np.uint8)
        in_use = rng.random(n) < 0.9
        self.flags[in_use] |= F.FLAG_IN_USE
        self.flags[rng.random(n) < 0.8] |= F.FLAG_INTERNED
        self.flags[rng.random(n) < 0.06] |= F.FLAG_BUSY
        self.flags[rng.random(n) < 0.04] |= F.FLAG_ROOT
        self.flags[rng.random(n) < 0.08] |= F.FLAG_HALTED
        self.recv = np.zeros(n, dtype=np.int64)
        self.recv[rng.random(n) < 0.1] = 3

    def edge_arrays(self):
        m = len(self.edges)
        src = np.fromiter((k[0] for k in self.edges), np.int32, m)
        dst = np.fromiter((k[1] for k in self.edges), np.int32, m)
        w = np.ones(m, dtype=np.int64)
        return src, dst, w

    def mutate(self, layout):
        """One random pair transition, mirrored into the layout."""
        rng = self.rng
        p = rng.random()
        if p < 0.5 or not self.edges:
            src = int(rng.integers(0, self.n))
            dst = int(rng.integers(0, self.n))
            if (src, dst) in self.edges:
                return
            self.edges[(src, dst)] = True
            layout.insert(src, dst, pinc.EDGE)
        elif p < 0.8:
            idx = int(rng.integers(0, len(self.edges)))
            key = list(self.edges)[idx]
            del self.edges[key]
            layout.remove(key[0], key[1], pinc.EDGE)
        else:
            child = int(rng.integers(0, self.n))
            old = int(self.supervisor[child])
            new = int(rng.integers(-1, self.n))
            if old == new:
                return
            if old >= 0:
                layout.remove(child, old, pinc.SUP)
            if new >= 0:
                layout.insert(child, new, pinc.SUP)
            self.supervisor[child] = new

    def expected_marks(self):
        src, dst, w = self.edge_arrays()
        return trace_ops.trace_marks_np(
            self.flags, self.recv, self.supervisor, src, dst, w
        )


def run_history(seed, n, steps, check_every, interpret=True, **layout_kw):
    rng = np.random.default_rng(seed)
    gt = GroundTruth(rng, n)
    # seed an initial population so the base layout is non-trivial
    for _ in range(n * 2):
        src = int(rng.integers(0, n))
        dst = int(rng.integers(0, n))
        gt.edges[(src, dst)] = True
    sup_mask = rng.random(n) < 0.3
    gt.supervisor[sup_mask] = rng.integers(0, n, size=int(sup_mask.sum()))

    # s_rows=8 keeps supertiles at 1024 nodes so these graph sizes span
    # several of them (the compact-tier super_ids scatter and out-block
    # revisit logic need multi-supertile coverage; the production default
    # of 32 would collapse n=2500 into one supertile).
    layout_kw.setdefault("s_rows", 8)
    tracer = DecrementalTracer(n, interpret=interpret, **layout_kw)
    layout = tracer.layout
    src, dst, w = gt.edge_arrays()
    tracer.rebuild(src, dst, w, gt.supervisor)

    checks = 0
    for step in range(steps):
        gt.mutate(layout)
        if (step + 1) % check_every == 0:
            if layout.needs_repack:
                src, dst, w = gt.edge_arrays()
                tracer.rebuild(src, dst, w, gt.supervisor)
            got = derive(tracer, gt)
            expected = gt.expected_marks()
            assert np.array_equal(got, expected), f"divergence at step {step}"
            checks += 1
    assert checks > 0
    assert layout.stats["anomalies"] == 0
    return layout


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_incremental_matches_oracle(seed):
    # n spans multiple supertiles (super = 8 * 128 = 1024 nodes here)
    layout = run_history(seed, n=2500, steps=600, check_every=60)
    # the whole point: churn was absorbed without full repacks
    assert layout.stats["rebuilds"] == 1


def test_forced_repacks_stay_correct():
    layout = run_history(
        7, n=1500, steps=400, check_every=40, min_repack=32, repack_fraction=0.01
    )
    assert layout.stats["rebuilds"] > 1


def test_freeze_and_consolidate_stay_correct():
    """Tiny thresholds force the full tier lifecycle: live tier -> frozen
    compact chain -> consolidation, with deletes masking frozen slots."""
    layout = run_history(
        13, n=2500, steps=500, check_every=25, freeze_threshold=24, max_frozen=2
    )
    assert layout.stats["freezes"] > 2
    assert layout.stats["consolidations"] >= 1
    assert layout.stats["rebuilds"] == 1


def test_delete_then_reinsert_base_pair():
    n = 1200
    rng = np.random.default_rng(3)
    gt = GroundTruth(rng, n)
    # one deterministic keep-alive chain through three supertile-crossing hops
    a, b, c = 5, 600, 1100
    gt.flags[[a, b, c]] = F.FLAG_IN_USE | F.FLAG_INTERNED
    gt.flags[a] |= F.FLAG_ROOT
    gt.edges[(a, b)] = True
    gt.edges[(b, c)] = True
    tracer = DecrementalTracer(n, s_rows=8, interpret=True)
    layout = tracer.layout
    src, dst, w = gt.edge_arrays()
    tracer.rebuild(src, dst, w, gt.supervisor)
    assert derive(tracer, gt)[c]

    # delete (a,b) from the base -> c unreachable
    del gt.edges[(a, b)]
    layout.remove(a, b, pinc.EDGE)
    got = derive(tracer, gt)
    assert not got[b] and not got[c]
    assert np.array_equal(got, gt.expected_marks())

    # re-insert the same pair -> lands in the delta, reachability restored
    gt.edges[(a, b)] = True
    layout.insert(a, b, pinc.EDGE)
    got = derive(tracer, gt)
    assert got[b] and got[c]
    assert np.array_equal(got, gt.expected_marks())
    assert layout.stats["anomalies"] == 0


def test_graph_level_protocol_parity():
    """Drive the full entry-fold path (ArrayShadowGraph) through the
    incremental Pallas layout in interpret mode: the _pair_log plumbing
    between graph mutations and the layout is what's under test."""
    from test_trace_parity import Sim

    sim = Sim(11, backend="decremental")
    for _ in range(6):
        for _ in range(80):
            sim.random_step()
        sim.collect_round()

    sim.drain_inboxes()
    for actor in sim.live_actors():
        for ref in list(actor.acquaintances):
            actor.release(ref)
    sim.drain_inboxes()
    for actor in sim.live_actors():
        actor.flush()
    for _ in range(5):
        sim.collect_round()
    survivors = {a.cell for a in sim.live_actors()}
    assert survivors == {sim.root.cell}

    dec = sim.array._dec
    assert dec is not None and dec.layout.stats["anomalies"] == 0


@pytest.mark.parametrize("seed", [0, 1])
def test_resident_operands_match_a_fresh_tracer(seed):
    """The device-resident operands (mirrors + O(churn) masking
    scatters) must give the same marks as a tracer rebuilt from the
    graph as it stands, whose mirrors are uploaded whole, across a
    mutation history with freezes and consolidations — including after
    rebuilds, which must invalidate the mirrors."""
    import jax

    rng = np.random.default_rng(seed)
    n = 2500
    gt = GroundTruth(rng, n)
    for _ in range(n * 2):
        gt.edges[(int(rng.integers(0, n)), int(rng.integers(0, n)))] = True
    tracer = DecrementalTracer(
        n, s_rows=8, interpret=True, freeze_threshold=24, max_frozen=2
    )
    layout = tracer.layout
    src, dst, w = gt.edge_arrays()
    tracer.rebuild(src, dst, w, gt.supervisor)

    flags_dev = jax.device_put(gt.flags)
    recv_dev = jax.device_put(gt.recv)

    def resident():
        tracer.invalidate()
        return tracer.unpack_marks(tracer.wake_device(flags_dev, recv_dev))

    def fresh():
        other = DecrementalTracer(n, s_rows=8, interpret=True)
        other.rebuild(*gt.edge_arrays(), gt.supervisor)
        return other.unpack_marks(other.wake_device(flags_dev, recv_dev))

    for step in range(8):
        for _ in range(40):
            gt.mutate(layout)
        got = resident()
        assert np.array_equal(got, fresh()), f"divergence at step {step}"
        assert np.array_equal(got, gt.expected_marks())
    assert layout.stats["anomalies"] == 0
    # the run must actually exercise the frozen-tier mirrors and their
    # GC at consolidation, or this test is not covering what it claims
    assert layout.stats["freezes"] > 0
    assert layout.stats["consolidations"] >= 1
    assert layout._dev_scatter is not None  # the O(churn) sync ran

    # a forced rebuild must drop stale mirrors
    src, dst, w = gt.edge_arrays()
    tracer.rebuild(src, dst, w, gt.supervisor)
    assert np.array_equal(resident(), gt.expected_marks())


def test_small_scatters_share_one_padded_length():
    """The O(churn) syncs of the device mirrors pad their writes to a
    power of two, and every padded length is a program of its own.  Up
    to ``_scatter_pad``'s floor they share one: a served wake's writes
    vary from a few to a few thousand, and nothing may compile inside a
    benchmark window.  The jump-parent mirror stays the host's."""
    floor = 1 << pinc._SCATTER_PAD_LOG2
    assert [pinc._scatter_pad(k) for k in (1, 63, 300, floor, floor + 1, 3 * floor)] == \
        [floor, floor, floor, floor, 2 * floor, 4 * floor]
    n = 8192
    layout = pinc.IncrementalPallasLayout(n, s_rows=8, interpret=True)
    none = np.empty(0, np.int32)
    layout.rebuild(none, none, np.empty(0, np.int64), np.full(n, -1, np.int32))
    assert np.array_equal(np.asarray(layout.jump_device()), layout.jump_parent)
    for lo, hi in [(0, 3), (3, 300), (300, 310), (310, 2000)]:
        for d in range(lo, hi):
            layout.insert(d + 4000, d, pinc.EDGE)
        assert np.array_equal(np.asarray(layout.jump_device()), layout.jump_parent)
    for d in range(0, 2000):  # the removals are writes too
        layout.remove(d + 4000, d, pinc.EDGE)
    assert np.array_equal(np.asarray(layout.jump_device()), layout.jump_parent)
    assert (layout.jump_parent == n).all()
    assert layout._jump_scatter._cache_size() == 1  # five syncs, one program
