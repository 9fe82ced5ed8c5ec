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


# --------------------------------------------------------------------- #
# apply_log: the pair-transition log as columns against a sequential
# insert() / remove() replay
# --------------------------------------------------------------------- #

LOG_SHAPES = [
    "churn", "duplicate_insert", "remove_absent", "insert_then_remove",
    "remove_then_insert", "frozen_hits", "ids_past_n", "empty", "one_row",
]
#: shapes that insert and remove ONE (src, dst) pair in a batch: there
#: the batched jump-parent fold is, as documented, more conservative
#: than the replay, so the pointers are held to soundness, not equality
_IN_BATCH = ("insert_then_remove", "remove_then_insert")


def _twin_layouts(seed, n=2500, count=3):
    """``count`` layouts with one history: a packed base, a frozen tier
    and a live tier, their device mirrors up (so every write queues)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, 2 * n)
    dst = rng.integers(0, n, 2 * n)
    keep = np.unique((src << 32) | dst, return_index=True)[1]
    src, dst = src[keep].astype(np.int32), dst[keep].astype(np.int32)
    sup = np.full(n, -1, np.int32)
    kids = rng.random(n) < 0.3
    sup[kids] = rng.integers(0, n, int(kids.sum()))
    base = set(zip(src.tolist(), dst.tolist()))
    fresh = []
    while len(fresh) < 120:
        pair = (int(rng.integers(0, n)), int(rng.integers(0, n)))
        if pair not in base:
            base.add(pair)
            fresh.append(pair)
    frozen, pending, spare = fresh[:40], fresh[40:60], fresh[60:]
    twins = []
    for _ in range(count):
        layout = pinc.IncrementalPallasLayout(
            n, s_rows=8, interpret=True, freeze_threshold=24
        )
        layout.rebuild(src, dst, np.ones(src.size, np.int64), sup)
        for s, d in frozen:
            layout.insert(s, d, pinc.EDGE)
        layout.prepare_device_wake()  # freezes the 40, uploads the mirrors
        for s, d in pending:
            layout.insert(s, d, pinc.EDGE)
        assert layout.stats["freezes"] == 1 and len(layout.pending) == 20
        twins.append(layout)
    homes = {
        "base": list(zip(src.tolist(), dst.tolist())),
        "frozen": frozen, "pending": pending, "spare": spare,
    }
    return twins, homes


def _shaped_log(shape, rng, homes, n):
    """Per key its ops in order, then all keys' ops shuffled together
    with each key's order kept (a pair's transitions alternate)."""
    def some(home, k):
        at = rng.choice(len(homes[home]), size=k, replace=False)
        return [homes[home][i] for i in at]

    live = some("base", 30) + some("frozen", 10) + some("pending", 8)
    spare = homes["spare"]
    if shape == "churn":
        gone = {d for _, d in live}
        ops = [(p, [False]) for p in live]
        ops += [(p, [True]) for p in spare[:30] if p[1] not in gone]
        ops += [((s, d, pinc.SUP), [True]) for s, d in spare[30:40] if d not in gone]
    elif shape == "duplicate_insert":
        ops = [(p, [True]) for p in live] + [(spare[0], [True])]
    elif shape == "remove_absent":
        ops = [(p, [False]) for p in spare[:20]] + [(live[0], [False])]
    elif shape == "insert_then_remove":
        ops = [(p, [True, False]) for p in spare[:20] + live]
    elif shape == "remove_then_insert":
        ops = [(p, [False, True]) for p in live + spare[:5]]
        ops += [(spare[6], [False, True, False]), (live[0][::-1], [True, False, True])]
    elif shape == "frozen_hits":
        ops = [(p, [False]) for p in homes["frozen"][:25]]
        ops += [(p, [True]) for p in homes["frozen"][25:30]]
    elif shape == "ids_past_n":
        ops = [((n + 7, 3), [True]), ((4, n + 9), [True]), ((n + 2, n + 3), [True]),
               ((n + 11, 5), [False]), (live[0], [False])]
    elif shape == "empty":
        ops = []
    elif shape == "one_row":
        ops = [(live[0], [False])] if rng.random() < 0.5 else [(spare[0], [True])]
    rows = []
    for pair, seq in ops:
        kind = pair[2] if len(pair) == 3 else pinc.EDGE
        when = np.sort(rng.random(len(seq)))
        rows += [(t, (ins, pair[0], pair[1], kind)) for t, ins in zip(when, seq)]
    rows.sort(key=lambda r: r[0])
    return [row for _, row in rows]


def _layout_state(layout):
    preps = [layout.base] + layout.frozen
    return {
        "pending": set(layout.pending),
        "pending_len": np.fromiter(layout.pending, np.int64, len(layout.pending)).size,
        "frozen_slot": dict(layout.frozen_slot),
        "base_len": len(layout.base_slot),
        "base_dead": layout.base_slot._dead.tolist(),
        "base_extra": dict(layout.base_slot._extra),
        "masked_base": layout.masked_base,
        "masked_frozen": layout.masked_frozen,
        "anomalies": layout.stats["anomalies"],
        "row_pos": [p["row_pos"].tolist() for p in preps],
        "emeta": [p["emeta"].tolist() for p in preps],
        "dev_writes": {
            tok: sorted(np.concatenate(w).tolist() if w else [])
            for tok, w in layout._dev_writes.items()
        },
    }


def _jump_state(layout):
    return layout.jump_parent.tolist(), set(layout._jump_writes.items())


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("shape", LOG_SHAPES)
def test_apply_log_matches_a_sequential_replay(shape, seed):
    """One batched ``apply_log`` leaves the layout as a replay of the
    same log through ``insert()`` / ``remove()`` does, anomaly for
    anomaly and queued write for queued write; and the log as a list of
    tuples and as a ``PairLog`` are one road."""
    from uigc_tpu.ops.slotmap import PairLog, unpack_keys

    n = 2500
    (replayed, from_tuples, from_columns), homes = _twin_layouts(seed)
    log = _shaped_log(shape, np.random.default_rng(100 + seed), homes, n)
    assert (shape == "empty") == (not log)

    for ins, s, d, kind in log:
        (replayed.insert if ins else replayed.remove)(s, d, kind)
    from_tuples.apply_log(log)
    columns = PairLog()
    half = len(log) // 2
    for row in log[:half]:  # the scalar road, then the batched one
        columns.append(row)
    for ins, s, d, kind in log[half:]:
        columns.extend(ins, np.array([s]), np.array([d], np.int32), kind)
    assert len(columns) == len(log)
    from_columns.apply_log(columns)

    want = _layout_state(replayed)
    assert _layout_state(from_tuples) == want
    assert _layout_state(from_columns) == want
    assert _jump_state(from_tuples) == _jump_state(from_columns)
    if shape not in _IN_BATCH:
        assert _jump_state(from_columns) == _jump_state(replayed)
    # a pointer is always a current live pair's source (or the sentinel)
    live = set()
    for layout_keys in (from_columns.pending, from_columns.frozen_slot):
        live.update(zip(*[a.tolist() for a in unpack_keys(np.fromiter(layout_keys, np.int64))]))
    slots = from_columns.base_slot
    live.update(zip(*[a.tolist() for a in unpack_keys(slots._keys[~slots._dead])]))
    jump = from_columns.jump_parent
    for d in np.flatnonzero(jump[:n] != n).tolist():
        assert (int(jump[d]), d) in live
    # the work counters: rows in, distinct keys after the fold
    for layout in (from_tuples, from_columns):
        assert layout.stats["log_rows"] == len(log)
        assert layout.stats["log_keys"] == len({row[1:] for row in log})
        assert layout.stats["base_lookups"] <= 2 * layout.stats["log_keys"]
    assert replayed.stats["log_rows"] == 0


def test_pair_log_keeps_rows_in_order_across_both_roads():
    """Scalar rows are staged and join the columns, in order, at the
    next batch or read; growth, ``clear`` and the door for a list."""
    from uigc_tpu.ops.slotmap import PairLog

    log = PairLog()
    want = []
    rng = np.random.default_rng(5)
    for round_ in range(40):
        for _ in range(int(rng.integers(0, 4))):
            row = (bool(rng.random() < 0.5), int(rng.integers(0, 1 << 31)),
                   int(rng.integers(0, 1 << 31)), int(rng.integers(0, 2)))
            log.append(row)
            want.append(row)
        k = int(rng.integers(0, 200))
        srcs = rng.integers(0, 1 << 31, k)
        dsts = rng.integers(0, 1 << 31, k).astype(np.int32)
        log.extend(round_ % 2 == 0, srcs, dsts, round_ % 2)
        want += [(round_ % 2 == 0, int(s), int(d), round_ % 2) for s, d in zip(srcs, dsts)]
        assert len(log) == len(want)
    assert len(log) > 1024  # outgrew its first room
    ins, src, dst, kind = log.columns()
    assert all(c.dtype == np.int64 for c in (ins, src, dst, kind))
    assert list(zip(ins.astype(bool).tolist(), src.tolist(), dst.tolist(), kind.tolist())) == want
    assert log.nbytes == 32 * len(want)
    assert PairLog.of(log) is log
    again = PairLog.of(want)
    assert all(np.array_equal(a, b) for a, b in zip(again.columns(), log.columns()))
    log.clear()
    assert len(log) == 0 and log.nbytes == 0 and log.columns()[0].size == 0
    log.append((True, 1, 2, 0))  # the staging list survived the clear
    assert [c.tolist() for c in log.columns()] == [[1], [1], [2], [0]]


@pytest.mark.parametrize("seed", [0, 1])
def test_fold_log_gives_each_keys_first_and_last_op(seed):
    """The sorted fold against the two dicts it replaced."""
    from uigc_tpu.ops.slotmap import PairLog, fold_log, pack_key

    rng = np.random.default_rng(seed)
    log = [(bool(rng.random() < 0.5), int(rng.integers(0, 12)), int(rng.integers(0, 12)),
            int(rng.integers(0, 2))) for _ in range(400)]
    first, last = {}, {}
    for ins, s, d, kind in log:
        first.setdefault(pack_key(s, d, kind), ins)
        last[pack_key(s, d, kind)] = ins
    removes, cond_removes, inserts, n_keys = fold_log(*PairLog.of(log).columns())
    assert removes.tolist() == sorted(k for k, ins in first.items() if not ins)
    assert cond_removes.tolist() == sorted(k for k, ins in first.items() if ins and not last[k])
    assert inserts.tolist() == sorted(k for k, ins in last.items() if ins)
    assert n_keys == len(first)
