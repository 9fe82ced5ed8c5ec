"""The sweep by the dead: garbage ids from the wake's packed verdict
words, dead edges from the endpoint index (``ops/edgeindex.py``).

The property: whatever road the sweep takes, it frees the edges that
``(w != 0) & (garbage[edge_src] | garbage[edge_dst])`` names over the
arrays as they stood before it, in that order, and its garbage and kill
slots are the nonzeros of ``trace_ops.garbage_and_kills_np`` over the
oracle's marks.  One driver mutates an ``ArrayShadowGraph`` at the slot
level (the graph's own mutators, so the pair log and the index hear of
everything) and is run over the host backend, the decremental wake
(interpreted here), the mesh's decremental wake (its shards' verdict words
through the same sweep) and a graph of foreign uids.
"""

from __future__ import annotations

from contextlib import nullcontext

import numpy as np
import pytest

from uigc_tpu.engines.crgc import arrays, mesh
from uigc_tpu.engines.crgc.arrays import ArrayShadowGraph, PackedVerdicts
from uigc_tpu.engines.crgc.packed import FOREIGN_BIT
from uigc_tpu.engines.crgc.state import CrgcContext
from uigc_tpu.ops import pallas_decremental as pd
from uigc_tpu.ops import trace as F
from uigc_tpu.ops.edgeindex import EndpointIndex

IN_USE, INTERNED, LOCAL = int(F.FLAG_IN_USE), int(F.FLAG_INTERNED), int(F.FLAG_LOCAL)
ROOT, BUSY, HALTED = int(F.FLAG_ROOT), int(F.FLAG_BUSY), int(F.FLAG_HALTED)


class FakeSystem:
    address = "uigc://sweeptest"


class FakeCell:
    __slots__ = ("uid", "system", "told")

    def __init__(self, uid):
        self.uid = uid
        self.system = FakeSystem
        self.told = []

    def tell(self, msg):
        self.told.append(msg)


class FakeWake:
    """Stands for the collector's active wake (``telemetry/profile.py``):
    brackets nothing, keeps what the backend notes."""

    ordinal = 0

    def __init__(self):
        self.fields = {}

    def note(self, **fields):
        self.fields.update(fields)

    def phase(self, name):
        return nullcontext()

    def part(self, field, annotation=None):
        return nullcontext()

    def defer(self, read, handle):
        pass


class Rig:
    """A graph under random slot-level churn, and what the scan and the
    dense oracle say each of its sweeps must do."""

    def __init__(self, case, seed, **graph_kwargs):
        self.case = case
        self.rng = np.random.default_rng([seed, len(case)])
        self.foreign = case == "foreign"
        ctx = CrgcContext(delta_graph_size=64, entry_field_size=4)
        graph_kwargs.setdefault("initial_capacity", 64)
        if case == "mesh-decremental":
            self.graph = g = mesh.MeshShadowGraph(
                ctx, FakeSystem.address, decremental=True, **graph_kwargs)
        else:
            self.graph = g = ArrayShadowGraph(
                ctx, FakeSystem.address,
                use_device=case == "decremental", **graph_kwargs)
        g._endpoints.overlay_bound = 8  # seals and merges at this size
        self.wake = g.profile_wake = FakeWake()
        self.answers = []
        if self.foreign:
            g.foreign_sink = lambda k, f: self.answers.append((k, f))
        self.next_uid = 0
        self.seen = dict.fromkeys(
            ("negative_freed", "held_by_halted_live_source", "reused_other_source",
             "scans", "index_queries", "runs_max", "dropped", "kills"), 0)
        self.last_src = np.full(1 << 16, -1, dtype=np.int64)
        self.swept = {}
        free, kill = g._free_slots_batch, g._kill_slots_bulk
        g._free_slots_batch = lambda s: self.swept.__setitem__("garbage", s) or free(s)
        g._kill_slots_bulk = lambda s: self.swept.__setitem__("kill", s) or kill(s)

    # -- mutation ------------------------------------------------------ #

    def spawn(self, k, sup=None, flags=INTERNED | LOCAL):
        g = self.graph
        if self.foreign:
            fuids = np.arange(self.next_uid, self.next_uid + k, dtype=np.int64)
            slots = g._slots_for_foreign(fuids, int(fuids[-1]))
        else:
            slots = np.array(
                [g.slot_for(FakeCell(self.next_uid + i)) for i in range(k)], np.int64)
        self.next_uid += k
        g.flags[slots] |= np.uint8(flags)
        if sup is not None:
            for s in slots.tolist():
                g._set_supervisor(s, int(sup))
        return slots

    def in_use(self):
        return np.flatnonzero(self.graph.flags & np.uint8(IN_USE))

    def deltas(self, src, dst, delta):
        """Net deltas per pair through the batch road."""
        keys, inverse = np.unique((src.astype(np.int64) << 32) | dst, return_inverse=True)
        net = np.zeros(keys.size, np.int64)
        np.add.at(net, inverse, delta)
        self.graph._apply_edge_deltas(keys[net != 0], net[net != 0])

    def churn(self, among):
        g, rng = self.graph, self.rng
        pick = lambda k: among[rng.integers(0, among.size, k)]
        for parent in pick(3).tolist():
            kids = self.spawn(int(rng.integers(2, 12)), sup=parent)
            self.deltas(np.full(kids.size, parent), kids, np.ones(kids.size, np.int64))
            among = np.concatenate([among, kids])
        k = int(rng.integers(10, 40))
        self.deltas(pick(k), pick(k), rng.choice([1, 1, 1, 2, -1], k))
        for _ in range(6):  # and the scalar road
            g._update_edge(int(pick(1)[0]), int(pick(1)[0]), int(rng.choice([1, -1])))
        alive = np.flatnonzero(g.edge_weight)
        drop = alive[rng.integers(0, alive.size, min(alive.size, 12))]
        drop = np.unique(drop)
        by = -g.edge_weight[drop] - (rng.random(drop.size) < 0.3)  # to zero, or below
        self.deltas(g.edge_src[drop], g.edge_dst[drop], by)
        some, one = pick(4), pick(1)
        g.flags[some] ^= np.uint8(BUSY)
        g.flags[one] |= np.uint8(HALTED if rng.random() < 0.3 else 0)
        g._touch_batch(np.concatenate([some, one]))  # as a fold's write would
        return among

    def plant_garbage_held_from_live(self):
        """A target only a HALTED live source references (halted actors
        are marked and do not propagate), and one a live source holds by
        a negative count: both die, both sources stay."""
        root = self.keeper
        halted = int(self.spawn(1, sup=root)[0])
        self.graph._update_edge(root, halted, 1)
        self.graph.flags[halted] |= np.uint8(HALTED)
        for src, weight in ((halted, 1), (root, -1)):
            target = int(self.spawn(1, sup=root)[0])
            self.graph._update_edge(src, target, weight)

    # -- one wake -------------------------------------------------------- #

    def sweep_and_check(self):
        g = self.graph
        # the dense oracle over the state the trace is about to read
        mark = F.trace_marks_np(
            g.flags, g.recv_count, g.supervisor, g.edge_src, g.edge_dst, g.edge_weight)
        garbage, kill = F.garbage_and_kills_np(g.flags, g.supervisor, mark)
        w = g.edge_weight.copy()
        src, dst = g.edge_src.copy(), g.edge_dst.copy()
        want = np.nonzero((w != 0) & (garbage[src] | garbage[dst]))[0]
        reused = want[(self.last_src[want] >= 0) & (self.last_src[want] != src[want])]
        self.seen["reused_other_source"] += reused.size
        self.seen["negative_freed"] += int((w[want] < 0).sum())
        halted = (g.flags[src[want]] & np.uint8(HALTED)) != 0
        self.seen["held_by_halted_live_source"] += int(
            (halted & ~garbage[src[want]] & (w[want] > 0)).sum())
        uid_of = g._slot_uid.copy()
        free_n, asked = g.free_edges.n, self.seen["index_queries"]
        self.swept.clear()
        self.answers.clear()

        n = g.trace(should_kill=True)

        garbage_slots, kill_slots = np.nonzero(garbage)[0], np.nonzero(kill)[0]
        assert n == garbage_slots.size
        assert np.array_equal(self.swept.get("garbage", garbage_slots[:0]), garbage_slots)
        assert np.array_equal(self.swept.get("kill", kill_slots[:0]), kill_slots)
        self.seen["kills"] += kill_slots.size
        freed = np.flatnonzero((w != 0) & (g.edge_weight[: w.size] == 0))
        assert np.array_equal(freed, want)
        assert g.free_edges.n == free_n + want.size
        assert np.array_equal(g.free_edges.buf[free_n : g.free_edges.n], want)
        assert len(g.edge_of) == np.count_nonzero(g.edge_weight)
        assert g.edge_of.get_batch((src[want].astype(np.int64) << 32) | dst[want]).max(initial=-1) == -1
        assert not g.flags[garbage_slots].any() and (g.supervisor[garbage_slots] == -1).all()
        assert not g._dying.any()
        examined = 0
        if garbage_slots.size:  # the index was asked, or the scan ran
            took_scan = garbage_slots.size * arrays._SCAN_SHARE > g.edge_capacity
            assert self.seen["index_queries"] == asked + (not took_scan)
            self.seen["scans"] += took_scan
            examined = g.edge_capacity if took_scan else self.examined
        assert self.wake.fields["sweep_edge_slots"] == examined
        assert self.wake.fields["freed"] == garbage_slots.size
        if self.foreign:
            (kills, freed_uids), = self.answers
            assert np.array_equal(kills, uid_of[kill_slots] ^ FOREIGN_BIT)
            assert np.array_equal(freed_uids, uid_of[garbage_slots] ^ FOREIGN_BIT)
        self.last_src[want] = src[want]
        self.seen["runs_max"] = max(self.seen["runs_max"], g._endpoints.runs)
        return garbage_slots.size


def counting_rig(case, seed, monkeypatch):
    """A rig whose index queries and overlay drops are counted."""
    # graphs of a few hundred slots: the regimes' border moved to where
    # a round's trickle takes the index and a cluster's death the scan
    monkeypatch.setattr(arrays, "_SCAN_SHARE", 8)
    rig = Rig(case, seed)
    incident = EndpointIndex.incident

    def counted(self, *args):
        rig.seen["index_queries"] += 1
        eids, rig.examined = incident(self, *args)
        return eids, rig.examined

    def counted_drop(self, drop=EndpointIndex._drop):
        rig.seen["dropped"] += 1
        drop(self)

    monkeypatch.setattr(EndpointIndex, "incident", counted)
    monkeypatch.setattr(EndpointIndex, "_drop", counted_drop)
    return rig


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", ["array", "decremental", "mesh-decremental", "foreign"])
def test_sweep_frees_what_the_scan_and_the_dense_oracle_name(case, seed, monkeypatch):
    rig = counting_rig(case, seed, monkeypatch)
    g = rig.graph
    # a root that holds nothing live but what plant_...() hangs on it,
    # so that it survives the mass death and little else does
    rig.keeper = int(rig.spawn(1, flags=INTERNED | LOCAL | ROOT)[0])
    among = rig.spawn(4, flags=INTERNED | LOCAL | ROOT)
    caps = {(g.capacity, g.edge_capacity)}
    rounds = 10 if g.use_device else 16
    for round_ in range(rounds):
        among = rig.churn(among)
        if round_ % 3 == 1:
            rig.plant_garbage_held_from_live()
        if round_ == 2:  # a bulk load: it outruns the overlay
            kids = rig.spawn(300, sup=int(among[0]))
            rig.deltas(np.full(300, among[0]), kids, np.ones(300, np.int64))
            rig.deltas(kids[:-1], kids[1:], np.ones(299, np.int64))
        if round_ == rounds - 3:
            # a mass death: every root but the keeper lets go
            g.flags[among] &= np.uint8(0xFF & ~(ROOT | BUSY))
            g._touch_batch(among)
        died = rig.sweep_and_check()
        caps.add((g.capacity, g.edge_capacity))
        among = rig.in_use()
        assert rig.keeper in among
        among = among[among != rig.keeper]
        if round_ == rounds - 3:
            assert died * 8 > g.edge_capacity, "the mass death took the index"
            among = np.concatenate([among, rig.spawn(2, flags=INTERNED | LOCAL | ROOT)])
    seen = rig.seen
    assert seen["scans"] >= 1 and seen["index_queries"] >= rounds // 2, seen
    assert seen["negative_freed"] and seen["held_by_halted_live_source"], seen
    assert seen["reused_other_source"] and seen["kills"], seen
    assert seen["runs_max"] >= 2 and seen["dropped"], seen
    assert len({c for c, _ in caps}) > 1 and len({e for _, e in caps}) > 1, caps


def invalidated(g, monkeypatch):
    g.invalidate_wake_state()


def readback_raises(g, monkeypatch):
    """The wake's result never lands: the readback raises, after the
    dispatch committed the wake's state and cleared its suspects."""

    def poisoned(array, site):
        raise RuntimeError("transport died")

    with monkeypatch.context() as patch:
        patch.setattr(mesh, "_readback", poisoned)
        with pytest.raises(RuntimeError, match="transport died"):
            g.trace(should_kill=True)


@pytest.mark.parametrize("doubt", [invalidated, readback_raises])
def test_the_mesh_derives_from_nothing_after_a_wake_state_it_dropped(doubt, monkeypatch):
    """The mesh's one wake, ``_compute_marks_decremental``, dispatches
    and reads back in place: a readback that raises drops the state the
    dispatch committed, as ``invalidate_wake_state()`` does, and the next
    wake's verdicts are the dense oracle's over deletions whose suspects
    went with it."""
    rig = counting_rig("mesh-decremental", 2, monkeypatch)
    g = rig.graph
    rig.keeper = int(rig.spawn(1, flags=INTERNED | LOCAL | ROOT)[0])
    among = rig.spawn(4, flags=INTERNED | LOCAL | ROOT)
    # room for the whole script: a growth would rebuild, and drop the
    # state by itself
    kids = rig.spawn(400, sup=rig.keeper)
    rig.deltas(np.full(400, rig.keeper), kids, np.ones(400, np.int64))
    for _ in range(3):
        rig.churn(among)
        rig.sweep_and_check()
        among = rig.in_use()
        among = among[(among != rig.keeper) & ~np.isin(among, kids)]
    assert g._wake_state is not None and g.stats["wakes"] == 3
    rebuilds = g.stats["rebuilds"]
    among = rig.churn(among)
    rig.plant_garbage_held_from_live()
    roots = among[(g.flags[among] & np.uint8(ROOT)) != 0]
    g.flags[roots[:2]] &= np.uint8(0xFF & ~(ROOT | BUSY))  # and two roots let go
    g._touch_batch(roots[:2])
    g._sync_device()
    assert g._pending_del_dst  # suspects a repair would have started from
    doubt(g, monkeypatch)
    assert g._wake_state is None
    assert not g._pending_del_dst and not g._pending_fresh_dst
    assert rig.sweep_and_check() >= 2
    assert g._wake_state is not None
    for _ in range(2):  # and repairs go on from the new fixpoint
        among = rig.in_use()
        rig.churn(among[(among != rig.keeper) & ~np.isin(among, kids)])
        rig.sweep_and_check()
    assert g.stats == {"rebuilds": rebuilds, "wakes": 6 + (doubt is readback_raises),
                       "anomalies": 0, "bucket_grows": 0}


def test_verdict_words_equal_unpack_marks_off_the_word_grid():
    """The words' reduce against ``unpack_marks`` at a capacity that
    fills neither a word row (32 x 128 slots) nor a word."""
    n = 5003
    rng = np.random.default_rng(5)
    flags = np.zeros(n, np.uint8)
    flags[rng.random(n) < 0.8] |= np.uint8(IN_USE | INTERNED | LOCAL)
    flags[rng.random(n) < 0.02] |= np.uint8(ROOT)
    flags[rng.random(n) < 0.03] |= np.uint8(HALTED)
    flags[n - 1] = IN_USE | INTERNED | LOCAL  # the last slot: garbage
    recv = np.zeros(n, np.int64)
    src = rng.integers(0, n - 1, 2 * n).astype(np.int32)
    dst = rng.integers(0, n - 1, 2 * n).astype(np.int32)
    w = rng.choice([1, 1, -1], 2 * n).astype(np.int64)
    sup = np.where(rng.random(n) < 0.5, rng.integers(0, n - 1, n), -1).astype(np.int32)
    sup[n - 1] = int(np.flatnonzero(flags & np.uint8(ROOT))[0])
    tracer = pd.DecrementalTracer(n)
    tracer.rebuild(src, dst, w, sup)
    import jax

    mark_w = tracer.wake_device(jax.device_put(flags), jax.device_put(recv))
    mark = tracer.unpack_marks(mark_w)
    assert np.array_equal(mark, F.trace_marks_np(flags, recv, sup, src, dst, w))
    garbage_w, marked = tracer.verdict_words(mark_w)
    garbage, kill = F.garbage_and_kills_np(flags, sup, mark)
    assert garbage[n - 1] and kill[n - 1]
    assert marked == np.count_nonzero(mark)
    bits = np.unpackbits(garbage_w.view(np.uint8), bitorder="little")
    assert np.array_equal(bits[:n].astype(bool), garbage) and not bits[n:].any()
    graph = ArrayShadowGraph(CrgcContext(delta_graph_size=64, entry_field_size=4))
    graph.flags, graph.supervisor = flags, sup  # what the device read
    g, k, live = graph._verdict_slots(PackedVerdicts(garbage_w, marked))
    assert np.array_equal(g, np.nonzero(garbage)[0])
    assert np.array_equal(k, np.nonzero(kill)[0]) and live == marked
    tracer.invalidate()
    with pytest.raises(Exception, match="no longer the tracer's last"):
        tracer.verdict_words(mark_w)


def test_a_small_death_examines_few_edge_slots():
    """``sweep_edge_slots`` is on the wake's record: the edge capacity
    where the sweep scanned, the index's candidates after a small death."""
    ctx = CrgcContext(delta_graph_size=64, entry_field_size=4)
    g = ArrayShadowGraph(ctx, FakeSystem.address)
    wake = g.profile_wake = FakeWake()
    n = 40_000
    slots = g._slots_for_foreign(np.arange(n, dtype=np.int64), n - 1)
    g.flags[slots] |= np.uint8(INTERNED | LOCAL)
    g.flags[slots[0]] |= np.uint8(ROOT)
    star = (slots[:1].repeat(n - 1) << 32) | slots[1:]  # the root holds everyone
    chain = (slots[1:-1] << 32) | slots[2:]
    keys = np.unique(np.concatenate([star, chain]))
    g._apply_edge_deltas(keys, np.ones(keys.size, np.int64))
    g.supervisor[slots[1:]] = slots[0]
    assert g.trace(should_kill=True) == 0 and wake.fields["sweep_edge_slots"] == 0
    # the root lets go of one actor in mid-chain: it dies with three edges
    victim = int(slots[n // 2])
    g._update_edge(int(slots[0]), victim, -1)
    g._update_edge(int(slots[n // 2 - 1]), victim, -1)
    assert g.trace(should_kill=True) == 1
    assert wake.fields["kills"] == 1 and wake.fields["freed"] == 1
    assert 1 <= wake.fields["sweep_edge_slots"] <= 8
    assert wake.fields["sweep_edge_slots"] * 1000 < g.edge_capacity
    assert g.edge_weight[g.edge_of.get_batch(keys)[-1]] == 1
    # everything goes: the scan, and it says so
    g.flags[slots[0]] &= np.uint8(0xFF & ~ROOT)
    assert g.trace(should_kill=True) == n - 1
    assert wake.fields["sweep_edge_slots"] == g.edge_capacity
    assert not np.count_nonzero(g.edge_weight) and len(g.edge_of) == 0
