"""Foreign actors: actors the collector holds by uid alone.

A foreign actor's cell lives in a mutator process that ships this
collector its entry flushes (``engines/crgc/packed.py``): rows reach the
plane through ``PackedPlane.write_foreign`` in the mutator side's own
dense uids, ``ArrayShadowGraph`` interns them as arrays (no
``ActorCell``, no per-uid Python), keeps a tombstone for each one it
swept, and the sweep hands the uids to stop and the uids it freed to
the sink the engine exposes.

The differential is ``tests/test_packed_plane.py``'s, with one world of
``FakeCell``s and one of foreign uids under the same scripts, compared
by uid; the verdicts are held to ``ops/trace.py trace_marks_np``.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from uigc_tpu.engines.crgc import collector
from uigc_tpu.engines.crgc.arrays import ArrayShadowGraph
from uigc_tpu.engines.crgc.packed import (
    FOREIGN_BIT, PackedPlane, PackedRing, foreign, row_width, uid_columns,
)
from uigc_tpu.engines.crgc.refob import CrgcRefob
from uigc_tpu.engines.crgc.state import CrgcContext, CrgcState
from uigc_tpu.ops import trace as F

E = 4
W = row_width(E)


class FakeSystem:
    address = "uigc://foreigntest"


class FakeCell:
    __slots__ = ("uid", "system", "told")

    def __init__(self, uid, system):
        self.uid = uid
        self.system = system
        self.told = []

    def tell(self, msg):
        self.told.append(msg)


class Sink:
    """What a mutator side keeps of the collector's answers."""

    def __init__(self):
        self.calls = []

    def __call__(self, kill_uids, freed_uids):
        assert kill_uids.dtype == np.int64 and freed_uids.dtype == np.int64
        self.calls.append((kill_uids.copy(), freed_uids.copy()))

    @property
    def freed(self):
        return np.concatenate([f for _, f in self.calls]) if self.calls else np.empty(0, np.int64)

    @property
    def kills(self):
        return np.concatenate([k for k, _ in self.calls]) if self.calls else np.empty(0, np.int64)


#: the backends that hold foreign actors: the host's fixpoint, the
#: device's wake on one chip, and the wake sharded over a mesh (four of
#: the virtual CPU devices ``conftest.py`` gives)
BACKENDS = ("array", "decremental", "mesh-decremental")


def new_graph(use_device=False, backend=None):
    ctx = CrgcContext(delta_graph_size=64, entry_field_size=E)
    if backend == "mesh-decremental":
        from uigc_tpu.engines.crgc.mesh import MeshShadowGraph

        graph = MeshShadowGraph(
            ctx, FakeSystem.address, n_devices=4, decremental=True
        )
    else:
        graph = ArrayShadowGraph(
            ctx, FakeSystem.address,
            use_device=use_device or backend == "decremental",
        )
    plane = PackedPlane(E)
    registry = {}
    graph.attach_packed_plane(plane, registry.get)
    sink = graph.foreign_sink = Sink()
    return graph, plane, registry, sink


def row(uid, busy=False, root=False, recv=0, created=(), spawned=(), updated=()):
    """One row in plain uids (``write_foreign`` tags and stamps it)."""
    r = np.full(W, -1, dtype=np.int64)
    r[1] = uid
    r[2] = (1 if busy else 0) | (2 if root else 0)
    r[3] = recv
    for i, (o, t) in enumerate(created):
        r[4 + 2 * i], r[5 + 2 * i] = o, t
    for i, c in enumerate(spawned):
        r[4 + 2 * E + i] = c
    for i, (t, info) in enumerate(updated):
        r[4 + 3 * E + 2 * i], r[5 + 3 * E + 2 * i] = t, info
    return r


def fold_foreign(graph, plane, rows):
    plane.write_foreign(np.stack(rows))
    graph.merge_packed(plane.drain())


def foreign_slot(graph, uid):
    return int(graph._fuid_to_slot[uid])


# --------------------------------------------------------------------- #
# the differential: a world of FakeCells against a world of foreign uids
# --------------------------------------------------------------------- #


class World:
    """One half of the differential.  Both halves flush ``CrgcState``s
    into a packed plane; the foreign half's plane stands for a mutator
    process: its rows are drained and shipped to the collector's plane
    through ``write_foreign``, which knows no cell."""

    def __init__(self, n, is_foreign):
        self.is_foreign = is_foreign
        self.ctx = CrgcContext(delta_graph_size=64, entry_field_size=E)
        system = FakeSystem()
        self.cells = [FakeCell(uid, system) for uid in range(1, n + 1)]
        self.states = [CrgcState(CrgcRefob(c), self.ctx) for c in self.cells]
        self.refobs = {}
        self.graph = ArrayShadowGraph(self.ctx, system.address)
        self.plane = PackedPlane(E)
        if is_foreign:
            self.mutator_plane = PackedPlane(E)
            self.graph.attach_packed_plane(self.plane, lambda uid: None)
            self.sink = self.graph.foreign_sink = Sink()
        else:
            self.mutator_plane = self.plane
            self.graph.attach_packed_plane(self.plane, {c.uid: c for c in self.cells}.get)

    def flush(self, a, busy):
        self.states[a].flush_to_ring(busy, self.mutator_plane)

    def drain(self):
        if self.is_foreign:
            shipped = self.mutator_plane.drain()
            if shipped is not None:
                self.plane.write_foreign(shipped)
        rows = self.plane.drain()
        if rows is not None:
            self.graph.merge_packed(rows)

    def snapshot(self):
        g = self.graph
        if self.is_foreign:
            slots = np.nonzero(g._slot_uid >= FOREIGN_BIT)[0]
            slot_uid = dict(zip(slots.tolist(), (g._slot_uid[slots] ^ FOREIGN_BIT).tolist()))
            assert not g.slot_of, "a foreign actor got a cell"
        else:
            slot_uid = {slot: cell.uid for cell, slot in g.slot_of.items()}
        nodes = {
            uid: (int(g.flags[s]), int(g.recv_count[s]), slot_uid.get(int(g.supervisor[s]), -1))
            for s, uid in slot_uid.items()
        }
        edges = {}
        for key, eid in g.edge_of.items():
            if int(g.edge_weight[eid]) != 0:
                edges[(slot_uid[key >> 32], slot_uid[key & 0xFFFFFFFF])] = int(g.edge_weight[eid])
        return nodes, edges


def run_script(rng, worlds, alive, held, ops, p_release):
    """One round of identical mutator operations in every world, among
    the actors still in the graph (a swept actor acts no more).  A send
    is received, so the counts balance once everybody has flushed;
    ``held`` is the references created and not yet released."""
    def pick():
        return int(alive[rng.integers(0, len(alive))])

    for _ in range(ops):
        a, r = pick(), rng.random()
        if r < p_release:
            if not held:
                continue
            o, t = held.pop(int(rng.integers(0, len(held))))
            for w in worlds:
                st = w.states[o]
                ref = CrgcRefob(w.cells[t])
                if not st.can_record_updated_refob(ref):
                    w.flush(o, True)
                ref.deactivate()
                st.record_updated_refob(ref)
        elif r < p_release + 0.3:
            o, t = pick(), pick()
            held.append((o, t))
            for w in worlds:
                st = w.states[a]
                if not st.can_record_new_refob():
                    w.flush(a, True)
                st.record_new_refob(CrgcRefob(w.cells[o]), CrgcRefob(w.cells[t]))
        elif r < p_release + 0.4:
            c = pick()
            for w in worlds:
                st = w.states[a]
                if not st.can_record_new_actor():
                    w.flush(a, True)
                st.record_new_actor(CrgcRefob(w.cells[c]))
        else:
            t = pick()
            for w in worlds:
                st = w.states[a]
                ref = w.refobs.get((a, t))
                if ref is None:
                    ref = w.refobs[(a, t)] = CrgcRefob(w.cells[t])
                if not ref.can_inc_send_count() or not st.can_record_updated_refob(ref):
                    w.flush(a, True)
                ref.inc_send_count()
                st.record_updated_refob(ref)
                st = w.states[t]
                if not st.can_record_message_received():
                    w.flush(t, True)
                st.record_message_received()
    for a in alive:
        for w in worlds:
            w.flush(int(a), a % 16 == 1)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_foreign_world_matches_cell_world(seed):
    rng = np.random.default_rng(seed)
    n = 64
    cells, uids = World(n, is_foreign=False), World(n, is_foreign=True)
    worlds = [cells, uids]
    for a in range(0, n, 16):
        for w in worlds:
            w.states[a].mark_as_root()
    alive, held = np.arange(n), []
    for round_ in range(5):
        run_script(rng, worlds, alive, held, ops=200, p_release=0.1)
        for w in worlds:
            w.drain()
        assert cells.snapshot() == uids.snapshot(), f"seed {seed} round {round_}"

    swept = set()
    for round_ in range(4):
        for w in worlds:
            w.graph.trace(should_kill=True)
        assert cells.snapshot() == uids.snapshot(), f"seed {seed} sweep {round_}"
        nodes, _ = uids.snapshot()
        alive = np.array(sorted(uid - 1 for uid in nodes))
        swept = set(range(1, n + 1)) - set(nodes)
        held = [(o, t) for o, t in held if o + 1 in nodes and t + 1 in nodes]
        run_script(rng, worlds, alive, held, ops=150, p_release=0.5)
        for w in worlds:
            w.drain()
        assert cells.snapshot() == uids.snapshot(), f"seed {seed} churn {round_}"
    # what the sink was told is what left the graph, each uid once, and
    # the stopped cells of the other world are the uids to kill
    freed = uids.sink.freed
    assert sorted(freed.tolist()) == sorted(swept) and swept
    assert len(uids.sink.calls) == 4
    told = sorted(c.uid for c in cells.cells if c.told)
    assert told == sorted(uids.sink.kills.tolist())


# --------------------------------------------------------------------- #
# interning and the tombstone
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("backend", BACKENDS)
def test_swept_uid_is_dropped_and_unseen_uid_is_interned(backend):
    graph, plane, _, sink = new_graph(backend=backend)
    # 1 is a root holding 2; 3 is referenced by nobody: garbage
    fold_foreign(graph, plane, [
        row(1, root=True, created=[(1, 2)], spawned=[2, 3]),
        row(2), row(3),
    ])
    assert graph.trace(should_kill=True) == 1
    assert sink.freed.tolist() == [3] and sink.kills.tolist() == [3]
    assert foreign_slot(graph, 3) == -2  # the tombstone
    seen = graph.total_actors_seen

    # a late row of the swept actor, naming a live one and a new one:
    # its own facts and the pair that names it are dropped; the pair
    # between the two others is kept, and uid 4 is interned
    fold_foreign(graph, plane, [
        row(3, busy=True, recv=5, created=[(3, 1), (1, 4)], updated=[(2, 2 << 1)]),
    ])
    assert foreign_slot(graph, 3) == -2, "a swept uid was interned again"
    assert graph.total_actors_seen == seen + 1
    s1, s2, s4 = (foreign_slot(graph, u) for u in (1, 2, 4))
    assert s4 >= 0 and graph.flags[s4] == F.FLAG_IN_USE
    assert graph.recv_count[s2] == 0, "a swept sender's count was applied"
    keys = {k: int(graph.edge_weight[e]) for k, e in graph.edge_of.items()}
    assert keys == {(s1 << 32) | s2: 1, (s1 << 32) | s4: 1}
    # not interned yet, so a pseudoroot: nothing more to free
    assert graph.trace(should_kill=True) == 0
    assert sink.freed.tolist() == [3]


def test_interning_100k_unseen_uids_calls_slot_for_zero_times(monkeypatch):
    graph, plane, _, _ = new_graph()
    calls = []
    monkeypatch.setattr(
        ArrayShadowGraph, "slot_for",
        lambda self, cell: calls.append(cell) or pytest.fail("slot_for called"),
    )
    n = 100_000
    rows = np.full((n, W), -1, dtype=np.int64)
    rows[:, 1] = np.arange(n)
    rows[:, 2] = 0
    rows[:, 3] = 0
    rows[1:, 4] = np.arange(n - 1)  # a chain of references 0 -> 1 -> ...
    rows[1:, 5] = np.arange(1, n)
    rows[0, 2] = 2  # the head is a root
    plane.write_foreign(rows)
    t0 = time.perf_counter()
    graph.merge_packed(plane.drain())
    took = time.perf_counter() - t0
    assert not calls and not graph.slot_of
    assert graph.total_actors_seen == n and graph.capacity >= n
    slots = graph._fuid_to_slot[:n]
    assert np.array_equal(np.sort(slots), np.arange(n)), "slots popped in bulk, lowest first"
    assert np.array_equal(graph._slot_uid[slots], np.arange(n) | FOREIGN_BIT)
    assert len(graph.edge_of) == n - 1
    assert took < 5.0, f"the fold of {n} rows took {took:.1f}s: per-uid Python?"
    # the sweep of cell-less slots is as flat: drop the root, all die
    fold_foreign(graph, plane, [row(0)])
    sink = graph.foreign_sink
    assert graph.trace(should_kill=True) == n
    assert len(sink.calls) == 1 and np.array_equal(np.sort(sink.freed), np.arange(n))
    assert not (graph._fuid_to_slot[:n] != -2).any()


def test_each_garbage_uid_reaches_the_sink_once_as_one_array_per_wake():
    graph, plane, _, sink = new_graph()
    # root 0 spawns and holds 1..9; 10..19 hang off 1..9 pairwise
    fold_foreign(graph, plane, [
        row(0, root=True, created=[(0, 1), (0, 2), (0, 3), (0, 4)], spawned=[1, 2, 3, 4]),
        row(0, root=True, created=[(0, 5), (0, 6)], spawned=[5, 6]),
    ] + [row(u, created=[(u, u + 10)], spawned=[u + 10]) for u in range(1, 7)]
      + [row(u) for u in range(11, 17)])
    assert graph.trace(should_kill=True) == 0
    assert len(sink.calls) == 1 and not sink.freed.size and not sink.kills.size
    gone = []
    for wake, u in enumerate((1, 2, 3), start=2):
        # the root drops its reference to u: u and u + 10 die, u is the
        # one to stop (its supervisor lives), u + 10 falls with it
        fold_foreign(graph, plane, [row(0, root=True, updated=[(u, 1)])])
        assert graph.trace(should_kill=True) == 2
        assert len(sink.calls) == wake
        kills, freed = sink.calls[-1]
        assert kills.tolist() == [u] and sorted(freed.tolist()) == [u, u + 10]
        gone += [u, u + 10]
    assert sorted(sink.freed.tolist()) == sorted(gone)
    live = set(range(17)) - {7, 8, 9, 10} - set(gone)
    assert {u for u in range(17) if foreign_slot(graph, u) >= 0} == live
    # with should_kill off the sweep frees and tells the sink no kill
    fold_foreign(graph, plane, [row(0, root=True, updated=[(4, 1)])])
    assert graph.trace(should_kill=False) == 2
    assert not sink.calls[-1][0].size and sorted(sink.calls[-1][1].tolist()) == [4, 14]


@pytest.mark.parametrize("backend", BACKENDS)
def test_local_and_foreign_actors_in_one_graph(backend):
    """A local actor kept alive only by a foreign one, and the reverse:
    when the keeper lets go, the local one gets ``StopMsg`` and the
    foreign one goes to the sink."""
    from uigc_tpu.engines.crgc.messages import StopMsg

    graph, plane, registry, sink = new_graph(backend=backend)
    system = FakeSystem()
    root, kept = FakeCell(1, system), FakeCell(2, system)
    registry.update({1: root, 2: kept})
    f_root, f_kept = 7, 8
    rows = np.stack([
        # local root 1 supervises local 2 and foreign 8; holds foreign 8
        row(1, root=True, created=[(1, foreign(f_kept))], spawned=[2, foreign(f_kept)]),
        row(2),
        # foreign root 7 holds local 2
        row(foreign(f_root), root=True, created=[(foreign(f_root), 2)]),
        row(foreign(f_kept)),
    ])
    rows[:, 0] = [plane.next_seq() for _ in rows]
    plane.ring().extend(rows)
    graph.merge_packed(plane.drain())
    assert graph.trace(should_kill=True) == 0
    assert graph.slot_of.keys() == {root, kept}
    assert foreign_slot(graph, f_kept) >= 0 and graph.cells[foreign_slot(graph, f_kept)] is None

    # the foreign root drops local 2; the local root drops foreign 8
    rows = np.stack([
        row(foreign(f_root), root=True, updated=[(2, 1)]),
        row(1, root=True, updated=[(foreign(f_kept), 1)]),
    ])
    rows[:, 0] = [plane.next_seq() for _ in rows]
    plane.ring().extend(rows)
    graph.merge_packed(plane.drain())
    assert graph.trace(should_kill=True) == 2
    assert kept.told == [StopMsg] and not root.told
    assert sink.calls[-1][0].tolist() == [f_kept] and sink.calls[-1][1].tolist() == [f_kept]
    assert kept not in graph.slot_of and root in graph.slot_of
    assert 2 not in plane.uid_strong


# --------------------------------------------------------------------- #
# verdicts against trace_marks_np
# --------------------------------------------------------------------- #


def random_graph(rng, n):
    """A graph in uid space with every wrinkle of the semantics: cycles,
    busy and halted actors, receive counts that do not balance,
    references with a count of zero or less."""
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED | F.FLAG_LOCAL, np.uint8)
    flags[rng.random(n) < 0.03] |= F.FLAG_ROOT
    flags[rng.random(n) < 0.03] |= F.FLAG_BUSY
    halted = rng.random(n) < 0.05
    recv = np.where(rng.random(n) < 0.03, rng.integers(-2, 3, n), 0).astype(np.int64)
    supervisor = np.full(n, -1, np.int32)
    kids = np.arange(1, n)
    has = rng.random(n - 1) < 0.8
    supervisor[kids[has]] = (rng.random(int(has.sum())) * kids[has]).astype(np.int32)
    m = int(2.0 * n)
    src = rng.integers(0, n, m)
    dst = np.where(rng.random(m) < 0.5, (src + rng.integers(1, 4, m)) % n, rng.integers(0, n, m))
    released = rng.random(m) < 0.3
    return flags, halted, recv, supervisor, src, dst, released


def rows_of(flags, recv, supervisor, src, dst, released):
    """The graph as rows: every reference a created pair in a row of its
    owner, every release an updated field, every child a spawned field."""
    rows = []
    n = flags.shape[0]
    for a in range(n):
        bits = dict(busy=bool(flags[a] & F.FLAG_BUSY), root=bool(flags[a] & F.FLAG_ROOT))
        mine = np.nonzero(src == a)[0]
        created = [(a, int(dst[e])) for e in mine]
        updated = [(int(dst[e]), 1) for e in mine[released[mine]]]
        spawned = np.nonzero(supervisor == a)[0].tolist()
        first = True
        while first or created or updated or spawned:
            rows.append(row(a, recv=int(recv[a]) if first else 0, created=created[:E],
                            spawned=spawned[:E], updated=updated[:E], **bits))
            created, updated, spawned = created[E:], updated[E:], spawned[E:]
            first = False
    return rows


@pytest.mark.parametrize(
    "backend,n", [("array", 400), ("decremental", 160), ("mesh-decremental", 160)]
)
@pytest.mark.parametrize("seed", [0, 1])
def test_random_graphs_against_trace_marks_np(backend, n, seed):
    rng = np.random.default_rng([seed, n])
    flags, halted, recv, supervisor, src, dst, released = random_graph(rng, n)
    graph, plane, _, sink = new_graph(backend=backend)
    rows = rows_of(flags, recv, supervisor, src, dst, released)
    for at in range(0, len(rows), 97):  # several drains, several blocks
        fold_foreign(graph, plane, rows[at:at + 97])
    slots = graph._fuid_to_slot[:n]
    assert (slots >= 0).all()
    graph.flags[slots[halted]] |= F.FLAG_HALTED  # a dead node's actors
    flags = flags | np.where(halted, F.FLAG_HALTED, 0).astype(np.uint8)

    for wake in range(3):
        weight = np.where(released, 0, 1).astype(np.int64)
        marks = F.trace_marks_np(flags, recv, supervisor, src, dst, weight)
        in_use = (flags & F.FLAG_IN_USE) != 0
        want = np.nonzero(in_use & ~marks)[0]
        assert graph.trace(should_kill=True) == want.size
        kills, freed = sink.calls[-1]
        assert np.array_equal(np.sort(freed), want), f"wake {wake}"
        sup_marked = (supervisor >= 0) & marks[np.maximum(supervisor, 0)]
        want_kills = want[sup_marked[want] & ((flags[want] & F.FLAG_HALTED) == 0)]
        assert np.array_equal(np.sort(kills), want_kills), f"wake {wake}"
        # the freed leave the reference's graph too; then more churn
        flags[want] = 0
        recv[want] = 0
        supervisor[want] = -1
        gone = ~in_use | ~marks
        dead_edge = gone[src] | gone[dst]
        released = released | dead_edge
        live = np.nonzero(marks & ((flags & F.FLAG_ROOT) == 0))[0]
        if not live.size:
            break
        drop = np.nonzero(~released & marks[src] & marks[dst])[0]
        drop = drop[rng.random(drop.size) < 0.3]
        released[drop] = True
        # the owner's next flush carries the releases, four to a row
        batch = []
        for a in np.unique(src[drop]).tolist():
            mine = [(int(dst[e]), 1) for e in drop[src[drop] == a]]
            bits = dict(busy=bool(flags[a] & F.FLAG_BUSY), root=bool(flags[a] & F.FLAG_ROOT))
            for at in range(0, len(mine), E):
                batch.append(row(a, updated=mine[at:at + E], **bits))
        if batch:
            fold_foreign(graph, plane, batch)
    assert np.unique(sink.freed).size == sink.freed.size, "a uid was delivered twice"
    if backend != "array":
        assert graph.trace_impl == "pallas-interpret" and graph.device_wakes >= 1


# --------------------------------------------------------------------- #
# the plane
# --------------------------------------------------------------------- #


def test_write_foreign_tags_stamps_and_publishes_a_block_at_once():
    plane = PackedPlane(E)
    assert uid_columns(E).tolist() == [1] + list(range(4, 16)) + [16, 18, 20, 22]
    before = plane.next_seq()
    rows = np.stack([
        row(5, created=[(5, 6)], spawned=[7], updated=[(6, (3 << 1) | 1)]),
        row(6, recv=3),
    ])
    plane.write_foreign(rows)
    got = plane.drain()
    assert got[:, 0].tolist() == [before + 1, before + 2], "consecutive stamps, in order"
    assert got[:, 1].tolist() == [foreign(5), foreign(6)]
    assert got[0, 4:6].tolist() == [foreign(5), foreign(6)]
    assert got[0, 12] == foreign(7) and got[0, 16] == foreign(6)
    assert got[0, 17] == (3 << 1) | 1, "the refob info is no uid"
    assert (got[1, 4:] == -1).all() and got[1, 3] == 3, "an empty field stays empty"
    plane.write_foreign(np.empty((0, W), np.int64))
    assert plane.drain() is None


def test_ring_extend_wraps_and_grows():
    ring = PackedRing(width=2, cap=8)
    block = lambda lo, hi: np.stack([np.arange(lo, hi), -np.arange(lo, hi)], axis=1)
    ring.extend(block(0, 5))
    assert ring.drain()[:, 0].tolist() == list(range(5))
    ring.extend(block(5, 11))  # wraps across the boundary
    assert ring.cap == 8 and ring.drain()[:, 0].tolist() == list(range(5, 11))
    v = ring.begin()
    v[:] = 11
    ring.commit()
    ring.extend(block(12, 112))  # grows in one jump, the unread row kept
    got = ring.drain()
    assert ring.cap == 128 and got[:, 0].tolist() == list(range(11, 112))
    assert got[:, 1].tolist()[1:] == [-i for i in range(12, 112)]
    assert ring.drain() is None


# --------------------------------------------------------------------- #
# through the engine
# --------------------------------------------------------------------- #


def _foreign_tree_rows(n):
    """Root 0 supervises and holds 1..n-1."""
    rows = []
    for at in range(1, n, E):
        kids = list(range(at, min(at + E, n)))
        rows.append(row(0, root=True, created=[(0, k) for k in kids], spawned=kids))
    return rows + [row(k) for k in range(1, n)]


def _wait(predicate, seconds=30.0):
    deadline = time.time() + seconds
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


def test_engine_folds_foreign_rows_beside_running_actors_with_uigcsan_clean():
    from uigc_tpu.interfaces import NoRefs
    from uigc_tpu.runtime.behaviors import AbstractBehavior, Behaviors
    from uigc_tpu.runtime.signals import PostStop
    from uigc_tpu.runtime.testkit import ActorTestKit

    class Drop(NoRefs):
        pass

    stopped = []

    class Kid(AbstractBehavior):
        def on_message(self, msg):
            return self

        def on_signal(self, signal):
            if signal is PostStop:
                stopped.append(self.context.name)
            return self

    kit = ActorTestKit({
        "uigc.crgc.wakeup-interval": 10,
        "uigc.analysis.sanitizer": True,
        "uigc.telemetry.wake-profile": True,
    })
    try:
        engine = kit.system.engine
        sink = Sink()
        delivered = threading.Event()
        engine.set_foreign_sink(lambda k, f: (sink(k, f), f.size and delivered.set()))
        kids = {}

        def root_setup(ctx):
            kids["refs"] = [ctx.spawn(Behaviors.setup(lambda c: Kid(c)), f"k{i}") for i in range(6)]

            class Root(AbstractBehavior):
                def on_message(self, msg):
                    ctx.release(kids["refs"])
                    return self

            return Root(ctx)

        root = kit.spawn(Behaviors.setup_root(root_setup), "root")
        n = 40
        engine.packed_plane.write_foreign(np.stack(_foreign_tree_rows(n)))
        graph = engine.bookkeeper.shadow_graph
        assert _wait(lambda: graph.total_actors_seen >= n + 7)
        assert not sink.freed.size and not stopped

        # local and foreign garbage in the same wakes
        root.tell(Drop())
        engine.packed_plane.write_foreign(np.stack([
            row(0, root=True, updated=[(k, 1) for k in range(at, at + E)])
            for at in range(1, 21, E)
        ]))
        assert delivered.wait(30.0)
        assert _wait(lambda: len(stopped) == 6 and sink.freed.size == 20)
        assert sorted(sink.freed.tolist()) == list(range(1, 21))
        assert sorted(sink.kills.tolist()) == list(range(1, 21))
        assert sorted(stopped) == [f"/user/root/k{i}" for i in range(6)]
        assert kit.system.sanitizer.checks > 0
        assert kit.system.sanitizer.violations == []
        # the wakes' records carry the path's counters
        records = kit.system.engine.wake_profiler.to_json()["recent"]
        assert all(
            {"fold_rows", "uids_interned", "upload_bytes", "kill_uids", "layout_rows",
             "layout_rebuilt"} <= r.keys() for r in records
        )
        assert sum(r["uids_interned"] for r in records) >= n
        assert sum(r["kill_uids"] for r in records) == 20
        assert max(r["fold_rows"] for r in records) >= 5
    finally:
        kit.shutdown()


def test_fold_message_folds_and_leaves_the_trace_to_the_next_wakeup():
    from uigc_tpu.runtime.testkit import ActorTestKit

    kit = ActorTestKit({"uigc.crgc.wakeup-interval": 10})
    try:
        engine = kit.system.engine
        keeper = engine.bookkeeper
        keeper.stop_timers()
        time.sleep(0.1)
        sink = Sink()
        engine.set_foreign_sink(sink)
        base = keeper.total_entries
        calls = len(sink.calls)
        rows = _foreign_tree_rows(12) + [row(100)]  # 100: held by nobody
        engine.packed_plane.write_foreign(np.stack(rows))
        engine.bookkeeper_cell.tell(collector.FOLD)
        assert _wait(lambda: keeper.total_entries == base + len(rows))
        time.sleep(0.1)
        assert len(sink.calls) == calls, "a fold-only wake traced"
        engine.bookkeeper_cell.tell(collector.WAKEUP)
        assert _wait(lambda: sink.freed.size == 1)
        assert sink.freed.tolist() == [100] and not sink.kills.size
    finally:
        kit.shutdown()


def test_a_sink_needs_the_packed_plane():
    from uigc_tpu.runtime.testkit import ActorTestKit

    kit = ActorTestKit({"uigc.crgc.shadow-graph": "oracle"})
    try:
        with pytest.raises(ValueError, match="packed plane"):
            kit.system.engine.set_foreign_sink(lambda k, f: None)
    finally:
        kit.shutdown()


def test_decremental_wake_record_counts_the_upload():
    """``upload_bytes`` is what the wake handed the device for node
    features: the whole ``flags`` and ``recv_count`` arrays on the first
    wake, after it the padded patch of the slots written since (an int32
    index, a flag byte and an int32 count a slot)."""
    from uigc_tpu.engines.crgc import arrays
    from uigc_tpu.telemetry.profile import WakeProfiler

    graph, plane, _, sink = new_graph(use_device=True)
    profiler = WakeProfiler("test")
    logged = []  # the pair log's rows when each wake's fold is done
    for wake_no in range(4):
        wake = graph.profile_wake = profiler.begin_wake()
        if wake_no < 2:
            fold_foreign(graph, plane, _foreign_tree_rows(9) if not wake_no else [
                row(0, root=True, updated=[(3, 1)])])
        logged.append(0 if graph._pair_log is None else len(graph._pair_log))
        graph.trace(should_kill=True)
        graph.profile_wake = None
        wake.end(entries=0, garbage=0)
    first, second, third, fourth = profiler.to_json()["recent"]
    assert first["fold_rows"] == 10 and first["uids_interned"] == 9
    assert second["fold_rows"] == 1 and second["uids_interned"] == 0
    assert second["kill_uids"] == 1 and second["freed"] == 1
    # ``layout_rows`` beside it: the pair transitions the layout phase
    # folded, which are what the fold logged since the wake before (and,
    # in the third wake, what the second's sweep found hanging on the
    # dead); the first wake packs the layout and reads no log
    assert [r["layout_rebuilt"] for r in (first, second, third, fourth)] == [1, 0, 0, 0]
    assert logged[0] == 0 and logged[1] == 1 and logged[2] > 0 and logged[3] == 0
    assert [r["layout_rows"] for r in (first, second, third, fourth)] == logged
    assert first["upload_bytes"] == graph.flags.nbytes + graph.recv_count.nbytes
    assert second["upload_bytes"] == arrays._patch_pad(2) * (4 + 1 + 4)
    assert sink.freed.tolist() == [3]


def test_mesh_wake_record_has_every_phase_and_note_of_the_one_chip_road():
    """A ``WakeProfiler`` attached to a ``mesh-decremental`` graph
    records what it records of the ``decremental`` road: the same phases
    above zero (``layout``, ``upload``, ``device``, ``readback``,
    ``sweep``), the parts ``stage_s`` and ``dispatch_s``, the notes
    (``upload_bytes``, ``layout_rows``, ``layout_rebuilt``, ``fold_rows``,
    ``kill_uids``, ...) and the wake program's deferred sweep counters,
    with the same values where the road does not enter (what the fold
    and the sweep count, what the program's loops decide)."""
    from uigc_tpu.telemetry.profile import WakeProfiler

    records = {}
    for backend in ("decremental", "mesh-decremental"):
        graph, plane, _, sink = new_graph(backend=backend)
        profiler = WakeProfiler("test")
        for wake_no in range(3):
            wake = graph.profile_wake = profiler.begin_wake()
            if wake_no < 2:
                fold_foreign(graph, plane, _foreign_tree_rows(9) if not wake_no else [
                    row(0, root=True, updated=[(3, 1)])])
            graph.trace(should_kill=True)
            graph.profile_wake = None
            wake.end(entries=0, garbage=0)
        records[backend] = profiler.to_json()["recent"]
        assert sink.freed.tolist() == [3]
    for one, sharded in zip(records["decremental"], records["mesh-decremental"]):
        assert set(one) <= set(sharded), set(one) - set(sharded)
        for name in ("layout", "upload", "device", "readback", "sweep"):
            assert one["phases"][name] > 0 and sharded["phases"][name] > 0, name
        for part in ("stage_s", "dispatch_s", "device_s"):
            assert one[part] > 0 and sharded[part] > 0, part
        assert sharded["stage_s"] <= sharded["phases"]["upload"]
        assert sharded["dispatch_s"] <= sharded["phases"]["device"]
        for note in ("layout_rows", "layout_rebuilt", "fold_rows", "uids_interned",
                     "kill_uids", "freed", "kills", "trace_mode", "n_sweeps",
                     "closure_sweeps", "closure_bailed", "jump_sweeps"):
            assert one.get(note) == sharded.get(note), note
        assert sharded["upload_bytes"] > 0 or sharded["layout_rows"] == 0
    first = records["mesh-decremental"][0]
    # the first wake puts both node arrays whole, at the padded size
    assert first["layout_rebuilt"] == 1 and first["upload_bytes"] == graph._n_pad * (1 + 8)


@pytest.mark.parametrize("backend", ["decremental", "mesh-decremental"])
def test_a_wake_that_swept_carries_the_cpu_clocks_on_the_one_chip_and_the_mesh_road(backend):
    """Both roads bracket through the same ``_Wake``: a record of a wake
    that swept has thread CPU beside the wall (the wake's, each phase's,
    each part's), the workers' CPU clocks and CPython's collections."""
    import threading

    from uigc_tpu.telemetry.profile import PHASES, WakeProfiler

    graph, plane, _, sink = new_graph(backend=backend)
    profiler = WakeProfiler("test", threads=lambda: {"workers": [threading.get_ident()]})
    profiler.start()
    try:
        for wake_no in range(2):
            wake = graph.profile_wake = profiler.begin_wake()
            fold_foreign(graph, plane, _foreign_tree_rows(9) if not wake_no else [
                row(0, root=True, updated=[(3, 1)])])
            graph.trace(should_kill=True)
            graph.profile_wake = None
            wake.end(entries=0, garbage=0)
        doc = profiler.to_json()
    finally:
        profiler.close()
    assert sink.freed.tolist() == [3] and doc["stalls"] is not None
    first, swept = doc["recent"]
    assert swept["freed"] == 1
    for rec in (first, swept):
        assert set(rec["phases_cpu"]) == set(PHASES)
        # (the fold above is in no phase here: the wake's CPU, no phase's)
        assert sum(rec["phases_cpu"].values()) <= rec["cpu_s"] <= rec["wall_s"] + 0.001
        for name in ("layout", "upload", "device", "readback", "sweep"):
            assert 0 < rec["phases_cpu"][name] <= rec["phases"][name] + 0.001, name
        for part in ("stage", "dispatch"):
            assert 0 < rec[part + "_cpu_s"] <= rec[part + "_s"] + 0.001, part
        # the test's thread stands for the workers: what it ran, they ran
        assert abs(rec["workers_cpu_s"] - rec["cpu_s"]) < 0.01
        assert rec["workers_busy_max_s"] == rec["workers_cpu_s"]
        assert 0 < rec["workers_cpu_sweep_s"] <= rec["workers_cpu_s"]
        assert abs(rec["workers_cpu_sweep_s"] - rec["phases_cpu"]["sweep"]) < 0.005
        assert rec["process_cpu_s"] >= rec["cpu_s"] - 0.005
        assert rec["gc_s"] >= rec["gc_sweep_s"] >= 0 and rec["gc_full"] >= 0
    assert first["workers_cpu_gap_s"] is None and swept["workers_cpu_gap_s"] >= 0
    assert doc["phases"]["sweep"]["cpu_total_s"] == pytest.approx(
        first["phases_cpu"]["sweep"] + swept["phases_cpu"]["sweep"])
