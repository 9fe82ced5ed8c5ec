"""The served runtime's sessions beside a resident graph held by uid alone.

One ``ActorSystem``, its collector on its own timer: local actors that
stay, short sessions that are spawned, used once and released, and a
seeded power-law graph of foreign actors (``engines/crgc/packed.py``)
shipped as blocks of rows through ``PackedPlane.write_foreign`` under
``CRGC.hold_traces()``, the engine's one way to fold without tracing
while a bulk load is in progress.  The verdicts on the foreign side are
held to ``ops/trace.py trace_marks_np`` on the generator's arrays, the
local side to its ``PostStop`` signals.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from uigc_tpu.engines.crgc.packed import FOREIGN_BIT
from uigc_tpu.interfaces import NoRefs
from uigc_tpu.models.graphgen import powerlaw_actor_graph
from uigc_tpu.ops import pallas_trace as pt
from uigc_tpu.ops import trace as F
from uigc_tpu.runtime.behaviors import AbstractBehavior, Behaviors
from uigc_tpu.runtime.signals import PostStop
from uigc_tpu.runtime.testkit import ActorTestKit

E = 4
W = 4 + 5 * E


def rows_of(g) -> np.ndarray:
    """The generator's graph as packed rows in plain uids (its ids): an
    actor's bits and receive count, its references as created pairs and
    the children it supervises as spawned uids, ``E`` of each to a row."""
    n = g["flags"].shape[0]
    refs = [[] for _ in range(n)]
    for s, d in zip(g["edge_src"].tolist(), g["edge_dst"].tolist()):
        refs[s].append(d)
    kids = [[] for _ in range(n)]
    for child, sup in enumerate(g["supervisor"].tolist()):
        if sup >= 0:
            kids[sup].append(child)
    rows = []
    for a in range(n):
        created, spawned, first = refs[a], kids[a], True
        while first or created or spawned:
            r = np.full(W, -1, dtype=np.int64)
            r[1] = a
            r[2] = 2 if g["flags"][a] & F.FLAG_ROOT else 0
            r[3] = int(g["recv_count"][a]) if first else 0
            for i, d in enumerate(created[:E]):
                r[4 + 2 * i], r[5 + 2 * i] = a, d
            r[4 + 2 * E : 4 + 2 * E + len(spawned[:E])] = spawned[:E]
            rows.append(r)
            created, spawned, first = created[E:], spawned[E:], False
    return np.stack(rows)


class Sink:
    def __init__(self):
        self.calls = []

    def __call__(self, kill_uids, freed_uids):
        self.calls.append((kill_uids.copy(), freed_uids.copy()))

    @property
    def freed(self):
        return np.concatenate([f for _, f in self.calls] or [np.empty(0, np.int64)])

    @property
    def kills(self):
        return np.concatenate([k for k, _ in self.calls] or [np.empty(0, np.int64)])


class _Start(NoRefs):
    def __init__(self, sid, stopped):
        self.sid, self.stopped = sid, stopped


class _Use(NoRefs):
    pass


class World:
    """The local side: a resident tree that stays referenced and an owner
    that spawns, uses once and releases a subtree per ``_Start``."""

    def __init__(self, kit, residents=21, session=9, fanout=4):
        self.session = session
        self.lock = threading.Lock()
        self.resident_stops = 0
        self.stops = {}  # sid -> [PostStops per actor of the session]
        self.built = {}
        world = self

        def node(size, stopped, root=False):
            class Node(AbstractBehavior):
                def __init__(self, ctx):
                    super().__init__(ctx)
                    self.idx = stopped(None)
                    rest = size - 1
                    k = min(fanout, rest)
                    self.children = [
                        ctx.spawn(node(rest // k + (1 if i < rest % k else 0), stopped), f"c{i}")
                        for i in range(k)
                    ]

                def on_message(self, msg):
                    return self

                def on_signal(self, signal):
                    if signal is PostStop:
                        stopped(self.idx)
                    return None

            return (Behaviors.setup_root if root else Behaviors.setup)(Node)

        def resident_stopped(idx):
            if idx is not None:
                with world.lock:
                    world.resident_stops += 1

        class Owner(AbstractBehavior):
            def on_message(self, msg):
                top = self.context.spawn(
                    node(session, msg.stopped), f"s{msg.sid}")
                top.tell(_Use(), self.context)
                self.context.release(top)
                return self

        self.resident = kit.spawn(node(residents, resident_stopped, root=True), "resident")
        self.owner = kit.spawn(Behaviors.setup_root(Owner), "owner")

    def _session_hook(self, sid):
        self.stops[sid] = [0] * self.session
        self.built[sid] = 0

        def stopped(idx):
            with self.lock:
                if idx is None:  # a constructor taking its index
                    self.built[sid] += 1
                    return self.built[sid] - 1
                self.stops[sid][idx] += 1

        return stopped

    def run_sessions(self, sids):
        for sid in sids:
            self.owner.tell(_Start(sid, self._session_hook(sid)))

    def all_stopped(self, sids):
        with self.lock:
            return all(min(self.stops[sid]) >= 1 for sid in sids)


def _wait(predicate, seconds=60.0):
    deadline = time.time() + seconds
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


def _whole_wakes(profiler, n=2, each=lambda: True):
    """Wait until ``n`` wakes that BEGAN after this call have ended, and
    return the ordinal of the next.  What was flushed before the call has
    by then been folded, traced and swept: a drain takes every row stamped
    before it, and a stopped actor flushes its last before its ``PostStop``
    (``CRGC.pre_signal``).  ``each`` runs at every poll."""
    first = profiler.to_json()["wakes"] + 1  # its own ordinal: one in flight began before
    assert _wait(lambda: each() and profiler.to_json()["wakes"] >= first + n, 120.0)
    return profiler.to_json()["wakes"]


def _kit(backend, **more):
    return ActorTestKit({
        "uigc.crgc.shadow-graph": backend,
        "uigc.crgc.wakeup-interval": 10,
        "uigc.crgc.entry-field-size": E,
        **more,
    })


def _load(engine, rows, block=700):
    for at in range(0, rows.shape[0], block):
        engine.packed_plane.write_foreign(rows[at : at + block].copy())
        time.sleep(0.03)  # a few timer wake-ups between blocks


def _foreign_graph_by_uid(graph, n):
    """What the engine holds of the foreign side, in uid space:
    ``(held, flags, recv_count, supervisor uid, sorted (src << 32 | dst,
    weight) rows)``, and how many references tie the two sides."""
    slot = graph._fuid_to_slot[:n]
    held = slot >= 0
    at = slot[held]
    code = graph._slot_uid
    uid_of = np.where(code >= FOREIGN_BIT, code ^ FOREIGN_BIT, -1)  # -1: a local or a free slot
    sup = graph.supervisor[at]
    sup_uid = np.where(sup >= 0, uid_of[np.maximum(sup, 0)], -1)
    eids = np.nonzero(graph.edge_weight != 0)[0]
    s, d = uid_of[graph.edge_src[eids]], uid_of[graph.edge_dst[eids]]
    crossing = int(np.count_nonzero((s >= 0) != (d >= 0)))
    both = (s >= 0) & (d >= 0)
    edges = np.stack([(s[both] << 32) | d[both], graph.edge_weight[eids][both]], axis=1)
    edges = edges[np.argsort(edges[:, 0])]
    return held, graph.flags[at], graph.recv_count[at], sup_uid, edges, crossing


@pytest.mark.parametrize("backend", ["array", "decremental"])
def test_sessions_beside_a_foreign_graph_loaded_under_the_hold(backend):
    n = 2000
    g = powerlaw_actor_graph(n, seed=42)
    marks = F.trace_marks_np(g["flags"], g["recv_count"], g["supervisor"],
                             g["edge_src"], g["edge_dst"], g["edge_weight"])
    garbage = np.nonzero(~marks)[0]
    live = np.nonzero(marks)[0]
    assert garbage.size and np.array_equal(~marks, g["expected_garbage"])
    kit = _kit(backend)
    try:
        engine = kit.system.engine
        sink = Sink()
        with engine.hold_traces():
            world = World(kit)
            engine.set_foreign_sink(sink)
            _load(engine, rows_of(g))
            world.run_sessions([0, 1])  # released before the graph's first trace
        graph = engine.bookkeeper.shadow_graph
        assert _wait(lambda: sink.freed.size >= garbage.size and world.all_stopped([0, 1]))
        world.run_sessions([2, 3, 4])
        assert _wait(lambda: world.all_stopped([2, 3, 4]))
        world.run_sessions([5])
        assert _wait(lambda: world.all_stopped([5]))
        # a stopped actor's last flush interns its cell once more; the wake
        # after frees that slot too
        assert _wait(lambda: len(graph.slot_of) == 21 + 1)
        time.sleep(0.2)  # a PostStop too many, a uid too many

        assert np.array_equal(np.sort(sink.freed), garbage), "each garbage uid once, no other"
        # to stop: the garbage whose supervisor lives (the partition's head)
        sup = g["supervisor"]
        assert np.array_equal(np.sort(sink.kills), garbage[marks[np.maximum(sup[garbage], 0)]
                                                           & (sup[garbage] >= 0)])
        with world.lock:
            assert all(c == 1 for stops in world.stops.values() for c in stops)
            assert world.resident_stops == 0
        held, flags, recv, sup_uid, edges, crossing = _foreign_graph_by_uid(graph, n)
        assert np.array_equal(held, marks)
        assert np.array_equal(flags, g["flags"][live])
        assert np.array_equal(recv, g["recv_count"][live])
        assert np.array_equal(sup_uid, sup[live])
        keep = marks[g["edge_src"]] & marks[g["edge_dst"]]
        keys, counts = np.unique(
            (g["edge_src"][keep].astype(np.int64) << 32) | g["edge_dst"][keep], return_counts=True)
        assert np.array_equal(edges, np.stack([keys, counts], axis=1))
        assert crossing == 0
        assert graph.actors_foreign == live.size
        assert len(graph.slot_of) == 21 + 1  # the residents and the owner
        if backend == "decremental":
            assert graph.trace_impl == "pallas-interpret" and graph.device_wakes >= 2
    finally:
        kit.shutdown()


def test_held_wakeups_fold_and_the_first_one_after_traces():
    n = 300
    g = powerlaw_actor_graph(n, seed=7, num_roots=4)
    rows = rows_of(g)
    kit = _kit("decremental")
    try:
        engine = kit.system.engine
        keeper, graph = engine.bookkeeper, engine.bookkeeper.shadow_graph
        sink = Sink()
        engine.set_foreign_sink(sink)
        with engine.hold_traces():
            time.sleep(0.1)  # a wake-up that began before the hold
            wakes, calls, folded = graph.device_wakes, len(sink.calls), keeper.total_entries
            engine.packed_plane.write_foreign(rows[:200].copy())
            assert _wait(lambda: keeper.total_entries == folded + 200), "the timer did not fold"
            with engine.hold_traces():  # another loader comes and goes
                engine.packed_plane.write_foreign(rows[200:].copy())
            assert _wait(lambda: keeper.total_entries == folded + rows.shape[0])
            time.sleep(0.1)
            assert graph.total_actors_seen >= n
            assert (graph.device_wakes, len(sink.calls)) == (wakes, calls), "a held wake-up traced"
        # nobody sends a wake-up: the timer's next one traces
        assert _wait(lambda: sink.freed.size == int(g["expected_garbage"].sum()))
        assert graph.device_wakes > wakes and engine.trace_holds == 0
        assert np.array_equal(np.sort(sink.freed), np.nonzero(g["expected_garbage"])[0])
    finally:
        kit.shutdown()


def test_a_hold_is_refused_where_there_is_no_trace_to_hold():
    kit = ActorTestKit({"uigc.crgc.shadow-graph": "oracle"})
    try:
        engine = kit.system.engine
        engine.distributed = True  # the partitioned collector: waves, no trace
        with pytest.raises(ValueError, match="no trace to hold"):
            with engine.hold_traces():
                pass
        assert engine.trace_holds == 0
    finally:
        kit.shutdown()


def test_a_session_release_beside_residents_repairs_a_region():
    """Beside 6,000 residents held by uid (four supertiles of slots) a
    released session is an island: its closure ends under its price, the
    repair forces the island's supertiles and not all of them, and the
    wake's record says so, with the slots in use by kind."""
    n = 12000
    g = powerlaw_actor_graph(n, seed=3)
    kit = _kit("decremental", **{"uigc.telemetry.wake-profile": True})
    try:
        engine = kit.system.engine
        sink = Sink()
        with engine.hold_traces():
            world = World(kit)
            engine.set_foreign_sink(sink)
            _load(engine, rows_of(g), block=4000)
        graph = engine.bookkeeper.shadow_graph
        n_garbage = int(g["expected_garbage"].sum())
        assert _wait(lambda: sink.freed.size == n_garbage, 120.0)
        world.run_sessions([0])  # warm: the pack after the mass death
        assert _wait(lambda: world.all_stopped([0]), 120.0)
        profiler = engine.wake_profiler
        before = _whole_wakes(profiler)  # and their second sweep: none of it below
        by_wake = {}

        def gather():
            # at every poll: the profiler keeps its last 256 wakes, and an
            # idle collector on a 10 ms timer makes a hundred a second
            by_wake.update((r["wake"], r) for r in profiler.to_json()["recent"]
                           if r["wake"] >= before and r["device_s"] > 0)
            return True

        world.run_sessions([1])
        assert _wait(lambda: gather() and world.all_stopped([1]), 120.0)
        _whole_wakes(profiler, each=gather)  # and their second sweep
        assert len(graph.slot_of) == 21 + 1
        records = [by_wake[wake] for wake in sorted(by_wake)]
        # a stopped actor's last flush interns its cell once more, and the
        # wake after frees that slot too
        assert records and sum(r["freed"] for r in records) in (world.session, 2 * world.session)
        n_super = -(-graph.capacity // (graph._dec.layout.s_rows * pt.LANE))
        assert n_super >= 4
        for r in records:
            assert {"actors_local", "actors_foreign", "closure_bailed", "gated_tiles"} <= r.keys()
            assert r["closure_bailed"] == 0, r
            assert r["gated_tiles"] < n_super, r
            assert r["actors_foreign"] == n - n_garbage
        assert any(r["gated_tiles"] > 0 for r in records)
        assert records[-1]["actors_local"] == len(graph.slot_of) == 21 + 1
        with world.lock:
            assert world.resident_stops == 0
    finally:
        kit.shutdown()
