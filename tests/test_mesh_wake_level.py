"""The sharded wake level with the one-chip wake: ``parallel/sharded_trace.py
make_sharded_decremental_wake`` against ``ops/pallas_decremental.py
_build_wake_fn``, on the virtual CPU mesh (``conftest.py`` gives 8 host
devices), interpreted kernels, seeded graphs of a few thousand actors.

(a) the verdict words of a ``MeshShadowGraph(decremental=True)`` over D
    devices equal the one-chip backend's and the pointer oracle's, wake
    after wake under churn;
(b) on a strongly connected live set the sharded closure gives up in the
    sweep in which the one-chip closure does, and every shard says so;
(c) the shards' kernel counters are what ``tools/sweep_profile.py
    simulate_sweeps`` counts from each destination shard's layout, and a
    mesh of one shard reads the one-chip program's counters to the digit;
(d) the share test: the verdict words as the devices hold them, a D-th
    of the slot space each in slot order (the shards own supertiles
    dealt round-robin, ``sharded_trace.Partition``, and the verdict is
    put back in slot order on the device), laid end to end, are the
    whole verdict, and what every shard computes alike (the gathered table: its sweeps, its dirty chunks,
    its marks) is counted once.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

from test_foreign_uids import E, FakeSystem, Sink, fold_foreign, random_graph, rows_of
from test_sweep_index import BUSY, Rig, ROOT
from uigc_tpu.engines.crgc.arrays import ArrayShadowGraph
from uigc_tpu.engines.crgc.mesh import SHARD_STATS, MeshShadowGraph
from uigc_tpu.engines.crgc.packed import PackedPlane
from uigc_tpu.engines.crgc.state import CrgcContext
from uigc_tpu.models import powerlaw_actor_graph
from uigc_tpu.ops import pallas_decremental as pd
from uigc_tpu.ops import pallas_incremental as pinc
from uigc_tpu.ops import pallas_trace as pt
from uigc_tpu.ops import trace as F
from uigc_tpu.parallel import sharded_trace as st

CAPACITY = 4096
#: supertiles of 1,024 slots, so that 4,096 slots are four shards of one
S_ROWS = 8
#: what every shard reads alike: decided on the gathered table
REPLICATED = ("n_sweeps", "closure_sweeps", "closure_bailed", "closure_spent",
              "jump_sweeps", "jump_spent", "dirty_chunks", "pull_on", "jump_on", "gathers")


def _sweep_profile():
    tools = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import sweep_profile

    return sweep_profile


def new_graph(n_devices, mode):
    """A one-chip backend (``n_devices`` 0) or a mesh of ``n_devices``."""
    ctx = CrgcContext(delta_graph_size=64, entry_field_size=E)
    if n_devices:
        graph = MeshShadowGraph(ctx, FakeSystem.address, n_devices=n_devices, decremental=True,
                                trace_mode=mode, initial_capacity=CAPACITY)
        graph.s_rows = S_ROWS
    else:
        graph = ArrayShadowGraph(ctx, FakeSystem.address, use_device=True, trace_mode=mode,
                                 initial_capacity=CAPACITY)
    plane = PackedPlane(E)
    graph.attach_packed_plane(plane, {}.get)
    graph.foreign_sink = Sink()
    return graph, plane


def oracle_words(graph):
    """(garbage words, marks) of the pointer oracle over the graph's arrays."""
    marks = F.trace_marks_np(graph.flags, graph.recv_count, graph.supervisor,
                             graph.edge_src, graph.edge_dst, graph.edge_weight)
    garbage = ((graph.flags & F.FLAG_IN_USE) != 0) & ~marks
    return np.packbits(garbage, bitorder="little").view(np.uint32), int(marks.sum())


def churn(graph, rng):
    """A wake's churn through the graph's own mutators: references
    released and made among the slots in use, busy bits flipped, a root
    let go.  Drawn from ``rng`` over the graph's state alone, so that two
    graphs in the same state, each with a generator of the same seed,
    take the same churn."""
    used = np.flatnonzero(graph.flags & np.uint8(F.FLAG_IN_USE))
    alive = np.flatnonzero(graph.edge_weight > 0)
    drop = np.unique(alive[rng.integers(0, alive.size, 60)])
    keys = (graph.edge_src[drop].astype(np.int64) << 32) | graph.edge_dst[drop]
    graph._apply_edge_deltas(keys, -graph.edge_weight[drop])
    src, dst = used[rng.integers(0, used.size, 40)], used[rng.integers(0, used.size, 40)]
    keys = np.unique((src.astype(np.int64) << 32) | dst)
    graph._apply_edge_deltas(keys, np.ones(keys.size, np.int64))
    some = used[rng.integers(0, used.size, 6)]
    graph.flags[some] ^= np.uint8(BUSY)
    roots = used[(graph.flags[used] & np.uint8(ROOT)) != 0]
    graph.flags[roots[rng.integers(0, roots.size, 1)]] &= np.uint8(0xFF & ~ROOT)
    graph._touch_batch(np.concatenate([some, roots]))


@pytest.mark.parametrize("mode", [pt.MODE_PUSH, pt.MODE_AUTO])
@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_sharded_verdict_words_equal_the_one_chip_wakes_and_the_oracles(n_devices, mode):
    n = 3000  # slots in three of four shards of 1,024
    flags, halted, recv, supervisor, src, dst, released = random_graph(
        np.random.default_rng([3, n_devices]), n)
    rows = rows_of(flags, recv, supervisor, src, dst, released)
    graphs, rngs = [], []
    for devices in (0, n_devices):
        graph, plane = new_graph(devices, mode)
        for at in range(0, len(rows), 997):
            fold_foreign(graph, plane, rows[at:at + 997])
        slots = graph._fuid_to_slot[:n]
        graph.flags[slots[halted]] |= F.FLAG_HALTED
        graph._touch_batch(slots[halted])
        graphs.append(graph)
        rngs.append(np.random.default_rng(11))
    one, sharded = graphs
    words = CAPACITY // 32
    freed = 0
    for wake in range(5):
        want_w, want_live = oracle_words(one)
        got = [g.compute_marks() for g in graphs]
        for graph, verdicts in zip(graphs, got):
            assert np.array_equal(verdicts.garbage_w[:words], want_w), (wake, type(graph).__name__)
            assert not verdicts.garbage_w[words:].any()
            assert verdicts.num_live == want_live
        assert sharded._n_pad == CAPACITY and sharded._shard_size == CAPACITY // n_devices
        # (d) every device holds a D-th of the verdict, in slot order
        shards = sharded.shard_verdict_words()
        assert [w.size * 32 for w in shards] == [sharded._shard_size] * n_devices
        assert np.array_equal(np.concatenate(shards), got[1].garbage_w)
        for graph, verdicts in zip(graphs, got):
            freed += graph._sweep(True, verdicts)[0]
            churn(graph, rngs[graphs.index(graph)])
        assert np.array_equal(one.flags, sharded.flags)
    assert freed > 0 and sharded.stats == {
        "rebuilds": 1, "wakes": 5, "anomalies": 0, "bucket_grows": 0}
    # what the shards decide on the gathered table, they decide alike, and
    # as the one chip does: every wake ends in the same sweep on both
    one_stats = one._dec.wake_stats()
    for s1, sm in zip(one_stats, sharded.wake_stats()):
        assert set(sm) == set(s1) | {"gathers"}
        for key in SHARD_STATS:
            assert len(sm[key]) == n_devices, key
        # (under ``auto`` the mesh prices a jump sweep in its own totals,
        # ``sharded_trace._mesh_jump_policy``: padded blocks of D shards, so
        # on a graph this small the jump may engage a sweep apart)
        same = ("closure_sweeps", "closure_bailed") if mode == pt.MODE_AUTO else (
            "n_sweeps", "closure_sweeps", "closure_bailed", "dirty_chunks", "pull_on")
        for key in same:
            assert sm[key] == s1[key], key
        assert sm["gathers"] == sm["n_sweeps"] + sm["closure_sweeps"] + (
            4 if mode == pt.MODE_AUTO else 3)
    assert any(s["closure_sweeps"] for s in one_stats[1:])


@pytest.mark.parametrize("n_devices", [2, 4])
def test_the_sharded_closure_gives_up_in_the_one_chip_closures_sweep(n_devices):
    """A chain of 600 actors, each holding the next and supervised by
    the one before it (a child marks its supervisor): the live set is
    one strongly connected component over three shards, and the closure
    of a released reference in mid-chain is every mark.  Both programs
    leave it at the price, in the same sweep, with the same spend, and
    re-derive from the seeds."""
    graphs = []
    for devices in (0, n_devices):
        rig = Rig("mesh-decremental" if devices else "decremental", 0,
                  trace_mode=pt.MODE_PUSH, initial_capacity=CAPACITY,
                  **({"n_devices": devices} if devices else {}))
        g = rig.graph
        if devices:
            g.s_rows = 256 // 128  # 256 slots a supertile: the chain crosses shards
        root = int(rig.spawn(1, flags=F.FLAG_INTERNED | F.FLAG_LOCAL | F.FLAG_ROOT)[0])
        chain = [root]
        for _ in range(599):
            chain.append(int(rig.spawn(1, sup=chain[-1])[0]))
        chain = np.array(chain)
        rig.deltas(chain[:-1], chain[1:], np.ones(599, np.int64))
        assert g.trace(should_kill=True) == 0
        # a second reference into mid-chain, released again: nothing
        # dies, and its target's closure is everything
        rig.deltas(chain[:1], chain[300:301], np.ones(1, np.int64))
        assert g.trace(should_kill=True) == 0
        rig.deltas(chain[:1], chain[300:301], -np.ones(1, np.int64))
        assert g.trace(should_kill=True) == 0
        graphs.append(g)
    one, sharded = graphs
    last = one._dec.wake_stats(1)[0]
    assert last["closure_bailed"] == 1 and last["closure_sweeps"] >= pt.CLOSURE_MIN_WALKS
    # every shard's own row of the counters, as the program left them
    rows = {key: np.asarray(value) for key, value in sharded._wake_counters[-1].items()}
    for key in ("closure_bailed", "closure_sweeps", "closure_spent", "n_sweeps"):
        assert rows[key].tolist() == [last[key]] * n_devices, key
    stats = sharded.wake_stats(1)[0]
    assert stats["closure_bailed"] == 1 and stats["closure_sweeps"] == last["closure_sweeps"]
    assert stats["gated_tiles"] == [0] * n_devices  # the cold road forces no tile


@pytest.mark.parametrize("mode", [pt.MODE_PUSH, pt.MODE_AUTO])
@pytest.mark.parametrize("n_devices", [1, 4])
def test_shard_counters_are_the_simulators_and_one_shard_reads_as_one_chip(n_devices, mode):
    n = 4 * 1024 * 8  # four shards of eight supertiles each
    g = powerlaw_actor_graph(n, seed=0, garbage_fraction=0.5)
    psrc, pdst, _ = pinc.IncrementalPallasLayout.pairs_from_graph(
        g["edge_src"], g["edge_dst"], g["edge_weight"], g["supervisor"])
    stacked, meta, _ = st.pack_shard_layouts(psrc, pdst, n, n_devices, s_rows=S_ROWS)
    bucket = 64
    wake = st.make_sharded_decremental_wake(
        st.build_mesh(n_devices), n, meta["shard_size"], meta["n_blocks"], meta["r_rows"],
        S_ROWS, bucket, sub=meta["sub"], group=meta["group"], mode=mode)
    zeros = np.zeros(n // 32, np.int32)
    jump = (pt.jump_parents(psrc, pdst, n),) if mode == pt.MODE_AUTO else ()
    part = st.Partition(n_devices, S_ROWS * 128)
    *state, stats = wake(
        part.owner_major(g["flags"]), part.owner_major(g["recv_count"]), zeros, zeros, *([zeros] * 5), np.zeros((), np.int32),
        stacked["bmeta1"], stacked["bmeta2"], stacked["row_pos"], stacked["emeta"],
        np.full((n_devices, bucket), n, np.int32), np.zeros((n_devices, bucket), np.int32), *jump)
    mark_w = part.slot_major(np.asarray(state[0]), per=32)
    marks = np.unpackbits(mark_w.view(np.uint8), bitorder="little")[:n] > 0
    assert np.array_equal(marks, F.trace_marks_np(
        g["flags"], g["recv_count"], g["supervisor"], g["edge_src"], g["edge_dst"],
        g["edge_weight"]))
    stats = {key: np.asarray(rows) for key, rows in stats.items()}
    assert set(stats) == set(pd.WAKE_STATS) | {"gathers"}
    assert all(rows.shape[0] == n_devices for rows in stats.values())
    for key in REPLICATED:  # decided on the gathered table: alike on every shard
        assert (stats[key] == stats[key][:1]).all(), key

    simulate = _sweep_profile().simulate_sweeps
    # ``auto`` prices a jump sweep in the mesh's totals: every shard's blocks
    group_rows = pt.ROWS * meta["group"]
    totals = (n_devices * meta["n_blocks"] * pt.ROWS * meta["sub"] * pt.LANE,
              meta["r_rows"] // group_rows, group_rows * pt.LANE * pt.WORD_BITS)
    for d in range(n_devices):
        shard = pd.host_stats({key: rows[d] for key, rows in stats.items() if key != "gathers"})
        sim = simulate(g, n, [mode], geometry=totals,
                       layout=st.shard_layout(stacked, meta, d))[mode]
        assert sim["sweeps"] == shard["n_sweeps"] and sim["dirty_chunks"] == shard["dirty_chunks"]
        assert sum(sim["steps"]) == shard["kernel_steps"], d
        assert sum(sim["contracting"]) == shard["kernel_contractions"], d
        assert sum(sim["chunk_iterations"]) == shard["kernel_chunk_walks"], d
        assert sum(sim["walk_trips"]) == shard["kernel_walk_trips"], d
        assert shard["kernel_steps_full"] == shard["n_sweeps"] * meta["n_blocks"]
    assert int(state[5]) == sum(sim["dirty_chunks"])  # the derivation's walks, replicated
    assert stats["kernel_steps"].sum() > 0 and stats["closure_sweeps"].max() == 0

    if n_devices == 1:
        tracer = pd.DecrementalTracer(n, mode=mode, s_rows=S_ROWS)
        tracer.rebuild(g["edge_src"], g["edge_dst"], g["edge_weight"], g["supervisor"])
        assert np.array_equal(tracer.marks(g["flags"], g["recv_count"]), marks)
        assert tracer.wake_stats(1)[0] == shard
