"""The mesh's insert bucket tier sized by what it holds
(``engines/crgc/mesh.py _grow_buckets``), on the virtual CPU mesh
(``conftest.py`` gives 8 host devices), interpreted kernels, a script of
a few hundred actors.  The floor is lowered to 64 columns a shard, so that
at 4,096 slots floor and ceiling differ (1,024 / 512 / 256 columns on 1 /
2 / 4 shards); three graphs take the same script in step: the one-chip
backend, the mesh, and a twin of the mesh whose tier has today's size
from its first pack on (it never shrinks), which therefore never grows.

(a) after the first wake the tier has the floor, not ``capacity // (4 D)``;
(b) a batch whose inserts overfill one shard grows the tier without a
    pack, the wake's record says so, and the verdict words before and
    after equal the one-chip backend's, the pointer oracle's and the
    marks ``tools/sweep_profile.py simulate_sweeps`` derives;
(c) a release of a pair inserted BEFORE the growth frees its own column,
    on the host and on the device, and the next insert takes it;
(d) the size survives a pack and never shrinks;
(e) a growth stops at the ceiling, and an overflow there still packs;
(f) a growth leaves the previous fixpoint in place: the wake that grows
    runs the closure as the twin's does, not the cold road.
The plain sharded trace (``shadow-graph: mesh``) takes the same tier.
"""

from __future__ import annotations

import numpy as np
import pytest

from test_mesh_wake_level import CAPACITY, S_ROWS, _sweep_profile, oracle_words
from test_sweep_index import INTERNED, LOCAL, ROOT, FakeCell, FakeSystem, Rig
from uigc_tpu.engines.crgc import mesh
from uigc_tpu.engines.crgc.state import CrgcContext
from uigc_tpu.ops import pallas_trace as pt
from uigc_tpu.ops import trace as F
from uigc_tpu.ops.slotmap import pack_keys

FLOOR = 64
#: the script's first growth: 100 pairs into shard 0, at most half of 256
GROWN = 256
WORDS = CAPACITY // 32
#: what a cold road and a repair from the previous fixpoint read apart
FIXPOINT_STATS = ("n_sweeps", "closure_sweeps", "closure_bailed", "gated_tiles")


def rigs_of(n_devices, mode):
    """The one-chip backend, the mesh, and the mesh's twin that never
    grows: its tier has the ceiling from the first pack on."""
    rigs = []
    for devices in (0, n_devices, n_devices):
        rig = Rig("mesh-decremental" if devices else "decremental", 0, trace_mode=mode,
                  initial_capacity=CAPACITY, **({"n_devices": devices} if devices else {}))
        if devices:
            rig.graph.s_rows = S_ROWS  # supertiles of 1,024 slots: four shards of one
        rigs.append(rig)
    twin = rigs[2].graph
    twin._bucket_m = twin._bucket_ceiling()
    return rigs


def each(rigs, call):
    """``call(rig)`` on every rig; they answer alike (the same slots)."""
    got = [call(rig) for rig in rigs]
    for other in got[1:]:
        assert np.array_equal(other, got[0])
    return got[0]


def refs(rigs, src, dst, delta):
    src, dst = np.broadcast_arrays(
        np.atleast_1d(np.asarray(src, np.int64)), np.atleast_1d(np.asarray(dst, np.int64)))
    each(rigs, lambda rig: rig.deltas(src, dst, np.full(src.size, delta, np.int64)))


def free_stack(graph, shard):
    """The freed bucket columns of a shard, the next to be taken last."""
    return graph._pb_free[shard, : graph._pb_nfree[shard]].tolist()


def wake(rigs):
    """One wake of every graph: the verdict words against the pointer
    oracle's and the simulator's marks, then the sweep.  Returns the
    slots freed."""
    one = rigs[0].graph
    want_w, want_live = oracle_words(one)
    arrays = {key: getattr(one, key) for key in (
        "flags", "recv_count", "supervisor", "edge_src", "edge_dst", "edge_weight")}
    sim = _sweep_profile().simulate_sweeps(
        arrays, CAPACITY, [pt.MODE_PUSH], geometry=(CAPACITY, 4, CAPACITY // 4),
        suspects=np.zeros(0, np.int64))[pt.MODE_PUSH]
    assert sim["closure"]["marks"] == want_live
    freed = []
    for rig in rigs:
        verdicts = rig.graph.compute_marks()
        assert np.array_equal(verdicts.garbage_w[:WORDS], want_w), type(rig.graph).__name__
        assert not verdicts.garbage_w[WORDS:].any()
        assert verdicts.num_live == want_live
        freed.append(rig.graph._sweep(True, verdicts)[0])
    assert len(set(freed)) == 1
    for rig in rigs[1:]:
        assert np.array_equal(rig.graph.flags, one.flags)
    return freed[0]


def grown_rigs(n_devices, mode, monkeypatch):
    """The script up to its first growth: a root holding 40 supervised
    actors (wake 1: a pack, the tier at the floor), then 100 more actors
    the root holds, all in shard 0, and one of the 40 let go (wake 2: the
    tier grows).  Returns (rigs, root, the 40, the 100)."""
    monkeypatch.setattr(mesh, "_BUCKET_FLOOR", FLOOR)
    rigs = rigs_of(n_devices, mode)
    _, grown, twin = (rig.graph for rig in rigs)
    ceiling = grown._bucket_ceiling()
    assert ceiling == CAPACITY // (4 * n_devices) > FLOOR
    root = int(each(rigs, lambda rig: rig.spawn(1, flags=INTERNED | LOCAL | ROOT))[0])
    held = each(rigs, lambda rig: rig.spawn(40, sup=root))
    refs(rigs, root, held, 1)
    assert wake(rigs) == 0
    # (a) the floor, not capacity // (4 D)
    assert grown._bucket_m == FLOOR and twin._bucket_m == ceiling
    assert grown._dev_psrc.shape == (n_devices, FLOOR)
    assert rigs[1].wake.fields["bucket_cols"] == FLOOR
    assert rigs[1].wake.fields["bucket_fill"] == 0

    more = each(rigs, lambda rig: rig.spawn(100))
    assert not grown._part.owner(more).any()  # one shard takes them all
    refs(rigs, root, more, 1)
    refs(rigs, root, held[:1], -1)
    assert grown._wake_state is not None
    assert wake(rigs) == 1
    return rigs, root, held, more


@pytest.mark.parametrize("mode", [pt.MODE_PUSH, pt.MODE_AUTO])
@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_an_overfilled_shard_grows_the_tier_without_a_pack(n_devices, mode, monkeypatch):
    rigs, _, _, _ = grown_rigs(n_devices, mode, monkeypatch)
    _, grown, twin = (rig.graph for rig in rigs)
    # (b) grown in place, straight to where the need is at most half
    assert grown.stats == {"rebuilds": 1, "wakes": 2, "anomalies": 0, "bucket_grows": 1}
    assert twin.stats == {"rebuilds": 1, "wakes": 2, "anomalies": 0, "bucket_grows": 0}
    assert grown._bucket_m == GROWN and grown._pb_src.shape == (n_devices, GROWN)
    assert grown._dev_psrc.shape == grown._dev_pdst.shape == (n_devices, GROWN)
    assert np.array_equal(np.asarray(grown._dev_psrc), grown._pb_src)
    assert np.array_equal(np.asarray(grown._dev_pdst), grown._pb_dst)
    # the columns past the old width hold the rest
    assert np.count_nonzero(grown._pb_src[:, FLOOR:] != grown._n_pad) == 100 - FLOOR
    for rig, cols in ((rigs[1], GROWN), (rigs[2], twin._bucket_ceiling())):
        fields = rig.wake.fields
        assert (fields["bucket_cols"], fields["bucket_fill"]) == (cols, 100)
        assert (fields["layout_rows"], fields["layout_rebuilt"]) == (101, 0)
    # (f) the wake that grew repaired from the previous fixpoint
    last, twins = grown.wake_stats(1)[0], twin.wake_stats(1)[0]
    assert last["closure_sweeps"] > 0
    for key in FIXPOINT_STATS:
        assert last[key] == twins[key], key
    first = grown.wake_stats()[0]  # the cold road, for contrast
    assert first["closure_sweeps"] == 0


@pytest.mark.parametrize("mode", [pt.MODE_PUSH, pt.MODE_AUTO])
@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_a_pair_from_before_the_growth_frees_its_own_column(n_devices, mode, monkeypatch):
    rigs, root, _, more = grown_rigs(n_devices, mode, monkeypatch)
    _, grown, twin = (rig.graph for rig in rigs)
    # (c) a pair the narrow tier took, and one the growth made room for
    for victim in (int(more[5]), int(more[90])):
        key = pack_keys([root], [victim], [0])
        packed = int(grown._pb_slot.get_batch(key)[0])
        shard, col = packed >> 32, packed & 0xFFFFFFFF
        assert shard == 0 and (col < FLOOR) == (victim == more[5])
        assert grown._pb_src[0, col] == root
        refs(rigs, root, victim, -1)
        assert wake(rigs) == 1  # it stopped propagating: its target died
        assert grown._pb_slot.get_batch(key)[0] == -1
        assert free_stack(grown, 0) == [col] and grown._pb_src[0, col] == grown._n_pad
        assert np.asarray(grown._dev_psrc)[0, col] == grown._n_pad
        assert rigs[1].wake.fields["bucket_fill"] == 99
        # the next insert takes the freed column
        new = int(each(rigs, lambda rig: rig.spawn(1))[0])
        refs(rigs, root, new, 1)
        assert wake(rigs) == 0
        assert grown._pb_slot.get_batch(pack_keys([root], [new], [0]))[0] == packed
        assert free_stack(grown, 0) == [] and grown._pb_count[0] == 100
        assert np.asarray(grown._dev_psrc)[0, col] == root
    for graph, grows in ((grown, 1), (twin, 0)):
        assert graph.stats == {"rebuilds": 1, "wakes": 6, "anomalies": 0,
                               "bucket_grows": grows}


@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_the_size_survives_a_pack_and_the_ceiling_still_packs(n_devices, monkeypatch):
    rigs, root, held, more = grown_rigs(n_devices, pt.MODE_PUSH, monkeypatch)
    _, grown, twin = (rig.graph for rig in rigs)
    ceiling = grown._bucket_ceiling()
    # (d) a pack (here: the log's overflow) keeps the size the tier grew to
    for rig in rigs:
        rig.graph._pair_log = None
    refs(rigs, root, held[1:2], -1)
    assert wake(rigs) == 1
    assert grown.stats == {"rebuilds": 2, "wakes": 3, "anomalies": 0, "bucket_grows": 1}
    assert grown._bucket_m == GROWN and grown._dev_psrc.shape == (n_devices, GROWN)
    assert not grown._pb_count.any() and (grown._pb_src == grown._n_pad).all()
    fields = rigs[1].wake.fields
    assert (fields["layout_rebuilt"], fields["bucket_cols"], fields["bucket_fill"]) == (
        1, GROWN, 0)

    # (e) references among the live actors, every target in shard 0: six
    # short of the ceiling, the tier grows to the ceiling and no further
    src, dst = np.meshgrid(more, held[10:], indexing="ij")
    src, dst = src.ravel(), dst.ravel()
    refs(rigs, src[: ceiling - 6], dst[: ceiling - 6], 1)
    assert wake(rigs) == 0
    grows = 1 + (GROWN < ceiling)
    assert grown._bucket_m == ceiling and grown.stats["bucket_grows"] == grows
    assert grown.stats["rebuilds"] == 2
    assert rigs[1].wake.fields["bucket_fill"] == ceiling - 6
    assert grown.wake_stats(1)[0]["closure_sweeps"] == twin.wake_stats(1)[0]["closure_sweeps"]
    # ten more overflow it: a pack, as today, on the mesh and on its twin
    refs(rigs, src[ceiling - 6: ceiling + 4], dst[ceiling - 6: ceiling + 4], 1)
    refs(rigs, root, held[2:3], -1)
    assert wake(rigs) == 1
    for graph, grew in ((grown, grows), (twin, 0)):
        assert graph.stats == {"rebuilds": 3, "wakes": 5, "anomalies": 0,
                               "bucket_grows": grew}
        assert graph._bucket_m == ceiling and not graph._pb_count.any()
    # and the wakes go on from the new fixpoint
    refs(rigs, root, held[3:4], -1)
    assert wake(rigs) == 1
    assert grown.stats["rebuilds"] == 3 and grown.wake_stats(1)[0]["closure_sweeps"] > 0


@pytest.mark.parametrize("n_devices", [2, 4])
def test_the_plain_sharded_trace_takes_the_grown_tier(n_devices, monkeypatch):
    """``shadow-graph: mesh``: the sharded trace from nothing, a program
    of its own that takes ``_bucket_m`` too."""
    monkeypatch.setattr(mesh, "_BUCKET_FLOOR", FLOOR)
    ctx = CrgcContext(delta_graph_size=64, entry_field_size=4)
    g = mesh.MeshShadowGraph(ctx, FakeSystem.address, n_devices=n_devices,
                             trace_mode=pt.MODE_PUSH, initial_capacity=CAPACITY)
    g.s_rows = S_ROWS

    def spawn(k, flags=INTERNED | LOCAL):
        slots = np.array([g.slot_for(FakeCell(len(g.slot_of))) for _ in range(k)], np.int64)
        g.flags[slots] |= np.uint8(flags)
        return slots

    def marks_equal_the_oracles():
        want = F.trace_marks_np(g.flags, g.recv_count, g.supervisor, g.edge_src,
                                g.edge_dst, g.edge_weight)
        assert np.array_equal(np.asarray(g.compute_marks()), want)
        return int(want.sum())

    root = spawn(1, INTERNED | LOCAL | ROOT)
    first = spawn(40)
    g._apply_edge_deltas((root.astype(np.int64) << 32) | first, np.ones(40, np.int64))
    assert marks_equal_the_oracles() == 41 and g._bucket_m == FLOOR
    more = spawn(100)
    g._apply_edge_deltas((root.astype(np.int64) << 32) | more, np.ones(100, np.int64))
    assert marks_equal_the_oracles() == 141
    assert g._bucket_m == GROWN
    assert g.stats == {"rebuilds": 1, "wakes": 2, "anomalies": 0, "bucket_grows": 1}
    g._apply_edge_deltas((root.astype(np.int64) << 32) | more[:50], -np.ones(50, np.int64))
    assert marks_equal_the_oracles() == 91 and g.stats["bucket_grows"] == 1
