"""The mesh's partition (``parallel/sharded_trace.py Partition``): a
destination supertile belongs to shard ``(slot // super_sz) % D``, not to
a contiguous slot range, on the virtual CPU mesh (``conftest.py`` gives 8
host devices), interpreted kernels.

(a) the map is a bijection, ``global_of`` its inverse, the two reorders
    each other's, and none of it moves when the capacity doubles;
(b) a graph in interning order (the live actors in the lowest slots, as
    a loader makes it; three tenths of the slot space, the 10M cell's 5M
    actors of 2^24 slots) is divided evenly over four shards, where
    contiguous slot ranges, the partition before this one, give one shard
    over three times the mean of the kernel's steps: counted by
    ``simulate_sweeps`` from a layout packed by slot range here in the
    test, since the program keeps no second partition;
(c) per shard the program's kernel counters are the simulator's from
    ``shard_layout``, and over the shards they sum to the one-chip
    ``DecrementalTracer``'s on the same graph: the work is the same, it is
    divided;
(d) a ``MeshShadowGraph`` under a churn stream that grows the capacity
    twice gives the one-chip backend's verdict words after every wake, and
    the words the devices hold, laid end to end, are that verdict and
    name exactly the slots the sweep freed.
"""

from __future__ import annotations

import numpy as np
import pytest

from test_mesh_wake_level import _sweep_profile, oracle_words
from test_sweep_index import INTERNED, LOCAL, ROOT, Rig
from uigc_tpu.models import powerlaw_actor_graph
from uigc_tpu.ops import pallas_decremental as pd
from uigc_tpu.ops import pallas_incremental as pinc
from uigc_tpu.ops import pallas_trace as pt
from uigc_tpu.ops import trace as F
from uigc_tpu.parallel import sharded_trace as st

#: supertiles of 1,024 slots
S_ROWS = 8
SUPER = S_ROWS * 128
KERNEL_COUNTERS = ("kernel_steps", "kernel_contractions", "kernel_chunk_walks")


# --------------------------------------------------------------------- #
# (a) the map
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("supers_a_shard", [1, 3, 8])
@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_the_map_is_a_bijection_that_a_doubling_does_not_move(n_devices, supers_a_shard):
    part = st.Partition(n_devices, SUPER)
    n_pad = n_devices * supers_a_shard * SUPER
    shard_size = n_pad // n_devices
    slots = np.arange(n_pad)
    owner, local = part.owner(slots), part.local(slots)
    assert owner.min() == 0 and owner.max() == n_devices - 1
    assert local.min() == 0 and local.max() == shard_size - 1
    assert np.array_equal(np.bincount(owner), np.full(n_devices, shard_size))
    assert np.array_equal(part.global_of(owner, local), slots)
    # every (shard, local) is some slot's, once: a permutation of the space
    at = part.owner_major_index(slots, shard_size)
    assert np.array_equal(np.sort(at), slots)
    # a shard holds its supertiles whole, in ascending global order
    for d in range(n_devices):
        mine = slots[owner == d]
        assert np.array_equal(local[owner == d], np.arange(shard_size))
        assert np.array_equal(np.unique(mine // SUPER % n_devices), [d])
    # the reorders: an element lands where the index says, and comes back
    x = np.random.default_rng(n_devices).integers(0, 1 << 30, n_pad)
    major = part.owner_major(x)
    assert np.array_equal(major[at], x)
    assert np.array_equal(part.slot_major(major), x)
    words = x[: n_pad // 32]  # packed words: 32 slots an element
    assert np.array_equal(part.owner_major(words, per=32)[at[::32] // 32], words)
    assert np.array_equal(part.slot_major(part.owner_major(words, per=32), per=32), words)
    if n_devices == 1:  # the identity
        assert np.array_equal(at, slots) and major is x
    # the capacity doubles: a slot stays with its owner, where it was
    grown = np.arange(2 * n_pad)
    assert np.array_equal(part.owner(grown)[:n_pad], owner)
    assert np.array_equal(part.local(grown)[:n_pad], local)
    assert np.array_equal(np.bincount(part.owner(grown)), np.full(n_devices, 2 * shard_size))


# --------------------------------------------------------------------- #
# (b), (c) the kernel's work, divided
# --------------------------------------------------------------------- #

D = 4
#: four shards of 128 supertiles, and sixteen walk chunks of 32,768 slots
N = D * SUPER * 128
#: the live actors fill five chunks (31% of the slots; the 10M cell's 5M
#: actors fill 30% of 2^24): shard 0's slot range and a quarter of shard
#: 1's.  A block whose sources are garbage, in the upper chunks, never has
#: a dirty chunk in its span and takes no step, as at the cell's scale
LIVE = 5 * 32768


def range_layout(psrc, pdst, meta, shard):
    """Shard ``shard``'s layout under the partition this one replaced,
    contiguous slot ranges (a shard the slots ``[d, d + 1) * n / D``), packed as
    ``pack_shard_layouts`` packs a shard's and named for the simulator."""
    size = N // D
    mine = pdst // size == shard
    prep = pt.prepare_pairs(
        psrc[mine], pdst[mine] - shard * size, size, s_rows=S_ROWS, n_src=N,
        sub=meta["sub"], group=meta["group"])
    prep["tiles"] = shard * prep["n_super"] + np.arange(prep["n_super"])
    return prep


@pytest.fixture(scope="module", params=[pt.MODE_PUSH, pt.MODE_AUTO])
def derivation(request):
    """A derivation from nothing over a graph in interning order, on
    four shards: the graph, its pairs, the layouts, the marks and every
    shard's counters."""
    mode = request.param
    g = powerlaw_actor_graph(N, seed=1, garbage_fraction=1 - LIVE / N)
    in_use = (g["flags"] & F.FLAG_IN_USE) != 0
    assert in_use.all() and not g["expected_garbage"][:LIVE].any()
    assert g["expected_garbage"][LIVE:].all()  # the live actors hold the lowest slots
    psrc, pdst, _ = pinc.IncrementalPallasLayout.pairs_from_graph(
        g["edge_src"], g["edge_dst"], g["edge_weight"], g["supervisor"])
    stacked, meta, _ = st.pack_shard_layouts(psrc, pdst, N, D, s_rows=S_ROWS)
    bucket = 64
    wake = st.make_sharded_decremental_wake(
        st.build_mesh(D), N, meta["shard_size"], meta["n_blocks"], meta["r_rows"], S_ROWS,
        bucket, sub=meta["sub"], group=meta["group"], mode=mode)
    part = st.Partition(D, SUPER)
    zeros = np.zeros(N // 32, np.int32)
    jump = (pt.jump_parents(psrc, pdst, N),) if mode == pt.MODE_AUTO else ()
    *state, stats = wake(
        part.owner_major(g["flags"]), part.owner_major(g["recv_count"]), zeros, zeros,
        *([zeros] * 5), np.zeros((), np.int32),
        stacked["bmeta1"], stacked["bmeta2"], stacked["row_pos"], stacked["emeta"],
        np.full((D, bucket), N, np.int32), np.zeros((D, bucket), np.int32), *jump)
    mark_w = part.slot_major(np.asarray(state[0]), per=32)
    marks = np.unpackbits(mark_w.view(np.uint8), bitorder="little")[:N] > 0
    stats = {key: np.asarray(rows) for key, rows in stats.items()}
    shards = [pd.host_stats({key: rows[d] for key, rows in stats.items() if key != "gathers"})
              for d in range(D)]
    return dict(mode=mode, g=g, psrc=psrc, pdst=pdst, stacked=stacked, meta=meta,
                marks=marks, shards=shards)


def simulated(derivation, layout):
    """The simulator's totals of the kernel's counters over ``layout``."""
    d = derivation
    meta, mode = d["meta"], d["mode"]
    group_rows = pt.ROWS * meta["group"]
    # ``auto`` prices a jump sweep in the mesh's totals: every shard's blocks
    totals = (D * meta["n_blocks"] * pt.ROWS * meta["sub"] * pt.LANE,
              meta["r_rows"] // group_rows, group_rows * pt.LANE * pt.WORD_BITS)
    sim = _sweep_profile().simulate_sweeps(d["g"], N, [mode], geometry=totals, layout=layout)[mode]
    return {"n_sweeps": sim["sweeps"], "kernel_steps": sum(sim["steps"]),
            "kernel_contractions": sum(sim["contracting"]),
            "kernel_chunk_walks": sum(sim["chunk_iterations"])}


def test_the_sharded_derivation_gives_the_oracles_marks(derivation):
    g = derivation["g"]
    assert np.array_equal(derivation["marks"], F.trace_marks_np(
        g["flags"], g["recv_count"], g["supervisor"], g["edge_src"], g["edge_dst"],
        g["edge_weight"]))
    assert not derivation["marks"][LIVE:].any() and derivation["marks"][:LIVE].all()


def test_interning_order_is_divided_evenly_where_slot_ranges_were_not(derivation):
    steps = np.array([shard["kernel_steps"] for shard in derivation["shards"]], np.float64)
    assert steps.min() > 0 and steps.max() / steps.mean() < 1.25, steps
    # the partition before: the live slots fill shard 0's range and a
    # quarter of shard 1's
    d = derivation
    before = np.array([
        simulated(d, range_layout(d["psrc"], d["pdst"], d["meta"], shard))["kernel_steps"]
        for shard in range(D)], np.float64)
    assert before.max() / before.mean() > 3 and not before[D // 2:].any(), before
    # the same steps, but for what block boundaries round: divided, not changed
    assert abs(before.sum() - steps.sum()) <= 0.02 * steps.sum()


@pytest.mark.parametrize("shard", range(D))
def test_a_shards_counters_are_the_simulators_from_its_layout(derivation, shard):
    d = derivation
    want = simulated(d, st.shard_layout(d["stacked"], d["meta"], shard))
    got = d["shards"][shard]
    assert {key: got[key] for key in want} == want


def test_the_shards_counters_sum_to_the_one_chip_tracers(derivation):
    d, g = derivation, derivation["g"]
    tracer = pd.DecrementalTracer(N, mode=d["mode"], s_rows=S_ROWS)
    tracer.rebuild(g["edge_src"], g["edge_dst"], g["edge_weight"], g["supervisor"])
    assert np.array_equal(tracer.marks(g["flags"], g["recv_count"]), d["marks"])
    one = tracer.wake_stats(1)[0]
    for key in ("n_sweeps", "dirty_chunks", "closure_sweeps"):  # the gathered table's
        assert all(shard[key] == one[key] for shard in d["shards"]), key
    total = {key: sum(shard[key] for shard in d["shards"]) for key in KERNEL_COUNTERS}
    # a tile's pairs make the same blocks over the same spans in either
    # pack: the same steps, the same walks.  WHICH of a tile's blocks a
    # pair lands in is the pack's sort's to say, and with it whether a
    # block's gather finds a new bit in a given sweep: a contraction or
    # two in a hundred, under any partition (slot ranges read 6,685 here
    # to the one chip's 6,635)
    assert total["kernel_steps"] == one["kernel_steps"]
    assert total["kernel_chunk_walks"] == one["kernel_chunk_walks"]
    assert abs(total["kernel_contractions"] - one["kernel_contractions"]) <= (
        0.02 * one["kernel_contractions"])


# --------------------------------------------------------------------- #
# (d) the backend across two doublings
# --------------------------------------------------------------------- #

CAPACITY = 4096


#: a wake's actors taken on: a loader's bulk (the wake packs, for the
#: capacity has grown) between wakes of a trickle (the O(churn) sync)
SCRIPT = (3000, 120, 120, 3000, 120, 120, 5000, 120, 120)


@pytest.mark.parametrize("n_devices", [2, 4])
def test_verdicts_equal_the_one_chip_backends_while_the_capacity_doubles_twice(n_devices):
    rigs = []
    for devices in (0, n_devices):
        rig = Rig("mesh-decremental" if devices else "decremental", 0,
                  trace_mode=pt.MODE_PUSH, initial_capacity=CAPACITY,
                  **({"n_devices": devices} if devices else {}))
        if devices:
            rig.graph.s_rows = S_ROWS
        rig.root = int(rig.spawn(1, flags=INTERNED | LOCAL | ROOT)[0])
        rig.held = np.zeros(0, np.int64)
        rigs.append(rig)
    one, sharded = (rig.graph for rig in rigs)
    capacities, freed_total = [], 0
    for wake, taken_on in enumerate(SCRIPT):
        for rig in rigs:
            # the root takes on actors, wired among themselves and held by
            # some it held before, and lets go of 150 of those: most of
            # them die, some stay held by the others
            rng = np.random.default_rng([7, wake])
            new = rig.spawn(taken_on, sup=rig.root)
            rig.deltas(np.full(new.size, rig.root), new, np.ones(new.size, np.int64))
            k = taken_on // 3
            rig.deltas(new[rng.integers(0, new.size, k)], new[rng.integers(0, new.size, k)],
                       np.ones(k, np.int64))
            if rig.held.size:
                held = rig.held
                rig.deltas(held[rng.integers(0, held.size, 40)], new[rng.integers(0, new.size, 40)],
                           np.ones(40, np.int64))
                gone = rng.choice(held, 150, replace=False)
                for slot in gone.tolist():  # supervised by the root no more
                    rig.graph._set_supervisor(slot, -1)
                rig.deltas(np.full(gone.size, rig.root), gone, -np.ones(gone.size, np.int64))
                rig.held = np.setdiff1d(held, gone)
            rig.held = np.concatenate([rig.held, new])
        assert np.array_equal(one.flags, sharded.flags) and one.capacity == sharded.capacity
        want_w, want_live = oracle_words(one)
        words = one.capacity // 32
        freed = []
        for rig in rigs:
            graph = rig.graph
            verdicts = graph.compute_marks()
            assert np.array_equal(verdicts.garbage_w[:words], want_w), (wake, type(graph).__name__)
            assert not verdicts.garbage_w[words:].any() and verdicts.num_live == want_live
            rig.swept.clear()
            freed.append(graph._sweep(True, verdicts)[0])
            # what was freed is held no more
            rig.held = rig.held[(graph.flags[rig.held] & np.uint8(F.FLAG_IN_USE)) != 0]
        assert freed[0] == freed[1] and (freed[0] > 0) == (wake > 0)
        freed_total += freed[0]
        # the words as the devices hold them: a D-th of the slot space each,
        # in slot order, and together the verdict the sweep took
        shards = sharded.shard_verdict_words()
        assert [w.size * 32 for w in shards] == [sharded._shard_size] * n_devices
        assert sharded._shard_size * n_devices == sharded._n_pad >= sharded.capacity
        laid = np.concatenate(shards)
        assert np.array_equal(laid, sharded.last_verdict_words)
        named = np.flatnonzero(np.unpackbits(laid.view(np.uint8), bitorder="little"))
        swept = rigs[1].swept.get("garbage", np.zeros(0, np.int64))
        assert np.array_equal(named, np.sort(swept)) and named.size == freed[1]
        capacities.append(sharded.capacity)
    assert freed_total > 500
    assert capacities == [CAPACITY] * 3 + [2 * CAPACITY] * 3 + [4 * CAPACITY] * 3
    # a doubling packs; a trickle is synced in O(churn), and loses no transition
    assert sharded.stats["rebuilds"] == 3 and sharded.stats["anomalies"] == 0
    assert sharded.stats["wakes"] == len(SCRIPT)
