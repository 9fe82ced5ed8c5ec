"""``flags`` and ``recv_count`` live on the device: the ``decremental``
backend keeps its copies from wake to wake and patches them with the
slots the host wrote since (``ArrayShadowGraph._node_operands``), or
uploads both whole where that is the cheaper road.

What a patched copy must never do is lag the host by a slot: that is a
missed or a wrong stop.  So every case here holds the device's copies to
the host's arrays bit for bit (narrowed as ``device_put`` narrows) and the
wake's verdicts to those of a twin graph under the same script that
uploads whole before every wake, or to ``ops/trace.py trace_marks_np``.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest
from test_foreign_uids import E, row
from test_sweep_index import FakeCell, FakeSystem, FakeWake

from uigc_tpu.engines.crgc import arrays
from uigc_tpu.engines.crgc.arrays import ArrayShadowGraph
from uigc_tpu.engines.crgc.packed import FOREIGN_BIT, PackedPlane
from uigc_tpu.engines.crgc.refob import CrgcRefob
from uigc_tpu.engines.crgc.state import CrgcContext, Entry
from uigc_tpu.ops import trace as F

#: bytes a patched slot hands the device: an int32 index, a flag byte, an
#: int32 count (x64 is off)
SLOT_BYTES = 4 + 1 + 4


def new_graph(capacity=64):
    """A ``decremental`` graph of foreign uids and ``FakeCell``s whose
    every upload, on either road, is held to the host's arrays: what the
    wake program is about to read is what a whole upload would give it,
    bit for bit."""
    ctx = CrgcContext(delta_graph_size=64, entry_field_size=E)
    g = ArrayShadowGraph(ctx, FakeSystem.address, use_device=True, initial_capacity=capacity)
    g.plane = PackedPlane(E)
    g.attach_packed_plane(g.plane, lambda uid: None)
    g.foreign_sink = lambda kills, freed: None
    g.profile_wake = FakeWake()
    upload = g._node_operands

    def checked_upload():
        flags_dev, recv_dev, nbytes = upload()
        assert g._resident[0] is flags_dev and g._resident[1] is recv_dev
        assert flags_dev.shape == recv_dev.shape == (g.capacity,)
        assert np.array_equal(np.asarray(flags_dev), g.flags)
        # narrowed as ``device_put`` narrows the whole array
        assert np.array_equal(np.asarray(recv_dev), np.asarray(jax.device_put(g.recv_count)))
        return flags_dev, recv_dev, nbytes

    g._node_operands = checked_upload
    return g


def fold_foreign(g, rows):
    g.plane.write_foreign(np.stack(rows))
    g.merge_packed(g.plane.drain())


def wake(g):
    """One wake, its verdicts kept: ``(garbage words, marks,
    garbage actors, upload_bytes)``."""
    verdicts = g.compute_marks()
    words, live = verdicts.garbage_w.copy(), verdicts.num_live
    n_garbage, _ = g._sweep(True, verdicts)
    return words, live, n_garbage, g.profile_wake.fields["upload_bytes"]


def whole_bytes(g):
    return g.flags.nbytes + g.recv_count.nbytes


def assert_copies_are_the_hosts(g):
    """Also of what the last sweep wrote: the next wake's upload, now."""
    g._node_operands()


def oracle_garbage(g):
    """The foreign uids ``trace_marks_np`` has for garbage, ascending."""
    mark = F.trace_marks_np(
        g.flags, g.recv_count, g.supervisor, g.edge_src, g.edge_dst, g.edge_weight)
    garbage, _ = F.garbage_and_kills_np(g.flags, g.supervisor, mark)
    return np.sort(g._slot_uid[np.flatnonzero(garbage)] ^ FOREIGN_BIT)


def star(g, n):
    """Foreign uid 0, a root, holds uids 1..n-1; folded in one batch."""
    rows = [row(0, root=True, created=[(0, t) for t in range(at, min(at + E, n))])
            for at in range(1, n, E)]
    rows += [row(t) for t in range(1, n)]
    fold_foreign(g, rows)


def release(g, targets):
    """The root lets go of ``targets`` (a deactivated refob each)."""
    targets = list(targets)
    fold_foreign(g, [row(0, root=True, updated=[(t, 1) for t in targets[at:at + E]])
                     for at in range(0, len(targets), E)])


# --------------------------------------------------------------------- #
# (a) a random script, against a twin that uploads whole
# --------------------------------------------------------------------- #


class Script:
    """Random churn through every road that writes ``flags`` or
    ``recv_count``: object entries one by one (``merge_entry``:
    ``_touch``) and as a batch (``merge_entries``), packed rows of foreign
    uids (``merge_packed``, interning new ones in bulk), sweeps that
    free, and a block of new uids that outgrows the capacity.  It draws
    from its seed alone, so two graphs under one seed see one script."""

    def __init__(self, seed, n_cells=48):
        self.rng = np.random.default_rng(seed)
        self.cells = [FakeCell(uid) for uid in range(1, n_cells + 1)]
        self.foreign = 0  # uids handed out so far
        self.held = []  # (owner, target): the references among them

    def entries(self, g, k):
        rng, cells = self.rng, self.cells
        ref = lambda: CrgcRefob(cells[int(rng.integers(0, len(cells)))])
        out = []
        for _ in range(k):
            e = Entry(g.context)
            a = int(rng.integers(0, len(cells)))
            e.self_ref = CrgcRefob(cells[a])
            e.is_busy = bool(rng.random() < 0.15)
            e.is_root = a < 2
            e.recv_count = int(rng.integers(-2, 3))
            for i in range(int(rng.integers(0, 3))):
                e.created_owners[i], e.created_targets[i] = ref(), ref()
            if rng.random() < 0.3:
                e.spawned_actors[0] = ref()
            for i in range(int(rng.integers(0, 3))):
                e.updated_refs[i] = ref()
                e.updated_infos[i] = (int(rng.integers(0, 3)) << 1) | int(rng.random() < 0.5)
            out.append(e)
        return out

    def foreign_rows(self, new, k):
        rng = self.rng
        first, self.foreign = self.foreign, self.foreign + new
        uid = lambda: int(rng.integers(0, self.foreign))
        rows = [row(0, root=True)]
        for u in range(max(first, 1), self.foreign):  # each held by one before it
            owner = int(rng.integers(0, u))
            self.held.append((owner, u))
            rows.append(row(owner, root=owner == 0, created=[(owner, u)]))
            rows.append(row(u))
        for _ in range(min(k // 3, len(self.held))):  # and some let go of
            owner, u = self.held.pop(int(rng.integers(0, len(self.held))))
            rows.append(row(owner, root=owner == 0, updated=[(u, 1)]))
        for _ in range(k):
            a, b = uid(), uid()
            rows.append(row(a, root=a == 0, busy=bool(rng.random() < 0.1),
                            updated=[(b, int(rng.integers(1, 3)) << 1)]))  # a sends to b
            # b counts it, now and then past 32 bits: the device holds that narrowed
            rows.append(row(b, root=b == 0, recv=int(rng.choice([1, 1, 1, 2, (1 << 33) + 5]))))
        return rows

    def round(self, g, round_):
        for entry in self.entries(g, 6):
            g.merge_entry(entry)
        g.merge_entries(self.entries(g, 20))
        new = 9000 if round_ == 3 else int(self.rng.integers(20, 40))  # 9000: a growth
        fold_foreign(g, self.foreign_rows(new, 30))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_copies_are_the_hosts_after_every_road_that_writes(seed):
    patched, whole = new_graph(8192), new_graph(8192)
    scripts = Script(seed), Script(seed)
    went_whole, freed = [], 0
    for round_ in range(8):
        for g, script in zip((patched, whole), scripts):
            script.round(g, round_)
        whole._drop_resident()  # the twin uploads whole, every wake
        want_words, want_live, want_garbage, whole_upload = wake(whole)
        assert whole_upload == whole_bytes(whole)
        got_words, got_live, got_garbage, upload = wake(patched)
        assert np.array_equal(got_words, want_words), f"round {round_}"
        assert (got_live, got_garbage) == (want_live, want_garbage)
        assert np.array_equal(patched.flags, whole.flags)
        assert np.array_equal(patched.recv_count, whole.recv_count)
        if upload == whole_bytes(patched):
            went_whole.append(round_)
        else:  # a patch: it costs by the slot
            assert upload == arrays._patch_pad(1) * SLOT_BYTES
        freed += got_garbage
    # the first wake, the growth, and after the mass death its sweep was
    assert went_whole == [0, 3, 4] and patched.capacity > 8192
    assert freed > 1000
    assert (patched.recv_count > 1 << 32).any()  # and the device holds it narrowed
    assert_copies_are_the_hosts(patched)


# --------------------------------------------------------------------- #
# (b) the upload costs by the change, not by the capacity
# --------------------------------------------------------------------- #


def churn_and_its_uploads(capacity):
    g = new_graph(capacity)
    star(g, 3000)
    first = wake(g)[3]
    release(g, range(100, 150))
    fold_foreign(g, [row(t, recv=1) for t in range(200, 900)])
    patched = wake(g)
    assert patched[2] == 50
    after_sweep = wake(g)  # the sweep's 50 frees: still a change
    g.merge_entries([])  # a fold that wrote no slot
    return g, first, patched[3], after_sweep[3], wake(g)[3]


@pytest.mark.parametrize("capacity", [1 << 14, 1 << 17])
def test_upload_bytes_cost_by_the_churn_at_any_capacity(capacity):
    g, first, patched, after_sweep, nothing = churn_and_its_uploads(capacity)
    assert g.capacity == capacity and first == whole_bytes(g)
    # the root, 50 released, 700 receivers: one padded length whatever the capacity
    assert patched == arrays._patch_pad(751) * SLOT_BYTES == 4096 * SLOT_BYTES
    assert after_sweep == arrays._patch_pad(50) * SLOT_BYTES
    assert nothing == 0
    assert_copies_are_the_hosts(g)


# --------------------------------------------------------------------- #
# (c) one compiled scatter for a round of 100 slots and one of 3,000
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("dirty", [(100, 3000), (3000, 100)])
def test_patches_of_100_and_of_3000_slots_share_one_program(dirty):
    capacity = 4096 * arrays._PATCH_SHARE  # its share is the pad's floor: one length to warm
    g = new_graph(capacity)
    star(g, 4000)
    patch = arrays._patch_fn()
    patch.clear_cache()
    assert wake(g)[3] == whole_bytes(g)
    assert patch._cache_size() == 1  # compiled with the whole upload, before any churn
    for k in dirty:
        fold_foreign(g, [row(t, recv=k) for t in range(k)])
        assert wake(g)[3] == 4096 * SLOT_BYTES
    assert patch._cache_size() == 1


def test_every_padded_length_is_compiled_with_the_whole_upload():
    """A capacity whose share spans several padded lengths compiles them
    all at its first whole upload, and none at a later one."""
    capacity = 16384 * arrays._PATCH_SHARE  # patches of up to 2^14 slots: 4096, 8192, 16384
    g = new_graph(capacity)
    star(g, 20)
    patch = arrays._patch_fn()
    patch.clear_cache()
    wake(g)
    assert patch._cache_size() == 3
    fold_foreign(g, [row(20 + t, created=[(0, 20 + t)]) for t in range(6000)])  # a new length
    g._drop_resident()
    assert wake(g)[3] == whole_bytes(g)
    fold_foreign(g, [row(20 + t, recv=1) for t in range(6000)])
    assert wake(g)[3] == 8192 * SLOT_BYTES
    assert patch._cache_size() == 3
    assert_copies_are_the_hosts(g)


# --------------------------------------------------------------------- #
# (d) the whole-array road, taken from the size of the log
# --------------------------------------------------------------------- #


def over_the_share(g):
    share = g.capacity // arrays._PATCH_SHARE
    fold_foreign(g, [row(t, recv=1) for t in range(1, share + 2)])
    return g.capacity


def overflowed(g):
    """Few slots, written more often than the log holds entries."""
    share = g.capacity // arrays._PATCH_SHARE
    for _ in range(share // 8 + 1):
        fold_foreign(g, [row(t, recv=1) for t in range(1, 9)])
    return g.capacity


def growth(g):
    grown = g.capacity * 2
    fold_foreign(g, [row(5000 + t, created=[(0, 5000 + t)]) for t in range(g.capacity)])
    return grown


@pytest.mark.parametrize("cause", [over_the_share, overflowed, growth])
def test_a_log_too_long_and_a_growth_upload_whole_and_keep_the_copies(cause):
    g = new_graph(2048)
    star(g, 1500)
    wake(g)
    release(g, range(10, 20))
    assert wake(g)[2:] == (10, arrays._patch_pad(11) * SLOT_BYTES)
    before = g._resident
    capacity = cause(g)
    assert (g._resident is None) == (cause is growth)
    garbage = oracle_garbage(g)
    _, _, n_garbage, upload = wake(g)
    assert g.capacity == capacity and upload == whole_bytes(g)
    assert n_garbage == garbage.size
    assert g._resident is not None and g._resident[0] is not before[0]
    release(g, range(300, 310))
    assert wake(g)[2:] == (10, arrays._patch_pad(11 + n_garbage) * SLOT_BYTES)
    assert_copies_are_the_hosts(g)


# --------------------------------------------------------------------- #
# (e) the copies hang on no fixpoint; a failed wake drops them
# --------------------------------------------------------------------- #


def invalidate(g):
    g._dec.invalidate()
    return True


def rebuild(g):
    g._pair_log = None  # the pair log overflowed: the layout is packed anew
    return True


def poisoned(g):
    """The wake's result never lands: the wait raises."""
    dispatch = g._dec.wake_device

    def wake_device(*args):
        g._dec.wake_device = dispatch
        dispatch(*args)
        raise RuntimeError("transport died")

    g._dec.wake_device = wake_device
    release(g, range(50, 55))
    with pytest.raises(RuntimeError, match="transport died"):
        g.compute_marks()
    assert g._resident is None and g._node_log is None
    return False


@pytest.mark.parametrize("doubt", [invalidate, rebuild, poisoned])
def test_the_next_wakes_verdicts_are_right(doubt):
    g = new_graph(2048)
    star(g, 1200)
    wake(g)
    release(g, range(10, 20))
    assert wake(g)[2] == 10
    kept = doubt(g)
    release(g, range(100, 130))
    fold_foreign(g, [row(300, recv=2)])
    garbage = oracle_garbage(g)
    freed = []
    g.foreign_sink = lambda kills, uids: freed.append(uids)
    _, _, n_garbage, upload = wake(g)
    assert n_garbage == garbage.size >= 30
    assert np.array_equal(np.sort(freed[0]), garbage)
    assert upload == (arrays._patch_pad(42) * SLOT_BYTES if kept else whole_bytes(g))
    release(g, range(200, 210))
    assert wake(g)[2:] == (10, arrays._patch_pad(11 + n_garbage) * SLOT_BYTES)
    assert_copies_are_the_hosts(g)


# --------------------------------------------------------------------- #
# (f) the pair log beside the node log: its rows on the wake's record,
# and past its cap the layout is packed anew
# --------------------------------------------------------------------- #


def test_the_wakes_record_counts_the_layouts_rows_and_a_log_past_its_cap_rebuilds():
    from uigc_tpu.ops.slotmap import PairLog

    g = new_graph(2048)
    star(g, 1500)
    assert g._pair_log is None  # no consumer yet: nothing is logged
    wake(g)
    fields = g.profile_wake.fields
    # the first wake packs the layout from the graph and starts the log
    assert (fields["layout_rows"], fields["layout_rebuilt"]) == (0, 1)
    assert isinstance(g._pair_log, PairLog) and len(g._pair_log) == 0
    release(g, range(10, 20))
    assert len(g._pair_log) == 10  # ten references went dead
    assert wake(g)[2] == 10
    assert (fields["layout_rows"], fields["layout_rebuilt"]) == (10, 0)
    swept = len(g._pair_log)  # what hung on the dead, logged by the sweep
    wake(g)
    assert (fields["layout_rows"], fields["layout_rebuilt"]) == (swept, 0)
    wake(g)
    assert (fields["layout_rows"], fields["layout_rebuilt"]) == (0, 0)
    assert g._dec.layout.stats["log_rows"] == 10 + swept
    assert g._dec.layout.stats["anomalies"] == 0

    # past the cap, on the batched road and on the scalar one, the log
    # collapses to the sentinel and the next wake packs from the graph
    for overflow in (lambda: release(g, range(100, 130)),
                     lambda: [g._log_pair(False, 0, t, 0) for t in range(40)]):
        g._log_cap = 16
        packs = g._dec.layout.stats["rebuilds"]
        overflow()
        assert g._pair_log is None
        g._log_cap = 1 << 20
        garbage = oracle_garbage(g)
        assert wake(g)[2] == garbage.size
        assert (fields["layout_rows"], fields["layout_rebuilt"]) == (0, 1)
        assert g._dec.layout.stats["rebuilds"] == packs + 1
        assert isinstance(g._pair_log, PairLog)
    assert ArrayShadowGraph(
        CrgcContext(delta_graph_size=64, entry_field_size=E), FakeSystem.address
    )._log_cap == 1 << 20
