"""The mesh's host sync as columns (``engines/crgc/mesh.py
_apply_pair_log`` / ``_sync_upload``) against the per-pair replay it
replaced, kept here as the oracle (``ParentReplay``: one Python iteration
a pair over dicts, lists and tuples), on the virtual CPU mesh.

A graph of ~3,500 actors over 4,096 slots is packed once (1,700 pairs in
the packed base), then pair logs are written straight into its
``_pair_log`` and folded by both.  After every log, equal: the live
``(src, dst)`` multiset a shard in the bucket plane, the masked base
slots, the queued masks, ``stats["anomalies"]``, both slot maps' lookups,
``_bucket_fill()`` and the tier's size; the pairs batch names no
``(shard, col)`` twice and carries what the host plane holds; after
``_sync_upload`` the device's buckets and base layouts read back equal
the host's; the wake's record notes ``bucket_writes`` / ``base_masks``
as the oracle counts them.  The floor is lowered to 64 columns a shard
so that floor and ceiling differ at this size.
"""

from __future__ import annotations

import copy

import numpy as np
import pytest

from test_mesh_bucket_tier import free_stack
from test_sweep_index import INTERNED, LOCAL, ROOT, FakeCell, FakeSystem, FakeWake
from uigc_tpu.engines.crgc import mesh
from uigc_tpu.engines.crgc.state import CrgcContext
from uigc_tpu.ops import pallas_trace as pt
from uigc_tpu.ops.slotmap import (
    PackedSlotMap, fold_log, pack_key, pack_keys, unpack_keys,
)

CAPACITY = 4096
S_ROWS = 8  # supertiles of 1,024 slots: four shards of one
ACTORS = 3500
FLOOR = 64


class ParentReplay:
    """PR 50's ``_apply_pair_log``, on a copy of a graph's host plane in
    the representation it had: a list of free columns a shard, a tuple a
    queued mask, a dict of bucket writes keyed by ``(shard, col)``."""

    def __init__(self, g):
        self.n_pad, self.part, self.n_devices = g._n_pad, g._part, g.n_devices
        self.ceiling = g._bucket_ceiling()
        self.bucket_m = g._bucket_m
        self.pb_src, self.pb_dst = g._pb_src.copy(), g._pb_dst.copy()
        self.pb_count = g._pb_count.copy()
        self.pb_free = [free_stack(g, d) for d in range(g.n_devices)]
        self.pb_slot = copy.deepcopy(g._pb_slot)
        self.base_slot = copy.deepcopy(g._base_slot)
        self.row_pos = g._stacked["row_pos"].copy()
        self.emeta = g._stacked["emeta"].copy()
        self.anomalies = g.stats["anomalies"]
        self.grows = g.stats["bucket_grows"]
        self.mask_writes = []
        self.del_dst, self.fresh_dst = set(), set()

    def bucket_fill(self, new=0):
        free = np.fromiter(map(len, self.pb_free), np.int64, self.n_devices)
        return int((self.pb_count - free + new).max())

    def grow(self, need):
        m = min(self.ceiling, mesh._pow2(2 * need))
        pad = ((0, 0), (0, m - self.bucket_m))
        self.pb_src = np.pad(self.pb_src, pad, constant_values=self.n_pad)
        self.pb_dst = np.pad(self.pb_dst, pad)
        self.bucket_m = m
        self.grows += 1

    def apply(self, ins, psrc, pdst, kind):
        self.mask_writes, self.del_dst, self.fresh_dst = [], set(), set()
        if not ins.size:  # ``_sync_layout`` does not call it
            return {}
        removes, cond_removes, inserts, _ = fold_log(ins, psrc, pdst, kind)
        self.del_dst = set(unpack_keys(np.concatenate([removes, cond_removes]))[1].tolist())
        self.fresh_dst = set(unpack_keys(inserts)[1].tolist())
        writes = {}

        def mask_base(packed):
            shard = packed >> 40
            ri = (packed >> 8) & 0xFFFFFFFF
            col = packed & 0xFF
            self.row_pos[shard, ri, col] = pt._PAD_ROW
            self.emeta[shard, ri, col] = 0
            self.mask_writes.append((shard, ri, col))

        def free_slot_batch(karr, found_is_anomaly):
            bucket_vals = self.pb_slot.pop_batch(karr)
            missing = bucket_vals < 0
            base_vals = np.full(karr.size, -1, dtype=np.int64)
            if missing.any():
                base_vals[missing] = self.base_slot.pop_batch(karr[missing])
            for bval, sval in zip(bucket_vals.tolist(), base_vals.tolist()):
                if bval >= 0:
                    if found_is_anomaly:
                        self.anomalies += 1
                    shard, colm = bval >> 32, bval & 0xFFFFFFFF
                    self.pb_src[shard, colm] = self.n_pad
                    self.pb_dst[shard, colm] = 0
                    self.pb_free[shard].append(colm)
                    writes[(shard, colm)] = (self.n_pad, 0)
                elif sval >= 0:
                    if found_is_anomaly:
                        self.anomalies += 1
                    mask_base(sval)
                elif not found_is_anomaly:
                    self.anomalies += 1

        if removes.size:
            free_slot_batch(removes, found_is_anomaly=False)
        if cond_removes.size:
            free_slot_batch(cond_removes, found_is_anomaly=True)
        if inserts.size:
            present = (self.pb_slot.get_batch(inserts) >= 0) | (
                self.base_slot.get_batch(inserts) >= 0)
            srcs, dsts = unpack_keys(inserts)
            new = np.bincount(self.part.owner(dsts[~present]), minlength=self.n_devices)
            need = self.bucket_fill(new)
            if need > self.bucket_m:
                if need > self.ceiling:
                    return None
                self.grow(need)
            for key, src, dst, dup in zip(
                    inserts.tolist(), srcs.tolist(), dsts.tolist(), present.tolist()):
                if dup:
                    self.anomalies += 1
                    continue
                shard = self.part.owner(dst)
                free = self.pb_free[shard]
                if free:
                    colm = free.pop()
                else:
                    colm = int(self.pb_count[shard])
                    self.pb_count[shard] = colm + 1
                self.pb_slot.add(key, (shard << 32) | colm)
                self.pb_src[shard, colm] = src
                local = self.part.local(dst)
                self.pb_dst[shard, colm] = local
                writes[(shard, colm)] = (src, local)
        return writes


class Twin:
    """A packed, uploaded mesh graph and the oracle over a copy of its
    host plane, fed the same pair logs."""

    def __init__(self, backend, n_devices, seed):
        self.rng = rng = np.random.default_rng([seed, n_devices])
        ctx = CrgcContext(delta_graph_size=64, entry_field_size=4)
        self.g = g = mesh.MeshShadowGraph(
            ctx, FakeSystem.address, n_devices=n_devices, initial_capacity=CAPACITY,
            decremental=backend == "mesh-decremental")
        g.s_rows = S_ROWS
        self.record = g.profile_wake = FakeWake()
        slots = np.array([g.slot_for(FakeCell(uid)) for uid in range(ACTORS)], np.int64)
        g.flags[slots] |= np.uint8(INTERNED | LOCAL)
        g.flags[slots[0]] |= np.uint8(ROOT)
        for child in rng.choice(slots[1:], 200, replace=False).tolist():
            g._set_supervisor(child, int(rng.integers(0, ACTORS)))
        refs = np.unique(
            (rng.integers(0, ACTORS, 1500) << 32) | rng.integers(0, ACTORS, 1500))
        g._apply_edge_deltas(refs, np.ones(refs.size, np.int64))
        g._sync_device()  # the pack: every pair so far in the packed base
        assert g._dev_ready and g._bucket_m == FLOOR and g._shard_size == CAPACITY // n_devices
        self.base_keys = g._base_slot._keys.copy()
        assert self.base_keys.size == refs.size + 200
        self.live = set(self.base_keys.tolist())
        self.seen = set(self.live)  # every key a log ever named
        self.oracle = ParentReplay(g)

    def fresh(self, k, shard=None):
        """``k`` pairs that are not live, their targets owned by ``shard``."""
        targets = np.arange(ACTORS)
        if shard is not None:
            targets = targets[self.g._part.owner(targets) == shard]
        out = []
        while len(out) < k:
            key = pack_key(int(self.rng.integers(0, ACTORS)), int(self.rng.choice(targets)),
                           int(self.rng.integers(0, 2)))
            if key not in self.live and key not in out:
                out.append(key)
        return out

    def some(self, keys, k):
        keys = sorted(keys)
        return [keys[i] for i in self.rng.choice(len(keys), min(k, len(keys)), replace=False)]

    def wake(self, rows, packs=False):
        """One sync over the log ``rows`` (``(insert?, key)`` in order):
        the columns against the replay."""
        g, oracle = self.g, self.oracle
        for i, (insert, key) in enumerate(rows):
            row = (int(insert), key >> 32, (key >> 1) & 0x7FFFFFFF, key & 1)
            if i % 3:  # both doors of the log
                g._pair_log.append(row)
            else:
                g._pair_log.extend(insert, [row[1]], [row[2]], row[3])
            self.seen.add(key)
        want = oracle.apply(*g._pair_log.columns())
        writes = g._sync_layout()
        fields = self.record.fields
        assert fields["layout_rows"] == len(rows)
        if packs:
            assert want is None and writes is None
            assert (fields["layout_rebuilt"], fields["bucket_writes"], fields["base_masks"]) == (
                1, 0, 0)
            return
        assert fields["layout_rebuilt"] == 0 and len(g._pair_log) == 0
        if not rows:
            assert writes.shape == (4, 0)

        # the host plane
        assert g.stats["anomalies"] == oracle.anomalies
        assert g.stats["bucket_grows"] == oracle.grows and g._bucket_m == oracle.bucket_m
        assert g._pb_src.shape == g._pb_dst.shape == oracle.pb_src.shape
        assert g._bucket_fill() == oracle.bucket_fill() == fields["bucket_fill"]
        for d in range(g.n_devices):
            held = g._pb_src[d] != g._n_pad
            theirs = oracle.pb_src[d] != g._n_pad
            assert sorted(zip(g._pb_src[d][held].tolist(), g._pb_dst[d][held].tolist())) == sorted(
                zip(oracle.pb_src[d][theirs].tolist(), oracle.pb_dst[d][theirs].tolist()))
            assert not g._pb_dst[d][~held].any()
            # a column is held, on the free stack, or never handed out
            free = free_stack(g, d)
            assert len(set(free)) == len(free) == len(oracle.pb_free[d])
            assert not held[free].any() and not held[g._pb_count[d]:].any()
            assert int(held.sum()) + len(free) == g._pb_count[d] == oracle.pb_count[d]
        assert np.array_equal(g._stacked["row_pos"], oracle.row_pos)
        assert np.array_equal(g._stacked["emeta"], oracle.emeta)
        queued = np.concatenate([np.zeros(0, np.int64), *g._mask_writes])
        assert sorted(zip((queued >> 40).tolist(), ((queued >> 8) & 0xFFFFFFFF).tolist(),
                          (queued & 0xFF).tolist())) == sorted(oracle.mask_writes)
        assert fields["base_masks"] == len(oracle.mask_writes)

        # the slot maps: the base's values, the buckets' columns by what they hold
        keys = np.array(sorted(self.seen), np.int64)
        assert np.array_equal(g._base_slot.get_batch(keys), oracle.base_slot.get_batch(keys))
        vals = g._pb_slot.get_batch(keys)
        assert np.array_equal(vals >= 0, oracle.pb_slot.get_batch(keys) >= 0)
        assert len(g._pb_slot) == len(oracle.pb_slot)
        srcs, dsts = unpack_keys(keys[vals >= 0])
        shard, col = vals[vals >= 0] >> 32, vals[vals >= 0] & 0xFFFFFFFF
        assert np.array_equal(shard, g._part.owner(dsts))
        assert np.array_equal(g._pb_src[shard, col], srcs)
        assert np.array_equal(g._pb_dst[shard, col], g._part.local(dsts))

        # the batch: the touched columns once each, as the host plane has them
        shs, cols, bsrc, bdst = writes
        assert set(zip(shs.tolist(), cols.tolist())) == set(want)
        assert writes.shape[1] == len(want) == fields["bucket_writes"]
        assert np.array_equal(bsrc, g._pb_src[shs, cols])
        assert np.array_equal(bdst, g._pb_dst[shs, cols])
        if g.decremental:
            pending = lambda chunks: set(np.concatenate([np.zeros(0, np.int64), *chunks]).tolist())
            assert pending(g._pending_del_dst) == oracle.del_dst
            assert pending(g._pending_fresh_dst) == oracle.fresh_dst
            g._pending_del_dst.clear()  # a wake's dispatch would
            g._pending_fresh_dst.clear()

        # the device
        g._sync_upload(writes)
        assert g._mask_writes == []
        assert np.array_equal(np.asarray(g._dev_psrc), g._pb_src)
        assert np.array_equal(np.asarray(g._dev_pdst), g._pb_dst)
        for key in ("row_pos", "emeta"):
            assert np.array_equal(np.asarray(g._dev_stacked[key]), g._stacked[key])

        for insert, key in rows:  # what is live now, by the log's last word
            (self.live.add if insert else self.live.discard)(key)


def plain_churn(t):
    inserted = []
    for _ in range(4):
        base = t.some(set(t.base_keys.tolist()) & t.live, 60)
        new = t.fresh(50)
        gone = t.some(inserted, 25)
        rows = [(0, k) for k in base + gone] + [(1, k) for k in new]
        order = t.rng.permutation(len(rows))
        t.wake([rows[i] for i in order])
        inserted = [k for k in inserted if k not in gone] + new
    assert t.g.stats["anomalies"] == 0


def a_column_freed_and_reused_in_one_wake(t):
    first = t.fresh(40, shard=0)
    t.wake([(1, k) for k in first])
    count = t.g._pb_count.copy()
    # as many leave as come, in the same shard: the counter does not move
    t.wake([(0, k) for k in first[:30]] + [(1, k) for k in t.fresh(30, shard=0)])
    assert np.array_equal(t.g._pb_count, count) and t.g._pb_nfree[0] == 0
    assert t.record.fields["bucket_writes"] == 30  # each column written once
    assert t.g.stats["anomalies"] == 0


def a_duplicate_insert_of_a_live_pair(t):
    new = t.fresh(20)
    t.wake([(1, k) for k in new])
    again = t.some(t.base_keys.tolist(), 7) + new[:5]
    t.wake([(1, k) for k in again + t.fresh(10)])
    assert t.g.stats["anomalies"] == 12


def a_remove_of_an_absent_key(t):
    t.wake([(0, k) for k in t.fresh(9) + t.some(t.base_keys.tolist(), 20)])
    assert t.g.stats["anomalies"] == 9 and t.record.fields["base_masks"] == 20


def insert_then_remove_of_a_live_key(t):
    new = t.fresh(20)
    t.wake([(1, k) for k in new])
    live = t.some(t.base_keys.tolist(), 6) + new[:4]  # really removed, each an anomaly
    passing = t.fresh(8)  # a net no-op
    t.wake([(1, k) for k in live + passing] + [(0, k) for k in live + passing])
    assert t.g.stats["anomalies"] == 10
    assert (t.record.fields["base_masks"], t.record.fields["bucket_writes"]) == (6, 4)


def every_pair_on_one_shard(t):
    last = t.g.n_devices - 1
    new = t.fresh(50, shard=last)
    t.wake([(1, k) for k in new])
    in_shard = [k for k in t.base_keys.tolist() if t.g._part.owner((k >> 1) & 0x7FFFFFFF) == last]
    t.wake([(0, k) for k in new[:20] + t.some(in_shard, 30)] + [(1, k) for k in t.fresh(10, shard=last)])
    assert not t.g._pb_count[:last].any() and t.g._pb_count[last] == 50


def an_empty_log(t):
    t.wake([])
    t.wake([(1, k) for k in t.fresh(5)])
    t.wake([])
    assert (t.record.fields["bucket_writes"], t.record.fields["base_masks"]) == (0, 0)


def a_wake_that_grows_the_tier(t):
    t.wake([(1, k) for k in t.fresh(40, shard=0)])
    assert t.g._bucket_m == FLOOR
    t.wake([(0, k) for k in t.some(t.base_keys.tolist(), 15)] + [(1, k) for k in t.fresh(60, shard=0)])
    assert t.g._bucket_m == 256 and t.g.stats["bucket_grows"] == 1
    assert t.g._pb_free.shape == t.g._dev_psrc.shape == (t.g.n_devices, 256)


def the_overflow_at_the_ceiling(t):
    ceiling = t.g._bucket_ceiling()
    assert ceiling == CAPACITY // (4 * t.g.n_devices)
    t.wake([(1, k) for k in t.fresh(ceiling - 5, shard=0)])
    assert t.g._bucket_m == ceiling and t.g.stats["rebuilds"] == 1
    t.wake([(0, k) for k in t.some(t.base_keys.tolist(), 3)] + [(1, k) for k in t.fresh(6, shard=0)],
           packs=True)
    assert t.g.stats["rebuilds"] == 2 and not t.g._pb_count.any() and not t.g._pb_nfree.any()


CASES = (
    plain_churn, a_column_freed_and_reused_in_one_wake, a_duplicate_insert_of_a_live_pair,
    a_remove_of_an_absent_key, insert_then_remove_of_a_live_key, every_pair_on_one_shard,
    an_empty_log, a_wake_that_grows_the_tier, the_overflow_at_the_ceiling,
)


@pytest.mark.parametrize("case", CASES, ids=lambda case: case.__name__)
@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("backend", ["mesh", "mesh-decremental"])
def test_the_columns_fold_a_log_as_the_replay_did(backend, n_devices, case, monkeypatch):
    monkeypatch.setattr(mesh, "_BUCKET_FLOOR", FLOOR)
    case(Twin(backend, n_devices, seed=CASES.index(case)))


def test_add_batch_adds_what_the_batched_lookups_find():
    bulk = pack_keys([1, 2, 3, 4], [5, 6, 7, 8], [0, 1, 0, 1])
    slots = PackedSlotMap(bulk, np.arange(4, dtype=np.int64))
    assert slots.pop_batch(bulk[1:2]).tolist() == [1]  # a tombstone on a bulk key
    new = pack_keys([0, 9], [1, 1], [0, 0])
    keys = np.sort(np.concatenate([new, bulk[1:2]]))
    slots.add_batch(keys, np.array([70, 71, 72], np.int64))
    assert len(slots) == 6 and all(int(k) in slots for k in keys)
    probe = np.sort(np.concatenate([bulk, new]))
    want = dict(zip(bulk.tolist(), range(4))) | dict(zip(keys.tolist(), (70, 71, 72)))
    assert slots.get_batch(probe).tolist() == [want[k] for k in probe.tolist()]
    # the added value wins over the tombstoned bulk entry, and pops once
    assert slots.pop_batch(bulk[1:2]).tolist() == [want[int(bulk[1])]]
    assert slots.pop_batch(bulk[1:2]).tolist() == [-1]
    assert slots.pop_batch(np.sort(new)).tolist() == [want[k] for k in np.sort(new).tolist()]
    assert len(slots) == 3 and (slots.get_batch(probe) >= 0).sum() == 3
