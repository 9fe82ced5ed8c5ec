"""Mesh shadow-graph backend: the collector's data plane sharded over a
TPU device mesh.

This is the node-level sharding capability of the reference
(LocalGC.scala:191-196 replicates per-node graphs via DeltaGraph gossip)
re-expressed the TPU way, per SURVEY §7: instead of replicating the graph
per host, the detection state is *partitioned* across the devices of one
slice —

- node feature arrays (flags, recv_count) live device-resident, sharded
  over the mesh axis by supertile, dealt round-robin
  (``sharded_trace.Partition``: supertile ``t`` of 4,096 slots belongs to
  shard ``t % D``; NOT contiguous slot ranges, which hand the actors
  interned first, the live ones, to the first shards, because slots are
  handed out from 0 upward and the capacity doubles as uids arrive);
- propagation pairs (positive refob edges + supervisor pointers) live
  device-resident as per-destination-shard buckets, so each device's
  scatter lands only in its own node shard;
- each trace wave all_gathers the mark vector over ICI (the collective
  analogue of the DeltaMsg broadcast) and decides convergence with a
  global psum (parallel/sharded_trace.py).

The host keeps its mirror (interning, edge dict, sweep bookkeeping) and
streams *only the per-wake changes* to the device: dirty node rows
(``_node_log``) and pair transitions (``_pair_log``) are scatter-applied
with donated buffers, so steady-state host->device traffic is O(churn),
not O(graph).  Full rebuilds happen only on capacity growth, on log
overflow, or where the insert buckets overflow at their ceiling (below
it they grow in place to what they hold: ``_grow_buckets``).

Composes with the multi-node path: a cluster of collectors can each run
a mesh graph and still gossip DeltaGraphs/undo logs between hosts — the
mesh shards one node's replica, the fabric replicates across nodes (the
two levels the reference collapses into one).
"""

from __future__ import annotations

import threading
from collections import deque
from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np

from ...ops import pallas_decremental
from ...ops import trace as trace_ops
from ...ops.slotmap import (
    PackedSlotMap, PairLog, fold_log, pack_keys, unpack_keys,
)
from ...parallel import sharded_trace
from ...utils import events
from .arrays import (
    ArrayShadowGraph, PackedVerdicts, _NodeLog, _readback, audit_donation,
)
from .state import CrgcContext

#: The O(churn) scatters of a sync (bucket writes, deletion masks, dirty
#: node rows, jump parents) pad their batches to 4,096 entries times a
#: power of four: each padded length is a program of its own, so the
#: classes are few, and ``_warm_scatters`` runs every one a capacity can
#: meet before the first wake's traffic (a wake whose churn is the first
#: to reach a length would otherwise compile it amid the traffic, as
#: ``ArrayShadowGraph._warm_patches`` has it for the one-chip patch).
_SCATTER_PAD = 4096
#: the largest batch warmed, as a share of the padded slots (262,144
#: entries at 2^24): a larger one (a bulk load, a mass death) compiles
#: when it comes.  Each length is four programs of ~2.6 s on a cold
#: four-chip host (PERF.md section 6, PR 49)
_WARM_SHARE = 64
#: the insert bucket tier's columns a shard after a pack, until the pairs
#: it holds ask for more: the one-chip live tier's floor
#: (``IncrementalPallasLayout._xla_cap``).  Every sweep of a wake pays for
#: every column on every shard, full or empty (``pt.build_sweep_contribs``'
#: ``"xla"`` branch: ~17 ns a column a sweep on the v5e for the gather, the
#: scatter-max and its sort), so the tier is sized by what it holds, not
#: by the capacity (PERF.md section 6, PR 50)
_BUCKET_FLOOR = 1024
#: the bucket scatter batch of a sync whose pair log was empty
_NO_WRITES = np.zeros((4, 0), dtype=np.int64)
#: the sharded wake's counters of a shard's OWN work (its kernel's steps,
#: the tiles it forced or skipped); every other one is decided on the
#: gathered table and reads alike on every shard
SHARD_STATS = (
    "gated_tiles", "kernel_steps", "kernel_contractions", "kernel_chunk_walks",
    "kernel_walk_trips", "kernel_steps_full", "tiles_skipped",
)

#: Serializes sharded-collective dispatch + readback across EVERY
#: MeshShadowGraph in the process.  The virtual CPU mesh (and a real
#: slice) is ONE set of devices; two collector threads concurrently
#: executing all_gather-bearing programs on it can deadlock each other
#: (observed as permanently wedged Bookkeeper threads when several
#: mesh-backend systems coexist in one test process — each program
#: waits for all devices, and the runtime interleaves the two
#: collectives).  Per-wake serialization costs nothing in the
#: steady state — one collector per process is the deployment shape —
#: and makes multi-system processes (the test suite) hang-free.
#: Only the collective-bearing programs (the sharded trace and the
#: decremental wake) need the lock; _sync_device's scatters and folds
#: are per-shard local work with no rendezvous, so they run outside it.
_MESH_COLLECTIVE_LOCK = threading.Lock()

#: Traced collective programs shared across graphs: every system in a
#: process meshes the same devices, so graphs with identical geometry
#: reuse ONE jit object (and therefore one XLA compilation — first
#: caller compiles under the collective lock, the rest hit the cache
#: instead of serializing ~seconds of duplicate compile work behind it).
#: Bounded: cleared wholesale at the cap (a growing graph re-keys as its
#: padding doubles; without a cap a long-lived process would accumulate
#: one compiled program per geometry ever seen).  A clear only costs a
#: recompile on the next wake of each live geometry.
_SHARED_PROGRAM_CACHE: Dict[tuple, object] = {}
_SHARED_PROGRAM_CACHE_MAX = 32


def _pow2(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


def _scatter_pad(k: int) -> int:
    """The padded length of a scatter batch of ``k`` entries."""
    pad = _SCATTER_PAD
    while pad < k:
        pad *= 4
    return pad


def _split_by_shard(shard: np.ndarray, n_shards: int) -> tuple:
    """A batch's entries split by destination shard, as every sharded
    scatter of a sync and the insert buckets' free stacks need them: a
    stable order of the entries by ``shard`` (a shard's entries keep the
    batch's order), each entry's shard in that order, its rank among its
    shard's entries, and the entries a shard."""
    order = np.argsort(shard, kind="stable")
    shard = shard[order]
    counts = np.bincount(shard, minlength=n_shards)
    rank = np.arange(shard.size) - (np.cumsum(counts) - counts)[shard]
    return order, shard, rank, counts


class MeshShadowGraph(ArrayShadowGraph):
    """Shadow graph whose fold/trace state is sharded across a device
    mesh; liveness semantics identical to the host oracle (differential
    tests drive both over the same entry streams)."""

    def __init__(
        self,
        context: CrgcContext,
        local_address: Optional[str] = None,
        n_devices: int = 0,
        initial_capacity: int = 1024,
        decremental: bool = False,
        trace_mode: str = "auto",
        pull_density: float = 0.25,
    ):
        super().__init__(
            context,
            local_address,
            use_device=True,
            initial_capacity=initial_capacity,
            trace_mode=trace_mode,
            pull_density=pull_density,
        )
        import jax

        avail = len(jax.devices())
        if n_devices <= 0:
            n_devices = avail
        # A mesh bigger than the host would silently mis-shard: build_mesh
        # slices jax.devices()[:n] while bucket geometry keeps n, leaving
        # pair_dst offsets relative to the wrong shard origin.
        assert n_devices <= avail, (
            f"uigc.crgc.mesh-devices={n_devices} but only {avail} devices"
        )
        self.n_devices = n_devices
        self.mesh = sharded_trace.build_mesh(n_devices)
        self._fold_fn = sharded_trace.make_sharded_fold(self.mesh, donate=True)
        self._mask_fn = sharded_trace.make_sharded_mask(self.mesh)
        self._node_log = _NodeLog()  # enable dirty-slot tracking in the base

        from ...ops import pallas_trace as pt

        self.s_rows = pt.S_ROWS
        #: jump/auto trace modes jump marks through a REPLICATED
        #: min-source parent array (every shard runs the same pointer
        #: doubling over replicated tables — no collective needed);
        #: maintained O(churn) from the raw pair log like the
        #: single-device IncrementalPallasLayout.jump_parent
        self._use_jump = trace_mode in (pt.MODE_JUMP, pt.MODE_AUTO)
        self._jump_parent: Optional[np.ndarray] = None
        self._jump_writes: Dict[int, int] = {}
        self._jump_dev = None

        # device state (built lazily on first trace)
        self._dev_ready = False
        self._dev_flags = None
        self._dev_recv = None
        self._n_pad = 0
        self._shard_size = 0  # slots a shard: n_pad // D
        #: who owns a slot and where it lies in its owner's shard
        #: (``sharded_trace.Partition``): set by a pack, from ``s_rows``
        self._part: Optional[sharded_trace.Partition] = None
        #: the verdict's way off the device (``make_sharded_verdict``)
        self._verdict_fn = None
        self._warmed_n_pad = 0  # the padding whose scatters are warm
        # --- packed base plane: per-shard Pallas layouts -------------- #
        self._layout_meta: Optional[dict] = None
        self._stacked: Optional[dict] = None  # host truth of the layouts
        self._dev_stacked: Optional[dict] = None
        #: packed (src, dst, kind) key -> (shard << 40 | ri << 8 | col)
        self._base_slot = PackedSlotMap()
        #: queued deletion masks for the device layouts: the masked slots'
        #: packed values, an array a batch of removes
        self._mask_writes: List[np.ndarray] = []
        # --- insert buckets: XLA scatter-max tier for new pairs ------- #
        self._bucket_m = 0  # columns per shard (pow2)
        self._pb_src: Optional[np.ndarray] = None  # [D, M] global src ids
        self._pb_dst: Optional[np.ndarray] = None  # [D, M] local dst ids
        self._pb_count: Optional[np.ndarray] = None  # [D] columns handed out
        #: freed columns, a stack a shard: ``_pb_free[d, :_pb_nfree[d]]``
        self._pb_free: Optional[np.ndarray] = None  # [D, M]
        self._pb_nfree: Optional[np.ndarray] = None
        #: packed (src, dst, kind) key -> packed (shard << 32 | column)
        self._pb_slot = PackedSlotMap()
        #: ``bucket_grows``: growths of the insert bucket tier in place
        self.stats = {"rebuilds": 0, "wakes": 0, "anomalies": 0, "bucket_grows": 0}

        #: per-wake closure+repair detection on the mesh
        #: (parallel/sharded_trace.make_sharded_decremental_wake)
        self.decremental = decremental
        #: the previous fixpoint: mark/seed/halt/iu/active words, sharded,
        #: and the replicated walks of the last derivation from nothing
        self._wake_state: Optional[tuple] = None
        #: the suspects of the next wake, as the id arrays each fold of
        #: the pair log left (duplicates and all: ``_word_array`` ORs them)
        self._pending_del_dst: List[np.ndarray] = []
        self._pending_fresh_dst: List[np.ndarray] = []
        #: the counters of the last wakes as the sharded wake left them
        #: on the device, a shard a row (``wake_stats`` reads them back)
        self._wake_counters: deque = deque(
            maxlen=pallas_decremental.STATS_KEPT
        )
        #: the last wake's verdict words, on the device (sharded) and as
        #: the sweep took them (``shard_verdict_words``)
        self._verdict_dev = None
        self.last_verdict_words: Optional[np.ndarray] = None
        if decremental:
            # found by whoever reads the wake programs' counters
            pallas_decremental.track(self)

        self._jit_cache: Dict[str, object] = {}

    def _shared_program(self, tag: str, meta, factory):
        """Process-wide cache of the traced collective programs, keyed
        by the full geometry (graphs with equal shapes share one jit
        object and one compilation)."""
        key = (
            tag,
            self._n_pad,
            self._shard_size,
            meta["n_blocks"],
            meta["r_rows"],
            self.s_rows,
            self._bucket_m,
            meta["sub"],
            meta["group"],
            self.trace_mode,
            self.pull_density,
            tuple(d.id for d in self.mesh.devices.flat),
            self.mesh.axis_names,
        )
        fn = _SHARED_PROGRAM_CACHE.get(key)
        if fn is None:
            if len(_SHARED_PROGRAM_CACHE) >= _SHARED_PROGRAM_CACHE_MAX:
                _SHARED_PROGRAM_CACHE.clear()
            import time as _time

            t0 = _time.perf_counter()
            built = factory()
            # setdefault: a build race costs one discarded closure, never
            # a duplicate compile (compilation happens at first call).
            fn = _SHARED_PROGRAM_CACHE.setdefault(key, built)
            if events.recorder.enabled:
                # Compile-cache plane (telemetry/device.py): a miss here
                # means a NEW collective program geometry.  One miss per
                # geometry is healthy (a growth of the insert buckets is
                # one: ``_bucket_m`` is in the key); a per-wake miss
                # stream for one (tag, geom) is the recompile_storm
                # alert's input.
                events.recorder.commit(
                    events.COMPILE,
                    duration_s=_time.perf_counter() - t0,
                    tag=f"mesh.{tag}",
                    geom=events.compile_geom(key),
                    hit=False,
                )
        elif events.recorder.enabled:
            events.recorder.commit(
                events.COMPILE,
                tag=f"mesh.{tag}",
                geom=events.compile_geom(key),
                hit=True,
            )
        return fn

    # ------------------------------------------------------------- #
    # Device state construction
    # ------------------------------------------------------------- #

    def _sharding(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return (
            NamedSharding(self.mesh, P("gc")),
            NamedSharding(self.mesh, P("gc", None)),
            NamedSharding(self.mesh, P("gc", None, None)),
        )

    def _full_rebuild(self) -> None:
        self._pack_from_graph()
        self._upload_all()

    def _pack_from_graph(self) -> None:
        """The host's half of a rebuild: the per-shard layouts, the jump
        parents and the empty insert buckets packed from the graph's
        arrays.  The device holds nothing valid until ``_upload_all``."""
        self._dev_ready = False
        self.stats["rebuilds"] += 1
        D = self.n_devices
        super_sz = self.s_rows * 128
        chunk = D * super_sz
        n_pad = ((self.capacity + chunk - 1) // chunk) * chunk
        self._n_pad = n_pad
        self._shard_size = n_pad // D
        self._part = sharded_trace.Partition(D, super_sz)
        self._verdict_fn = sharded_trace.make_sharded_verdict(self.mesh, super_sz)

        # --- packed base layouts from the host truth -------------- #
        from ...ops.pallas_incremental import IncrementalPallasLayout

        esrc, edst, kinds = IncrementalPallasLayout.pairs_from_graph(
            self.edge_src, self.edge_dst, self.edge_weight, self.supervisor
        )
        stacked, meta, slot_vals = sharded_trace.pack_shard_layouts(
            esrc, edst, n_pad, D, s_rows=self.s_rows
        )
        self._stacked = stacked
        self._layout_meta = meta
        self._base_slot = PackedSlotMap(
            pack_keys(esrc, edst, kinds), slot_vals
        )
        self._mask_writes = []
        if self._use_jump:
            from ...ops import pallas_trace as pt

            self._jump_parent = pt.jump_parents(esrc, edst, n_pad)
            self._jump_writes = {}
            self._jump_dev = None  # re-uploaded (replicated) on first sync

        # --- empty insert buckets --------------------------------- #
        # At the floor, or at the largest size this graph has grown them
        # to (``_grow_buckets``): like the one-chip ``_xla_cap`` the tier
        # never shrinks, so a pack keeps the programs the steady state
        # compiled.
        m = self._bucket_m = max(_BUCKET_FLOOR, self._bucket_m)
        self._pb_src = np.full((D, m), self._n_pad, dtype=np.int32)
        self._pb_dst = np.zeros((D, m), dtype=np.int32)
        self._pb_count = np.zeros(D, dtype=np.int64)
        self._pb_free = np.zeros((D, m), dtype=np.int32)
        self._pb_nfree = np.zeros(D, dtype=np.int64)
        self._pb_slot = PackedSlotMap()
        self._pair_log = PairLog()
        self._node_log = _NodeLog()
        self.invalidate_wake_state()

    def _upload_all(self) -> int:
        """The device's half of a rebuild: every operand put whole, the
        node features in owner-major order (each shard its own
        supertiles: one row permutation of the two arrays a pack).
        Returns the bytes handed over for node features."""
        import jax

        n_pad, stacked = self._n_pad, self._stacked
        nodes_s, pairs_s, pairs3_s = self._sharding()
        flags = np.zeros(n_pad, dtype=np.uint8)
        flags[: self.capacity] = self.flags
        recv = np.zeros(n_pad, dtype=np.int64)
        recv[: self.capacity] = self.recv_count
        self._dev_flags = jax.device_put(self._part.owner_major(flags), nodes_s)
        self._dev_recv = jax.device_put(self._part.owner_major(recv), nodes_s)
        self._dev_stacked = {
            "bmeta1": jax.device_put(stacked["bmeta1"], pairs_s),
            "bmeta2": jax.device_put(stacked["bmeta2"], pairs_s),
            "row_pos": jax.device_put(stacked["row_pos"], pairs3_s),
            "emeta": jax.device_put(stacked["emeta"], pairs3_s),
        }
        self._dev_psrc = jax.device_put(self._pb_src, pairs_s)
        self._dev_pdst = jax.device_put(self._pb_dst, pairs_s)
        # Host mirror of the last recv values synced to the device: the
        # sharded fold applies *deltas* (reference: ShadowGraph.java:75-83
        # folds counts, not absolutes), so per-wake sync needs the diff
        # against what the device already holds.  By slot, like the host's.
        self._recv_synced = recv
        self._sync_jump_mirror()
        if self._warmed_n_pad != n_pad:  # a capacity's first copies
            self._warm_scatters()
            self._warmed_n_pad = n_pad
        self._dev_ready = True
        return flags.nbytes + recv.nbytes

    # ------------------------------------------------------------- #
    # Incremental device sync (O(churn) per wake)
    # ------------------------------------------------------------- #

    def _bucket_ceiling(self) -> int:
        """The most columns a shard the insert buckets grow to: a
        meaningful fraction of the graph's scale in new pairs, past which
        a pack folds them into the packed base (the freeze/consolidate
        analogue)."""
        return _pow2(max(_BUCKET_FLOOR, self.capacity // (4 * self.n_devices)))

    def _bucket_fill(self, new=0) -> int:
        """The fullest shard's bucket columns in use, with ``new`` more
        a shard."""
        return int((self._pb_count - self._pb_nfree + new).max())

    def _grow_buckets(self, need: int) -> None:
        """Widen the insert buckets in place so that ``need`` columns a
        shard fill at most half of them (a draw's fluctuation then cannot
        cross the size again inside a window), or to the ceiling.
        Columns keep their index, so the slot map, the free stacks and the
        counts stand, the pairs are where they were and the previous
        fixpoint stays valid.  The wider ``[D, M]`` is a new wake program
        (``_shared_program``'s key) and a new ``pairs`` scatter at every
        padded length: the scatters are run here, all padding, so that
        only the wake that grows compiles (the one device work of the
        ``layout`` phase: the buckets are put whole, 8 bytes a column,
        before the wake's own writes are scattered into them)."""
        import jax

        m = min(self._bucket_ceiling(), _pow2(2 * need))
        pad = ((0, 0), (0, m - self._bucket_m))
        self._pb_src = np.pad(self._pb_src, pad, constant_values=self._n_pad)  # sink
        self._pb_dst = np.pad(self._pb_dst, pad)
        self._pb_free = np.pad(self._pb_free, pad)
        self._bucket_m = m
        self.stats["bucket_grows"] += 1
        _, pairs_s, _ = self._sharding()
        self._dev_psrc = jax.device_put(self._pb_src, pairs_s)
        self._dev_pdst = jax.device_put(self._pb_dst, pairs_s)
        for kp in self._warm_lengths():
            self._scatter_pairs(*self._pairs_batch(kp))

    def _remove_keys(self, karr: np.ndarray) -> Tuple[int, np.ndarray]:
        """Remove a batch of distinct keys (ascending) from wherever each
        lives.  A hit in the bucket tier frees its column: the sink is
        written on the host plane and the column pushed on its shard's
        free stack.  A hit in the packed base masks its slot in place on
        the host and queues it for the device, as
        ``IncrementalPallasLayout._mask_base_slots`` does.  Returns how
        many keys were found and the freed columns, packed as the slot
        map holds them (shard << 32 | column)."""
        from ...ops import pallas_trace as pt

        freed = self._pb_slot.pop_batch(karr)
        missing = freed < 0
        masked = self._base_slot.pop_batch(karr[missing])
        masked = masked[masked >= 0]
        freed = freed[~missing]
        order, shard, rank, counts = _split_by_shard(freed >> 32, self.n_devices)
        cols = (freed & 0xFFFFFFFF)[order]
        self._pb_src[shard, cols] = self._n_pad  # sink
        self._pb_dst[shard, cols] = 0
        self._pb_free[shard, self._pb_nfree[shard] + rank] = cols
        self._pb_nfree += counts
        if masked.size:
            slot = (masked >> 40, (masked >> 8) & 0xFFFFFFFF, masked & 0xFF)
            self._stacked["row_pos"][slot] = pt._PAD_ROW
            self._stacked["emeta"][slot] = 0
            self._mask_writes.append(masked)
        return freed.size + masked.size, freed

    def _apply_pair_log(self) -> Optional[np.ndarray]:
        """Fold pair transitions into the host plane; returns the bucket
        device-scatter batch, or None if the buckets overflowed their
        ceiling (full rebuild required; below it they grow in place).
        Deletions hitting the packed base mask its
        slot in place (host + queued device mask); deletions hitting the
        bucket free its column; inserts land in the bucket tier, in a
        freed column of their destination's shard before a new one.

        The batch is an int64 array of four rows (shard, column, src,
        local dst): every bucket column the log touched ONCE, with what
        the host plane holds after all of it (a column freed and taken
        again in one log carries the insert's pair; XLA gives a scatter
        with a duplicated index no order).

        Batched like IncrementalPallasLayout.apply_log (the net-effect
        argument and anomaly accounting live in slotmap.fold_log), with
        no interpreted step per pair: slot lookups are one vectorized
        binary search per batch, and what is the mesh's own, the split by
        destination shard, is ``_split_by_shard``."""
        ins, psrc, pdst, kind = self._pair_log.columns()
        if self._use_jump:
            # Batched jump-parent maintenance — the same
            # pt.fold_jump_log rules as the single-device layout plane
            # (min-fold on insert, invalidate-on-remove, conservative
            # about pairs both inserted and removed in one batch), so
            # the backends cannot diverge on which edges the jump
            # sweep may cross.
            from ...ops import pallas_trace as pt

            pt.fold_jump_log(
                self._jump_parent, ins, psrc, pdst, self._n_pad,
                self._jump_writes,
            )
        removes, cond_removes, inserts, _ = fold_log(ins, psrc, pdst, kind)
        if self.decremental:
            # Suspect bookkeeping for the decremental wake: removal
            # destinations must re-derive; insert destinations must see
            # their new pair once.  Over-approximation is sound.
            _, deleted = unpack_keys(np.concatenate([removes, cond_removes]))
            if deleted.size:
                self._pending_del_dst.append(deleted)
            _, fresh = unpack_keys(inserts)
            if fresh.size:
                self._pending_fresh_dst.append(fresh)

        # a remove of what lives nowhere is caller drift
        found, freed = self._remove_keys(removes)
        self.stats["anomalies"] += removes.size - found
        # insert-first/remove-last: net no-op unless the key was
        # already live (anomalous duplicate insert + real remove).
        found, freed_cond = self._remove_keys(cond_removes)
        self.stats["anomalies"] += found

        present = (self._pb_slot.get_batch(inserts) >= 0) | (
            self._base_slot.get_batch(inserts) >= 0
        )
        keys = inserts[~present]
        srcs, dsts = unpack_keys(keys)
        order, shard, rank, new = _split_by_shard(
            self._part.owner(dsts), self.n_devices
        )
        # the fullest shard's columns once the new pairs are in
        # (the removes above have freed theirs)
        need = self._bucket_fill(new)
        if need > self._bucket_m:
            if need > self._bucket_ceiling():
                return None  # overflow at the ceiling: pack
            self._grow_buckets(need)
        # a duplicate insert of a live pair is caller drift too
        self.stats["anomalies"] += inserts.size - keys.size
        # a shard's new pairs pop its free stack, then count on
        free = self._pb_nfree[shard]
        reused = rank < free
        cols = np.where(
            reused,
            self._pb_free[shard, np.maximum(free - 1 - rank, 0)],
            self._pb_count[shard] + rank - free,
        )
        popped = np.minimum(new, self._pb_nfree)
        self._pb_nfree -= popped
        self._pb_count += new - popped
        taken = (shard << 32) | cols
        self._pb_slot.add_batch(keys[order], taken)
        self._pb_src[shard, cols] = srcs[order]
        self._pb_dst[shard, cols] = self._part.local(dsts[order])
        self._pair_log.clear()
        touched = np.unique(np.concatenate([freed, freed_cond, taken]))
        shs, cols = touched >> 32, touched & 0xFFFFFFFF
        return np.stack([shs, cols, self._pb_src[shs, cols], self._pb_dst[shs, cols]])

    def _jit(self, name, builder):
        fn = self._jit_cache.get(name)
        if fn is None:
            fn = self._jit_cache[name] = builder()
            if events.recorder.enabled:
                events.recorder.commit(
                    events.COMPILE, tag=f"mesh.scatter.{name}",
                    geom="graph", hit=False,
                )
        elif events.recorder.enabled:
            # Hits commit like every instrumented cache, so the
            # hit/miss shape stays 1-miss-then-hits — without this,
            # N graphs' N innocent builds read as a storm downstream.
            events.recorder.commit(
                events.COMPILE, tag=f"mesh.scatter.{name}",
                geom="graph", hit=True,
            )
        return fn

    def _sync_jump_mirror(self) -> None:
        """Replicated jump-parent device mirror: full upload once per
        rebuild, O(churn) scatter after (same policy as the node
        arrays; replicated because the pointer doubling gathers
        globally on every shard)."""
        if not self._use_jump:
            return
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self._jump_dev is None:
            repl = NamedSharding(self.mesh, P())
            self._jump_dev = jax.device_put(self._jump_parent, repl)
            self._jump_writes = {}
        elif self._jump_writes:
            w = self._jump_writes
            self._jump_writes = {}
            k = len(w)
            idx, vals = self._jump_batch(_scatter_pad(k))
            idx[:k] = np.fromiter(w.keys(), np.int64, k)
            vals[:k] = np.fromiter(w.values(), np.int64, k)
            self._scatter_jump(idx, vals)

    def _jump_batch(self, kp: int) -> tuple:
        """An all-padding batch for ``_scatter_jump``."""
        return np.full(kp, self._n_pad + 1, np.int32), np.zeros(kp, np.int32)  # OOB -> drop

    def _scatter_jump(self, idx, vals) -> None:
        import jax

        def build_jump():
            @partial(jax.jit, donate_argnums=(0,))
            def apply_jump(jp, idx, vals):
                return jp.at[idx].set(vals, mode="drop")

            return apply_jump

        donated = self._jump_dev
        self._jump_dev = self._jit("jump", build_jump)(donated, idx, vals)
        if self.donation_audit:
            audit_donation("mesh.jump", donated)

    def _sync_device(self) -> int:
        """Bring the device's copy up to the host's graph; returns the
        bytes handed over for node features (``_sync_upload``)."""
        return self._sync_upload(self._sync_layout())

    def _sync_layout(self) -> Optional[np.ndarray]:
        """Layout maintenance, the host's share of a sync: the pair log
        folded into the host plane in O(changes) (``_apply_pair_log``),
        or everything packed from the graph where there is no device
        state, the log overflowed, the capacity outgrew the padding or
        the insert buckets overflowed their ceiling.  Returns the bucket
        scatter batch for ``_sync_upload`` (``_apply_pair_log``'s four
        rows, no column where the log was empty), None after a pack."""
        log = self._pair_log
        rows = 0 if log is None else len(log)
        writes = None
        if self._dev_ready and log is not None and self._n_pad >= self.capacity:
            writes = self._apply_pair_log() if rows else _NO_WRITES
        if writes is None:
            self._pack_from_graph()
        if self.profile_wake is not None:
            # as ArrayShadowGraph._synced_dec notes them; the last two
            # are what the wake's two layout scatters will carry
            self.profile_wake.note(
                layout_rows=rows, layout_rebuilt=int(writes is None),
                bucket_cols=self._bucket_m, bucket_fill=self._bucket_fill(),
                bucket_writes=0 if writes is None else writes.shape[1],
                base_masks=sum(masked.size for masked in self._mask_writes),
            )
        return writes

    def _sync_upload(self, pair_writes: Optional[np.ndarray]) -> int:
        """The device's share of a sync: every operand whole after a
        pack (``pair_writes`` None), else O(churn) scatters into donated
        buffers: the bucket writes (``_sync_layout``'s four rows, copied
        into the padded batch), the base layouts' deletion masks (the
        queued packed slots, split by shard), the dirty node rows, the
        jump parents.  Returns the bytes handed over for node features
        (``upload_bytes``)."""
        if pair_writes is None:
            return self._upload_all()
        nbytes = 0
        D = self.n_devices
        k = pair_writes.shape[1]
        if k:
            batch = self._pairs_batch(_scatter_pad(k))
            for padded, row in zip(batch, pair_writes):
                padded[:k] = row
            self._scatter_pairs(*batch)

        if self._mask_writes:
            # base-layout deletions: per-shard in-place masking
            masked = np.concatenate(self._mask_writes)
            self._mask_writes = []
            order, shard, rank, counts = _split_by_shard(masked >> 40, D)
            masked = masked[order]
            ri, col = self._mask_batch(_scatter_pad(int(counts.max())))
            ri[shard, rank] = (masked >> 8) & 0xFFFFFFFF
            col[shard, rank] = masked & 0xFF
            self._scatter_masks(ri, col)

        slots_arr = self._node_log.take()
        if slots_arr.size:
            # Bucket dirty slots by owning shard and run the sharded fold
            # (parallel/sharded_trace.make_sharded_fold): each device
            # scatter-applies only its own shard's rows — recv as deltas
            # against the synced mirror, flags as set/clear masks that
            # reproduce absolute assignment ((old | set) & ~clear = new).
            order, shard, col, counts = _split_by_shard(
                self._part.owner(slots_arr), D
            )
            slots_arr = slots_arr[order]
            batch = self._nodes_batch(_scatter_pad(int(counts.max(initial=1))))
            lslot, rdelta, fset, fclear = batch
            new_flags = self.flags[slots_arr]
            new_recv = self.recv_count[slots_arr]
            lslot[shard, col] = self._part.local(slots_arr)
            rdelta[shard, col] = new_recv - self._recv_synced[slots_arr]
            fset[shard, col] = new_flags
            fclear[shard, col] = ~new_flags
            self._recv_synced[slots_arr] = new_recv
            nbytes = sum(a.nbytes for a in batch)
            self._scatter_nodes(*batch)

        self._sync_jump_mirror()
        return nbytes

    # -- the scatters: an all-padding batch of each, and its dispatch -- #

    def _pairs_batch(self, kp: int) -> tuple:
        shs = np.full(kp, self.n_devices, dtype=np.int32)  # OOB -> drop
        return (shs, *(np.zeros(kp, dtype=np.int32) for _ in range(3)))

    def _scatter_pairs(self, shs, cols, srcs, dsts) -> None:
        import jax

        def build_pairs():
            @partial(jax.jit, donate_argnums=(0, 1))
            def apply_pairs(psrc, pdst, shs, cols, srcs, dsts):
                psrc = psrc.at[shs, cols].set(srcs, mode="drop")
                pdst = pdst.at[shs, cols].set(dsts, mode="drop")
                return psrc, pdst

            return apply_pairs

        donated_src, donated_dst = self._dev_psrc, self._dev_pdst
        self._dev_psrc, self._dev_pdst = self._jit("pairs", build_pairs)(
            donated_src, donated_dst, shs, cols, srcs, dsts
        )
        if self.donation_audit:
            audit_donation("mesh.pairs", donated_src, donated_dst)

    def _mask_batch(self, k: int) -> tuple:
        D, rows_total = self.n_devices, self._stacked["row_pos"].shape[1]
        ri = np.full((D, k), rows_total, dtype=np.int32)  # OOB -> drop
        return ri, np.zeros((D, k), dtype=np.int32)

    def _scatter_masks(self, ri, col) -> None:
        self._dev_stacked["row_pos"], self._dev_stacked["emeta"] = (
            self._mask_fn(
                self._dev_stacked["row_pos"],
                self._dev_stacked["emeta"],
                ri,
                col,
            )
        )

    def _nodes_batch(self, m: int) -> tuple:
        """Per-shard local slot buckets, padded with the sink (= a
        shard's size), and their zero deltas."""
        D = self.n_devices
        return (
            np.full((D, m), self._shard_size, dtype=np.int32),
            np.zeros((D, m), dtype=np.int64),
            np.zeros((D, m), dtype=np.uint8),
            np.zeros((D, m), dtype=np.uint8),
        )

    def _scatter_nodes(self, lslot, rdelta, fset, fclear) -> None:
        donated_flags, donated_recv = self._dev_flags, self._dev_recv
        self._dev_flags, self._dev_recv = self._fold_fn(
            donated_flags, donated_recv, lslot, rdelta, fset, fclear
        )
        if self.donation_audit:
            # The sharded fold donates its node shards
            # (sharded_trace.make_sharded_fold(donate=True)); a
            # surviving input means every wake now re-uploads
            # O(graph) node state instead of O(churn) deltas.
            audit_donation("mesh.fold", donated_flags, donated_recv)

    def _warm_lengths(self):
        """Every padded length this capacity's churn can meet."""
        kp = _SCATTER_PAD
        while kp <= _scatter_pad(self._n_pad // _WARM_SHARE):
            yield kp
            kp *= 4

    def _warm_scatters(self) -> None:
        """Run every scatter at every padded length this capacity's
        churn can meet, all padding, so nothing is written."""
        for kp in self._warm_lengths():
            self._scatter_pairs(*self._pairs_batch(kp))
            self._scatter_masks(*self._mask_batch(kp))
            self._scatter_nodes(*self._nodes_batch(kp))
            if self._use_jump:
                self._scatter_jump(*self._jump_batch(kp))

    # ------------------------------------------------------------- #
    # Trace
    # ------------------------------------------------------------- #

    def _word_array(self, id_chunks: List[np.ndarray]):
        """Scatter id arrays into the node-word array, sharded and
        ordered like the node arrays: owner-major, so a slot's bit lies
        in its owner's words (the ids mapped by the partition, then
        ``id_words``; word ``w`` of shard ``d`` covers its local slots
        ``32w..``).  No ids (the quiet steady state) reuse one cached
        zero array instead of allocating + transferring per wake."""
        import jax

        nodes_s, _, _ = self._sharding()
        n_words = self._n_pad // 32
        if not id_chunks:
            z = getattr(self, "_zero_words", None)
            if z is None or z.shape[0] != n_words:
                z = self._zero_words = jax.device_put(
                    np.zeros(n_words, np.int32), nodes_s
                )
            return z
        placed = self._part.owner_major_index(
            np.concatenate(id_chunks), self._shard_size
        )
        words = pallas_decremental.id_words([placed], n_words)
        return jax.device_put(words.view(np.int32), nodes_s)

    def compute_marks(self):
        self._note_device_wake()
        with self._device_call() as ev:
            if self.decremental:
                return self._compute_marks_decremental(ev.fields)
            self._sync_device()
            self.stats["wakes"] += 1
            meta = self._layout_meta
            traced = self._shared_program(
                "trace",
                meta,
                lambda: sharded_trace.make_sharded_pallas_trace(
                    self.mesh,
                    self._n_pad,
                    self._shard_size,
                    meta["n_blocks"],
                    meta["r_rows"],
                    self.s_rows,
                    self._bucket_m,
                    sub=meta["sub"],
                    group=meta["group"],
                    mode=self.trace_mode,
                    pull_density=self.pull_density,
                ),
            )
            jump = (self._jump_dev,) if self._use_jump else ()
            with _MESH_COLLECTIVE_LOCK:
                mark = traced(
                    self._dev_flags,
                    self._dev_recv,
                    self._dev_stacked["bmeta1"],
                    self._dev_stacked["bmeta2"],
                    self._dev_stacked["row_pos"],
                    self._dev_stacked["emeta"],
                    self._dev_psrc,
                    self._dev_pdst,
                    *jump,
                )
                return _readback(mark, "marks.mesh")[: self.capacity]

    def _stage_wake(self) -> tuple:
        """The host's share of a wake between the sync and the dispatch
        (``DecrementalTracer.stage_wake``'s): the wake program for the
        geometry the layouts have, a previous state where there is none,
        and the suspects' id words put on the device."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        meta = self._layout_meta
        wake = self._shared_program(
            "dec",
            meta,
            lambda: sharded_trace.make_sharded_decremental_wake(
                self.mesh,
                self._n_pad,
                self._shard_size,
                meta["n_blocks"],
                meta["r_rows"],
                self.s_rows,
                self._bucket_m,
                sub=meta["sub"],
                group=meta["group"],
                mode=self.trace_mode,
                pull_density=self.pull_density,
            ),
        )
        if self._wake_state is None:
            # no previous fixpoint: zero words and zero walks, the cold road
            z = self._word_array([])
            walks = jax.device_put(
                np.zeros((), np.int32), NamedSharding(self.mesh, P())
            )
            self._wake_state = (z, z, z, z, z, walks)
        del_w = self._word_array(self._pending_del_dst)
        fresh_w = self._word_array(self._pending_fresh_dst)
        return wake, del_w, fresh_w

    def _dispatch_decremental_wake(self, staged: tuple):
        """Dispatch one closure+repair wake on the mesh (regional
        re-derivation per shard, one word all_gather per sweep; a
        zeroed previous state, cold start or post-rebuild, is the full
        derivation).  State and suspects COMMIT at dispatch; an
        async-poisoned result surfaces at the first readback, where the
        caller invalidates so the next wake re-derives from zero state
        instead of feeding poisoned arrays forever.  Returns the mark
        and in-use words (sharded, on the device)."""
        wake, del_w, fresh_w = staged
        jump = (self._jump_dev,) if self._use_jump else ()
        *state, counters = wake(
            self._dev_flags,
            self._dev_recv,
            del_w,
            fresh_w,
            *self._wake_state,
            self._dev_stacked["bmeta1"],
            self._dev_stacked["bmeta2"],
            self._dev_stacked["row_pos"],
            self._dev_stacked["emeta"],
            self._dev_psrc,
            self._dev_pdst,
            *jump,
        )
        self._wake_state = tuple(state)
        self._wake_counters.append(counters)
        self._pending_del_dst.clear()
        self._pending_fresh_dst.clear()
        return state[0], state[3]

    def _compute_marks_decremental(self, event: dict) -> PackedVerdicts:
        """One wake's verdict words through the sharded wake, in the
        phases and with the notes of the one-chip road
        (``ArrayShadowGraph._compute_marks_decremental``): ``layout``
        (the pair log folded on the host, or a pack), ``upload`` (the
        O(churn) scatters, then ``stage``: the program and the suspects'
        words), ``device`` (``dispatch``, then the wait) and
        ``readback`` (1/8 of a byte a slot: the garbage words put back
        in slot order on the device, ``make_sharded_verdict``, and laid
        end to end, and the number of marks).  Dispatch and
        readback share one hold of the collective lock: exactly one
        collective program is in flight at a time.  The wake's state was
        committed at dispatch, so a poisoned result, which surfaces at
        the wait or the readback, drops it: the next wake derives from
        nothing."""
        wake = self.profile_wake
        with events.wake_phase(wake, "layout"):
            pair_writes = self._sync_layout()
        with events.wake_phase(wake, "upload"):
            nbytes = self._sync_upload(pair_writes)
            with events.wake_part(wake, "stage_s", "stage"):
                staged = self._stage_wake()
            event["upload_bytes"] = nbytes
            if wake is not None:
                wake.note(upload_bytes=nbytes)
        self.stats["wakes"] += 1
        with _MESH_COLLECTIVE_LOCK:
            try:
                with events.wake_phase(wake, "device"):
                    with events.wake_part(wake, "dispatch_s", "dispatch"):
                        mark_w, iu_w = self._dispatch_decremental_wake(staged)
                    mark_w.block_until_ready()
                if wake is not None:
                    # the counters stay on the device: whoever reads the
                    # record pays for their way to the host
                    wake.defer(self._read_sweep_stats, self._wake_counters[-1])
                with events.wake_phase(wake, "readback"):
                    garbage_w, marked = self._verdict_fn(mark_w, iu_w)
                    words = _readback(garbage_w, "marks.mesh_decremental")
                    self._verdict_dev = garbage_w
                    self.last_verdict_words = words.view(np.uint32)
                    return PackedVerdicts(
                        self.last_verdict_words,
                        int(_readback(marked, "marks.mesh_decremental.live")),
                    )
            except Exception:
                self.invalidate_wake_state()
                raise

    def invalidate_wake_state(self) -> None:
        """Drop the previous-fixpoint state (failed/poisoned wake, a
        pack): the next wake is a full derivation and pending suspects
        are moot."""
        self._wake_state = None
        self._pending_del_dst.clear()
        self._pending_fresh_dst.clear()

    # ------------------------------------------------------------- #
    # What the wakes left to read
    # ------------------------------------------------------------- #

    def wake_stats(self, last_n: Optional[int] = None) -> List[dict]:
        """The counters of the last ``last_n`` wakes (all that are kept,
        at most ``pallas_decremental.STATS_KEPT``, when None), oldest first, read back from
        the device now: per wake the keys of
        ``DecrementalTracer.wake_stats`` and ``gathers``.  What every
        shard decides alike on the gathered table (the sweeps of both
        loops, whether the closure gave up and what it spent, the dirty
        chunks, the pull and jump decisions, the all-gathers) is given
        once, as the one-chip wake gives it; a shard's own work
        (``SHARD_STATS``: the kernel's counters, ``gated_tiles``,
        ``tiles_skipped``) as a list with one entry a shard, whose sum
        is the mesh's work and whose maximum its pace.  Waits for a wake
        still in flight; costs the wakes nothing."""
        kept = list(self._wake_counters)
        if last_n is not None:
            kept = kept[max(0, len(kept) - last_n):]
        return _shard_stats(kept)

    @staticmethod
    def _read_sweep_stats(counters: list) -> List[dict]:
        """The wake records' fields of the sweep counters
        (``ArrayShadowGraph._read_sweep_stats``), of the mesh as a whole:
        a shard's own counts summed."""
        out = []
        for stats in _shard_stats(counters):
            fields = {
                key: stats[key]
                for key in ("n_sweeps", "jump_sweeps", "closure_sweeps",
                            "closure_bailed")
            }
            fields["gated_tiles"] = sum(stats["gated_tiles"])
            for key in ("dirty_chunks", "pull_on", "jump_on"):
                fields["sweep_" + key] = stats[key]
            fields["sweep_tiles_skipped"] = [
                sum(sweep) for sweep in zip(*stats["tiles_skipped"])
            ]
            out.append(fields)
        return out

    def shard_verdict_words(self) -> List[np.ndarray]:
        """The last wake's garbage words as the devices hold them once
        ``make_sharded_verdict`` has put them in slot order: device ``d``
        the words of slots ``[d, d + 1) * _shard_size`` (uint32; bit
        ``i & 31`` of word ``i >> 5`` is the ``i``-th of them), a D-th of
        the verdict each, which is not the range it OWNS (its supertiles
        are every D-th).  Laid end to end they are
        ``last_verdict_words``, the verdict the sweep took."""
        return [
            _readback(rows, "marks.mesh_decremental.shard").view(np.uint32)
            for rows in sharded_trace.shards_in_order(self._verdict_dev)
        ]


def _shard_stats(counters) -> List[dict]:
    """Some wakes' counters as the sharded wake left them on the device
    (a shard a row), read back in one crossing and shaped as
    ``MeshShadowGraph.wake_stats`` gives them; each shard's row through
    the one-chip wake's ``pallas_decremental.host_stats``."""
    import jax

    out = []
    for host in jax.device_get(list(counters)):  # readback: a few hundred bytes of counters a shard a wake, on request
        shards = [
            pallas_decremental.host_stats(
                {key: rows[d] for key, rows in host.items()}
            )
            for d in range(host["n_sweeps"].shape[0])
        ]
        stats = {
            key: [shard[key] for shard in shards] if key in SHARD_STATS
            else shards[0][key]
            for key in shards[0]
        }
        stats["gathers"] = int(host["gathers"][0])
        out.append(stats)
    return out
