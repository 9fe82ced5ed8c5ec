"""Mesh shadow-graph backend: the collector's data plane sharded over a
TPU device mesh.

This is the node-level sharding capability of the reference
(LocalGC.scala:191-196 replicates per-node graphs via DeltaGraph gossip)
re-expressed the TPU way, per SURVEY §7: instead of replicating the graph
per host, the detection state is *partitioned* across the devices of one
slice —

- node feature arrays (flags, recv_count) live device-resident, sharded
  by contiguous slot range over the mesh axis;
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
not O(graph).  Full rebuilds happen only on capacity growth or log
overflow.

Composes with the multi-node path: a cluster of collectors can each run
a mesh graph and still gossip DeltaGraphs/undo logs between hosts — the
mesh shards one node's replica, the fabric replicates across nodes (the
two levels the reference collapses into one).
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np

from ...ops import trace as trace_ops
from ...ops.slotmap import (
    PackedSlotMap, PairLog, fold_log, pack_keys, unpack_keys,
)
from ...parallel import sharded_trace
from ...utils import events
from .arrays import ArrayShadowGraph, _NodeLog, _readback, audit_donation
from .state import CrgcContext

_SINK_PAD = 64  # scatter batches are padded to multiples of this

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
        self._shard_size = 0
        # --- packed base plane: per-shard Pallas layouts -------------- #
        self._layout_meta: Optional[dict] = None
        self._stacked: Optional[dict] = None  # host truth of the layouts
        self._dev_stacked: Optional[dict] = None
        #: packed (src, dst, kind) key -> (shard << 40 | ri << 8 | col)
        self._base_slot = PackedSlotMap()
        #: queued deletion masks for the device layouts [(shard, ri, col)]
        self._mask_writes: List[Tuple[int, int, int]] = []
        # --- insert buckets: XLA scatter-max tier for new pairs ------- #
        self._bucket_m = 0  # columns per shard (pow2)
        self._pb_src: Optional[np.ndarray] = None  # [D, M] global src ids
        self._pb_dst: Optional[np.ndarray] = None  # [D, M] local dst ids
        self._pb_count: Optional[np.ndarray] = None
        self._pb_free: List[List[int]] = []
        #: packed (src, dst, kind) key -> packed (shard << 32 | column)
        self._pb_slot = PackedSlotMap()
        self.stats = {"rebuilds": 0, "wakes": 0, "anomalies": 0}

        #: per-wake closure+repair detection on the mesh
        #: (parallel/sharded_trace.make_sharded_decremental_wake)
        self.decremental = decremental
        self._wake_state: Optional[list] = None  # mark/seed/halt/iu/active
        self._pending_del_dst: set = set()
        self._pending_fresh_dst: set = set()

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
                # geometry is healthy; a per-wake miss stream for one
                # (tag, geom) is the recompile_storm alert's input.
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
        import jax

        self.stats["rebuilds"] += 1
        D = self.n_devices
        super_sz = self.s_rows * 128
        chunk = D * super_sz
        n_pad = ((self.capacity + chunk - 1) // chunk) * chunk
        self._n_pad = n_pad
        self._shard_size = n_pad // D

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
        # Sized so the bucket tier absorbs a meaningful fraction of the
        # graph's scale in new pairs before the next rebuild folds them
        # into the packed base (the freeze/consolidate analogue).
        m = _pow2(max(1024, self.capacity // (4 * D)))
        self._bucket_m = m
        self._pb_src = np.full((D, m), self._n_pad, dtype=np.int32)
        self._pb_dst = np.zeros((D, m), dtype=np.int32)
        self._pb_count = np.zeros(D, dtype=np.int64)
        self._pb_free = [[] for _ in range(D)]
        self._pb_slot = PackedSlotMap()

        # --- device arrays ---------------------------------------- #
        nodes_s, pairs_s, pairs3_s = self._sharding()
        flags = np.zeros(n_pad, dtype=np.uint8)
        flags[: self.capacity] = self.flags
        recv = np.zeros(n_pad, dtype=np.int64)
        recv[: self.capacity] = self.recv_count
        self._dev_flags = jax.device_put(flags, nodes_s)
        self._dev_recv = jax.device_put(recv, nodes_s)
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
        # against what the device already holds.
        self._recv_synced = recv.copy()

        self._pair_log = PairLog()
        self._node_log = _NodeLog()
        self._wake_state = None
        self._pending_del_dst.clear()
        self._pending_fresh_dst.clear()
        self._dev_ready = True

    # ------------------------------------------------------------- #
    # Incremental device sync (O(churn) per wake)
    # ------------------------------------------------------------- #

    def _apply_pair_log(self) -> Optional[list]:
        """Fold pair transitions into the host plane; returns the bucket
        device-scatter batch, or None if the buckets overflowed (full
        rebuild required).  Deletions hitting the packed base mask its
        slot in place (host + queued device mask); deletions hitting the
        bucket free its column; inserts land in the bucket tier.

        Batched like IncrementalPallasLayout.apply_log (the net-effect
        argument and anomaly accounting live in slotmap.fold_log): slot
        lookups are one vectorized binary search per batch."""
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
            _, d = unpack_keys(np.concatenate([removes, cond_removes]))
            self._pending_del_dst.update(d.tolist())
            _, d = unpack_keys(inserts)
            self._pending_fresh_dst.update(d.tolist())
        writes: Dict[Tuple[int, int], Tuple[int, int]] = {}
        stacked = self._stacked

        def mask_base(packed: int) -> None:
            from ...ops import pallas_trace as pt

            shard = packed >> 40
            ri = (packed >> 8) & 0xFFFFFFFF
            col = packed & 0xFF
            stacked["row_pos"][shard, ri, col] = pt._PAD_ROW
            stacked["emeta"][shard, ri, col] = 0
            self._mask_writes.append((shard, ri, col))

        def free_slot_batch(karr: np.ndarray, found_is_anomaly: bool) -> None:
            bucket_vals = self._pb_slot.pop_batch(karr)
            missing = bucket_vals < 0
            base_vals = np.full(karr.size, -1, dtype=np.int64)
            if missing.any():
                base_vals[missing] = self._base_slot.pop_batch(karr[missing])
            for bval, sval in zip(bucket_vals.tolist(), base_vals.tolist()):
                if bval >= 0:
                    if found_is_anomaly:
                        self.stats["anomalies"] += 1
                    shard, colm = bval >> 32, bval & 0xFFFFFFFF
                    self._pb_src[shard, colm] = self._n_pad  # sink
                    self._pb_dst[shard, colm] = 0
                    self._pb_free[shard].append(colm)
                    writes[(shard, colm)] = (self._n_pad, 0)
                elif sval >= 0:
                    if found_is_anomaly:
                        self.stats["anomalies"] += 1
                    mask_base(sval)
                elif not found_is_anomaly:
                    self.stats["anomalies"] += 1

        if removes.size:
            free_slot_batch(removes, found_is_anomaly=False)
        if cond_removes.size:
            # insert-first/remove-last: net no-op unless the key was
            # already live (anomalous duplicate insert + real remove).
            free_slot_batch(cond_removes, found_is_anomaly=True)

        if inserts.size:
            present = (self._pb_slot.get_batch(inserts) >= 0) | (
                self._base_slot.get_batch(inserts) >= 0
            )
            srcs, dsts = unpack_keys(inserts)
            for key, src, dst, dup in zip(
                inserts.tolist(), srcs.tolist(), dsts.tolist(),
                present.tolist(),
            ):
                if dup:
                    self.stats["anomalies"] += 1
                    continue
                shard = dst // self._shard_size
                free = self._pb_free[shard]
                if free:
                    colm = free.pop()
                else:
                    colm = int(self._pb_count[shard])
                    if colm >= self._bucket_m:
                        return None  # bucket overflow
                    self._pb_count[shard] = colm + 1
                self._pb_slot.add(key, (shard << 32) | colm)
                self._pb_src[shard, colm] = src
                local = dst - shard * self._shard_size
                self._pb_dst[shard, colm] = local
                writes[(shard, colm)] = (src, local)
        self._pair_log.clear()
        return list(writes.items())

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
            kp = max(_SINK_PAD, _pow2(k))
            idx = np.full(kp, self._n_pad + 1, np.int32)  # OOB -> drop
            vals = np.zeros(kp, np.int32)
            idx[:k] = np.fromiter(w.keys(), np.int64, k)
            vals[:k] = np.fromiter(w.values(), np.int64, k)

            def build_jump():
                @partial(jax.jit, donate_argnums=(0,))
                def apply_jump(jp, idx, vals):
                    return jp.at[idx].set(vals, mode="drop")

                return apply_jump

            donated = self._jump_dev
            self._jump_dev = self._jit("jump", build_jump)(
                donated, idx, vals
            )
            if self.donation_audit:
                audit_donation("mesh.jump", donated)

    def _sync_device(self) -> None:
        if (
            not self._dev_ready
            or self._pair_log is None
            or self._n_pad < self.capacity
        ):
            self._full_rebuild()
            self._sync_jump_mirror()
            return
        pair_writes = self._apply_pair_log() if self._pair_log else []
        if pair_writes is None:
            self._full_rebuild()
            self._sync_jump_mirror()
            return
        import jax
        import jax.numpy as jnp

        if pair_writes:
            k = len(pair_writes)
            kp = max(_SINK_PAD, _pow2(k))
            shs = np.full(kp, self.n_devices, dtype=np.int32)  # OOB -> drop
            cols = np.zeros(kp, dtype=np.int32)
            srcs = np.zeros(kp, dtype=np.int32)
            dsts = np.zeros(kp, dtype=np.int32)
            for i, ((sh, colm), (s, d)) in enumerate(pair_writes):
                shs[i], cols[i], srcs[i], dsts[i] = sh, colm, s, d

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

        if self._mask_writes:
            # base-layout deletions: per-shard in-place masking
            D = self.n_devices
            rows_total = self._stacked["row_pos"].shape[1]
            per_shard: List[List[Tuple[int, int]]] = [[] for _ in range(D)]
            for shard, ri, colm in self._mask_writes:
                per_shard[shard].append((ri, colm))
            self._mask_writes = []
            k = max(_SINK_PAD, _pow2(max(len(p) for p in per_shard)))
            ri = np.full((D, k), rows_total, dtype=np.int32)  # OOB -> drop
            col = np.zeros((D, k), dtype=np.int32)
            for d in range(D):
                for i, (r, c) in enumerate(per_shard[d]):
                    ri[d, i] = r
                    col[d, i] = c
            self._dev_stacked["row_pos"], self._dev_stacked["emeta"] = (
                self._mask_fn(
                    self._dev_stacked["row_pos"],
                    self._dev_stacked["emeta"],
                    ri,
                    col,
                )
            )

        slots_arr = self._node_log.take()
        if slots_arr.size:
            # Bucket dirty slots by owning shard and run the sharded fold
            # (parallel/sharded_trace.make_sharded_fold): each device
            # scatter-applies only its own shard's rows — recv as deltas
            # against the synced mirror, flags as set/clear masks that
            # reproduce absolute assignment ((old | set) & ~clear = new).
            D = self.n_devices
            ss = self._shard_size
            shard = slots_arr // ss
            order = np.argsort(shard, kind="stable")
            slots_arr = slots_arr[order]
            shard = shard[order]
            counts = np.bincount(shard, minlength=D).astype(np.int64)
            m = max(_SINK_PAD, _pow2(int(counts.max(initial=1))))
            # per-shard local slot buckets, padded with the sink (= ss)
            lslot = np.full((D, m), ss, dtype=np.int32)
            rdelta = np.zeros((D, m), dtype=np.int64)
            fset = np.zeros((D, m), dtype=np.uint8)
            fclear = np.zeros((D, m), dtype=np.uint8)
            starts = np.zeros(D, dtype=np.int64)
            starts[1:] = np.cumsum(counts)[:-1]
            col = np.arange(slots_arr.size, dtype=np.int64) - starts[shard]
            new_flags = self.flags[slots_arr]
            new_recv = self.recv_count[slots_arr]
            lslot[shard, col] = (slots_arr - shard * ss).astype(np.int32)
            rdelta[shard, col] = new_recv - self._recv_synced[slots_arr]
            fset[shard, col] = new_flags
            fclear[shard, col] = ~new_flags
            self._recv_synced[slots_arr] = new_recv
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

        self._sync_jump_mirror()

    # ------------------------------------------------------------- #
    # Trace
    # ------------------------------------------------------------- #

    def _word_array(self, id_set: set):
        """Scatter an id set into the node-word array, sharded like the
        node arrays (word w of shard d covers nodes d*shard + 32w..).
        Empty sets (the quiet steady state) reuse one cached zero array
        instead of allocating + transferring per wake."""
        import jax

        nodes_s, _, _ = self._sharding()
        n_words = self._n_pad // 32
        if not id_set:
            z = getattr(self, "_zero_words", None)
            if z is None or z.shape[0] != n_words:
                z = self._zero_words = jax.device_put(
                    np.zeros(n_words, np.int32), nodes_s
                )
            return z
        words = np.zeros(n_words, dtype=np.uint32)
        ids = np.fromiter(id_set, np.int64, len(id_set))
        np.bitwise_or.at(
            words, ids >> 5, np.uint32(1) << (ids & 31).astype(np.uint32)
        )
        return jax.device_put(words.view(np.int32), nodes_s)

    def compute_marks(self) -> np.ndarray:
        self._note_device_wake()
        with self._device_call():
            self._sync_device()
            self.stats["wakes"] += 1
            meta = self._layout_meta
            if self.decremental:
                # One hold spans dispatch AND readback: exactly one
                # collective program is in flight at a time.
                with _MESH_COLLECTIVE_LOCK:
                    return self._compute_marks_decremental(meta)
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

    def _dispatch_decremental_wake(self, meta) -> tuple:
        """Dispatch one closure+repair wake on the mesh (regional
        re-derivation per shard, one word all_gather per sweep; a
        zeroed previous state — cold start, post-rebuild — is the full
        derivation).  State and suspects COMMIT at dispatch; an
        async-poisoned result surfaces at the first readback, where the
        caller invalidates so the next wake re-derives from zero state
        instead of feeding poisoned arrays forever."""
        import jax

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
            nodes_s, _, _ = self._sharding()
            z = jax.device_put(
                np.zeros(self._n_pad // 32, np.int32), nodes_s
            )
            self._wake_state = [z] * 5
        del_w = self._word_array(self._pending_del_dst)
        fresh_w = self._word_array(self._pending_fresh_dst)
        jump = (self._jump_dev,) if self._use_jump else ()
        out = wake(
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
        self._wake_state = list(out[1:])
        self._pending_del_dst.clear()
        self._pending_fresh_dst.clear()
        return out

    def _compute_marks_decremental(self, meta) -> np.ndarray:
        """One wake's dense marks, dispatched and read back under the
        caller's hold of the collective lock.  The wake's state was
        committed at dispatch, so a poisoned result, which surfaces at
        the readback, drops it: the next wake derives from nothing."""
        mark_dev = self._dispatch_decremental_wake(meta)[0]
        try:
            return _readback(mark_dev, "marks.mesh_harvest")[: self.capacity]
        except Exception:
            self.invalidate_wake_state()
            raise

    def invalidate_wake_state(self) -> None:
        """Drop the previous-fixpoint state (failed/poisoned wake): the
        next wake is a full derivation and pending suspects are moot."""
        self._wake_state = None
        self._pending_del_dst.clear()
        self._pending_fresh_dst.clear()
