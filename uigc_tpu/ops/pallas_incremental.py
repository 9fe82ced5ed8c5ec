"""Incremental pair layout for the Pallas trace: base + frozen + live.

The full packer (pallas_trace.prepare_chunks) lexsorts every live
propagation pair — O(E log E) host work.  Fine for a static benchmark
graph; on the live collector path it used to run before nearly every
wake, because any positive edge insertion invalidated the cached
layout.  At 10M actors / 30M edges that sort dwarfs
the kernel it feeds.

This module keeps the full pack off the per-wake path with three tiers:

- **Base.**  A dense packed layout built from the whole graph, rebuilt
  only when accumulated churn crosses ``repack_fraction`` of its size.
  Deletions mask the pair's slot in place with the inert ``_PAD_ROW``
  sentinel (the packer's ``want_slots`` map locates it in O(1)); the
  layout, spans and block count never change, so no recompile.
- **Frozen deltas.**  When the live tier overflows, its pairs are packed
  into a *compact* layout (only the supertiles they touch, so a small
  delta over a 10M-node space stays small) and appended to a chain.
  Frozen pairs are slot-mapped, so later deletions mask them the same
  way.  When the chain exceeds ``max_frozen`` it is consolidated into
  one compact layout — O(d log d) in the total delta, amortized.
- **Live tier.**  The newest insertions sit in an ordered dict and ride
  along as raw pair arrays propagated by an XLA scatter-max
  (pallas_trace.xla_tier): zero pack cost, zero recompiles (static
  pow2 capacity), O(capacity) device work per fixpoint iteration —
  cheap while the tier is small, which freezing guarantees.

Per-wake maintenance is therefore O(changes since last wake), plus an
amortized freeze/consolidate.  The trace launches the propagation
kernel once per packed tier and combines all contributions before
thresholding (pallas_trace.build_sweep_contribs), which is equivalent to
one layout holding the union of the pairs.

Pairs are keyed (src, dst, kind) where kind distinguishes refob edges
from supervisor pointers — the same (src, dst) node pair can legally
carry both (reference: ShadowGraph.java:224-268 treats them as separate
propagation reasons).

Semantics are covered by differential tests against trace_marks_np
(tests/test_pallas_incremental.py) at every mutation step.
"""

from __future__ import annotations

from time import perf_counter
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import pallas_trace as pt
from ..utils.validation import require
from .slotmap import (
    PackedSlotMap, PairLog, fold_log, pack_key, pack_keys, unpack_keys,
)

#: pair kinds
EDGE = 0
SUP = 1

Key = Tuple[int, int, int]


#: least padded length of the O(churn) device scatters, log2.  A wake's
#: writes are padded to a power of two, and every padded length is a
#: program of its own: with a low floor a served wake's few hundred to
#: few thousand writes (a round of short sessions) wander over half a
#: dozen lengths, and one first met after the warm-up compiles in the
#: middle of the traffic.  Up to this many they share one program; the
#: padding is dropped writes, microseconds on the device.
_SCATTER_PAD_LOG2 = 12


def _scatter_pad(k: int) -> int:
    """The padded length of a device scatter of ``k`` writes."""
    return 1 << max(_SCATTER_PAD_LOG2, int(k - 1).bit_length())


def _members(home: dict, keys: list) -> np.ndarray:
    """Which of ``keys`` (Python ints) ``home`` holds, probed in C."""
    return np.fromiter(map(home.__contains__, keys), bool, len(keys))


class IncrementalPallasLayout:
    """Mutable pair layout with O(changes) per-wake maintenance."""

    def __init__(
        self,
        n: int,
        s_rows: int = pt.S_ROWS,
        repack_fraction: float = 0.25,
        min_repack: int = 1 << 18,
        freeze_threshold: int = 1 << 14,
        max_frozen: int = 4,
        interpret: Optional[bool] = None,
        sub: Optional[int] = None,
        group: Optional[int] = None,
        mode: str = pt.MODE_AUTO,
        pull_density: float = pt.DEFAULT_PULL_DENSITY,
    ):
        self.n = n
        self.s_rows = s_rows
        #: propagation strategy (pallas_trace MODE_*, uigc.crgc.trace-mode)
        require(
            mode in pt.TRACE_MODES, "config.trace_mode",
            "bad trace mode", mode=mode, valid=pt.TRACE_MODES,
        )
        self.mode = mode
        self.pull_density = pull_density
        self.use_jump = mode in (pt.MODE_JUMP, pt.MODE_AUTO)
        # Pin the kernel walk geometry once: base and delta tiers must
        # agree (they share one trace), and a mid-life platform change
        # must not silently mix geometries.  Explicit sub/group override
        # the platform default (tests cover the wide geometry in
        # interpret mode this way).
        d_sub, d_group = pt.default_geometry(interpret)
        self.sub = d_sub if sub is None else sub
        self.group = d_group if group is None else group
        self.repack_fraction = repack_fraction
        self.min_repack = min_repack
        self.freeze_threshold = freeze_threshold
        self.max_frozen = max_frozen
        self.base: Optional[Dict[str, np.ndarray]] = None
        #: packed (src, dst, kind) key -> packed (row << 8 | col) into the
        #: base row_pos/emeta.  Sorted numpy bulk + churn overlays, so a
        #: rebuild stays vectorized and O(E) ints, not O(E) Python objects.
        self.base_slot = PackedSlotMap()
        #: frozen compact delta layouts
        self.frozen: List[Dict[str, np.ndarray]] = []
        #: packed key -> (frozen index, row, col); churn-bounded, plain dict
        self.frozen_slot: Dict[int, Tuple[int, int, int]] = {}
        #: newest insertions, not yet packed (ordered set of packed keys)
        self.pending: Dict[int, None] = {}
        #: masked (deleted-in-place) slots, tracked per home so frozen
        #: masks can be forgiven when consolidation rebuilds the chain
        self.masked_base = 0
        self.masked_frozen = 0
        self._xla_cap = 1 << 10
        #: min-source jump-parent array (n + 1,) for the jump/auto trace
        #: modes.  Invariant: jump_parent[d] is always a CURRENT live
        #: pair's source (or the sentinel n) — a stale pointer would let
        #: the jump sweep propagate marks across a deleted edge.
        #: Maintained O(1) per mutation: inserts fold in by minimum,
        #: removing the pair a pointer was built from invalidates it
        #: (best-effort: the next insert or rebuild re-derives).
        self.jump_parent = np.full(n + 1, n, dtype=np.int32)
        #: queued jump-parent device writes (dst -> final host value;
        #: last-wins dedup keeps the device scatter order-independent)
        self._jump_writes: Dict[int, int] = {}
        self._jump_dev = None
        self.stats = {
            "rebuilds": 0,
            "freezes": 0,
            "consolidations": 0,
            "pack_s": 0.0,
            "anomalies": 0,
            # apply_log's work, summed over calls: rows applied, distinct
            # keys after the fold, keys that went to the base map
            "log_rows": 0,
            "log_keys": 0,
            "base_lookups": 0,
        }
        #: device-resident mirrors (_device_args): mirror token -> dict of
        #: device arrays; plus per-prep masked-slot write queues so the
        #: mirror syncs in O(churn) instead of re-uploading the layout.
        #: Tokens are monotonically assigned and stamped into the prep
        #: dict — keying by id(prep) would serve a stale mirror when the
        #: allocator recycles a freed dict's address.
        self._dev_mirror: Dict[int, dict] = {}
        #: mirror token -> masked slots (packed ri<<8|col) not yet on the
        #: device, as the int64 arrays each batch left
        self._dev_writes: Dict[int, List[np.ndarray]] = {}
        self._dev_scatter = None
        self._mirror_next = 0

    # ----------------------------------------------------------------- #
    # Building
    # ----------------------------------------------------------------- #

    @staticmethod
    def pairs_from_graph(edge_src, edge_dst, edge_weight, supervisor):
        """(psrc, pdst, kinds) for all live propagation pairs."""
        live = edge_weight > 0
        psrc = edge_src[live].astype(np.int64)
        pdst = edge_dst[live].astype(np.int64)
        kinds = np.zeros(psrc.size, dtype=np.int64)
        sup_src = np.nonzero(supervisor >= 0)[0].astype(np.int64)
        if sup_src.size:
            psrc = np.concatenate([psrc, sup_src])
            pdst = np.concatenate([pdst, supervisor[sup_src].astype(np.int64)])
            kinds = np.concatenate([kinds, np.ones(sup_src.size, np.int64)])
        return psrc, pdst, kinds

    def rebuild(self, edge_src, edge_dst, edge_weight, supervisor) -> None:
        """Full repack from the graph arrays (the only O(E log E) step)."""
        t0 = perf_counter()
        psrc, pdst, kinds = self.pairs_from_graph(
            edge_src, edge_dst, edge_weight, supervisor
        )
        self.base = pt.prepare_pairs(
            psrc,
            pdst,
            self.n,
            s_rows=self.s_rows,
            pad_blocks_pow2=True,
            want_slots=True,
            sub=self.sub,
            group=self.group,
        )
        if self.use_jump:
            self.jump_parent = pt.jump_parents(psrc, pdst, self.n)
        self._jump_writes.clear()
        self._jump_dev = None
        slot_ri = self.base.pop("slot_ri")
        slot_col = self.base.pop("slot_col")
        self.base_slot = PackedSlotMap(
            pack_keys(psrc, pdst, kinds), (slot_ri << 8) | slot_col
        )
        self.frozen = []
        self.frozen_slot = {}
        self.pending.clear()
        self.masked_base = 0
        self.masked_frozen = 0
        self.stats["rebuilds"] += 1
        self.stats["pack_s"] += perf_counter() - t0

    def _freeze_pending(self) -> None:
        """Pack the live tier into a compact frozen layout."""
        t0 = perf_counter()
        keys = list(self.pending)
        m = len(keys)
        psrc, pdst = unpack_keys(np.fromiter(keys, np.int64, m))
        prep = pt.prepare_pairs(
            psrc,
            pdst,
            self.n,
            s_rows=self.s_rows,
            pad_blocks_pow2=True,
            want_slots=True,
            compact_supers=True,
            sub=self.sub,
            group=self.group,
        )
        slot_ri = prep.pop("slot_ri")
        slot_col = prep.pop("slot_col")
        fidx = len(self.frozen)
        self.frozen.append(prep)
        for key, ri, co in zip(keys, slot_ri, slot_col):
            self.frozen_slot[key] = (fidx, int(ri), int(co))
        self.pending.clear()
        self.stats["freezes"] += 1
        self.stats["pack_s"] += perf_counter() - t0

    def _consolidate_frozen(self) -> None:
        """Merge the frozen chain into one compact layout."""
        t0 = perf_counter()
        keys = list(self.frozen_slot)
        m = len(keys)
        if m == 0:
            self.frozen = []
            self.masked_frozen = 0
            self.stats["consolidations"] += 1
            return
        psrc, pdst = unpack_keys(np.fromiter(keys, np.int64, m))
        prep = pt.prepare_pairs(
            psrc,
            pdst,
            self.n,
            s_rows=self.s_rows,
            pad_blocks_pow2=True,
            want_slots=True,
            compact_supers=True,
            sub=self.sub,
            group=self.group,
        )
        slot_ri = prep.pop("slot_ri")
        slot_col = prep.pop("slot_col")
        self.frozen = [prep]
        self.frozen_slot = {
            key: (0, int(ri), int(co))
            for key, ri, co in zip(keys, slot_ri, slot_col)
        }
        # consolidation dropped every masked frozen slot
        self.masked_frozen = 0
        self.stats["consolidations"] += 1
        self.stats["pack_s"] += perf_counter() - t0

    # ----------------------------------------------------------------- #
    # Mutation (O(1) per changed pair)
    # ----------------------------------------------------------------- #

    def _jump_insert(self, src: int, dst: int) -> None:
        """Fold a new live pair into the jump-parent array (minimum
        wins, see jump_parents); O(1), queued for the device mirror."""
        if not self.use_jump or dst >= self.n or src >= self.n:
            return
        if src < self.jump_parent[dst]:
            self.jump_parent[dst] = src
            if self._jump_dev is not None:
                self._jump_writes[dst] = src

    def _jump_remove(self, src: int, dst: int) -> None:
        """Invalidate the jump parent if it was built from this pair.
        Conservative: another live pair with the same (src, dst) node
        ids (the other kind) may remain, but a spurious invalidation
        only costs acceleration, never soundness."""
        if not self.use_jump or dst >= self.n:
            return
        if self.jump_parent[dst] == src:
            self.jump_parent[dst] = self.n
            if self._jump_dev is not None:
                self._jump_writes[dst] = self.n

    def insert(self, src: int, dst: int, kind: int) -> None:
        key = pack_key(src, dst, kind)
        self._jump_insert(src, dst)
        if key in self.pending or key in self.frozen_slot or key in self.base_slot:
            # The graph layer only reports dead->live transitions, so a
            # duplicate means caller-side accounting drift; the pair is
            # already live here, which keeps the trace correct.
            self.stats["anomalies"] += 1
            return
        self.pending[key] = None

    def _queue_dev_writes(self, prep, packed: np.ndarray) -> None:
        """Record masked slots (packed ri<<8|col) for the device mirror."""
        tok = prep.get("_mirror_token")
        writes = self._dev_writes.get(tok) if tok is not None else None
        if writes is not None and packed.size:
            writes.append(packed)

    def _mask_frozen(self, slot: Tuple[int, int, int]) -> None:
        fidx, ri, col = slot
        prep = self.frozen[fidx]
        prep["row_pos"][ri, col] = pt._PAD_ROW
        prep["emeta"][ri, col] = 0
        self._queue_dev_writes(prep, np.array([(ri << 8) | col], np.int64))
        self.masked_frozen += 1

    def remove(self, src: int, dst: int, kind: int) -> None:
        key = pack_key(src, dst, kind)
        self._jump_remove(src, dst)
        if key in self.pending:
            del self.pending[key]
            return
        slot = self.frozen_slot.pop(key, None)
        if slot is not None:
            self._mask_frozen(slot)
            return
        packed = self.base_slot.pop(key)
        if packed is None:
            self.stats["anomalies"] += 1
            return
        self._mask_base_slots(np.array([packed], np.int64))

    def _mask_base_slots(self, vals: np.ndarray) -> int:
        """Mask base slots from packed (row << 8 | col) values (-1 =
        absent); returns how many were found."""
        vals = vals[vals >= 0]
        self.base["row_pos"][vals >> 8, vals & 0xFF] = pt._PAD_ROW
        self.base["emeta"][vals >> 8, vals & 0xFF] = 0
        self._queue_dev_writes(self.base, vals)
        self.masked_base += vals.size
        return vals.size

    def _outside_base(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Which of ``keys`` the live tier holds and which a frozen slot
        (two masks; a key has one home), each map probed key by key in C."""
        none = np.zeros(keys.size, dtype=bool)
        if not (self.pending or self.frozen_slot):
            return none, none
        klist = keys.tolist()
        return (
            _members(self.pending, klist) if self.pending else none,
            _members(self.frozen_slot, klist) if self.frozen_slot else none,
        )

    def _remove_keys(self, keys: np.ndarray) -> Tuple[int, int]:
        """Remove a batch of distinct keys (ascending) from wherever
        each lives; returns ``(found, absent)``.  Only the hits in the
        live tier and in a frozen slot are walked (a ``del`` each: 0.3
        ms for a churn wake's 10,000, half what ``map`` takes over the
        same); the rest go to the base map in one lookup."""
        if not keys.size:
            return 0, 0
        live, frozen = self._outside_base(keys)
        for k in keys[live].tolist():
            del self.pending[k]
        for k in keys[frozen].tolist():
            self._mask_frozen(self.frozen_slot.pop(k))
        base_keys = keys[~(live | frozen)]
        self.stats["base_lookups"] += base_keys.size
        found = self._mask_base_slots(self.base_slot.pop_batch(base_keys))
        return keys.size - base_keys.size + found, base_keys.size - found

    def apply_log(self, log) -> None:
        """Batched replay of a pair-transition log: a ``PairLog``, or any
        sequence of ``(insert?, src, dst, kind)`` tuples, which is turned
        into columns once.  Equivalent to calling insert/remove in order
        (including anomaly accounting for caller-side drift), with no
        interpreted step per row: the fold is a sort of the packed keys
        (slotmap.fold_log documents the net-effect argument), the live
        tier and the frozen slots are probed in C, and the base map is
        searched once per class of key, in key order."""
        ins, src, dst, kind = PairLog.of(log).columns()
        if not ins.size:
            return
        if self.use_jump:
            # Batched jump-parent maintenance (pt.fold_jump_log):
            # conservative about insert-and-remove-in-one-batch pairs,
            # so an insert-then-remove of the pair a pointer came from
            # always leaves it invalidated, exactly as sequential
            # insert()/remove() calls would.
            pt.fold_jump_log(
                self.jump_parent, ins, src, dst, self.n,
                self._jump_writes if self._jump_dev is not None else None,
            )
        removes, cond_removes, inserts, n_keys = fold_log(ins, src, dst, kind)
        stats = self.stats
        stats["log_rows"] += ins.size
        stats["log_keys"] += n_keys

        # a remove of what lives nowhere is caller drift
        stats["anomalies"] += self._remove_keys(removes)[1]
        # Insert-first/remove-last keys: net no-op unless the key was
        # already live (anomalous duplicate insert followed by a real
        # remove) — then remove it, like the sequential replay would.
        stats["anomalies"] += self._remove_keys(cond_removes)[0]

        if inserts.size:
            # Anomalous duplicate inserts are harmless for liveness
            # (contributions are OR'd) but tracked for diagnostics.
            live, frozen = self._outside_base(inserts)
            keys = inserts[~(live | frozen)]
            stats["base_lookups"] += keys.size
            keys = keys[self.base_slot.get_batch(keys) < 0]
            stats["anomalies"] += inserts.size - keys.size
            self.pending.update(dict.fromkeys(keys.tolist()))

    @property
    def churn(self) -> int:
        return (
            len(self.frozen_slot)
            + len(self.pending)
            + self.masked_base
            + self.masked_frozen
        )

    @property
    def needs_repack(self) -> bool:
        base_pairs = self.base["n_pairs"] if self.base is not None else 0
        return self.churn > max(
            self.min_repack, int(self.repack_fraction * base_pairs)
        )

    # ----------------------------------------------------------------- #
    # Trace
    # ----------------------------------------------------------------- #

    def prepare_wake(self) -> list:
        """The per-wake layout maintenance: freeze an overflowing live
        tier, consolidate an overlong frozen chain, and materialize the
        tier list for this trace.  Apart from the device-operand
        assembly so its host cost can be measured without launching the
        kernel (tools/pack_bench.py)."""
        assert self.base is not None, "rebuild() before a wake"
        if len(self.pending) > self.freeze_threshold:
            self._freeze_pending()
        if len(self.frozen) > self.max_frozen:
            self._consolidate_frozen()
        preps = [self.base] + self.frozen
        if self.pending:
            m = len(self.pending)
            while self._xla_cap < m:
                self._xla_cap *= 2
            psrc, pdst = unpack_keys(np.fromiter(self.pending, np.int64, m))
            preps.append(pt.xla_tier(psrc, pdst, self.n, self._xla_cap))
        return preps

    # ----------------------------------------------------------------- #
    # Device-resident operands (steady-state wake path on real hardware)
    # ----------------------------------------------------------------- #

    def _device_args(self, prep) -> list:
        """Device operands for one layout, from a mirror that lives on
        the device across wakes and syncs only the slots masked since the
        last sync (an O(churn) scatter, not an O(layout) re-upload)."""
        import jax

        if "xla_src" in prep:
            # the live tier is small and fully rebuilt per wake; let the
            # call transfer it
            return list(pt.device_args(prep))
        pid = prep.get("_mirror_token")
        if pid is None:
            pid = prep["_mirror_token"] = self._mirror_next
            self._mirror_next += 1
        mirror = self._dev_mirror.get(pid)
        if mirror is None:
            mirror = {
                k: jax.device_put(prep[k])
                for k in ("bmeta1", "bmeta2", "row_pos", "emeta")
            }
            if "super_ids" in prep:
                mirror["super_ids"] = jax.device_put(prep["super_ids"])
            self._dev_mirror[pid] = mirror
            self._dev_writes[pid] = []
        else:
            writes = self._dev_writes[pid]
            if writes:
                import jax.numpy as jnp
                from functools import partial

                if self._dev_scatter is None:

                    @partial(jax.jit, donate_argnums=(0, 1))
                    def _scatter(row_pos, emeta, rows, cols):
                        row_pos = row_pos.at[rows, cols].set(
                            pt._PAD_ROW, mode="drop"
                        )
                        emeta = emeta.at[rows, cols].set(0, mode="drop")
                        return row_pos, emeta

                    self._dev_scatter = _scatter
                packed = np.concatenate(writes)
                k = packed.size
                kp = _scatter_pad(k)
                rows = np.full(kp, prep["row_pos"].shape[0], dtype=np.int32)
                cols = np.zeros(kp, dtype=np.int32)
                rows[:k] = packed >> 8
                cols[:k] = packed & 0xFF
                mirror["row_pos"], mirror["emeta"] = self._dev_scatter(
                    mirror["row_pos"], mirror["emeta"], rows, cols
                )
                writes.clear()
        out = [
            mirror["bmeta1"],
            mirror["bmeta2"],
            mirror["row_pos"],
            mirror["emeta"],
        ]
        if "super_ids" in prep:
            out.append(mirror["super_ids"])
        return out

    def jump_device(self):
        """The device-resident jump-parent mirror, synced with the
        queued host writes (an O(churn) scatter, like the masked-slot
        mirrors — the parent array never re-uploads per wake)."""
        import jax

        if self._jump_dev is None:
            self._jump_dev = jax.device_put(self.jump_parent)
            self._jump_writes.clear()
        elif self._jump_writes:
            import jax.numpy as jnp
            from functools import partial

            if getattr(self, "_jump_scatter", None) is None:

                @partial(jax.jit, donate_argnums=(0,))
                def _jscatter(jp, idx, vals):
                    return jp.at[idx].set(vals, mode="drop")

                self._jump_scatter = _jscatter
            k = len(self._jump_writes)
            kp = _scatter_pad(k)
            idx = np.full(kp, self.n + 1, dtype=np.int32)  # pad = dropped
            vals = np.zeros(kp, dtype=np.int32)
            idx[:k] = np.fromiter(self._jump_writes.keys(), np.int64, k)
            vals[:k] = np.fromiter(self._jump_writes.values(), np.int64, k)
            self._jump_dev = self._jump_scatter(self._jump_dev, idx, vals)
            self._jump_writes.clear()
        return self._jump_dev

    def prepare_device_wake(self):
        """prepare_wake + device-operand assembly + mirror GC: the
        device-resident wake entry of the decremental tracer
        (ops/pallas_decremental.py).  Returns
        (preps, args) with the jump-parent mirror leading ``args`` for
        jump/auto-mode layouts."""
        preps = self.prepare_wake()
        args = []
        if self.use_jump:
            args.append(self.jump_device())
        for p in preps:
            args.extend(self._device_args(p))
        live_tokens = {
            p["_mirror_token"] for p in preps if "_mirror_token" in p
        }
        for pid in list(self._dev_mirror):
            if pid not in live_tokens:
                del self._dev_mirror[pid]
                self._dev_writes.pop(pid, None)
        return preps, args
