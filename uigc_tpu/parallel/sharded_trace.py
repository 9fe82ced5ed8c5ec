"""Multi-device shadow-graph trace: shard_map over a device mesh.

The TPU-native replacement for the reference's node-level sharding, where
each cluster node's collector owns a shadow-graph replica and gossips
DeltaGraphs to every peer (reference: LocalGC.scala:191-196).  On a TPU
slice we instead *partition* the graph across devices and let XLA
collectives do the replication work per trace wave:

- node feature arrays are sharded by supertile, dealt round-robin over
  the axis "gc" (``Partition``: supertile ``t`` of 4,096 slots belongs to
  shard ``t % D``), and lie on the devices in OWNER-MAJOR order, a shard's
  supertiles one after another;
- propagation pairs (ref edges with positive weight, plus supervisor
  pointers re-encoded as edges) are sharded by *destination*, so each
  device's scatter lands only in its own node shard;
- the mark vector is rebuilt each wave by ``all_gather`` over ICI, which
  is the collective analogue of the DeltaMsg broadcast, and interleaved
  back into slot order, so sources stay global slot ids;
- convergence is decided with a global ``psum`` of per-shard change bits.

Why not contiguous slot ranges: slots are handed out from 0 upward as
uids are interned and the capacity doubles as they arrive, so the actors
interned first, the live ones, lie in the lowest slots, and every slot
handed out before the last doubling in the lower half of the space: a
range partition gives them to the first shards and leaves the others
freed garbage (at 10M actors one shard ran 90% of a wake's kernel steps).
Ownership is therefore a function of the slot that does not change with
the capacity, and slot ids themselves do not change anywhere on the host.

The fold step (scatter-adding a batch of entry deltas into the sharded
arrays) rides the same mesh: deltas are bucketed by destination shard on
the host, then scatter-added device-side.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict

import numpy as np

from ..utils import events
from ..utils.validation import require

#: Process-wide cache of the small per-graph jitted helpers (the
#: sharded fold/mask scatters): every MeshShadowGraph over the same
#: device set shares ONE jit object instead of re-tracing its own —
#: the same sharing discipline as mesh.py's _SHARED_PROGRAM_CACHE —
#: and the compile-cache telemetry sees genuine 1-miss-then-hits
#: streams instead of a miss per graph (which would read as a storm).
#: Bounded by construction: one entry per (kind, device set, axis,
#: donate) ever seen.
_HELPER_CACHE: Dict[tuple, object] = {}


def _cached_helper(kind: str, mesh, axis: str, extra: tuple, build):
    key = (
        kind,
        tuple(d.id for d in mesh.devices.flat),
        tuple(mesh.axis_names),
        axis,
        extra,
    )
    fn = _HELPER_CACHE.get(key)
    hit = fn is not None
    if not hit:
        fn = _HELPER_CACHE[key] = build()
    if events.recorder.enabled:
        # Compile-cache plane (telemetry/device.py): one miss per
        # geometry is healthy; per-wake misses are the storm signal.
        events.recorder.commit(
            events.COMPILE, tag=f"sharded_{kind}",
            geom=events.compile_geom(key), hit=hit,
        )
    return fn


def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def build_mesh(n_devices: int, axis: str = "gc"):
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()[:n_devices]
    return Mesh(np.array(devices), (axis,))


def pad_to(x: np.ndarray, size: int, fill=0) -> np.ndarray:
    out = np.full(size, fill, dtype=x.dtype)
    out[: x.shape[0]] = x
    return out


#: slots a destination supertile at the kernel's default ``pt.S_ROWS``
#: (32 rows of 128 lanes): one 128-word row of the packed bit table
SUPER_SZ = 4096


@dataclass(frozen=True)
class Partition:
    """THE partition of the slot space over a mesh: who owns a slot and
    where it lies in its owner's shard.  Destination supertile ``t``
    (``super_sz`` slots: the unit of the kernel's destination gate and of
    the pull gate, and with 4,096 slots exactly one 128-word row of the
    packed bit table) belongs to shard ``t % n_devices``, and a shard
    holds its supertiles in ascending global order.  A function of the
    slot alone: it does not move when the capacity doubles, and actors in
    interning order (the live ones in the lowest slots) are dealt evenly.
    With one device the map is the identity.

    The methods are array arithmetic, for numpy and for traced jax arrays
    alike.  An array over the whole slot space is either in SLOT order
    (index = slot: everything on the host, and every gathered table) or
    OWNER-MAJOR (shard 0's elements, then shard 1's...: what is sharded
    over the mesh axis, so that each device holds its own)."""

    n_devices: int
    super_sz: int = SUPER_SZ

    def owner(self, slot):
        return (slot // self.super_sz) % self.n_devices

    def local(self, slot):
        """A slot's index within its owner's shard."""
        return (
            slot // (self.super_sz * self.n_devices) * self.super_sz
            + slot % self.super_sz
        )

    def global_of(self, shard, local):
        """The slot that is ``local`` on ``shard``: the inverse."""
        return (
            (local // self.super_sz * self.n_devices + shard) * self.super_sz
            + local % self.super_sz
        )

    def owner_major_index(self, slot, shard_size):
        """Where a slot's element lies in an owner-major array over
        shards of ``shard_size`` slots."""
        return self.owner(slot) * shard_size + self.local(slot)

    def owner_major(self, x, per: int = 1):
        """A flat array in slot order (``per`` slots an element: 32 for
        packed words) reordered owner-major: a row permutation."""
        if self.n_devices == 1:
            return x
        rows = x.reshape(-1, self.n_devices, self.super_sz // per)
        return rows.swapaxes(0, 1).reshape(-1)

    def slot_major(self, x, per: int = 1):
        """The inverse reorder: what the shards hold, laid end to end,
        back in slot order (the interleave every all-gather of a table
        ends in, and the verdict's way off the device)."""
        if self.n_devices == 1:
            return x
        rows = x.reshape(self.n_devices, -1, self.super_sz // per)
        return rows.swapaxes(0, 1).reshape(-1)

    def shard_rows(self, x, shard):
        """Shard ``shard``'s elements of a flat array in slot order, as
        the strided view they are (``shard`` may be a traced index)."""
        import jax

        rows = x.reshape(-1, self.n_devices, self.super_sz)
        return jax.lax.dynamic_index_in_dim(
            rows, shard, axis=1, keepdims=False
        ).reshape(-1)


def shard_graph(
    graph: Dict[str, np.ndarray], n_devices: int
) -> Dict[str, np.ndarray]:
    """Repack kernel arrays for an n-device mesh.

    Nodes are padded to whole supertiles a shard and laid out owner-major
    by ``Partition(n_devices)`` (round-robin supertiles, not contiguous
    slot ranges: the module text says why).  Propagation pairs
    (positive-weight edges + supervisor pointers) are bucketed by
    destination shard and padded to equal bucket sizes, yielding
    [n_devices, m] arrays sharded on the leading axis.
    """
    part = Partition(n_devices)
    n = graph["flags"].shape[0]
    chunk = n_devices * part.super_sz
    n_pad = ((n + chunk - 1) // chunk) * chunk

    flags = part.owner_major(pad_to(graph["flags"], n_pad))
    recv = part.owner_major(pad_to(graph["recv_count"], n_pad))

    live = graph["edge_weight"] > 0
    esrc = graph["edge_src"][live]
    edst = graph["edge_dst"][live]
    sup = graph["supervisor"]
    sup_src = np.nonzero(sup >= 0)[0].astype(np.int32)
    sup_dst = sup[sup_src].astype(np.int32)

    # Supervisor pointers become propagation pairs like the reference's
    # supervisor marking (reference: ShadowGraph.java:242-267).
    psrc = np.concatenate([esrc, sup_src])
    pdst = np.concatenate([edst, sup_dst])

    shard_size = n_pad // n_devices
    owner = part.owner(pdst)

    buckets_src = []
    buckets_dst = []
    max_m = 1
    for d in range(n_devices):
        sel = owner == d
        buckets_src.append(psrc[sel])
        buckets_dst.append(pdst[sel])
        max_m = max(max_m, int(sel.sum()))
    # Pad buckets with a self-loop on the sink (src = n_pad, handled by
    # the kernel's padded mark vector).
    src2 = np.full((n_devices, max_m), n_pad, dtype=np.int32)
    dst2 = np.full((n_devices, max_m), 0, dtype=np.int32)
    for d in range(n_devices):
        m = buckets_src[d].shape[0]
        src2[d, :m] = buckets_src[d]
        # local destination index within the shard
        dst2[d, :m] = part.local(buckets_dst[d])

    return {
        "flags": flags,
        "recv_count": recv,
        "pair_src": src2,
        "pair_dst": dst2,
        "n_pad": n_pad,
        "shard_size": shard_size,
    }


def _shard_map_unchecked(local_fn, mesh, in_specs, out_specs):
    """shard_map with the varying-mesh-axes check off: pallas_call does
    not propagate the annotation, and jax has no replication rule for
    the while fixpoint under shard_map."""
    from jax import shard_map

    return shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _seed_masks(flags, recv):
    """(in_use, halted, seed) bool vectors from the node features — the
    one seed definition every trace variant shares (reference semantics:
    ShadowGraph.java:205-220)."""
    from ..ops import trace as F

    in_use = (flags & F.FLAG_IN_USE) != 0
    halted = (flags & F.FLAG_HALTED) != 0
    seed = (
        ((flags & F.FLAG_ROOT) != 0)
        | ((flags & F.FLAG_BUSY) != 0)
        | (recv != 0)
        | ((flags & F.FLAG_INTERNED) == 0)
    )
    return in_use, halted, seed


def make_local_shard_ops(axis, part, words_pad, r_rows, n_pad, shard_size, jnp):
    """The per-shard word-space primitives shared by the mesh trace and
    the mesh decremental wake: local bool pack, global-table all_gather
    (the shards' supertile rows interleaved back into slot order by
    ``part``), and the packed-table source-bit gather.  One definition
    keeps the two fixpoints propagating identically per sweep."""
    import jax

    from ..ops import pallas_trace as pt

    shifts = jnp.arange(pt.WORD_BITS, dtype=jnp.int32)

    def pack_words(local_bool):
        return (
            local_bool.reshape(-1, pt.WORD_BITS).astype(jnp.int32)
            << shifts[None, :]
        ).sum(axis=1, dtype=jnp.int32)

    def gather_table(local_words):
        w_all = part.slot_major(
            jax.lax.all_gather(local_words, axis).reshape(-1),
            per=pt.WORD_BITS,
        )
        w_all = jnp.concatenate(
            [w_all, jnp.zeros((words_pad - w_all.shape[0],), jnp.int32)]
        )
        return w_all.reshape(r_rows, pt.LANE)

    def src_bits(table, src):
        """Global source active bits from the packed table; bucket
        padding uses src = n_pad (the sink), masked explicitly."""
        word = src >> 5
        w = table[word >> 7, word & 127]
        return (((w >> (src & 31)) & 1) > 0) & (src < n_pad)

    def make_sweep(propagate, bmeta1, bmeta2, row_pos, emeta, bsrc, bdst):
        """One propagation sweep into this shard: dst-gated packed
        blocks + the insert-bucket scatter-max tier.  A zero gate makes
        the gated kernel behave exactly like the plain one."""
        t_local = shard_size // pt.LANE

        def sweep_hits(table, d, l, gate):
            # The kernel's table operand is the full table over its new
            # bits; the mesh counts every set bit as new (the full table
            # in both halves): a block gathers every set bit of the
            # chunks it walks, and contracts only if it found one.
            tables = pt.walk_tables(table, jnp.zeros_like(table), jnp)
            contrib = propagate(
                d, l, gate, bmeta1, bmeta2, tables, row_pos, emeta
            )
            src_active = src_bits(table, bsrc)
            prop = (
                jnp.zeros((shard_size + 1,), jnp.int32)
                .at[bdst]
                .max(src_active.astype(jnp.int32))
            )
            return (contrib.reshape(t_local, pt.LANE) > 0) | (
                prop[:shard_size].reshape(t_local, pt.LANE) > 0
            )

        return sweep_hits

    def jump_local(table, trans_table, jump_j):
        """One pointer-jump propagation for this shard's nodes + one
        round of pointer doubling.  ``jump_j`` is the REPLICATED global
        min-source parent array (n_pad + 1,): the doubling runs
        identically on every shard (gathers through the replicated
        all-gathered tables), so no collective is needed to keep the
        parents coherent — the shard only takes its own destinations'
        rows (a strided view) for the propagation gather."""
        with pt.scope("jump"):  # the parts as pt.jump_sweep names them
            with pt.scope("hits"):
                j_loc = part.shard_rows(
                    jump_j[:n_pad], jax.lax.axis_index(axis)
                )
                hits = pt.bits_at(table, j_loc, n_pad, jnp)
            with pt.scope("double"):
                for _ in range(pt.JUMP_STEPS):
                    j2 = jump_j[jump_j]
                    can = pt.bits_at(trans_table, jump_j, n_pad, jnp) & (
                        j2 < n_pad
                    )
                    jump_j = jnp.where(can, j2, jump_j)
        return hits, jump_j

    return pack_words, gather_table, make_sweep, jump_local


def make_sharded_trace(mesh, axis: str = "gc"):
    """Build the jitted multi-device trace step over ``mesh``.

    Returns fn(flags, recv_count, pair_src, pair_dst) -> mark (bool[n_pad],
    in slot order) with flags/recv owner-major as ``shard_graph`` lays
    them (``Partition(n_devices)``), sharded on the mesh axis, and pair
    arrays sharded on their leading device axis.
    """
    jax, jnp = _jax()
    from jax.sharding import PartitionSpec as P

    part = Partition(mesh.devices.size)
    F = __import__("uigc_tpu.ops.trace", fromlist=["trace"])

    def local_trace(flags, recv, pair_src, pair_dst):
        # flags/recv: [shard_size] local node shard
        # pair_src:   [1, m] global source ids of pairs targeting this shard
        # pair_dst:   [1, m] local destination ids
        flags = flags.reshape(-1)
        recv = recv.reshape(-1)
        pair_src = pair_src.reshape(-1)
        pair_dst = pair_dst.reshape(-1)
        shard_size = flags.shape[0]

        in_use = (flags & F.FLAG_IN_USE) != 0
        halted = (flags & F.FLAG_HALTED) != 0
        seed = (
            ((flags & F.FLAG_ROOT) != 0)
            | ((flags & F.FLAG_BUSY) != 0)
            | (recv != 0)
            | ((flags & F.FLAG_INTERNED) == 0)
        )
        local_mark = in_use & (~halted) & seed

        # Replicated view needed for gathers by global source id.
        halted_all = part.slot_major(
            jax.lax.all_gather(halted, axis).reshape(-1)
        )

        def cond(carry):
            _, changed = carry
            return changed

        def body(carry):
            local_mark, _ = carry
            mark_all = part.slot_major(
                jax.lax.all_gather(local_mark, axis).reshape(-1)
            )
            mark_all = jnp.concatenate([mark_all, jnp.zeros((1,), bool)])
            halted_pad = jnp.concatenate([halted_all, jnp.zeros((1,), bool)])
            src_active = mark_all[pair_src] & (~halted_pad[pair_src])
            prop = (
                jnp.zeros((shard_size,), jnp.int32)
                .at[pair_dst]
                .max(src_active.astype(jnp.int32))
            )
            new_local = local_mark | ((prop > 0) & in_use)
            changed_local = jnp.any(new_local != local_mark)
            changed = jax.lax.psum(changed_local.astype(jnp.int32), axis) > 0
            return new_local, changed

        local_mark, _ = jax.lax.while_loop(
            cond, body, (local_mark, jnp.array(True))
        )
        return local_mark.reshape(1, -1)

    spec_nodes = P(axis)
    spec_pairs = P(axis, None)

    fn = _shard_map_unchecked(
        local_trace,
        mesh,
        (spec_nodes, spec_nodes, spec_pairs, spec_pairs),
        spec_pairs,
    )

    @jax.jit
    def traced(flags, recv, pair_src, pair_dst):
        return part.slot_major(fn(flags, recv, pair_src, pair_dst).reshape(-1))

    return traced


def pack_shard_layouts(
    psrc: np.ndarray,
    pdst: np.ndarray,
    n_pad: int,
    n_devices: int,
    s_rows: int = None,
    interpret: bool = None,
):
    """Pack propagation pairs into one Pallas layout per destination
    shard, equalized to a common block count and stacked on a leading
    device axis (SPMD: every shard runs the same program over its own
    blocks).

    Sources stay *global* ids — the kernel gathers them from the
    all-gathered packed bit table, which is in slot order — while
    destinations are shard-local (``Partition.local``: a shard's
    supertiles are every ``n_devices``-th of the graph's), so each
    device's one-hot contraction lands only in its own node shard
    (prepare_pairs ``n_src`` mode).

    Returns (stacked, meta, slot_vals): ``stacked`` holds [D, ...] arrays
    (bmeta1, bmeta2, row_pos, emeta); ``slot_vals`` gives each input
    pair's packed (shard << 40 | ri << 8 | col) slot for in-place
    deletion masking, aligned with the input pair order."""
    from ..ops import pallas_trace as pt

    if s_rows is None:
        s_rows = pt.S_ROWS
    sub, group = pt.default_geometry(interpret)
    super_sz = s_rows * pt.LANE
    shard_size = n_pad // n_devices
    assert n_pad % n_devices == 0 and shard_size % super_sz == 0, (
        "n_pad must split into shards of whole supertiles"
    )
    part = Partition(n_devices, super_sz)
    psrc = np.asarray(psrc, dtype=np.int64)
    pdst = np.asarray(pdst, dtype=np.int64)
    owner = part.owner(pdst)

    preps = []
    slot_vals = np.empty(psrc.size, dtype=np.int64)
    for d in range(n_devices):
        sel = np.nonzero(owner == d)[0]
        prep = pt.prepare_pairs(
            psrc[sel],
            part.local(pdst[sel]),
            shard_size,
            s_rows=s_rows,
            want_slots=True,
            n_src=n_pad,
            sub=sub,
            group=group,
        )
        slot_ri = prep.pop("slot_ri")
        slot_col = prep.pop("slot_col")
        slot_vals[sel] = (d << 40) | (slot_ri << 8) | slot_col
        preps.append(prep)

    n_blocks = pt._pad_blocks_target(max(p["n_blocks"] for p in preps))
    for p in preps:
        pt.pad_layout_blocks(p, n_blocks)

    stacked = {
        "bmeta1": np.stack([p["bmeta1"] for p in preps]),
        "bmeta2": np.stack([p["bmeta2"] for p in preps]),
        "row_pos": np.stack([p["row_pos"] for p in preps]),
        "emeta": np.stack([p["emeta"] for p in preps]),
    }
    meta = {
        "n_pad": n_pad,
        "shard_size": shard_size,
        "n_blocks": n_blocks,
        "r_rows": preps[0]["r_rows"],
        "s_rows": s_rows,
        "sub": sub,
        "group": group,
    }
    return stacked, meta, slot_vals


def shards_in_order(x) -> list:
    """The per-device pieces of an array sharded on its leading axis
    (node words, a counter with a shard a row), still on their devices,
    in shard order."""
    return [
        sh.data
        for sh in sorted(
            x.addressable_shards, key=lambda sh: sh.index[0].start or 0
        )
    ]


def shard_layout(stacked: dict, meta: dict, shard: int) -> dict:
    """Shard ``shard``'s layout of ``pack_shard_layouts`` as the packed
    layout ``prepare_pairs`` gave it (global sources, its own
    destinations, tiles numbered within the shard): what a reader that
    counts a kernel's work from a layout takes
    (``tools/sweep_profile.py simulate_sweeps``, which finds the shard's
    tiles in the whole graph's by ``tiles``, the strided list they are:
    ``Partition.global_of`` in supertiles)."""
    n_super = meta["shard_size"] // (meta["s_rows"] * 128)
    n_devices = stacked["bmeta1"].shape[0]
    prep = {key: stacked[key][shard] for key in ("bmeta1", "bmeta2", "row_pos", "emeta")}
    prep.update(
        n=meta["shard_size"], n_super=n_super,
        tiles=Partition(n_devices, 1).global_of(shard, np.arange(n_super)),
        **{key: meta[key] for key in ("n_blocks", "r_rows", "s_rows", "sub", "group")},
    )
    return prep


def _mesh_jump_policy(mesh, n_pad, n_blocks, sub, n_chunks, pull_cut):
    """``pt.auto_jump_policy`` for a sharded fixpoint, in the mesh's
    totals: the whole node space and every shard's ``n_blocks`` blocks,
    as one chip holding the graph would count them (block padding
    apart).  What a shard pays differs, since each walks only its own
    blocks while the pointer doublings are replicated, so on D chips a
    jump sweep is dearer than this price says, by up to D: ``auto`` then
    engages early by that factor, still within a bounded multiple of
    the best (no four-chip cell measures it; PERF.md section 7)."""
    from ..ops import pallas_trace as pt

    slots = mesh.devices.size * n_blocks * pt.ROWS * sub * pt.LANE
    return pt.auto_jump_policy(n_pad, slots, n_chunks, pull_cut)


def make_sharded_pallas_trace(
    mesh,
    n_pad: int,
    shard_size: int,
    n_blocks: int,
    r_rows: int,
    s_rows: int,
    bucket_m: int,
    interpret: bool = None,
    axis: str = "gc",
    sub: int = None,
    group: int = None,
    mode: str = None,
    pull_density: float = None,
    with_stats: bool = False,
):
    """The mesh trace with the Pallas propagation kernel per shard.

    Per fixpoint wave each device packs its local active bits into words,
    ``all_gather``s the packed table over ICI (32x less traffic than
    gathering bools), runs the propagation kernel over its own packed
    blocks with the dirty-chunk lists, and adds an XLA scatter-max tier
    for its insert bucket ([1, bucket_m] per shard, global src ids, local
    dst).  The dirty-chunk diff is computed on the *global* table, so the
    convergence decision is replicated — no psum needed.

    ``mode`` (pallas_trace MODE_*, default push) adds the sharded forms
    of the direction-optimizing machinery: jump/auto take one extra
    trailing operand — the replicated (n_pad + 1,) jump-parent array —
    and pull/auto skip blocks whose local destination supertile is
    saturated (the pull decision and the dirty density are both derived
    from replicated tables, so every shard agrees on the sweep plan).
    auto engages the jump by the single-device rule on the same
    replicated dirty count (``pt.auto_jump_policy`` over the mesh's
    whole node space and all shards' slots), so the shards agree on that
    too, and a graph engages on the same sweep on one chip and on four.

    fn(flags, recv, bmeta1, bmeta2, row_pos, emeta, bsrc, bdst[, jump_j])
    -> mark (in slot order) with flags/recv owner-major
    (``Partition(D, s_rows * 128).owner_major``), sharded on the mesh
    axis, layout operands sharded on their leading device axis, jump_j
    replicated, in slot order.  With ``with_stats`` it returns
    (mark, {"n_sweeps", "jump_sweeps"}).
    """
    jax, jnp = _jax()
    from jax.sharding import PartitionSpec as P

    from ..ops import pallas_trace as pt

    if interpret is None:
        interpret = pt.default_interpret()
    if sub is None or group is None:
        d_sub, d_group = pt.default_geometry(interpret)
        sub = d_sub if sub is None else sub
        group = d_group if group is None else group
    if mode is None:
        mode = pt.MODE_PUSH
    if pull_density is None:
        pull_density = pt.DEFAULT_PULL_DENSITY
    require(
        mode in pt.TRACE_MODES, "config.trace_mode",
        "bad trace mode", mode=mode, valid=pt.TRACE_MODES,
    )
    use_jump = mode in (pt.MODE_JUMP, pt.MODE_AUTO)
    use_pull = mode in (pt.MODE_PULL, pt.MODE_AUTO)
    super_sz = s_rows * pt.LANE
    part = Partition(mesh.devices.size, super_sz)
    n_super_shard = shard_size // super_sz
    sup_words = s_rows * (pt.LANE // pt.WORD_BITS)
    # dst-gated kernel with a constant zero gate == the plain kernel;
    # using it here keeps ONE kernel build shared with the decremental
    # wake (which passes a real gate on its repair sweep).
    propagate = pt.build_propagate(
        n_blocks, n_super_shard, r_rows, s_rows, interpret,
        sub=sub, group=group, dst_gate=True,
    )
    group_rows = pt.ROWS * group
    n_chunks = r_rows // group_rows
    words_pad = r_rows * pt.LANE
    pull_cut = max(1, int(round(pull_density * n_chunks)))
    auto_jump = _mesh_jump_policy(
        mesh, n_pad, n_blocks, sub, n_chunks, pull_cut
    )

    def local_trace(flags, recv, bmeta1, bmeta2, row_pos, emeta, bsrc,
                    bdst, *rest):
        flags = flags.reshape(-1)
        recv = recv.reshape(-1)
        bmeta1 = bmeta1.reshape(-1)
        bmeta2 = bmeta2.reshape(-1)
        row_pos = row_pos.reshape(-1, pt.LANE)
        emeta = emeta.reshape(-1, pt.LANE)
        bsrc = bsrc.reshape(-1)
        bdst = bdst.reshape(-1)
        jump_j0 = rest[0] if use_jump else None

        in_use, halted, seed = _seed_masks(flags, recv)
        mark0 = in_use & (~halted) & seed

        pack_words, gather_table, make_sweep, jump_local = (
            make_local_shard_ops(
                axis, part, words_pad, r_rows, n_pad, shard_size, jnp
            )
        )
        sweep_hits = make_sweep(
            propagate, bmeta1, bmeta2, row_pos, emeta, bsrc, bdst
        )
        zero_gate = jnp.zeros((n_super_shard,), jnp.int32)

        def dirty_chunks(table, table_prev):
            return pt.dirty_group_lists(
                table, table_prev, n_chunks, group_rows, jnp
            )

        def cond(carry):
            return carry[-1]

        iu_w = pack_words(in_use)
        nh_w = pack_words(~halted)
        # replicated transparency table for the pointer doubling
        trans_table = (
            gather_table(iu_w & nh_w) if use_jump else None
        )

        def run_jump(mark_w, table, jump_j):
            jh, jump_j = jump_local(table, trans_table, jump_j)
            return mark_w | (pack_words(jh) & iu_w), jump_j

        def body(carry):
            mark_w, table, d, l, jump_j, jump_state, counts, _ = carry
            if use_pull:
                sat = pt.saturated_tiles(
                    mark_w, iu_w, n_super_shard, sup_words, jnp
                )
                if mode == pt.MODE_AUTO:
                    pull_on = d[n_chunks] >= pull_cut
                else:
                    pull_on = jnp.array(True)
                gate = jnp.where(pull_on, sat * pt.GATE_SKIP, zero_gate)
            else:
                gate = zero_gate
            hits2d = sweep_hits(table, d, l, gate)
            new_mark_w = mark_w | (pt.pack_hits_words(hits2d, jnp) & iu_w)
            if use_jump:
                new_mark_w, jump_j, jump_state = pt.jump_step(
                    mode, auto_jump, jump_state, d[n_chunks], run_jump,
                    new_mark_w, table, jump_j,
                )
            new_table = gather_table(new_mark_w & nh_w)
            d2, l2, changed = dirty_chunks(new_table, table)
            # [sweeps, sweeps that ran the jump]
            counts = counts + jnp.stack([jnp.array(True), jump_state[0]])
            return (new_mark_w, new_table, d2, l2, jump_j, jump_state,
                    counts, changed)

        mark_w0 = pack_words(mark0)
        table0 = gather_table(mark_w0 & nh_w)
        d0, l0, changed0 = dirty_chunks(table0, jnp.zeros_like(table0))
        jj0 = (
            jump_j0.reshape(-1).astype(jnp.int32)
            if use_jump
            else jnp.zeros((1,), jnp.int32)
        )
        mark_w, _, _, _, _, _, counts, _ = jax.lax.while_loop(
            cond, body,
            (mark_w0, table0, d0, l0, jj0, pt.jump_state0(mode, jnp),
             jnp.zeros((2,), jnp.int32), changed0),
        )
        shifts = jnp.arange(pt.WORD_BITS, dtype=jnp.int32)
        bits = (mark_w[:, None] >> shifts[None, :]) & 1
        return (bits.reshape(-1) > 0).reshape(1, -1), counts

    spec_nodes = P(axis)
    spec_dev = P(axis, None)
    spec_dev3 = P(axis, None, None)

    in_specs = (
        spec_nodes,
        spec_nodes,
        spec_dev,
        spec_dev,
        spec_dev3,
        spec_dev3,
        spec_dev,
        spec_dev,
    )
    if use_jump:
        in_specs = in_specs + (P(),)  # replicated jump parents
    fn = _shard_map_unchecked(
        local_trace, mesh, in_specs, (spec_dev, P())
    )

    @jax.jit
    def traced(*args):
        mark, counts = fn(*args)
        mark = part.slot_major(mark.reshape(-1))
        if not with_stats:
            return mark
        return mark, {"n_sweeps": counts[0], "jump_sweeps": counts[1]}

    return traced


def make_sharded_mask(mesh, axis: str = "gc"):
    """Per-shard deletion masking for the stacked packed layouts: scatter
    the inert sentinel into (ri, col) slots of each shard's row_pos/emeta
    (the device half of IncrementalPallasLayout-style in-place deletes).
    Buffers are donated — per wake this is an O(churn) in-place scatter.

    fn(row_pos, emeta, ri, col) with row_pos/emeta [D, nb*8, LANE] and
    ri/col [D, k] (ri padded with nb*8 = dropped)."""
    jax, jnp = _jax()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..ops import pallas_trace as pt

    def local_mask(row_pos, emeta, ri, col):
        rp = row_pos.reshape(row_pos.shape[1], row_pos.shape[2])
        em = emeta.reshape(emeta.shape[1], emeta.shape[2])
        r = ri.reshape(-1)
        c = col.reshape(-1)
        rp = rp.at[r, c].set(pt._PAD_ROW, mode="drop")
        em = em.at[r, c].set(0, mode="drop")
        return rp[None], em[None]

    def build():
        fn = shard_map(
            local_mask,
            mesh=mesh,
            in_specs=(P(axis, None, None), P(axis, None, None), P(axis, None), P(axis, None)),
            out_specs=(P(axis, None, None), P(axis, None, None)),
        )

        @partial(jax.jit, donate_argnums=(0, 1))
        def mask(row_pos, emeta, ri, col):
            return fn(row_pos, emeta, ri, col)

        return mask

    return _cached_helper("mask", mesh, axis, (), build)


def make_sharded_fold(mesh, axis: str = "gc", donate: bool = False):
    """Build the jitted multi-device fold step: scatter a batch of entry
    deltas (recv-count deltas + flag overwrites, bucketed by node shard on
    host) into the sharded node arrays.  The device-side analogue of
    mergeEntry's node updates (reference: ShadowGraph.java:75-83).

    Contract: slots within one batch must be UNIQUE per shard — the host
    bucketing must pre-combine multiple entries for the same actor (sum
    recv deltas, keep the last flag set/clear pair), because the flag
    scatter reads the pre-batch value once and duplicate-index scatter
    order is undefined.  recv uses `.at[].add` and would compose, but the
    flag path would not.

    ``donate=True`` donates the flags/recv buffers so a steady-state
    caller (the live mesh backend, per wake) updates its device arrays in
    place instead of copying the whole sharded state per fold."""
    jax, jnp = _jax()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def local_fold(flags, recv, slot, recv_delta, flag_set, flag_clear):
        flags = flags.reshape(-1)
        recv = recv.reshape(-1)
        slot = slot.reshape(-1)  # local slot ids, padded with shard_size
        recv_delta = recv_delta.reshape(-1)
        flag_set = flag_set.reshape(-1)
        flag_clear = flag_clear.reshape(-1)
        size = flags.shape[0]
        flags_pad = jnp.concatenate([flags, jnp.zeros((1,), flags.dtype)])
        recv_pad = jnp.concatenate([recv, jnp.zeros((1,), recv.dtype)])
        recv_pad = recv_pad.at[slot].add(recv_delta)
        old = flags_pad[slot]
        flags_pad = flags_pad.at[slot].set((old | flag_set) & (~flag_clear))
        return flags_pad[:size].reshape(1, -1), recv_pad[:size].reshape(1, -1)

    def build():
        fn = shard_map(
            local_fold,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis, None), P(axis, None), P(axis, None), P(axis, None)),
            out_specs=(P(axis, None), P(axis, None)),
        )

        @partial(jax.jit, donate_argnums=(0, 1) if donate else ())
        def fold(flags, recv, slot, recv_delta, flag_set, flag_clear):
            f2, r2 = fn(flags, recv, slot, recv_delta, flag_set, flag_clear)
            return f2.reshape(-1), r2.reshape(-1)

        return fold

    return _cached_helper("fold", mesh, axis, (donate,), build)


def make_sharded_verdict(mesh, super_sz: int = SUPER_SZ, axis: str = "gc"):
    """The sharded wake's verdict on its way off the device: the mesh's
    form of ``pallas_decremental.verdict_reduce``.

    fn(mark_w, iu_w) -> (garbage_w, marked): the wake's owner-major word
    arrays reduced to ``iu_w & ~mark_w`` and interleaved back into SLOT
    order (``Partition.slot_major``, a 1/8-byte-a-slot row permutation
    across the shards), and the number of marks.  ``garbage_w`` stays
    sharded on the mesh axis: each device holds the verdict words of a
    D-th of the slot space, in slot order, and laid end to end they are
    the whole verdict by slot, as the sweep takes it."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..ops import pallas_trace as pt

    part = Partition(mesh.devices.size, super_sz)

    def build():
        @partial(
            jax.jit,
            out_shardings=(
                NamedSharding(mesh, P(axis)), NamedSharding(mesh, P()),
            ),
        )
        def verdict(mark_w, iu_w):
            return (
                part.slot_major(iu_w & ~mark_w, per=pt.WORD_BITS),
                jax.lax.population_count(mark_w).sum(),
            )

        return verdict

    return _cached_helper("verdict", mesh, axis, (super_sz,), build)


def make_sharded_decremental_wake(
    mesh,
    n_pad: int,
    shard_size: int,
    n_blocks: int,
    r_rows: int,
    s_rows: int,
    bucket_m: int,
    interpret: bool = None,
    axis: str = "gc",
    sub: int = None,
    group: int = None,
    mode: str = None,
    pull_density: float = None,
):
    """The decremental wake (suspect closure + destination-gated repair,
    ops/pallas_decremental.py ``_build_wake_fn``) on the sharded data
    plane: the same three phases, the same policies and the same
    counters, per shard, with one packed-word all_gather over ICI per
    sweep.

    fn(flags, recv, del_w, fresh_w, prev_mark_w, prev_seed_w,
       prev_halted_w, prev_iu_w, prev_active_w, prev_walks,
       bmeta1, bmeta2, row_pos, emeta, bsrc, bdst[, jump_j])
      -> (mark_w, seed_w, halted_w, iu_w, active_w, walks, stats)

    flags/recv owner-major (``Partition(D, s_rows * 128)``: a shard's
    supertiles are every D-th of the slot space, dealt round-robin, not a
    contiguous slot range, so that actors in interning order are divided
    evenly whatever the capacity has grown to), sharded on the mesh axis;
    every *_w operand and result is the flat word array (n_pad/32 ints)
    in the same order (a supertile is whole words), sharded alike;
    ``prev_walks`` / ``walks`` is the replicated int32 scalar of the
    one-chip wake (the chunk walks of the last derivation from nothing,
    what the closure's price is a share of); layout operands as in
    make_sharded_pallas_trace.  The verdict is the words: a slot is
    garbage iff its bit of ``iu_w & ~mark_w`` is set
    (``make_sharded_verdict``, which puts them back in slot order on
    their way off the device).

    What a shard shares with the one-chip program is the code: the sweep
    over its layouts is ``pt.build_sweep_contribs`` (its packed blocks
    as a dense layout, its insert bucket as an xla tier), the kernel's
    table operand ``pt.walk_tables`` (the bits new since the sweep
    before), the dirty lists, the pull gate, the jump step and both
    policies (``pt.closure_gives_up``, ``pt.auto_jump_policy``) the
    ``pt.*`` helpers.  What differs is where a table comes from: a
    shard packs its own words and ``gather_table`` all-gathers them and
    interleaves the shards' rows into slot order (scope ``gather``), so
    a block's source span, the dirty chunk lists and both policies see
    the table one chip would hold.  Every loop decision is taken on values all
    shards hold alike, so every shard leaves a loop in the same sweep
    and no collective is left waiting: the dirty lists, ``changed``, the
    closure's ``spent`` and the repair's ``walks`` are counted on the
    GATHERED table, the cold road on the gathered previous table and on
    ``closure_bailed``; the one decision that hangs on per-shard state,
    whether any shard has a tile to force, is a ``psum`` (scope
    ``agree``).  A zeroed previous state takes the cold road, as on one
    chip: the derivation from the seeds, ungated.  ``mode`` applies to
    the repair fixpoint as in the one-chip wake: jump/auto take the
    replicated jump-parent operand, pull/auto skip saturated local
    supertiles.

    ``stats`` is the one-chip wake's dict (``_build_wake_fn``'s
    docstring names the keys) with a leading shard axis on every value:
    ``(D,)`` for the scalars, ``(D, pt.MAX_SWEEP_STATS)`` for the
    per-sweep vectors.  The kernel's counters (``kernel_steps``,
    ``kernel_contractions``, ``kernel_chunk_walks``, ``kernel_walk_trips``,
    ``kernel_steps_full``), ``gated_tiles`` and ``tiles_skipped`` are a
    shard's own; the others read alike on every shard.  One key is the
    mesh's: ``gathers``, the all-gathers of a word table the wake made
    (one a sweep of either loop, and the fixed ones around them).
    """
    jax, jnp = _jax()
    from jax.sharding import PartitionSpec as P

    from ..ops import pallas_decremental as pd
    from ..ops import pallas_trace as pt

    if interpret is None:
        interpret = pt.default_interpret()
    if sub is None or group is None:
        d_sub, d_group = pt.default_geometry(interpret)
        sub = d_sub if sub is None else sub
        group = d_group if group is None else group
    if mode is None:
        mode = pt.MODE_PUSH
    if pull_density is None:
        pull_density = pt.DEFAULT_PULL_DENSITY
    require(
        mode in pt.TRACE_MODES, "config.trace_mode",
        "bad trace mode", mode=mode, valid=pt.TRACE_MODES,
    )
    use_jump = mode in (pt.MODE_JUMP, pt.MODE_AUTO)
    use_pull = mode in (pt.MODE_PULL, pt.MODE_AUTO)
    super_sz = s_rows * pt.LANE
    part = Partition(mesh.devices.size, super_sz)
    n_super_shard = shard_size // super_sz
    # a shard's layouts, as the one-chip sweep takes them: its packed
    # blocks (global sources, local destinations) and its insert bucket
    specs = (("dense", n_blocks, sub, group), ("xla", bucket_m))
    gated = pt.build_layout_propagates(
        specs, n_super_shard, r_rows, s_rows, interpret, dst_gate=True
    )
    group_rows = pt.ROWS * group
    n_chunks = r_rows // group_rows
    words_pad = r_rows * pt.LANE
    sup_words = s_rows * (pt.LANE // pt.WORD_BITS)
    pull_cut = max(1, int(round(pull_density * n_chunks)))
    auto_jump = _mesh_jump_policy(
        mesh, n_pad, n_blocks, sub, n_chunks, pull_cut
    )

    def local_wake(*args):
        with pt.scope(pd.WAKE_SCOPE):
            return wake_body(*args)

    def wake_body(flags, recv, del_w, fresh_w, p_mark, p_seed, p_halt,
                  p_iu, p_active, p_walks, bmeta1, bmeta2, row_pos, emeta,
                  bsrc, bdst, *rest):
        jump_j0 = rest[0] if use_jump else None
        flags = flags.reshape(-1)
        recv = recv.reshape(-1)
        del_w = del_w.reshape(-1)
        fresh_w = fresh_w.reshape(-1)
        p_mark = p_mark.reshape(-1)
        p_seed = p_seed.reshape(-1)
        p_halt = p_halt.reshape(-1)
        p_iu = p_iu.reshape(-1)
        p_active = p_active.reshape(-1)
        layout_args = (
            bmeta1.reshape(-1), bmeta2.reshape(-1),
            row_pos.reshape(-1, pt.LANE), emeta.reshape(-1, pt.LANE),
            bsrc.reshape(-1), bdst.reshape(-1),
        )

        pack_words, gather_table, _, jump_local = make_local_shard_ops(
            axis, part, words_pad, r_rows, n_pad, shard_size, jnp
        )
        # the sink of a bucket's padding is src = n_pad, which the xla
        # tier masks by ``src < n``
        gated_sweep = pt.build_sweep_contribs(
            specs, gated, n_pad, n_super_shard, s_rows, jnp
        )

        def gather(words):
            with pt.scope("gather"):
                return gather_table(words)

        def contribs(table, table_prev, d, l, gate):
            """One sweep into this shard (``_build_wake_fn``'s
            ``contribs``): its hit plane (t_local, LANE), the grid steps
            its kernel took, those that contracted, the chunk-iterations
            walked and the trips they were walked in."""
            return gated_sweep.with_steps(
                pt.walk_tables(table, table_prev, jnp), d, l, layout_args,
                gate=gate,
            )

        def pack_hits(hits2d):
            with pt.scope("hits"):
                return pt.pack_hits_words(hits2d, jnp)

        def dirty_chunks(table, table_prev):
            return pt.dirty_group_lists(
                table, table_prev, n_chunks, group_rows, jnp
            )

        with pt.scope("pack"):
            in_use, halted, seed = _seed_masks(flags, recv)
            iu_w = pack_words(in_use)
            halted_w = pack_words(halted)
            nh_w = pack_words(~halted)
            seed_w = pack_words(in_use & (~halted) & seed)
        zero_gate = jnp.zeros((n_super_shard,), jnp.int32)
        zero_i = jnp.zeros((), jnp.int32)

        def per_super(words):
            return (
                words.reshape(n_super_shard, sup_words)
                .any(axis=1)
                .astype(jnp.int32)
            )

        # --- 1. suspect seeds (shard-local) ------------------------- #
        with pt.scope("suspects"):
            s_w = (
                (~iu_w)
                | (halted_w & ~p_halt)
                | (p_seed & ~seed_w)
                | del_w
            ) & p_mark

        # --- 2. closure: marks that depended on a suspect ----------- #
        # Priced as on one chip, on a count every shard holds alike: the
        # dirty chunks of the gathered table (pt.closure_gives_up).
        def c_cond(carry):
            return carry["changed"] & ~pt.closure_gives_up(
                carry["spent"], p_walks
            )

        def c_body(carry):
            table, d, l = carry["table"], carry["d"], carry["l"]
            hits2d, took, did, iters, trips = contribs(
                table, carry["table_prev"], d, l, zero_gate
            )
            new_closure = carry["closure"] | (pack_hits(hits2d) & p_mark)
            new_table = gather(new_closure)
            d2, l2, changed = dirty_chunks(new_table, table)
            return {
                "closure": new_closure, "table": new_table,
                "table_prev": table, "d": d2, "l": l2, "changed": changed,
                "sweeps": carry["sweeps"] + 1,
                "spent": carry["spent"] + d[n_chunks],
                "steps": carry["steps"] + took,
                "contracted": carry["contracted"] + did,
                "walked": carry["walked"] + iters,
                "tripped": carry["tripped"] + trips,
            }

        with pt.scope("closure"):
            c_table0 = gather(s_w)
            zero_t = jnp.zeros_like(c_table0)
            cd0, cl0, cch0 = dirty_chunks(c_table0, zero_t)
            closed = jax.lax.while_loop(c_cond, c_body, {
                "closure": s_w, "table": c_table0, "table_prev": zero_t,
                "d": cd0, "l": cl0, "changed": cch0, "sweeps": zero_i,
                "spent": zero_i, "steps": zero_i, "contracted": zero_i,
                "walked": zero_i, "tripped": zero_i,
            })
            closure_w = closed["closure"]
            closure_bailed = closed["changed"]
            # The cold road, as on one chip: the closure said by its cost
            # that the region is everything, or there is no previous
            # fixpoint.  Both from gathered tables: every shard agrees.
            prev_table = gather(p_active)
            cold = closure_bailed | ~prev_table.any()

        with pt.scope("gate"):
            suspect_g = jnp.where(
                cold,
                zero_gate,
                per_super(closure_w)
                | per_super(fresh_w)
                | per_super(iu_w & ~p_iu),
            )

        # --- 3. repair fixpoint ------------------------------------- #
        def r_cond(carry):
            return carry["changed"]

        def run_jump(mark_w, table, jump_j):
            jh, jump_j = jump_local(table, trans_table, jump_j)
            with pt.scope("jump"), pt.scope("pack"):
                return mark_w | (pack_words(jh) & iu_w), jump_j

        def r_body(carry):
            mark_w, table = carry["mark"], carry["table"]
            d, l = carry["d"], carry["l"]
            n_dirty = d[n_chunks]
            # Gate composition as in the one-chip wake.  Both inputs to
            # the pull decision, the dirty density (the gathered table's
            # diff) and the saturation of LOCAL tiles, are replicated or
            # own-shard state: the shards agree on the sweep plan.
            base_gate = jnp.where(carry["use_gate"], suspect_g, zero_gate)
            if use_pull:
                sat = pt.saturated_tiles(
                    mark_w, iu_w, n_super_shard, sup_words, jnp
                )
                if mode == pt.MODE_AUTO:
                    pull_on = n_dirty >= pull_cut
                else:
                    pull_on = jnp.array(True)
                gate = jnp.where(pull_on & (sat > 0), pt.GATE_SKIP,
                                 base_gate)
            else:
                sat = None
                pull_on = jnp.array(False)
                gate = base_gate
            hits2d, took, did, iters, trips = contribs(
                table, carry["table_prev"], d, l, gate
            )
            new_mark = mark_w | (pack_hits(hits2d) & iu_w)
            if use_jump:
                new_mark, jump_j, jump_state = pt.jump_step(
                    mode, auto_jump, carry["jump_state"], n_dirty,
                    run_jump, new_mark, table, carry["jump"],
                )
            new_table = gather(new_mark & nh_w)
            d2, l2, changed = dirty_chunks(new_table, table)
            i = jnp.minimum(carry["sweep_i"], pt.MAX_SWEEP_STATS - 1)
            out = dict(carry, mark=new_mark, table=new_table,
                       table_prev=table, d=d2, l=l2,
                       use_gate=jnp.array(False), changed=changed,
                       sweep_i=carry["sweep_i"] + 1,
                       walks=carry["walks"] + n_dirty,
                       steps=carry["steps"] + took,
                       contracted=carry["contracted"] + did,
                       walked=carry["walked"] + iters,
                       tripped=carry["tripped"] + trips,
                       st_dirty=carry["st_dirty"].at[i].set(n_dirty))
            if use_jump:
                jump_on = jump_state[0].astype(jnp.int32)
                out.update(
                    jump=jump_j, jump_state=jump_state,
                    jump_sweeps=carry["jump_sweeps"] + jump_on,
                    st_jump=carry["st_jump"].at[i].set(jump_on),
                )
            if use_pull:
                out["st_skip"] = carry["st_skip"].at[i].set(
                    jnp.where(pull_on, sat.sum(), 0)
                )
                out["st_pull"] = carry["st_pull"].at[i].set(
                    pull_on.astype(jnp.int32)
                )
            return out

        with pt.scope("repair"):
            zero_w = jnp.zeros_like(p_mark)
            kept_w = jnp.where(cold, zero_w, p_mark & ~closure_w)
            mark_w0 = kept_w | seed_w
            table0 = gather(mark_w0 & nh_w)
            table_prev0 = jnp.where(cold, zero_t, prev_table)
            rd0, rl0, rch0 = dirty_chunks(table0, table_prev0)
            # Run at least one gated sweep whenever ANY shard has a tile
            # to force: the one decision on per-shard state, so the one
            # collective that is not a table.
            with pt.scope("agree"):
                any_gate = jax.lax.psum(suspect_g.sum(), axis) > 0
            run0 = rch0 | any_gate
            # replicated transparency table for the pointer doubling
            trans_table = gather(iu_w & nh_w) if use_jump else None
            zero_stats = jnp.zeros((pt.MAX_SWEEP_STATS,), jnp.int32)
            carry0 = {"mark": mark_w0, "table": table0,
                      "table_prev": table_prev0, "d": rd0, "l": rl0,
                      "use_gate": jnp.array(True), "changed": run0,
                      "sweep_i": zero_i, "walks": zero_i, "steps": zero_i,
                      "contracted": zero_i, "walked": zero_i,
                      "tripped": zero_i, "st_dirty": zero_stats}
            if use_jump:
                carry0.update(
                    jump=jump_j0.reshape(-1).astype(jnp.int32),
                    jump_state=pt.jump_state0(mode, jnp),
                    jump_sweeps=zero_i, st_jump=zero_stats,
                )
            if use_pull:
                carry0.update(st_skip=zero_stats, st_pull=zero_stats)
            out = jax.lax.while_loop(r_cond, r_body, carry0)
        mark_w = out["mark"]
        walks = jnp.where(cold, out["walks"], p_walks)
        sweeps = closed["sweeps"] + out["sweep_i"]
        stats = {
            "closure_sweeps": closed["sweeps"],
            "closure_bailed": closure_bailed.astype(jnp.int32),
            "closure_spent": closed["spent"],
            "gated_tiles": suspect_g.sum(),
            "n_sweeps": out["sweep_i"],
            "kernel_steps": closed["steps"] + out["steps"],
            "kernel_contractions": closed["contracted"] + out["contracted"],
            "kernel_chunk_walks": closed["walked"] + out["walked"],
            "kernel_walk_trips": closed["tripped"] + out["tripped"],
            "kernel_steps_full": sweeps * n_blocks,
            "dirty_chunks": out["st_dirty"],
            "tiles_skipped": out.get("st_skip", zero_stats),
            "pull_on": out.get("st_pull", zero_stats),
            "jump_sweeps": out.get("jump_sweeps", zero_i),
            "jump_on": out.get("st_jump", zero_stats),
            "jump_spent": out["jump_state"][1] if use_jump else zero_i,
            # a table a sweep of either loop, and the closure's first,
            # the previous fixpoint's, the repair's first and the jump's
            # transparency table around them
            "gathers": sweeps + (4 if use_jump else 3),
        }
        one = lambda x: x.reshape(1, -1)
        return (
            one(mark_w), one(seed_w), one(halted_w), one(iu_w),
            one(mark_w & nh_w), walks,
            {k: v.reshape((1,) + v.shape) for k, v in stats.items()},
        )

    spec_nodes = P(axis)
    spec_dev = P(axis, None)
    spec_dev3 = P(axis, None, None)

    in_specs = (
        spec_nodes, spec_nodes,  # flags, recv
        spec_nodes, spec_nodes,  # del_w, fresh_w (word-sharded)
        spec_nodes, spec_nodes, spec_nodes, spec_nodes, spec_nodes,  # prev
        P(),  # prev_walks (replicated scalar)
        spec_dev, spec_dev, spec_dev3, spec_dev3,  # layout
        spec_dev, spec_dev,  # buckets
    )
    if use_jump:
        in_specs = in_specs + (P(),)  # replicated jump parents
    stat_spec = {
        k: spec_dev if k in pd.SWEEP_STATS else spec_nodes
        for k in pd.WAKE_STATS + ("gathers",)
    }
    out_specs = (spec_dev,) * 5 + (P(), stat_spec)
    fn = _shard_map_unchecked(local_wake, mesh, in_specs, out_specs)

    # named as the one-chip wake's: a device trace knows a module by its
    # jitted function's name (``jit_wake_fn``)
    def wake_fn(*args):
        *words, walks, stats = fn(*args)
        return (*(w.reshape(-1) for w in words), walks, stats)

    return jax.jit(wake_fn)
