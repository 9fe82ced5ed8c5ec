"""Pallas TPU kernel for the liveness-trace propagation step.

The trace (ops/trace.py) is an iterative frontier expansion whose inner op
is, per propagation pair (src, dst): OR the source's active bit into the
destination's mark.  XLA lowers both the gather of source bits and the
scatter into destinations to serialized per-element loops (~7 ns/edge
measured) — the bottleneck at graph scale.  This kernel vectorizes both
sides with the primitives the TPU VPU/MXU actually has:

**Gather side.**  The active bit-vector is packed into a 32-bit word table
``T[R, 128]`` that stays VMEM-resident across the whole sweep (128 KB per
1M actors).  Mosaic supports per-vreg dynamic lane shuffles
(``take_along_axis`` within an (8, 128) register) but nothing across
vregs, so each grid step walks 8-row table chunks.  Two layout invariants
make the walk cheap (and a third thing its loop: a slot vreg gathers
out of all the table vregs of a trip in a row, under one lane pattern,
and a trip walks two chunks; ``build_propagate``):

1. *Slot row = source row mod 8.*  An edge whose source bit lives at table
   position (row_e, lane_e) is parked at slot ``(row_e % 8, col)``, so
   when the walk reaches the edge's chunk a single lane-gather
   ``take_along_axis(chunk, lane, axis=1)`` lands the right word at the
   edge's own slot — no cross-sublane shuffle, no slot/lane binding table.
   Uniqueness (one edge per (chunk-row-class, col) pair) is guaranteed by
   the host packer, which ranks edges within each (dst supertile,
   row-class) group and assigns col = rank mod 128.

2. *Per-block chunk ranges.*  Within each (dst supertile, row-class)
   group the packer sorts edges by source row, so the 128-edge runs that
   land in one block cover a narrow, contiguous band of the table.  The
   block's ``[c_lo, c_lo + span)`` range is scalar-prefetched and the
   kernel's chunk loop walks only that band — total chunk-iterations per
   sweep are O(n_super · n_chunks + n_blocks), not O(n_blocks · n_chunks),
   which is what lets the kernel scale to 10M+ actors.

**Scatter side.**  Edges are pre-sorted by destination supertile
(``SUPER = S_ROWS * 128`` nodes = one (S_ROWS, 128) f32 output block).
The block's 8x128 gathered bits become a segment-sum via one fused one-hot
contraction on the MXU:

    A[s, r*128+c] = vals[r, c] * (dst_sub[r, c] == s)     (S_ROWS, 1024)
    B[r*128+c, l] = (dst_lane[r, c] == l)                 (1024, 128)
    contrib      += A @ B                                 (S_ROWS, 128)

A and B are 0/1 so bf16 inputs with f32 accumulation are exact, doubling
MXU rate.  The output BlockSpec revisits one supertile block per run of
grid steps via a scalar-prefetched supertile-id, so accumulation happens
in VMEM and each block hits HBM exactly once per sweep.  The grid visits
only the blocks that have work this sweep (``build_propagate``); a
supertile none of whose blocks has any reads as zeros.  Building A and B
and the contraction are what a visited block costs (about 2 of its 2.4 us
on the v5e), so a block pays for them only when its gather found a bit:
the gathered bits are reduced to one scalar and everything after the
gather sits under it.  And what is gathered are the bits that are NEW
since the sweep before (the table operand holds the full table over its
new bits; only a block forced by the destination gate reads the full
one): a bit set earlier was delivered in the sweep after it was set.

Per-edge metadata is packed into two int32 arrays (source row; and
lane|bit|dst_lane|dst_sub) to halve HBM streaming per sweep.

Semantics are identical to ``trace_marks_np`` (the oracle for the
reference's ShadowGraph.java:205-289): supervisor pointers are folded in
as ordinary propagation pairs, sources gate on ``mark & ~halted``, and
only positive-weight edges propagate.
"""

from __future__ import annotations

import math
from itertools import repeat
from typing import Dict

import numpy as np

from . import trace as trace_ops
from ..utils import events

LANE = 128  # lanes per vreg row
ROWS = 8  # sublane rows per edge-slot sub-block (slot row = src row mod 8)
#: default slot sub-blocks merged into one grid step on a real chip.
#: Each grid step streams a (ROWS * sub, LANE) slot block and runs ONE
#: (s_rows, ROWS*sub*LANE) @ (ROWS*sub*LANE, LANE) one-hot contraction —
#: sub-fold fewer grid steps (and their fixed stream/dispatch cost) for
#: the same total edges.
SUB_TPU = 4
#: default 8-row table chunks to a walk chunk (a ``group_rows``-row table
#: group) on a real chip: the unit of the dirty lists and of a block's
#: span, walked by `sub` x `group` statically-unrolled lane gathers.  What
#: a walk costs is its trips and its permutes, not its arithmetic: on the
#: v5e a chunk-iteration cost 0.21 us as one trip of 32 gathers whose lane
#: pattern changed with every gather, 0.155 with a slot vreg's eight
#: gathers in a row and 0.104 with two chunks a trip (``build_propagate``;
#: PERF.md section 6, PR 47).
GROUP_TPU = 8
#: interpret-mode defaults.  The wide geometry statically unrolls
#: sub*group gather stages per chunk iteration — on the CPU test tier
#: that inflates XLA compile time by minutes per trace geometry (enough
#: to stall a collector thread mid-protocol), while buying nothing
#: (interpret mode has no per-step hardware overhead to amortize).
SUB_CPU = 1
GROUP_CPU = 1
WORD_BITS = 32
#: default output sublane rows per block (s_rows * 128 dst nodes per
#: supertile).  32 is the packing limit (dst_sub is 5 bits) and measured
#: ~1.7x faster than 8 at the 10M-actor graph: the one-hot contraction
#: grows from (8, 1024) @ (1024, 128) to (32, 1024) @ (1024, 128), 4x the
#: MXU utilization per block for the same streamed bytes.
S_ROWS = 32
# Sentinel row for empty slots: beyond any table chunk, so they never hit.
_PAD_ROW = np.int32(1 << 28)
_SPAN_BITS = 12  # chunk index / span fit in 12 bits up to ~134M actors
#: bf16 1.0 in the low / the high half of a 32-bit word
_ONE_LO, _ONE_HI = 0x3F80, 0x3F800000
#: quantum for large-layout block padding (see _pad_blocks_target)
_BLOCK_QUANTUM = 8192

# --------------------------------------------------------------------- #
# Trace propagation modes (uigc.crgc.trace-mode)
# --------------------------------------------------------------------- #
#: plain source-push sweeps over the dirty-chunk frontier (the pre-mode
#: behavior; every other mode is a strict superset of its propagation).
MODE_PUSH = "push"
#: push walks + destination-pull saturation gates every sweep: blocks
#: whose output supertile has no unmarked in-use node left are skipped
#: outright (GraphACT's push-vs-pull density asymmetry, PAPERS.md).
MODE_PULL = "pull"
#: push walks + pointer-jumping: marks additionally jump through a
#: min-source parent array that is squared each sweep, so convergence
#: needs O(log diameter) sweeps instead of O(diameter) ("Adaptive
#: Work-Efficient Connected Components on the GPU", PAPERS.md).
MODE_JUMP = "jump"
#: the default: pull gates switched per sweep when the dirty-chunk
#: density crosses ``pull_density``, and pointer jumping engaged lazily,
#: only once the push fixpoint has stayed sparse for as many chunk walks
#: as one jump sweep costs (``auto_jump_policy``).  A shallow graph (the
#: 10M power-law benchmark: 12 push sweeps) never pays for a jump; a
#: deep one (a chain) pays at most about twice what jumping from sweep 0
#: would have cost.  A deployment known to be deep sets ``jump``.
MODE_AUTO = "auto"
TRACE_MODES = (MODE_AUTO, MODE_PUSH, MODE_PULL, MODE_JUMP)
#: dirty-chunk density (fraction of walk chunks dirty) above which AUTO
#: turns the pull gates on for a sweep.  Below it the source frontier is
#: sparse enough that dirty-chunk pruning already bounds the sweep, and
#: the per-tile saturation pass would only add latency.
DEFAULT_PULL_DENSITY = 0.25
#: pointer doublings applied per sweep.  One doubling gives the classic
#: 2^k reach-per-sweep schedule; two squares the relation twice per
#: sweep (4^k), which at the 10M-actor benchmark geometry converges in
#: 5 sweeps instead of 12 (tools/sweep_profile.py --simulate; the chip
#: counted the same 5, PERF.md section 6).  What the sweeps cost on the
#: v5e is the other way round from what that design assumed: a jump
#: sweep is 1 + 2 * JUMP_STEPS gathers over all n actors, 370 ms at 10M
#: (7.4 ns a gathered element), against 27-32 ms for a push sweep with
#: every live chunk dirty and the pull gates on (78 ms walking all 39
#: chunks ungated: 0.86 ns a streamed pair slot) — so the 7 sweeps saved
#: cost 1.8 s (PERF.md section 6, PRs 27 and 28).  Hence AUTO's laziness.
JUMP_STEPS = 2
#: what one element gathered by ``jump_sweep`` costs on the v5e, in pair
#: slots streamed by the propagate kernel.  PR 27's traced runs gave 7.3
#: ns (1,833 ms of ``jump_ms.rederive`` over 5 sweeps x 5 gathers x 10M
#: elements) against 0.85 ns (a 42 ms sweep over 49.7M pairs).  PR 28
#: measured both alone at the 10M geometry (tools/sweep_profile.py on
#: the chip): 7.406 ns a gathered element (one jump sweep 370.3 ms) and
#: 0.859 ns a slot (the full-dirty sweep, all 39 chunks, no gate: 77.59
#: ms over the 90.3M slots of 22,045 blocks, 49.7M pairs in them), so
#: 8.62.  A measured property of the hardware, not a setting: AUTO's
#: price of a jump sweep (``auto_jump_policy``) is built from it.
JUMP_GATHER_COST = 8.6
#: the share of a derivation from nothing (in chunk walks) that a wake's
#: suspect closure may cost before the wake gives it up and takes the
#: cold road (``closure_gives_up``).  Settled by measurement on the v5e,
#: as JUMP_GATHER_COST is: PERF.md section 6, PR 30.
CLOSURE_SHARE = 1 / 8
#: and the least that price can be, in chunk walks.  At a geometry of one
#: or two chunks a derivation is a handful of walks and its share rounds
#: to one: no closure at all, where the served cell's closures (sessions:
#: islands that no supervisor chain ties to the resident tree) finish in
#: one to three one-chunk sweeps of 1.7 ms and a derivation costs 36 ms
#: (its jump sweeps, which a count of walks does not see; PERF.md section
#: 6, PR 30).  Four walks are under the share wherever a derivation is
#: over 32, so the 10M geometry never sees the floor.
CLOSURE_MIN_WALKS = 4
#: per-sweep stat ring length of the wake's counters (sweeps beyond this
#: fold into the last slot; fixpoints run ~4-12 sweeps)
MAX_SWEEP_STATS = 32

#: per-tile gate values consumed by dst_gate kernels
GATE_PUSH = 0  # walk the dirty chunks inside the block's span (default)
GATE_FULL = 1  # walk the FULL span (decremental repair re-derivation)
GATE_SKIP = 2  # skip the block outright (saturated destination tile)

#: name of the propagate kernel's ``pallas_call``: what its events are
#: called in a device trace (the HLO instruction carries it)
KERNEL_NAME = "uigc_propagate"


def scope(name: str):
    """``jax.named_scope(name)``: names a phase of the trace programs.
    Compile-time metadata only (it lands in each HLO instruction's
    ``op_name``, which a device trace shows per event), so a scope
    costs nothing per wake.  The shared helpers below open ``push``,
    ``hits``, ``jump``, ``sat`` and ``dirty``; the wake program nests
    them under ``uigc.wake/<phase>`` (ops/pallas_decremental.py)."""
    import jax

    return jax.named_scope(name)



def jump_parents(psrc, pdst, n: int) -> np.ndarray:
    """Min-source jump-parent array: J[d] = the smallest source with a
    live propagation pair into ``d``, sentinel ``n`` when none.

    Minimum (not first/last) is the load-bearing choice: low slots are
    the oldest, shallowest actors (roots intern first; preferential
    attachment biases hub targets low), so the parent forest points
    toward the seed-rich end of the graph — the min-label hooking of the
    GPU connected-components literature.  Shaped (n + 1,) with J[n] = n
    so pointer doubling can gather through the sentinel."""
    j = np.full(n + 1, n, dtype=np.int32)
    pdst = np.asarray(pdst, dtype=np.int64)
    psrc = np.asarray(psrc, dtype=np.int64)
    ok = (pdst < n) & (psrc < n)
    np.minimum.at(j, pdst[ok], psrc[ok].astype(np.int32))
    j[n] = n
    return j


def fold_jump_log(jump_parent, ins, src, dst, n: int, writes=None) -> None:
    """Vectorized jump-parent maintenance for one pair-transition batch,
    given as the log's columns (``slotmap.PairLog.columns``) — the
    batched form of the min-fold-on-insert / invalidate-on-remove rules
    (``jump_parents``), shared by the single-device and mesh layout
    planes.

    Order-insensitive and conservative: pointers built from any pair
    removed in the batch are invalidated (even when an insert earlier
    in the same batch created them), and inserts whose (src, dst) pair
    is ALSO removed anywhere in the batch are not folded (their order
    against the remove is lost once the batch is vectorized).  A
    spurious invalidation or a skipped fold costs acceleration only;
    a pointer surviving its pair's removal would let the jump sweep
    cross a dead edge, which this can never produce.  Ids >= ``n``
    (node spaces that grew past the layout) are ignored.

    Mutates ``jump_parent`` in place; when ``writes`` is a dict the
    changed entries are recorded there too (the device-mirror scatter
    queue), O(changed) not O(batch)."""
    ins = ins != 0
    ok = (src >= 0) & (src < n) & (dst >= 0) & (dst < n)
    rs, rd = src[~ins & ok], dst[~ins & ok]
    if rd.size:
        hrd = rd[jump_parent[rd] == rs]
        if hrd.size:
            jump_parent[hrd] = n
            if writes is not None:
                writes.update(zip(hrd.tolist(), repeat(n)))
    isrc, idst = src[ins & ok], dst[ins & ok]
    if isrc.size and rd.size:
        keep = ~np.isin((isrc << 32) | idst, (rs << 32) | rd)
        isrc, idst = isrc[keep], idst[keep]
    if isrc.size:
        before = jump_parent[idst]
        np.minimum.at(
            jump_parent, idst, isrc.astype(jump_parent.dtype)
        )
        if writes is not None:
            after = jump_parent[idst]
            changed = after < before
            writes.update(zip(idst[changed].tolist(), after[changed].tolist()))


def jump_parents_from_graph(
    edge_src, edge_dst, edge_weight, supervisor, n: int
) -> np.ndarray:
    """jump_parents over a graph's live propagation pairs (edges with
    positive weight + supervisor pointers)."""
    live = edge_weight > 0
    psrc = edge_src[live].astype(np.int64)
    pdst = edge_dst[live].astype(np.int64)
    sup_src = np.nonzero(supervisor >= 0)[0].astype(np.int64)
    if sup_src.size:
        psrc = np.concatenate([psrc, sup_src])
        pdst = np.concatenate([pdst, supervisor[sup_src].astype(np.int64)])
    return jump_parents(psrc, pdst, n)


# --------------------------------------------------------------------- #
# Marking parents (why-live provenance; telemetry/inspect.py)
#
# The observability analogue of the jump-parent forest above: where
# jump_parents is an ACCELERATION structure (min-source over raw pairs,
# squared each sweep, free to over-shortcut), the marking-parent array is
# an EXPLANATION structure — parent[i] is the node whose propagation
# first marked i in a plain BFS fixpoint, so following parents from any
# live actor walks a concrete pseudoroot→actor retaining path in which
# every hop is a real positive-weight edge or supervisor pointer.  It is
# computed by a separate scatter-min XLA fixpoint over the same flat
# node/edge arrays the mark kernels consume, NOT inside the Pallas mark
# kernel: the mark kernel's one-hot MXU contraction reduces sources to a
# single OR bit per destination and cannot say *which* source fired, and
# threading an argmin through it would double the streamed bytes of
# every plain wake.  Keeping provenance in its own dispatch means the
# no-capture wake path is untouched (stats-variant gating discipline)
# and a capture costs exactly one extra device fixpoint.
# --------------------------------------------------------------------- #

_parents_fn_cache: Dict[str, object] = {}


def _build_parents_fn():
    import jax
    import jax.numpy as jnp

    F = trace_ops

    def parents_fn(flags, recv_count, supervisor, edge_src, edge_dst,
                   edge_weight):
        n = flags.shape[0]
        in_use = (flags & F.FLAG_IN_USE) != 0
        halted = (flags & F.FLAG_HALTED) != 0
        seed = (
            ((flags & F.FLAG_ROOT) != 0)
            | ((flags & F.FLAG_BUSY) != 0)
            | (recv_count != 0)
            | ((flags & F.FLAG_INTERNED) == 0)
        )
        mark0 = in_use & (~halted) & seed
        parent0 = jnp.full(n, -1, dtype=jnp.int32)

        live_edge = edge_weight > 0
        edst = jnp.where(live_edge, edge_dst, n)
        esrc = jnp.where(live_edge, edge_src, n).astype(jnp.int32)
        sup_dst = jnp.where(supervisor >= 0, supervisor, n)
        sup_src = jnp.arange(n, dtype=jnp.int32)

        def cond(carry):
            return carry[2]

        def body(carry):
            mark, parent, _ = carry
            active = mark & (~halted)
            active_pad = jnp.concatenate([active, jnp.zeros((1,), bool)])
            # Scatter-min of the active source's own index per
            # destination; slot n is the sink for dead edges/no-sup.
            cand = jnp.full((n + 1,), n, dtype=jnp.int32)
            cand = cand.at[edst].min(
                jnp.where(active_pad[esrc], esrc, n)
            )
            cand = cand.at[sup_dst].min(
                jnp.where(active, sup_src, n)
            )
            cand = cand[:n]
            newly = (cand < n) & (~mark) & in_use
            parent = jnp.where(newly, cand, parent)
            return mark | newly, parent, jnp.any(newly)

        mark, parent, _ = jax.lax.while_loop(
            cond, body, (mark0, parent0, jnp.array(True))
        )
        return mark, parent

    return jax.jit(parents_fn)


def marking_parents_jax(flags, recv_count, supervisor, edge_src, edge_dst,
                        edge_weight):
    """Device (XLA) mark fixpoint with marking-parent capture.  Same
    mark contract as ``trace_ops.trace_marks_np``; additionally returns
    ``parent`` (int32[n], -1 = pseudoroot seed or unmarked, else the
    minimum source whose propagation first marked the slot) — matching
    ``trace_ops.trace_marks_np_parents`` exactly, which is the parity
    oracle.  Shapes are static; the jitted fn is cached process-wide."""
    if "fn" not in _parents_fn_cache:
        _parents_fn_cache["fn"] = _build_parents_fn()
        if events.recorder.enabled:
            events.recorder.commit(
                events.COMPILE, tag="parents_fn", geom="static", hit=False
            )
    fn = _parents_fn_cache["fn"]
    mark, parent = fn(
        flags, recv_count, supervisor, edge_src, edge_dst, edge_weight
    )
    return np.asarray(mark), np.asarray(parent)  # readback: host boundary: device marks/parents -> np result contract


def bits_at(table, ids, n, jnp):
    """Gather per-node bits from a packed word table for an int32 id
    vector; ids >= n (the sentinel and any padding) read as 0."""
    flat = table.reshape(-1)
    word = jnp.minimum(ids >> 5, flat.shape[0] - 1)
    return (((flat[word] >> (ids & 31)) & 1) > 0) & (ids < n)


def jump_sweep(table, jump_j, trans_w, n, jnp, steps: int = JUMP_STEPS):
    """One pointer-jump propagation step + ``steps`` pointer doublings.

    Returns (hits, new_jump_j): ``hits`` is the (n,) bool plane of nodes
    whose current jump parent is active in ``table`` (mark & ~halted —
    the same source gate as edge propagation), and the parent array is
    then advanced by squaring, extending each pointer through
    ``trans_w``-transparent (in-use, non-halted) intermediates only.

    Soundness: by construction J[v] always reaches v through a path of
    live pairs whose intermediate nodes are all transparent, so
    mark[J[v]] & ~halted[J[v]] implies the plain fixpoint would
    eventually mark v — the jump only collapses the sweeps in between.
    Parents never extend through an opaque node, and the host layer
    invalidates J[d] whenever the pair it was built from is removed, so
    a jump can never cross a deleted edge or a halted relay.

    Its two parts carry scopes of their own under ``jump``: ``jump/hits``
    and ``jump/double`` (the wake program adds ``jump/pack``)."""
    with scope("jump"):
        with scope("hits"):  # the gather of the parents' bits
            hits = bits_at(table, jump_j[:n], n, jnp)
        with scope("double"):  # 2 gathers a doubling
            for _ in range(steps):
                j2 = jump_j[jump_j]
                can = bits_at(trans_w, jump_j, n, jnp) & (j2 < n)
                jump_j = jnp.where(can, j2, jump_j)
    return hits, jump_j


def kernel_slots(specs) -> int:
    """Pair slots the propagate kernels stream when every chunk is walked:
    the packed layouts' capacity, static in their specs (xla tiers, the
    landing pads of the newest churn, are not kernel work)."""
    return sum(
        spec[1] * ROWS * spec[-2] * LANE for spec in specs if spec[0] != "xla"
    )


def auto_jump_policy(n: int, n_slots: int, n_chunks: int, pull_cut: int,
                     steps: int = JUMP_STEPS):
    """When ``trace-mode: auto`` jumps: the one statement of it, shared
    by the three fixpoints (the wake, and the sharded trace and wake) and
    by ``tools/sweep_profile.py --simulate``.

    Returns ``decide(engaged, spent, n_dirty) -> (engaged, spent)``,
    called once per sweep before the sweep runs, on Python, numpy or
    traced scalars alike.  ``spent`` counts the chunk walks of the
    sweeps so far that were *sparse*: they ran under the pull cut
    (``n_dirty < pull_cut``, the regime AUTO already tells apart) or
    walked a single chunk (as sparse as the geometry can show: a layout
    of under six chunks has ``pull_cut`` 1); each counts at least 1.
    The jump engages, and stays engaged to the end of the fixpoint, once
    ``spent`` reaches ``decide.price``: one jump sweep in chunk walks,
    its ``(1 + 2 * steps) * n`` gathered elements times
    JUMP_GATHER_COST over the ``n_slots / n_chunks`` pair slots of a
    walk chunk.

    Dense sweeps are left out on purpose: they are productive (millions
    of new marks each at 10M), and a rule that counted them would engage
    near the end of a shallow fixpoint and pay a jump sweep to save less
    than one.  A fixpoint that STAYS sparse sweep after sweep is the
    signature of depth, and there this is the ski-rental rule: at most
    about twice the cost of having jumped from the start."""
    per_chunk = max(1, n_slots // max(1, n_chunks))
    gathered = (1 + 2 * steps) * n
    price = max(1, int(round(gathered * JUMP_GATHER_COST / per_chunk)))
    sparse_cut = max(pull_cut, 2)

    def decide(engaged, spent, n_dirty):
        engaged = engaged | (spent >= price)
        walked = n_dirty + (n_dirty < 1)
        return engaged, spent + (n_dirty < sparse_cut) * walked

    decide.price = price
    return decide


def closure_price(derivation_walks: int) -> int:
    """What a wake's suspect closure may cost before the wake gives it
    up, in chunk walks: CLOSURE_SHARE of the ``derivation_walks`` that the
    last derivation from nothing cost on this graph, and at least
    CLOSURE_MIN_WALKS."""
    return max(CLOSURE_MIN_WALKS, math.ceil(CLOSURE_SHARE * derivation_walks))


def closure_gives_up(spent, derivation_walks):
    """When the decremental wake stops closing over its suspects and
    re-derives everything from the seeds instead: the one statement of
    it, shared by the wake program (ops/pallas_decremental.py, where
    both arguments are traced scalars in the carry) and by
    ``tools/sweep_profile.py --simulate`` (Python ints).

    Asked before each closure sweep that still has something to do.
    ``spent`` is the chunk walks of the closure sweeps so far,
    ``derivation_walks`` those of the last derivation from nothing on
    this graph (the same unit, counted by the same loop).  True once
    ``spent`` has reached ``closure_price(derivation_walks)``.

    Why a price and not a size: under CRGC a child marks its supervisor,
    so the live set is one strongly connected component and the closure
    of any marked suspect is every mark; the regional repair that
    follows IS the derivation from nothing, having first paid the
    closure to find that out (10 sweeps of 22 at 10M, PERF.md section 6,
    PR 30).  A closure confined to a halted or unrooted island finishes
    under the price and keeps its regional repair.  This is the
    ski-rental rule again: a wake that gives up costs at most
    (1 + CLOSURE_SHARE) derivations and the sweep that crossed the
    price."""
    return (spent >= CLOSURE_MIN_WALKS) & (
        spent >= CLOSURE_SHARE * derivation_walks
    )


def jump_step(mode, decide, state, n_dirty, run, mark_w, table, jump_j):
    """The pointer jump's share of one sweep of a ``jump`` or ``auto``
    fixpoint.  ``run(mark_w, table, jump_j) -> (mark_w | jump hits,
    advanced jump_j)``; ``state`` is the carried (engaged, spent) of
    ``decide`` (``auto_jump_policy``), starting from ``jump_state0``.
    Returns (mark_w, jump_j, state).

    ``jump`` runs it in every sweep, with no conditional in the program.
    ``auto`` runs it under ``lax.cond`` on the carried flag; skipped,
    marks and parents pass through untouched (the doublings depend only
    on ``jump_j`` and the transparency table, so starting them late
    loses nothing).  The conditional sits under the ``jump`` scope:
    whatever it costs is the jump's."""
    import jax

    if mode == MODE_JUMP:
        return (*run(mark_w, table, jump_j), state)
    engaged, spent = decide(*state, n_dirty)
    with scope("jump"):
        mark_w, jump_j = jax.lax.cond(
            engaged, run, lambda m, _t, j: (m, j), mark_w, table, jump_j
        )
    return mark_w, jump_j, (engaged, spent)


def jump_state0(mode, jnp):
    """(engaged, chunk walks spent while sparse) before the first sweep."""
    return jnp.array(mode == MODE_JUMP), jnp.zeros((), jnp.int32)


def saturated_tiles(mark_w, iu_w, n_super, sup_words, jnp):
    """Per-supertile saturation bits (int32, 1 = no unmarked in-use node
    left): the destination-pull summary.  A saturated tile's blocks can
    be skipped outright — every contribution they could make would land
    on an already-marked or never-markable bit."""
    with scope("sat"):
        un = (iu_w & ~mark_w).reshape(-1)[: n_super * sup_words]
        return (
            ~(un.reshape(n_super, sup_words).any(axis=1))
        ).astype(jnp.int32)


def pack_hits_words(hits2d, jnp):
    """Word-pack a (t, LANE) boolean hits plane into flat int32 words.

    The one layout invariant every fixpoint pack shares: lane g*32+b of
    row t is bit b of flat word t*4+g (node id = 32*word + bit), so the
    flat words lay out row-major into the (r_rows, LANE) table at
    position (w >> 7, w & 127).  Callers pad/reshape to their table
    geometry (global table, shard-local words, or a benchmark probe)."""
    t = hits2d.shape[0]
    shifts = jnp.arange(WORD_BITS, dtype=jnp.int32)
    h3 = hits2d.astype(jnp.int32).reshape(t, LANE // WORD_BITS, WORD_BITS)
    w = (h3 << shifts[None, None, :]).sum(axis=2, dtype=jnp.int32)
    return w.reshape(-1)


def pack_bools(active, n, r_rows, jnp):
    """Scatter-pack an (n,) bool vector into the (r_rows, LANE) word
    table (bits >= n stay 0).  O(n) — used once per trace for seed/gate
    vectors; the fixpoint's per-sweep pack is pack_hits_table."""
    shifts = jnp.arange(WORD_BITS, dtype=jnp.int32)
    a = jnp.zeros(r_rows * LANE * WORD_BITS, jnp.int32)
    a = a.at[:n].set(active.astype(jnp.int32))
    w = (a.reshape(-1, WORD_BITS) << shifts[None, :]).sum(
        axis=1, dtype=jnp.int32
    )
    return w.reshape(r_rows, LANE)


def dirty_group_lists(table, table_prev, n_chunks, group_rows, jnp):
    """Prefix D and compacted index list L of the walk groups whose words
    changed — the kernel ABI build_propagate consumes (D sized
    n_chunks+1, L sized n_chunks, plus the any-changed flag)."""
    with scope("dirty"):
        chunk_ids = jnp.arange(n_chunks, dtype=jnp.int32)
        diff = (
            (table != table_prev)
            .reshape(n_chunks, group_rows * LANE)
            .any(axis=1)
        )
        counts = diff.astype(jnp.int32)
        d = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)])
        pos = jnp.where(diff, d[:-1], n_chunks)
        l = (
            jnp.zeros((n_chunks + 1,), jnp.int32)
            .at[pos]
            .set(chunk_ids)[:n_chunks]
        )
        return d, l, d[n_chunks] > 0


def walk_tables(table, table_prev, jnp):
    """The table operand of ``build_propagate``'s kernels, (2 * r_rows,
    LANE): ``table`` over its bits that are new since ``table_prev``,
    the table of the sweep before."""
    return jnp.concatenate([table, table & ~table_prev], axis=0)


def pack_hits_table(hits2d, r_rows, jnp):
    """pack_hits_words padded and reshaped into the (r_rows, LANE) word
    table — the exact per-sweep pack on the fixpoint path and the
    expression benchmark probes must time."""
    with scope("hits"):
        flat = pack_hits_words(hits2d, jnp)
        flat = jnp.concatenate(
            [flat, jnp.zeros((r_rows * LANE - flat.shape[0],), jnp.int32)]
        )
        return flat.reshape(r_rows, LANE)


def unpack_table(words, n, jnp):
    """Unpack the (r_rows, LANE) word table back to an (n,) bool vector
    (inverse of pack_bools/pack_hits_table for bits < n)."""
    shifts = jnp.arange(WORD_BITS, dtype=jnp.int32)
    bits = (words.reshape(-1)[:, None] >> shifts[None, :]) & 1
    return bits.reshape(-1)[:n] > 0


def build_sweep_contribs(specs, propagates, n, n_super, s_rows, jnp):
    """The per-layout propagation sweep of the decremental wake's two
    fixpoints: returns fn(table, d, l, layout_args, gate) -> hits
    plane (t_rows, LANE) bool; ``fn.with_steps`` returns beside it the
    grid steps the sweep's kernels took, those of them that contracted,
    the chunk-iterations their walks took and the loop trips they took
    them in (``build_propagate``).

    ``propagates`` holds one kernel per packed spec (None for xla
    tiers).  ``gate`` is the per-global-supertile dst-gate vector for
    dst_gate=True kernels, or None when the kernels were built without a
    gate operand.  Keeping this loop in one place is what guarantees the
    two fixpoints propagate identically per sweep — the parity the
    differential tests rely on."""
    t_rows = n_super * s_rows
    n_pad_nodes = t_rows * LANE
    sub_iota_rows = jnp.arange(s_rows, dtype=jnp.int32)

    def sweep(tables, d, l, layout_args, gate=None):
        return with_steps(tables, d, l, layout_args, gate)[0]

    def with_steps(tables, d, l, layout_args, gate=None):
        with scope("push"):
            return push(tables, d, l, layout_args, gate)

    def push(tables, d, l, layout_args, gate):
        contrib = jnp.zeros((t_rows, LANE), jnp.float32)
        xla_hits2d = jnp.zeros((t_rows, LANE), bool)
        have_xla = False
        steps = contracted = walks = trips = jnp.zeros((), jnp.int32)
        pos = 0
        for spec, propagate in zip(specs, propagates):
            if spec[0] == "xla":
                psrc, pdst = layout_args[pos:pos + 2]
                pos += 2
                # Source-active bits gathered straight from the packed
                # table (the FULL one: the operand's first half); sink
                # pads (src = n) masked out.
                word = psrc >> 5
                w = tables[word >> 7, word & 127]
                src_active = (((w >> (psrc & 31)) & 1) > 0) & (psrc < n)
                prop = (
                    jnp.zeros((n_pad_nodes + 1,), jnp.int32)
                    .at[pdst]
                    .max(src_active.astype(jnp.int32))
                )
                xla_hits2d = xla_hits2d | (
                    prop[:n_pad_nodes].reshape(t_rows, LANE) > 0
                )
                have_xla = True
                continue
            compact = spec[0] == "compact"
            bmeta1, bmeta2, row_pos, emeta = layout_args[pos:pos + 4]
            pos += 4
            gates = ()
            if compact:
                super_ids = layout_args[pos]
                pos += 1
                if gate is not None:
                    gates = (gate[super_ids],)
            elif gate is not None:
                gates = (gate,)
            c, took, did, walked, tripped = propagate.with_steps(
                d, l, *gates, bmeta1, bmeta2, tables, row_pos, emeta
            )
            steps = steps + took
            contracted = contracted + did
            walks = walks + walked
            trips = trips + tripped
            if compact:
                rows = (
                    super_ids[:, None] * s_rows + sub_iota_rows[None, :]
                ).reshape(-1)
                contrib = contrib.at[rows].add(
                    c, mode="drop", unique_indices=False
                )
            else:
                contrib = contrib + c
        hits2d = contrib > 0
        if have_xla:
            hits2d = hits2d | xla_hits2d
        return hits2d, steps, contracted, walks, trips

    sweep.with_steps = with_steps
    return sweep


def default_geometry(interpret: bool | None = None) -> tuple:
    """(sub, group) for new layouts: wide on a real chip, minimal in
    interpret mode (see SUB_CPU note)."""
    if interpret is None:
        interpret = default_interpret()
    return (SUB_CPU, GROUP_CPU) if interpret else (SUB_TPU, GROUP_TPU)


def _parallel_argsort(keys: np.ndarray) -> np.ndarray:
    """argsort through torch's multi-threaded sort when available —
    numpy's is single-threaded and dominates the 50M-pair pack (~9s vs
    ~2s).  Equal keys may land in either order; the packer's placement
    is valid under any tie-break (the composite key carries every field
    the placement reads)."""
    if keys.size < (1 << 20):
        return np.argsort(keys)
    try:
        import torch

        return torch.from_numpy(keys).argsort().numpy()
    except Exception:
        return np.argsort(keys)


def _pad_blocks_target(n_blocks: int) -> int:
    """Padded block count for a mutable layout: power of two while small
    (maximum kernel-cache reuse), then multiples of ``_BLOCK_QUANTUM``.
    Block metadata is scalar-prefetched into SMEM (1 MB): pow2 padding of
    a ~90k-block layout would waste ~350 KB of it and OOM the 10M-actor
    graph, while quantum padding stays within budget up to ~60M actors."""
    if n_blocks <= _BLOCK_QUANTUM:
        return 1 << max(0, int(n_blocks - 1).bit_length())
    return ((n_blocks + _BLOCK_QUANTUM - 1) // _BLOCK_QUANTUM) * _BLOCK_QUANTUM


def prepare_chunks(
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    edge_weight: np.ndarray,
    supervisor: np.ndarray,
    n: int,
    s_rows: int = S_ROWS,
    pad_blocks_pow2: bool = False,
    sub: int = None,
    group: int = None,
) -> Dict[str, np.ndarray]:
    """Host-side packer: place propagation pairs into kernel blocks.

    Rebuild whenever the edge set or supervisor pointers change (one
    lexsort of the live pairs, amortized across the trace's fixpoint
    iterations and across traces between graph mutations; a live,
    churning graph should use ops/pallas_incremental.py instead, which
    keeps this full pack off the per-wake path).

    ``pad_blocks_pow2`` rounds the block count up to a power of two with
    inert padding blocks (they re-accumulate zeros into the last
    supertile), so a live, mutating graph triggers at most log-many
    kernel recompiles instead of one per edge-set change.
    """
    live = edge_weight > 0
    psrc = edge_src[live].astype(np.int64)
    pdst = edge_dst[live].astype(np.int64)
    sup_src = np.nonzero(supervisor >= 0)[0].astype(np.int64)
    if sup_src.size:
        psrc = np.concatenate([psrc, sup_src])
        pdst = np.concatenate([pdst, supervisor[sup_src].astype(np.int64)])
    return prepare_pairs(
        psrc, pdst, n, s_rows=s_rows, pad_blocks_pow2=pad_blocks_pow2,
        sub=sub, group=group,
    )


def prepare_pairs(
    psrc: np.ndarray,
    pdst: np.ndarray,
    n: int,
    s_rows: int = S_ROWS,
    pad_blocks_pow2: bool = False,
    want_slots: bool = False,
    compact_supers: bool = False,
    n_src: int = None,
    sub: int = None,
    group: int = None,
) -> Dict[str, np.ndarray]:
    """Pack explicit propagation pairs (already filtered to live ones)
    into kernel blocks.

    With ``want_slots`` the result also carries ``slot_ri``/``slot_col``
    — each input pair's (row, column) in ``row_pos``/``emeta``, aligned
    with the *input* pair order — so a caller can later mask individual
    pairs in place (the deletion path of the incremental layout).

    With ``compact_supers`` the layout covers only the destination
    supertiles this pair set actually touches: the kernel's output is
    (k_touched * s_rows, LANE) and ``super_ids`` maps each compact tile
    back to its global supertile.  Without it, a tiny delta layout over
    a 10M-node space would still pay one (mostly dummy) grid step per
    global supertile; with it the cost scales with the delta.

    ``n_src`` decouples the source space from the destination space: the
    bit-table geometry (r_rows) covers ``n_src`` nodes while supertiles
    cover ``n`` destinations.  The mesh path uses this — sources are
    global ids gathered from the all-gathered table, destinations are
    shard-local (parallel/sharded_trace)."""
    assert 1 <= s_rows <= 32, "dst_sub is packed in 5 bits"
    if sub is None or group is None:
        d_sub, d_group = default_geometry()
        sub = d_sub if sub is None else sub
        group = d_group if group is None else group
    block_rows = ROWS * sub
    group_rows = ROWS * group
    super_sz = s_rows * LANE
    psrc = np.asarray(psrc, dtype=np.int64)
    pdst = np.asarray(pdst, dtype=np.int64)

    n_super = max(1, -(-n // super_sz))
    n_pad = n_super * super_sz
    # Bit table geometry: R rows of 128 lanes of 32-bit words, padded to
    # whole walk groups.
    n_words = -(-(n_src if n_src is not None else n_pad) // WORD_BITS)
    r_rows = -(-n_words // LANE)
    r_rows = ((r_rows + group_rows - 1) // group_rows) * group_rows
    assert r_rows // group_rows < (1 << _SPAN_BITS), (
        "graph too large for span packing"
    )

    m = psrc.size
    word = psrc >> 5
    w_row = word >> 7
    if super_sz & (super_sz - 1) == 0:
        # pow2 supertile (any pow2 s_rows): shifts instead of int64
        # division, which costs whole seconds at 50M pairs
        ss = super_sz.bit_length() - 1
        d_super = pdst >> ss
        d_local = pdst & (super_sz - 1)
    else:
        d_super = pdst // super_sz
        d_local = pdst % super_sz
    # per-pair emeta value, computed pre-sort so the sort permutation
    # needs only two gathers (composite + this) instead of six
    eval32 = (
        (word & 127)
        | ((psrc & 31) << 7)
        | ((d_local & 127) << 12)
        | ((d_local >> 7) << 19)
    ).astype(np.int32)

    if compact_supers:
        touched = np.unique(d_super)
        if touched.size == 0:
            touched = np.zeros(1, dtype=np.int64)
        d_super = np.searchsorted(touched, d_super)
        n_tiles = int(touched.size)
    else:
        touched = None
        n_tiles = n_super

    # --- placement -----------------------------------------------------
    # Sort by (dst supertile, row%8 class, source row); rank within each
    # class gives (block-in-supertile, column) such that each column holds
    # at most one edge per class — the slot row can then be the class
    # itself — and each block's 128-edge runs are source-sorted, keeping
    # its table-chunk span narrow.  One composite-key argsort instead of
    # a 3-key lexsort: a third of the sorting passes on the 50M-pair
    # packs, and equal keys are interchangeable so stability is not
    # needed (w_row fits 31 bits for any graph the span packing admits).
    # The key also CARRIES d_super/r8/w_row, so the sorted values are
    # recovered by bit ops on one gathered array instead of per-field
    # gathers.
    composite = (d_super << 34) | ((w_row & 7) << 31) | w_row
    order = _parallel_argsort(composite)
    comp_s = composite[order]
    eval32 = eval32[order]
    w_row = (comp_s & ((1 << 31) - 1)).astype(np.int32)
    r8 = (comp_s >> 31) & 7
    d_super = comp_s >> 34

    # rank of each edge within its (d_super, r8) class
    if m:
        key_change = np.ones(m, dtype=bool)
        cls = comp_s >> 31  # (d_super, r8) in one compare
        key_change[1:] = cls[1:] != cls[:-1]
        start_idx = np.nonzero(key_change)[0]
        starts = np.repeat(start_idx, np.diff(np.append(start_idx, m)))
        rank = np.arange(m, dtype=np.int64) - starts
    else:
        rank = np.zeros(0, dtype=np.int64)

    # blocks needed per (compact) supertile = max over classes of
    # ceil(ceil(class/128)/sub)
    sub_shift = sub.bit_length() - 1 if sub & (sub - 1) == 0 else None
    blocks_needed = np.zeros(n_tiles, dtype=np.int64)
    if m:
        sub_seq = (
            (rank >> 7) >> sub_shift if sub_shift is not None
            else (rank >> 7) // sub
        )
        np.maximum.at(blocks_needed, d_super, sub_seq + 1)
    blocks_needed = np.maximum(blocks_needed, 1)  # dummy for empty supertiles

    n_blocks = int(blocks_needed.sum())
    block_base = np.zeros(n_tiles, dtype=np.int64)
    block_base[1:] = np.cumsum(blocks_needed)[:-1]

    # --- fill kernel arrays -------------------------------------------
    shape = (n_blocks * block_rows, LANE)
    row_pos = np.full(shape, _PAD_ROW, dtype=np.int32)
    emeta = np.zeros(shape, dtype=np.int32)

    slot_ri = slot_col = None
    if m:
        sub_idx = rank >> 7  # sub-block sequence within the class
        g_block = block_base[d_super] + (
            sub_idx >> sub_shift if sub_shift is not None else sub_idx // sub
        )
        col = rank & 127
        # slot row = (sub-block within grid block, source row mod 8)
        sub_in = (
            sub_idx & (sub - 1) if sub_shift is not None else sub_idx % sub
        )
        ri = g_block * block_rows + sub_in * ROWS + r8
        if want_slots:
            # Undo the placement sort: slot of the i-th *input* pair.
            slot_ri = np.empty(m, dtype=np.int64)
            slot_col = np.empty(m, dtype=np.int64)
            slot_ri[order] = ri
            slot_col[order] = col
        row_pos[ri, col] = w_row
        emeta[ri, col] = eval32
        # per-block table walk-group range
        if group_rows & (group_rows - 1) == 0:
            chunk = (w_row >> (group_rows.bit_length() - 1)).astype(np.int64)
        else:
            chunk = (w_row // group_rows).astype(np.int64)
        c_lo = np.full(n_blocks, 1 << 30, dtype=np.int64)
        c_hi = np.zeros(n_blocks, dtype=np.int64)
        np.minimum.at(c_lo, g_block, chunk)
        np.maximum.at(c_hi, g_block, chunk + 1)
        empty = c_lo > c_hi
        c_lo[empty] = 0
        c_hi[empty] = 0
    else:
        c_lo = np.zeros(n_blocks, dtype=np.int64)
        c_hi = np.zeros(n_blocks, dtype=np.int64)

    span = c_hi - c_lo
    assert span.max(initial=0) < (1 << _SPAN_BITS)

    block_super = np.repeat(np.arange(n_tiles, dtype=np.int64), blocks_needed)
    block_first = np.zeros(n_blocks, dtype=np.int64)
    block_first[block_base] = 1

    if compact_supers and pad_blocks_pow2:
        # Pad the compact tile count to a power of two so repeated delta
        # packs reuse cached kernels.  Each pad tile gets one inert
        # first-visit block (initializes its output to zero); the
        # host-side scatter maps pad tiles to global supertile 0 with a
        # zero contribution, which is a no-op add.
        k_pad = 1 << max(0, int(n_tiles - 1).bit_length())
        if k_pad > n_tiles:
            extra_t = k_pad - n_tiles
            block_super = np.concatenate(
                [block_super, np.arange(n_tiles, k_pad, dtype=np.int64)]
            )
            block_first = np.concatenate(
                [block_first, np.ones(extra_t, dtype=np.int64)]
            )
            c_lo = np.concatenate([c_lo, np.zeros(extra_t, dtype=np.int64)])
            span = np.concatenate([span, np.zeros(extra_t, dtype=np.int64)])
            row_pos = np.concatenate(
                [row_pos, np.full((extra_t * block_rows, LANE), _PAD_ROW, np.int32)]
            )
            emeta = np.concatenate(
                [emeta, np.zeros((extra_t * block_rows, LANE), np.int32)]
            )
            n_blocks += extra_t
            n_tiles = k_pad

    if pad_blocks_pow2:
        padded = _pad_blocks_target(n_blocks)
        if padded > n_blocks:
            extra = padded - n_blocks
            # Inert blocks: span 0 (no gather), accumulate zeros into the
            # last (compact) supertile (keeps output revisits consecutive).
            block_super = np.concatenate(
                [block_super, np.full(extra, n_tiles - 1, dtype=np.int64)]
            )
            block_first = np.concatenate(
                [block_first, np.zeros(extra, dtype=np.int64)]
            )
            c_lo = np.concatenate([c_lo, np.zeros(extra, dtype=np.int64)])
            span = np.concatenate([span, np.zeros(extra, dtype=np.int64)])
            row_pos = np.concatenate(
                [row_pos, np.full((extra * block_rows, LANE), _PAD_ROW, np.int32)]
            )
            emeta = np.concatenate(
                [emeta, np.zeros((extra * block_rows, LANE), np.int32)]
            )
            n_blocks = padded

    # meta1 = supertile id | first block of its tile; meta2 = chunk range.
    # The kernel reads the tile only: which block initialises an output
    # tile is decided per sweep, among the blocks that have work
    # (build_propagate).
    bmeta1 = (block_super << 1 | block_first).astype(np.int32)
    bmeta2 = (c_lo << _SPAN_BITS | span).astype(np.int32)

    prep = {
        "row_pos": row_pos,
        "emeta": emeta,
        "bmeta1": bmeta1,
        "bmeta2": bmeta2,
        "n_super": n_super,
        "n_blocks": n_blocks,
        "r_rows": r_rows,
        "n_pad": n_pad,
        "n": n,
        "s_rows": s_rows,
        "sub": sub,
        "group": group,
        "n_pairs": int(m),
    }
    if compact_supers:
        k = int(touched.size)
        super_ids = np.zeros(n_tiles, dtype=np.int32)
        super_ids[:k] = touched.astype(np.int32)
        prep["super_ids"] = super_ids
        prep["out_supers"] = n_tiles
    if want_slots:
        prep["slot_ri"] = (
            slot_ri if slot_ri is not None else np.zeros(0, dtype=np.int64)
        )
        prep["slot_col"] = (
            slot_col if slot_col is not None else np.zeros(0, dtype=np.int64)
        )
    return prep


def pad_layout_blocks(prep: Dict[str, np.ndarray], target: int) -> None:
    """Pad a packed layout with inert blocks (span 0, not first-visit,
    accumulating nothing into the last supertile) up to ``target`` blocks,
    in place.  The mesh path uses this to equalize per-shard block counts
    so one SPMD program covers every shard."""
    extra = target - prep["n_blocks"]
    if extra <= 0:
        return
    block_rows = ROWS * prep["sub"]
    n_tiles = prep.get("out_supers", prep["n_super"])
    bmeta1_pad = np.full(extra, (n_tiles - 1) << 1, dtype=np.int32)
    prep["bmeta1"] = np.concatenate([prep["bmeta1"], bmeta1_pad])
    prep["bmeta2"] = np.concatenate(
        [prep["bmeta2"], np.zeros(extra, dtype=np.int32)]
    )
    prep["row_pos"] = np.concatenate(
        [prep["row_pos"], np.full((extra * block_rows, LANE), _PAD_ROW, np.int32)]
    )
    prep["emeta"] = np.concatenate(
        [prep["emeta"], np.zeros((extra * block_rows, LANE), np.int32)]
    )
    prep["n_blocks"] = target


def slot_sources(prep: Dict[str, np.ndarray], empty: int) -> np.ndarray:
    """(blocks, slots a block) source node of every slot of a packed
    layout, decoded from ``row_pos`` and ``emeta`` as the kernel's gather
    reads them; ``empty`` where a slot holds no pair (its row is beyond
    the table)."""
    slots = ROWS * prep["sub"] * LANE
    row = prep["row_pos"].reshape(-1, slots).astype(np.int64)
    emeta = prep["emeta"].reshape(-1, slots)
    src = (row * LANE + (emeta & 127)) * WORD_BITS + ((emeta >> 7) & 31)
    return np.where(row < prep["r_rows"], src, empty)


def device_args(prep: Dict[str, np.ndarray]) -> tuple:
    """The kernel operands (after flags/recv) in call order."""
    if "xla_src" in prep:
        return (prep["xla_src"], prep["xla_dst"])
    args = (prep["bmeta1"], prep["bmeta2"], prep["row_pos"], prep["emeta"])
    if "out_supers" in prep:
        args = args + (prep["super_ids"],)
    return args


def xla_tier(psrc, pdst, n: int, capacity: int) -> Dict[str, np.ndarray]:
    """A propagation tier held as raw pair arrays, padded to a static
    ``capacity`` with inert sink pairs (src=dst=n).  Propagated by an
    XLA scatter-max instead of the Pallas kernel: O(capacity) per
    fixpoint iteration, but zero pack cost and zero recompiles while
    the capacity is stable — the landing pad for the newest churn."""
    m = len(psrc)
    assert m <= capacity
    src = np.full(capacity, n, dtype=np.int32)
    dst = np.full(capacity, n, dtype=np.int32)
    src[:m] = psrc
    dst[:m] = pdst
    return {"xla_src": src, "xla_dst": dst, "capacity": capacity, "n": n}


def layout_spec(prep: Dict[str, np.ndarray]) -> tuple:
    """The static shape signature of a layout (kernel cache key
    component), one of:
      ("dense", n_blocks, sub, group)   — full layout, every supertile
      ("compact", n_blocks, out_tiles, sub, group) — only touched
        supertiles; the kernel output is scattered into the global
        contribution by the layout's ``super_ids`` operand
      ("xla", capacity)                 — raw pair arrays propagated by
        an XLA scatter-max; O(capacity) per iteration but zero pack and
        zero recompile cost, the landing tier for the newest churn
    Packed layouts sharing a trace must share (sub, group): the walk
    geometry fixes the dirty-list granularity."""
    if "xla_src" in prep:
        return ("xla", prep["capacity"])
    if "out_supers" in prep:
        return (
            "compact",
            prep["n_blocks"],
            prep["out_supers"],
            prep["sub"],
            prep["group"],
        )
    return ("dense", prep["n_blocks"], prep["sub"], prep["group"])


def build_layout_propagates(
    specs, n_super, r_rows, s_rows, interpret, dst_gate=False
):
    """One propagation kernel per packed layout spec (None for xla
    tiers), for the decremental wake."""
    out = []
    for spec in specs:
        if spec[0] == "dense":
            out.append(
                build_propagate(
                    spec[1], n_super, r_rows, s_rows, interpret,
                    sub=spec[2], group=spec[3], dst_gate=dst_gate,
                )
            )
        elif spec[0] == "compact":
            out.append(
                build_propagate(
                    spec[1], spec[2], r_rows, s_rows, interpret,
                    sub=spec[3], group=spec[4], dst_gate=dst_gate,
                )
            )
        else:
            out.append(None)
    return out


def build_propagate(
    n_blocks: int,
    out_tiles: int,
    r_rows: int,
    s_rows: int,
    interpret: bool,
    sub: int = None,
    group: int = None,
    dst_gate: bool = False,
):
    """One propagation sweep as a pallas_call: gather source bits from the
    packed table, one-hot segment-sum into per-supertile contributions.
    Returns ``propagate(d, l, [gate,] bmeta1, bmeta2, tables, row_pos,
    emeta) -> contributions``; ``propagate.with_steps`` returns beside
    them the grid steps the launch took, those of them that contracted,
    the chunk-iterations the steps' walks took and the loop trips they
    took them in.

    Operands (after the scalar-prefetch ones): the (2 * r_rows, LANE) bit
    tables (``walk_tables``: the full table over its bits that are new
    since the sweep before), then row_pos and emeta.  Scalar-prefetch
    operands are the dirty-chunk prefix D (size n_chunks + 1, D[c] =
    number of dirty chunks below c), the compacted dirty-chunk index list
    L, and bmeta1, bmeta2: each block walks only the *dirty* chunks inside
    its span, and gathers only their NEW bits.
    Correct under the trace's monotone OR-accumulation: a clean chunk's
    words are unchanged since the sweep that last walked them, so the
    skipped contribution is already in the mark vector; and so is that
    of a bit of a dirty chunk that was set before the last sweep: it was
    delivered in the sweep after it was set, or its destination tile was
    saturated (``GATE_SKIP``) and stays so while its marks stand, or a
    later wake forces that tile (``GATE_FULL``: the closure's members,
    fresh inserts, reused slots), and a forced block reads the FULL
    table.  A caller with no table of the sweep before hands the full
    table in both halves.

    The walk's loop: what does not depend on the chunk is made once a
    block (a slot's table row less its row class, its lane index by slot
    vreg; the gather's indices are ``emeta & 127`` and promised in
    bounds), each of the block's `sub` slot vregs gathers out of every
    table vreg of a trip in a row (one lane pattern, `group` permutes a
    chunk: the permute path is what a chunk costs, and a pattern that
    changes with every gather doubles it), and a trip walks TWO chunks,
    both chunk ids read and both table groups loaded before the first
    gather: a loop with a dynamic trip count is a basic block a trip, and
    nothing of the next chunk could start before the last select of this
    one.  An odd count's first chunk is walked by a loop of its own, of
    one trip.  Exactly one sub-chunk of one chunk hits a slot, so the
    contributions do not depend on the order (PERF.md section 6, PR 47).

    A block whose gather found no bit skips its contraction: the gathered
    bits are reduced to one scalar, and the one-hot operands, the MXU and
    the accumulation run under it.  All such a block still owes, when it
    is the first active block of its output tile, is the zero tile (the
    tile's buffer is written back when the block index changes, whatever
    the step did to it).  Contributions are bit-identical to contracting
    every block: a contraction of zeros adds zero.  The steps that did
    contract are counted where it happens, in an SMEM scalar output (the
    grid is sequential).  On a derivation of the 10M power-law graph
    25,885 of 69,782 steps have nothing to contract (PERF.md section 6,
    PR 39).

    A block with no chunk to walk is not visited at all.  Before each
    launch the callable computes, in XLA and by the kernel's own rule
    (``block_iters``), the list of blocks that have work, in block order;
    the list is one more scalar-prefetch operand, the index maps of
    row_pos, emeta and the output read block and tile through it, and the
    grid is as long as the list (a dynamic bound): a grid over all
    n_blocks paid 0.24 us for every step that skipped, 5.9 ms a sweep at
    10M actors, three steps in four of a derivation (PERF.md section 6,
    PR 32).  A tile's active blocks stay consecutive, so its first ACTIVE
    block initialises it and each output block still hits HBM once a
    sweep; a tile with none keeps the zeros of the plane aliased in as
    the output buffer.

    With ``dst_gate`` a fifth scalar-prefetch operand S (one int per
    output tile) selects the walk per block from the destination side:
    ``GATE_FULL`` (1) forces blocks whose output tile is flagged to walk
    their FULL chunk span regardless of the dirty lists.  The decremental
    wake's repair pass needs this: after unmarking a suspect region, the
    region's supertiles must re-derive their contributions from ALL their
    in-edges — including sources whose table groups did not change —
    which the source-side dirty machinery cannot express
    (ops/pallas_decremental.py).  ``GATE_SKIP`` (2) skips the block
    outright — the pull side of direction-optimizing propagation: a
    saturated destination tile (no unmarked in-use node left) cannot
    gain a bit from any contribution, so its blocks need not walk even a
    dirty span.  ``GATE_PUSH`` (0) is the default dirty-chunk walk.
    Skip wins over full: a tile both saturated and repair-gated has
    nothing left to re-derive (contributions are not carried across
    sweeps, only marks are).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if sub is None or group is None:
        d_sub, d_group = default_geometry(interpret)
        sub = d_sub if sub is None else sub
        group = d_group if group is None else group
    block_rows = ROWS * sub
    group_rows = ROWS * group
    span_mask = (1 << _SPAN_BITS) - 1

    def block_iters(d, gate, bmeta1, bmeta2):
        """Chunk-iterations per block this sweep: the kernel's own rule,
        on whole vectors (here) or on one block's scalars (in a step)."""
        c_lo = jax.lax.shift_right_logical(bmeta2, _SPAN_BITS)
        span = bmeta2 & span_mask
        n_iter = d[c_lo + span] - d[c_lo]
        if dst_gate:
            g = gate[bmeta1 >> 1]
            n_iter = jnp.where(
                g == GATE_SKIP, 0, jnp.where(g == GATE_FULL, span, n_iter)
            )
        return n_iter

    def active_blocks(d, gate, bmeta1, bmeta2):
        """(act, count, walks, trips): the blocks with work this sweep, in
        block order, in the first ``count`` entries of ``act`` (the rest
        the last block), the chunk-iterations their walks will take (a
        block without work has none) and the loop trips they take them
        in, two chunks a trip.  A sort and not a prefix sum and a
        scatter: on the v5e the scatter of 24,576 ids costs 122 us a
        launch, the sort 18 (PERF.md section 6, PR 32)."""
        with scope("active"):
            n_iter = block_iters(d, gate, bmeta1, bmeta2)
            active = n_iter > 0
            ids = jnp.arange(n_blocks, dtype=jnp.int32)
            act = jnp.sort(jnp.where(active, ids, n_blocks))
            return (
                jnp.minimum(act, n_blocks - 1),
                active.sum(dtype=jnp.int32),
                n_iter.sum(dtype=jnp.int32),
                # two chunks a trip, and a trip of one where the count is odd
                ((n_iter + 1) >> 1).sum(dtype=jnp.int32),
            )

    def kernel(*refs):
        if dst_gate:
            d_ref, l_ref, s_ref, meta1_ref, meta2_ref, act_ref = refs[:6]
        else:
            d_ref, l_ref, meta1_ref, meta2_ref, act_ref = refs[:5]
            s_ref = None
        # the aliased plane (refs[-6]) is never read: it is what an
        # unvisited output tile holds
        table_ref, row_ref, emeta_ref, out_ref, cnt_ref = refs[-5:]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            cnt_ref[0] = 0

        blk = act_ref[i]
        m1 = meta1_ref[blk]
        m2 = meta2_ref[blk]
        c_lo = jax.lax.shift_right_logical(m2, _SPAN_BITS)
        span = m2 & span_mask
        # The first ACTIVE block of a tile initialises it: the list keeps
        # block order, so a tile's active blocks are consecutive steps.
        before = meta1_ref[act_ref[jnp.maximum(i - 1, 0)]]
        first = (i == 0) | ((before >> 1) != (m1 >> 1))

        j_lo = d_ref[c_lo]
        n_iter = block_iters(d_ref, s_ref, m1, m2)
        if dst_gate:
            gated = s_ref[m1 >> 1] == GATE_FULL
            l_cap = l_ref.shape[0] - 1
            # a forced block re-derives from the FULL table (the first
            # half of the operand); every other block gathers what is NEW
            half = jnp.where(gated, 0, r_rows)
        else:
            gated = None
            half = r_rows

        row_iota = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANE), 0)
        r8_iota = row_iota & 7  # slot row class = src row mod 8

        @pl.when(n_iter > 0)
        def _():
            row_pos = row_ref[:]
            emeta = emeta_ref[:]
            lane_idx = emeta & 127
            bit_pos = (emeta >> 7) & 31

            # A slot's table row less its row class, once a block: slot
            # row (sb * 8 + r8) holds a source of table row key + r8, so
            # a sub-chunk hits it when key is the sub-chunk's first row.
            key = row_pos - r8_iota
            vregs = [slice(k * ROWS, (k + 1) * ROWS) for k in range(sub)]
            idx_rows = [lane_idx[v, :] for v in vregs]
            key_rows = [key[v, :] for v in vregs]

            def chunk_of(j):
                if dst_gate:
                    # Gated blocks walk the plain span; ungated blocks
                    # the compacted dirty list (clamped load: the list
                    # value is unused when gated).
                    lc = l_ref[jnp.minimum(j_lo + j, l_cap)]
                    return jnp.where(gated, c_lo + j, lc)
                return l_ref[j_lo + j]

            def walk(chunks, accs):
                # Each chunk is a group_rows-row table group.  All the
                # chunk ids are read and all the groups loaded before any
                # gather (a loop with a dynamic trip count is a basic
                # block a trip: what one trip holds is what the scheduler
                # can overlap).  Then each of the block's `sub` slot vregs
                # gathers its lanes out of every table vreg of the trip
                # in a row (one lane pattern, `group` permutes a chunk)
                # and keeps the word of the sub-chunk its source row
                # falls in: exactly one sub-chunk of one chunk hits a
                # slot, so the order of the selects does not matter.
                bases = [c * group_rows for c in chunks]
                tabs = [
                    table_ref[pl.ds(half + base, group_rows), :]
                    for base in bases
                ]
                accs = list(accs)
                for k in range(sub):
                    a = accs[k]
                    for base, tab_g in zip(bases, tabs):
                        for s in range(group):
                            # lane_idx is ``emeta & 127``: in bounds
                            g = jnp.take_along_axis(
                                tab_g[s * ROWS : (s + 1) * ROWS, :],
                                idx_rows[k], axis=1, mode="promise_in_bounds",
                            )
                            a = jnp.where(
                                key_rows[k] == base + s * ROWS, g, a
                            )
                    accs[k] = a
                return tuple(accs)

            # Two chunks a trip; an odd count's first chunk in a loop of
            # its own, of one trip (``active_blocks`` counts both's trips).
            odd = n_iter & 1
            accs = jax.lax.fori_loop(
                0,
                odd,
                lambda _, accs: walk([chunk_of(0)], accs),
                (jnp.zeros((ROWS, LANE), jnp.int32),) * sub,
            )
            accs = jax.lax.fori_loop(
                0,
                n_iter >> 1,
                lambda t, accs: walk(
                    [chunk_of(odd + 2 * t), chunk_of(odd + 2 * t + 1)], accs
                ),
                accs,
            )
            words = jnp.concatenate(accs, axis=0) if sub > 1 else accs[0]
            bits = jax.lax.shift_right_logical(words, bit_pos) & 1
            # Sublane rows on the VPU first, then one lane reduce.
            any_bit = jnp.max(jnp.max(bits, axis=0, keepdims=True)) > 0

            # A block that gathered nothing has nothing to contract: the
            # operands below and the MXU are what a visited block costs.
            @pl.when(any_bit)
            def _():
                cnt_ref[0] = cnt_ref[0] + 1
                dst_lane = (emeta >> 12) & 127
                dst_sub = (emeta >> 19) & 31
                # One-hot segment-sum on the MXU: one (s_rows,
                # block_rows*LANE) x (LANE, block_rows*LANE) contraction a
                # block, over the slot index, which stays on lanes in both
                # operands (no lane vector moves onto sublanes).  The
                # one-hots are written as the int32 WORDS of the bf16
                # operands (``pltpu.bitcast``'s row order: word row i
                # holds bf16 rows 2i, low half, and 2i + 1).  A slot's
                # word row is its even row, and the word's value, made
                # once a block, has bf16 1.0 in the half the row's parity
                # picks: one compare and one select a vreg and no convert,
                # where ``(iota == x).astype(bf16)`` went through f32 and
                # a pack, 2.34 us a block against 0.45 (PERF.md section 6,
                # PR 41).  Exact: 0 and 1.0 in bf16, summed in f32.
                one_sub = jnp.where((dst_sub & 1) > 0, _ONE_HI, _ONE_LO)
                one_lane = jnp.where((dst_lane & 1) > 0, _ONE_HI, _ONE_LO)

                def one_hot_words(n_words, row, word):
                    """bf16 (2 * n_words, block_rows*LANE): column k holds
                    ``word[k]`` in word row ``row[k] // 2``."""
                    even = 2 * jax.lax.broadcasted_iota(
                        jnp.int32, (n_words, LANE), 0
                    )
                    row = row & ~1
                    parts = [
                        jnp.where(
                            even == row[r, :][None, :], word[r, :][None, :], 0
                        )
                        for r in range(block_rows)
                    ]
                    return pltpu.bitcast(
                        jnp.concatenate(parts, axis=1), jnp.bfloat16
                    )

                # the sub one-hot times the gathered bit; an odd last
                # row's word has a spare half
                a = one_hot_words(
                    (s_rows + 1) // 2, dst_sub,
                    jnp.where(bits > 0, one_sub, 0),
                )
                b = one_hot_words(LANE // 2, dst_lane, one_lane)
                acc = jax.lax.dot_general(
                    a, b, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if s_rows % 2:
                    acc = acc[:s_rows]

                @pl.when(first)
                def _():
                    out_ref[:] = acc

                @pl.when(jnp.logical_not(first))
                def _():
                    out_ref[:] = out_ref[:] + acc

            # What a skipped block still owes: a tile's buffer is written
            # back when the block index changes, whatever its steps did.
            @pl.when(first & jnp.logical_not(any_bit))
            def _():
                out_ref[:] = jnp.zeros((s_rows, LANE), jnp.float32)

        # only the one step of a launch with no active block gets here
        @pl.when(jnp.logical_not(n_iter > 0) & first)
        def _():
            out_ref[:] = jnp.zeros((s_rows, LANE), jnp.float32)

    def imap_block(i, *meta):
        return (meta[-1][i], 0)

    def imap_table(i, *_meta):
        return (0, 0)

    def imap_out(i, *meta):
        return (meta[-3][meta[-1][i]] >> 1, 0)

    n_scalars = 6 if dst_gate else 5
    blockmap = pl.BlockSpec((block_rows, LANE), imap_block)
    out_shape = jax.ShapeDtypeStruct((out_tiles * s_rows, LANE), jnp.float32)
    cnt_shape = jax.ShapeDtypeStruct((1,), jnp.int32)

    def assemble(steps, *operands):
        """The call over a grid of ``steps``, a traced value: a dynamic
        bound is part of the grid spec, so the call is assembled where it
        is traced and not once in ``build_propagate``'s body."""
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_scalars,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # plane, aliased out
                # bit tables, full over new: whole array, VMEM-resident
                # across all steps
                pl.BlockSpec((2 * r_rows, LANE), imap_table),
                blockmap,  # row_pos
                blockmap,  # emeta
            ],
            out_specs=[
                pl.BlockSpec((s_rows, LANE), imap_out),
                # the steps that contracted: the grid is sequential
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
        )
        return pl.pallas_call(  # uigc-lint: disable=UC304
            kernel,
            grid_spec=grid_spec,
            out_shape=[out_shape, cnt_shape],
            input_output_aliases={n_scalars: 0},
            interpret=interpret,
            name=KERNEL_NAME,
        )(*operands)

    # Jitted (inlined, as ``pallas_call``'s own wrapper is): the closure
    # and the repair loop of a wake program launch with the same shapes,
    # so the second is a cache hit and the program traces and lowers ONE
    # kernel per layout, as it did when the call was built once (0.4 s a
    # program otherwise, 5 s of the served cell's set-up).
    launch = jax.jit(assemble, inline=True)

    def onto(plane, d, l, *operands):
        """(contributions, grid steps that had work, steps of them that
        contracted, chunk-iterations the steps walked, loop trips they
        walked them in) of one launch over the output buffer ``plane``,
        which it consumes.  The grid is as long as the list of active
        blocks, and at least one step: a launch with nothing to do writes one zero
        tile.  Tiles with no active block are never visited and keep what
        ``plane`` held; the first active block of a tile overwrites it."""
        gate = operands[0] if dst_gate else None
        bmeta1, bmeta2, tables, row_pos, emeta = operands[-5:]
        act, count, walks, trips = active_blocks(d, gate, bmeta1, bmeta2)
        out, contracted = launch(
            jnp.maximum(count, 1), d, l, *operands[:-3], act, plane, tables,
            row_pos, emeta,
        )
        return out, count, contracted[0], walks, trips

    def with_steps(d, l, *operands):
        """``onto`` a zero plane: an unvisited tile contributes nothing."""
        zeros = jnp.zeros(out_shape.shape, out_shape.dtype)
        return onto(zeros, d, l, *operands)

    def propagate(d, l, *operands):
        return with_steps(d, l, *operands)[0]

    propagate.with_steps = with_steps
    propagate.onto = onto
    return propagate


def default_interpret() -> bool:
    """Whether a kernel built without an explicit ``interpret`` runs in
    Pallas interpret mode: False on a TPU (Mosaic compiles it), True on
    any other platform (Mosaic cannot compile there — the CPU test
    tier).  Callers that must not interpret assert on what this
    resolved to (ArrayShadowGraph.trace_impl) instead of trusting it."""
    import jax

    from ..utils.platform import is_tpu_platform

    return not is_tpu_platform(jax.devices()[0].platform)
