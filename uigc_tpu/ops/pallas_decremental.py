"""Decremental per-wake garbage detection: suspect closure + region repair.

The full trace re-derives reachability from the seeds every wake — ~12
propagation sweeps over a 10M-actor graph even when the wake's churn
touched a few thousand nodes.  The reference never faces this regime (its
collector traces ~10^4-10^5 node-local shadows per 50ms wake,
LocalGC.scala:144-186); at BASELINE.md's 10M-actor scale the <=10ms p50
detection target is unreachable by full re-trace.  Marks
do not shrink monotonically under churn — releasing a ref can turn live
actors into garbage — so a sound incremental wake must re-derive exactly
the region whose old derivation might have depended on what changed.

Per wake, relative to the previous fixpoint:

1. **Suspect seeds** ``S``: nodes whose mark derivation inputs may have
   shrunk — destinations of deleted propagation pairs, previously-seed
   nodes that stopped seeding (busy cleared, recv drained, root dropped),
   and newly-halted nodes (their out-edges stop propagating) — all
   intersected with the previous marks (an unmarked node has nothing to
   invalidate).
2. **Closure**: the forward closure of ``S`` through the current layout,
   restricted to previously-marked nodes — every mark that transitively
   depended on a suspect.  A monotone fixpoint over the dirty walk
   chunks, so what it costs is the chunks its frontier touches, sweep
   after sweep — and under CRGC that is seldom a region.  Marks flow
   along references AND from a child to its supervisor, so every live
   actor reaches a root by its supervisor chain, a root reaches every
   live actor, the marked set is one strongly connected component, and
   the closure of ANY marked suspect among live actors is every mark (at
   10M actors one suspect closes over all 5M marks in 16 sweeps, 20,000
   in 10: PERF.md section 6, PR 30).  Only suspects on an island that
   no supervisor chain ties to the live set (halted, unrooted or freed
   actors) have a closure smaller than the marks.  So the loop counts
   the chunk walks it spends and **gives up** once they reach a price,
   a fixed share of what the last derivation from nothing cost on this
   graph (``pt.closure_gives_up``; the derivation's walks ride with the
   previous state as a device scalar).
3. **Repair**: clear the closure's marks, reseed from the current seed
   vector, and run the propagation fixpoint where the FIRST sweep forces
   blocks whose output supertile intersects the closure to walk their
   full chunk span (``build_propagate(dst_gate=True)``) — those
   supertiles must re-derive contributions from ALL in-edges, including
   sources whose table groups never changed.  Later sweeps are monotone
   growth and fall back to the ordinary dirty-group walk.
   **The cold road**: when the closure gave up, or there is no previous
   fixpoint (first wake, ``invalidate()``, ``rebuild()``), the region is
   everything: the marks restart from the seeds, the dirty lists are
   taken against a zero table and no supertile is forced, so the first
   sweep walks the chunks that hold seeds and nothing else.

Soundness: a previously-marked node outside the closure retains a support
path untouched by any deletion, de-seeding, or halt (otherwise some node
on the path would have entered ``S`` and pushed the rest into the
closure), so its mark stays valid; closure members are re-derived from
scratch against that stable boundary.  Additions (new pairs, new seeds)
ride the same repair fixpoint through the ordinary monotone machinery.
The cold road needs no such argument: from the seeds against a zero
table every marked source is a changed word, so the plain dirty walk IS
the full trace, exact by construction: it is the one derivation from
nothing a single chip has (:func:`derive` runs it over given layouts,
the tracer's first wake over its own).  A wake that gives up costs at most
(1 + ``pt.CLOSURE_SHARE``) derivations and the sweep that crossed the
price.

Differential coverage: tests/test_pallas_decremental.py drives random
mutation/flag-change schedules and compares every wake against the numpy
oracle re-run from scratch (trace_marks_np, the reference semantics of
ShadowGraph.java:205-289).
"""

from __future__ import annotations

import weakref
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from . import pallas_trace as pt
from . import trace as trace_ops
from ..utils import events
from ..utils.validation import require
from .pallas_incremental import IncrementalPallasLayout
from .slotmap import PairLog

_fn_cache: Dict[tuple, object] = {}

#: root of the wake program's named scopes; its phases nest under it as
#: ``uigc.wake/<phase>`` and the shared helpers of pallas_trace.py
#: (``push``, ``hits``, ``jump``, ``sat``, ``dirty``) under those
WAKE_SCOPE = "uigc.wake"
WAKE_PHASES = ("pack", "suspects", "closure", "gate", "repair")
#: wakes whose stats handles a tracer keeps (device arrays of a few
#: hundred bytes each, read back only by :meth:`DecrementalTracer.wake_stats`)
STATS_KEPT = 256
#: the wake program's counters (``_build_wake_fn``'s ``stats``; the
#: sharded wake's carry the same keys, a shard a row): those kept per
#: repair sweep, for the first ``pt.MAX_SWEEP_STATS``, and all of them
SWEEP_STATS = ("dirty_chunks", "tiles_skipped", "pull_on", "jump_on")
WAKE_STATS = (
    "closure_sweeps", "closure_bailed", "closure_spent", "gated_tiles",
    "n_sweeps", "kernel_steps", "kernel_contractions", "kernel_chunk_walks",
    "kernel_walk_trips", "kernel_steps_full", "jump_sweeps", "jump_spent",
) + SWEEP_STATS

_live_tracers: "weakref.WeakSet" = weakref.WeakSet()


def live_tracers() -> list:
    """The objects alive in this process that run wake programs and keep
    their counters (:class:`DecrementalTracer`; a mesh backend's sharded
    wake, ``engines/crgc/mesh.py``): the road by which a reader that
    holds no reference to the system under test (a benchmark's per-layer
    reader, after the window) finds their ``wake_stats()``."""
    return list(_live_tracers)


def track(tracer) -> None:
    """Have :func:`live_tracers` find ``tracer`` (anything with
    :meth:`DecrementalTracer.wake_stats`'s signature) while it lives."""
    _live_tracers.add(tracer)


def _build_wake_fn(
    n: int,
    specs: tuple,
    n_super: int,
    r_rows: int,
    s_rows: int,
    interpret: bool,
    mode: str = pt.MODE_PUSH,
    pull_density: float = pt.DEFAULT_PULL_DENSITY,
):
    """The jitted wake: (flags, recv, del_words, fresh_words, prev
    state, [jump parents,] *layout args) -> (mark_w, seed_w, halted_w,
    iu_w, table, walks, stats): the previous state and the next are five
    word tables, (r_rows, LANE) int32 device arrays, and an int32 scalar,
    the chunk walks of the last derivation from nothing; ``stats`` is
    the wake's sweep counters (below).

    ``mode`` applies to the REPAIR fixpoint only (pallas_trace MODE_*
    docs): on a cold start the repair IS the full derivation, which is
    where the O(diameter) sweep wall lives.  ``jump`` runs the pointer
    jump in every repair sweep; ``auto`` decides per sweep, from the
    dirty-chunk counts in its carry, and runs ``jump_sweep`` under a
    ``lax.cond`` only once the fixpoint has stayed sparse for what one
    jump sweep costs (``pt.auto_jump_policy``): on the v5e a jump sweep
    over 10M actors costs nine push sweeps, so a shallow repair never
    engages it and a deep one does after a bounded wait.  Until then the
    jump-parent operand passes through the loop untouched.  The closure
    phase stays a plain push fixpoint, and a priced one: among live
    actors it would end as every mark (the module docstring), so it ends
    itself once its chunk walks reach ``pt.closure_price(prev_walks)``
    and the wake takes the cold road: the derivation from the seeds,
    ungated, which is also the road of a wake with no previous table.
    ``prev_walks``, the chunk walks of the last such derivation, comes in
    with the previous state and goes out with the next (this wake's own
    walks if it was cold).  Jump hits in the closure would only
    over-approximate it — sound but more re-derivation for nothing.

    ``stats`` is counted by the program that runs, every wake (one
    program per geometry; a few scalar updates per sweep):
    ``closure_sweeps``, ``closure_spent`` (the closure's chunk walks at
    exit, against :attr:`DecrementalTracer.closure_price`),
    ``closure_bailed`` (1 if it gave up), ``gated_tiles`` (supertiles
    the first repair sweep walks in full; 0 on the cold road),
    ``n_sweeps`` (repair), ``kernel_steps`` and ``kernel_steps_full``
    (grid steps the propagate kernels took in both loops, over all packed
    layouts: the blocks that had work; and launches x blocks, what a grid
    over every block would take), ``kernel_contractions`` (the steps that
    contracted, counted by the kernels themselves: the blocks whose
    gather found a bit), ``kernel_chunk_walks`` (the chunk-iterations the
    kernels' walks took: per block with work, the dirty chunks in its
    span, or the whole span where the gate forces it; the sum of the
    vector the list of active blocks is made from), ``kernel_walk_trips``
    (the trips of the walks' loops, two chunks a trip: per block with
    work, half its chunk-iterations rounded up, so ``2 - walks / trips``
    is the share of trips that had one chunk to walk and walked it
    twice), ``jump_sweeps`` (the
    repair sweeps that ran the jump) and ``jump_spent`` (the policy's
    ``spent`` at exit, to be read against the static
    :attr:`DecrementalTracer.jump_price`) are int32 scalars, ``dirty_chunks``,
    ``tiles_skipped``, ``pull_on`` and ``jump_on`` hold the repair
    fixpoint's first ``pt.MAX_SWEEP_STATS`` sweeps (later ones fold into
    the last slot).  They stay on the device until somebody asks
    (:meth:`DecrementalTracer.wake_stats`).

    The phases carry named scopes (``uigc.wake/pack``, ``/suspects``,
    ``/closure``, ``/gate``, ``/repair``, and the helpers' own inside
    the two loops): compile-time metadata that a device trace shows per
    operation, so the wake's device time can be summed by phase."""
    import jax
    import jax.numpy as jnp

    F = trace_ops
    require(
        mode in pt.TRACE_MODES, "config.trace_mode",
        "bad trace mode", mode=mode, valid=pt.TRACE_MODES,
    )
    use_jump = mode in (pt.MODE_JUMP, pt.MODE_AUTO)
    use_pull = mode in (pt.MODE_PULL, pt.MODE_AUTO)

    geoms = {spec[-2:] for spec in specs if spec[0] != "xla"}
    assert len(geoms) == 1, "packed layouts must share (sub, group)"
    ((_, group),) = geoms
    group_rows = pt.ROWS * group

    # One dst-gated kernel per packed layout serves both phases: a zero
    # gate vector makes it behave exactly like the plain kernel.
    gated = pt.build_layout_propagates(
        specs, n_super, r_rows, s_rows, interpret, dst_gate=True
    )

    n_chunks = r_rows // group_rows
    launch_blocks = sum(spec[1] for spec in specs if spec[0] != "xla")
    n_pad_nodes = n_super * s_rows * pt.LANE
    t_rows = n_super * s_rows
    sup_words = s_rows * (pt.LANE // pt.WORD_BITS)  # words per supertile
    pull_cut = max(1, int(round(pull_density * n_chunks)))
    auto_jump = pt.auto_jump_policy(
        n, pt.kernel_slots(specs), n_chunks, pull_cut
    )

    def wake_fn(flags, recv_count, del_w, fresh_w, prev_mark_w,
                prev_seed_w, prev_halted_w, prev_iu_w, prev_table,
                prev_walks, *rest):
        with pt.scope(WAKE_SCOPE):
            return wake_body(flags, recv_count, del_w, fresh_w,
                             prev_mark_w, prev_seed_w, prev_halted_w,
                             prev_iu_w, prev_table, prev_walks, *rest)

    def wake_body(flags, recv_count, del_w, fresh_w, prev_mark_w,
                  prev_seed_w, prev_halted_w, prev_iu_w, prev_table,
                  prev_walks, *rest):
        if use_jump:
            jump_j0, *layout_args = rest
        else:
            jump_j0, layout_args = None, rest
        def pack(active):
            return pt.pack_bools(active, n, r_rows, jnp)

        def dirty_chunks(table, table_prev):
            return pt.dirty_group_lists(
                table, table_prev, n_chunks, group_rows, jnp
            )

        gated_sweep = pt.build_sweep_contribs(
            specs, gated, n, n_super, s_rows, jnp
        )

        def contribs(table, table_prev, d, l, gate):
            """One propagation sweep over every layout (shared loop:
            pallas_trace.build_sweep_contribs), the grid steps its kernels
            took, those of them that contracted, the chunk-iterations they
            walked and the loop trips they walked them in; a zero gate
            vector makes the dst-gated kernels behave exactly like the plain
            ones.  ``d`` and ``l`` are the dirty lists of ``table``
            against ``table_prev``, the table of the sweep before: the
            kernels gather the bits that are new since (what was set
            before has been delivered, or its tile is forced)."""
            return gated_sweep.with_steps(
                pt.walk_tables(table, table_prev, jnp), d, l, layout_args,
                gate=gate,
            )

        with pt.scope("pack"):
            in_use = (flags & F.FLAG_IN_USE) != 0
            halted = (flags & F.FLAG_HALTED) != 0
            seed = (
                ((flags & F.FLAG_ROOT) != 0)
                | ((flags & F.FLAG_BUSY) != 0)
                | (recv_count != 0)
                | ((flags & F.FLAG_INTERNED) == 0)
            )
            iu_w = pack(in_use)
            nh_w = pack(~halted)
            halted_w = pack(halted)
            seed_w = pack(in_use & (~halted) & seed)

        # --- 1. suspect seeds --------------------------------------- #
        # A previously-marked node is suspect when any input of its old
        # derivation may have shrunk: it was freed (in_use dropped — the
        # oracle gates marks on in_use, so the mark itself must go), it
        # newly halted (stops propagating), it stopped seeding, or an
        # in-edge was deleted.
        with pt.scope("suspects"):
            s_w = (
                (~iu_w)
                | (halted_w & ~prev_halted_w)
                | (prev_seed_w & ~seed_w)
                | del_w
            ) & prev_mark_w

        # --- 2. closure: marks that depended on a suspect ----------- #
        # The loop pays for itself in chunk walks and leaves, still
        # ``changed``, once they reach the price (pt.closure_gives_up):
        # under CRGC's supervisor edges the closure of a live suspect is
        # every mark, and finding that out costs as much as acting on it.
        def c_cond(carry):
            changed, spent = carry[4], carry[6]
            return changed & ~pt.closure_gives_up(spent, prev_walks)

        zero_gate = jnp.zeros((n_super,), jnp.int32)
        zero_i = jnp.zeros((), jnp.int32)

        def c_body(carry):
            (closure_w, closure_prev, d, l, _, sweeps, spent, steps,
             contracted, walked, tripped) = carry
            hits2d, took, did, iters, trips = contribs(
                closure_w, closure_prev, d, l, zero_gate
            )
            hit_w = pt.pack_hits_table(hits2d, r_rows, jnp)
            new_closure = closure_w | (hit_w & prev_mark_w)
            d2, l2, changed = dirty_chunks(new_closure, closure_w)
            return (new_closure, closure_w, d2, l2, changed, sweeps + 1,
                    spent + d[n_chunks], steps + took, contracted + did,
                    walked + iters, tripped + trips)

        with pt.scope("closure"):
            zero_w = jnp.zeros_like(s_w)
            d0, l0, changed0 = dirty_chunks(s_w, zero_w)
            (closure_w, _, _, _, closure_bailed, closure_sweeps,
             closure_spent, closure_steps, closure_contracted,
             closure_walked, closure_tripped) = jax.lax.while_loop(
                c_cond, c_body,
                (s_w, zero_w, d0, l0, changed0, zero_i, zero_i, zero_i,
                 zero_i, zero_i, zero_i),
            )
            # The cold road: the region to repair is everything, because
            # the closure said so by its cost or because there is no
            # previous fixpoint (first wake, invalidate(), rebuild()).
            # Word-table selects on one scalar, not a lax.cond.
            cold = closure_bailed | ~prev_table.any()

        # per-supertile gate: closure members must re-derive; fresh
        # insert destinations must see their new pairs' contributions at
        # least once (a new edge changes no node word, so the dirty
        # machinery alone would never walk it — and a pair frozen into a
        # packed tier before its first propagation would otherwise be
        # skipped forever).  Gating only ADDS contributions, so it is
        # monotone-safe.
        def per_super(words):
            return (
                words.reshape(-1)[: n_super * sup_words]
                .reshape(n_super, sup_words)
                .any(axis=1)
                .astype(jnp.int32)
            )

        # Newly-in-use nodes (slot reuse) are the additive mirror of the
        # fresh-insert case: reachable but with no word change anywhere,
        # so their supertile must re-derive once to pick the mark up.
        # On the cold road nothing is gated: against a zero table every
        # marked source is a changed word, so the dirty walk alone is the
        # full trace and the first sweep walks the seeds' chunks only.
        with pt.scope("gate"):
            suspect_g = jnp.where(
                cold,
                zero_gate,
                per_super(closure_w)
                | per_super(fresh_w)
                | per_super(iu_w & ~prev_iu_w),
            )

        # --- 3. repair fixpoint ------------------------------------- #
        def r_cond(carry):
            return carry["changed"]

        def run_jump(mark_w, table, jump_j):
            jh, jump_j = pt.jump_sweep(table, jump_j, trans_w, n, jnp)
            with pt.scope("jump"), pt.scope("pack"):  # its hits' pack is its cost
                return mark_w | (pack(jh) & iu_w), jump_j

        def r_body(carry):
            mark_w, table = carry["mark"], carry["table"]
            d, l = carry["d"], carry["l"]
            n_dirty = d[n_chunks]
            # Gate composition: the repair forcing (GATE_FULL on suspect
            # tiles, first sweep only) under the pull skip (GATE_SKIP on
            # saturated tiles — a saturated tile has nothing left to
            # re-derive, contributions are not carried across sweeps).
            base_gate = jnp.where(carry["use_gate"], suspect_g, zero_gate)
            if use_pull:
                sat = pt.saturated_tiles(
                    mark_w, iu_w, n_super, sup_words, jnp
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
            hit_w = pt.pack_hits_table(hits2d, r_rows, jnp)
            new_mark_w = mark_w | (hit_w & iu_w)
            if use_jump:
                new_mark_w, jump_j, jump_state = pt.jump_step(
                    mode, auto_jump, carry["jump_state"], n_dirty,
                    run_jump, new_mark_w, table, carry["jump"],
                )
            new_table = new_mark_w & nh_w
            d2, l2, changed = dirty_chunks(new_table, table)
            # The gated sweep fully re-derives suspect supertiles; the
            # monotone dirty machinery is sufficient (and cheaper) after.
            i = jnp.minimum(carry["sweep_i"], pt.MAX_SWEEP_STATS - 1)
            out = dict(carry, mark=new_mark_w, table=new_table,
                       table_prev=table, d=d2,
                       l=l2, use_gate=jnp.array(False), changed=changed,
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
            kept_w = jnp.where(cold, zero_w, prev_mark_w & ~closure_w)
            mark_w0 = kept_w | seed_w
            table0 = mark_w0 & nh_w
            table_prev0 = jnp.where(cold, zero_w, prev_table)
            rd0, rl0, rchanged0 = dirty_chunks(table0, table_prev0)
            trans_w = iu_w & nh_w  # jump-transparent intermediates
            # Run at least one gated sweep whenever anything is suspect,
            # even if the table diff alone is empty.
            run0 = rchanged0 | (suspect_g.sum() > 0)
            zero_stats = jnp.zeros((pt.MAX_SWEEP_STATS,), jnp.int32)
            carry0 = {"mark": mark_w0, "table": table0,
                      "table_prev": table_prev0, "d": rd0,
                      "l": rl0, "use_gate": jnp.array(True),
                      "changed": run0,
                      "sweep_i": zero_i, "walks": zero_i, "steps": zero_i,
                      "contracted": zero_i, "walked": zero_i,
                      "tripped": zero_i,
                      "st_dirty": zero_stats}
            if use_jump:
                carry0.update(jump=jump_j0.astype(jnp.int32),
                              jump_state=pt.jump_state0(mode, jnp),
                              jump_sweeps=zero_i, st_jump=zero_stats)
            if use_pull:
                carry0.update(st_skip=zero_stats, st_pull=zero_stats)
            out = jax.lax.while_loop(r_cond, r_body, carry0)
        # what a derivation from nothing costs on this graph, for the
        # next wakes' closure price: this wake's walks if it was one
        walks = jnp.where(cold, out["walks"], prev_walks)
        stats = {
            "closure_sweeps": closure_sweeps,
            "closure_bailed": closure_bailed.astype(jnp.int32),
            "closure_spent": closure_spent,
            # supertiles whose blocks the first repair sweep walks in full
            "gated_tiles": suspect_g.sum(),
            "n_sweeps": out["sweep_i"],
            # grid steps the kernels took in both loops, and what as many
            # launches over every block would have taken
            "kernel_steps": closure_steps + out["steps"],
            # those of them that gathered a new bit and contracted
            "kernel_contractions": closure_contracted + out["contracted"],
            # the chunk-iterations their walks took
            "kernel_chunk_walks": closure_walked + out["walked"],
            # the trips of the walks' loops, two chunks a trip
            "kernel_walk_trips": closure_tripped + out["tripped"],
            "kernel_steps_full": (closure_sweeps + out["sweep_i"])
            * launch_blocks,
            "dirty_chunks": out["st_dirty"],
            "tiles_skipped": out.get("st_skip", zero_stats),
            "pull_on": out.get("st_pull", zero_stats),
            "jump_sweeps": out.get("jump_sweeps", zero_i),
            "jump_on": out.get("st_jump", zero_stats),
            # the policy's chunk walks spent while sparse, at exit
            "jump_spent": out["jump_state"][1] if use_jump else zero_i,
        }
        return (out["mark"], seed_w, halted_w, iu_w, out["table"], walks,
                stats)

    jitted = jax.jit(wake_fn)
    #: what ``auto`` prices one jump sweep at, in chunk walks (static)
    jitted.jump_price = auto_jump.price if use_jump else None
    return jitted


def get_wake_fn(n, specs, n_super, r_rows, s_rows, interpret=None,
                mode=pt.MODE_PUSH, pull_density=pt.DEFAULT_PULL_DENSITY):
    """Cached jitted wake fn, one per geometry and mode."""
    if interpret is None:
        interpret = pt.default_interpret()
    key = (
        n, tuple(specs), n_super, r_rows, s_rows, interpret, mode,
        pull_density,
    )
    fn = _fn_cache.get(key)
    if fn is None:
        import time as _time

        t0 = _time.perf_counter()
        fn = _fn_cache[key] = _build_wake_fn(
            n, tuple(specs), n_super, r_rows, s_rows, interpret,
            mode=mode, pull_density=pull_density,
        )
        if events.recorder.enabled:
            # Compile-cache plane (telemetry/device.py): one miss per
            # geometry is healthy; a per-wake miss stream for one
            # (tag, geom) is a shape-key bug (recompile_storm).
            events.recorder.commit(
                events.COMPILE, duration_s=_time.perf_counter() - t0,
                tag="dec_wake", geom=events.compile_geom(key), hit=False,
            )
    elif events.recorder.enabled:
        events.recorder.commit(
            events.COMPILE, tag="dec_wake",
            geom=events.compile_geom(key), hit=True,
        )
    return fn


def wake_fn_for(preps, interpret, mode, pull_density):
    """The wake program for the geometry ``preps`` have (the first, a
    packed layout, pins it)."""
    first = preps[0]
    return get_wake_fn(
        first["n"],
        tuple(pt.layout_spec(p) for p in preps),
        first["n_super"],
        first["r_rows"],
        first["s_rows"],
        interpret,
        mode=mode,
        pull_density=pull_density,
    )


def no_previous_state(r_rows: int) -> tuple:
    """The previous state of a wake that has none: five zero word tables
    and zero walks.  Every previous mark is gone, so everything must
    re-derive, which the wake does on its cold road (empty suspects, no
    gate, the seeds' chunks as the dirty set)."""
    import jax

    z = jax.device_put(np.zeros((r_rows, pt.LANE), np.int32))
    return (z, z, z, z, z, jax.device_put(np.zeros((), np.int32)))


def host_stats(host: dict) -> dict:
    """One wake's counters, read back, as :meth:`DecrementalTracer.wake_stats`
    and :func:`derive` return them (of a sharded wake: one shard's)."""
    k = min(int(host["n_sweeps"]), pt.MAX_SWEEP_STATS)
    return {
        key: host[key][:k].tolist() if key in SWEEP_STATS else int(host[key])
        for key in WAKE_STATS
    }


def id_words(id_chunks: List[np.ndarray], n_words: int) -> np.ndarray:
    """Id arrays (duplicates and all) ORed into ``n_words`` flat uint32
    words, bit ``i & 31`` of word ``i >> 5`` for id ``i``: a wake's
    suspects as the wake programs take them, one chip's and a mesh's."""
    ids = np.concatenate(id_chunks)
    words = np.zeros(n_words, dtype=np.uint32)
    np.bitwise_or.at(
        words, ids >> 5, np.uint32(1) << (ids & 31).astype(np.uint32)
    )
    return words


def read_counters(counters) -> List[dict]:
    """The counters of some wakes (:meth:`DecrementalTracer.
    last_counters`, or what a wake program returned last), read back
    from the device now, in one crossing, as :meth:`DecrementalTracer.
    wake_stats` gives them."""
    import jax

    return [
        host_stats(host)
        for host in jax.device_get(list(counters))  # readback: a few hundred bytes of counters per wake, on request
    ]


def derivation(flags, recv_count, preps, interpret=None, mode=pt.MODE_PUSH,
               pull_density=pt.DEFAULT_PULL_DENSITY, jump_parent=None):
    """A derivation from nothing over these layouts, as (fn, args): the
    wake program for their geometry and its operands with no suspects and
    no previous state, so that ``fn(*args)`` runs the cold road, as a
    tracer's first wake does.

    The layouts share a node space; their contributions are combined
    before thresholding, so the union of their pairs propagates.  The
    first must be a packed (non-xla) one; it pins the geometry.  ``mode``
    jump/auto requires ``jump_parent``, the (n + 1,) min-source parent
    array over the SAME live pair set the layouts hold
    (``pt.jump_parents`` / ``IncrementalPallasLayout.jump_parent``): a
    stale parent crossing a deleted pair would carry marks along a dead
    edge."""
    first = preps[0]
    n = first["n"]
    require(
        "xla_src" not in first, "trace.layouts",
        "the first layout pins the packed geometry",
    )
    for p in preps[1:]:
        require(
            p["n"] == n
            and (
                "xla_src" in p
                or all(
                    p[k] == first[k]
                    for k in ("n_super", "r_rows", "s_rows", "sub", "group")
                )
            ),
            "trace.layouts", "layouts must share node space and geometry",
        )
    fn = wake_fn_for(preps, interpret, mode, pull_density)
    state = no_previous_state(first["r_rows"])
    no_suspects = state[0]  # a zero word table: nothing deleted, nothing fresh
    args = [flags[:n], recv_count[:n], no_suspects, no_suspects, *state]
    if mode in (pt.MODE_JUMP, pt.MODE_AUTO):
        require(
            jump_parent is not None, "trace.jump_parent",
            "jump modes need the parent array", mode=mode,
        )
        args.append(jump_parent)
    for p in preps:
        args.extend(pt.device_args(p))
    return fn, args


def derive(flags, recv_count, preps, interpret=None, mode=pt.MODE_PUSH,
           pull_density=pt.DEFAULT_PULL_DENSITY, jump_parent=None):
    """Marks of this graph from nothing over these layouts
    (:func:`derivation`, run).  Returns (marks, stats): the oracle's
    (n,) bool vector and the wake's counters as
    :meth:`DecrementalTracer.wake_stats` gives them."""
    import jax
    import jax.numpy as jnp

    fn, args = derivation(
        flags, recv_count, preps, interpret, mode, pull_density, jump_parent
    )
    mark_w, *_, stats = fn(*args)
    marks = pt.unpack_table(mark_w, preps[0]["n"], jnp)
    return (
        np.asarray(marks),  # readback: host boundary: device marks -> np result contract
        host_stats(jax.device_get(stats)),  # readback: a few hundred bytes of counters, the result contract
    )


def verdict_reduce():
    """The jitted reduce behind :meth:`DecrementalTracer.verdict_words`:
    ``(mark_w, iu_w) -> (iu_w & ~mark_w, popcount(mark_w))`` over the
    packed word tables, one program for every geometry's shape."""
    import jax

    reduce = _fn_cache.get(("verdict_reduce",))
    if reduce is None:

        @jax.jit
        def reduce(mark_w, iu_w):
            return iu_w & ~mark_w, jax.lax.population_count(mark_w).sum()

        _fn_cache[("verdict_reduce",)] = reduce
    return reduce


class DecrementalTracer:
    """Per-wake detection state on top of IncrementalPallasLayout.

    Owns the device-resident previous-fixpoint words (marks, seeds,
    halted/in-use bits, active table) and the deleted-destination set gathered
    from the mutation log, and runs the closure+repair wake.  The first
    wake (or any wake after the previous state was invalidated) runs the
    full derivation through the same program, on its cold road.
    """

    def __init__(self, n: int, interpret: Optional[bool] = None, **kwargs):
        self.layout = IncrementalPallasLayout(n, interpret=interpret, **kwargs)
        self.n = n
        self.interpret = interpret
        #: the sweep counters of the last STATS_KEPT wakes, as the wake
        #: program left them on the device (wake_stats reads them back)
        self._stats: deque = deque(maxlen=STATS_KEPT)
        track(self)
        self._mark_w = None
        self._seed_w = None
        self._halted_w = None
        self._iu_w = None
        self._table = None
        #: chunk walks of the last derivation from nothing (device
        #: scalar): what the next wakes' closure price is a share of
        self._walks = None
        #: the suspects of the next wake, as the id arrays each mutation
        #: left (duplicates and all: ``_id_words`` ORs them into words)
        self._pending_del_dst: List[np.ndarray] = []
        self._pending_fresh_dst: List[np.ndarray] = []
        self._unpack = None
        self._zeros = None
        self._wake_fn = None

    @property
    def jump_price(self) -> Optional[int]:
        """What ``auto`` prices one jump sweep at, in chunk walks
        (``pt.auto_jump_policy``), for the geometry of the last wake
        staged; None before the first wake and in a mode without jump."""
        return getattr(self._wake_fn, "jump_price", None)

    @property
    def closure_price(self) -> Optional[int]:
        """What the next wake's suspect closure may cost before the wake
        gives it up, in chunk walks (``pt.closure_price`` of the last
        derivation from nothing, read back from the device now); None
        while there is no previous fixpoint: that wake has no closure."""
        if self._walks is None:
            return None
        return pt.closure_price(int(self._walks))  # readback: one scalar, on request

    # -- building / mutation (layout pass-throughs that watch removals) --

    def rebuild(self, edge_src, edge_dst, edge_weight, supervisor) -> None:
        """Full repack from graph arrays.  The previous fixpoint is
        invalidated: a rebuild may drop pairs that never went through
        remove()/apply_log(), so the next wake re-derives everything (the
        zero prev-state path)."""
        self.layout.rebuild(edge_src, edge_dst, edge_weight, supervisor)
        self.invalidate()

    def insert(self, src: int, dst: int, kind: int) -> None:
        if dst < self.n:
            self._pending_fresh_dst.append(np.array([dst], np.int64))
        self.layout.insert(src, dst, kind)

    def remove(self, src: int, dst: int, kind: int) -> None:
        if dst < self.n:
            self._pending_del_dst.append(np.array([dst], np.int64))
        self.layout.remove(src, dst, kind)

    def apply_log(self, log) -> None:
        """Replay a pair-transition log, a ``slotmap.PairLog`` or any
        sequence of ``(insert?, src, dst, kind)`` tuples (turned into
        columns here, once), into the layout, and take its
        destinations as the next wake's suspects."""
        log = PairLog.of(log)
        ins, _src, dst, _kind = log.columns()
        # Over-approximation is sound: a removal that nets out (or
        # hits a never-propagated pending pair) adds a suspect whose
        # repair is a no-op; an insert dst only forces one full
        # re-derivation of its supertile.
        ins = ins != 0
        inside = dst < self.n
        deleted, fresh = dst[inside & ~ins], dst[inside & ins]
        if deleted.size:
            self._pending_del_dst.append(deleted)
        if fresh.size:
            self._pending_fresh_dst.append(fresh)
        self.layout.apply_log(log)

    # -- the wake ------------------------------------------------------ #

    def _id_words(self, id_chunks: List[np.ndarray], r_rows: int):
        # Scatter id arrays into a packed word table (device).  The
        # list is NOT drained here: a wake whose dispatch raises (compile
        # error, immediate transport error) keeps its suspects for the
        # retry; wake_device clears them only after dispatch succeeds.
        # An async-poisoned result (error surfacing at readback) loses
        # the device state itself — the caller recovers via
        # invalidate(), after which suspects are irrelevant.
        import jax

        if not id_chunks:
            if self._zeros is None or self._zeros.shape[0] != r_rows:
                self._zeros = jax.device_put(
                    np.zeros((r_rows, pt.LANE), np.int32)
                )
            return self._zeros
        words = id_words(id_chunks, r_rows * pt.LANE)
        return jax.device_put(words.view(np.int32).reshape(r_rows, pt.LANE))

    def stage_wake(self) -> tuple:
        """The host's share of a wake before its dispatch: the layout's
        device operands (tier deltas and jump-parent writes go up here),
        the wake program for the geometry they have, and the suspect id
        words uploaded.  Returns what :meth:`wake_device` takes as
        ``staged``; a caller that times upload and run apart (the
        ``decremental`` backend) calls it first."""
        preps, args = self.layout.prepare_device_wake()
        r_rows = preps[0]["r_rows"]
        fn = wake_fn_for(
            preps, self.interpret, self.layout.mode, self.layout.pull_density
        )
        if self._mark_w is None or self._mark_w.shape[0] != r_rows:
            (self._mark_w, self._seed_w, self._halted_w, self._iu_w,
             self._table, self._walks) = no_previous_state(r_rows)
        del_w = self._id_words(self._pending_del_dst, r_rows)
        fresh_w = self._id_words(self._pending_fresh_dst, r_rows)
        self._wake_fn = fn
        return fn, del_w, fresh_w, args

    def wake_device(self, flags_dev, recv_dev, staged=None):
        """Run one wake; returns the packed mark words (device).  Use
        :meth:`marks` for the boolean vector."""
        fn, del_w, fresh_w, args = staged or self.stage_wake()
        *state, stats = fn(
            flags_dev,
            recv_dev,
            del_w,
            fresh_w,
            self._mark_w,
            self._seed_w,
            self._halted_w,
            self._iu_w,
            self._table,
            self._walks,
            *args,
        )
        # State + suspects commit when dispatch succeeds.  Under async
        # dispatch a transport death can still poison the returned
        # arrays at first readback — after any such failure the caller
        # must invalidate() (the previous fixpoint is lost with the
        # device state anyway), which makes the next wake a full
        # re-derivation and the drained suspects irrelevant.
        (self._mark_w, self._seed_w, self._halted_w, self._iu_w,
         self._table, self._walks) = state
        self._stats.append(stats)
        self._pending_del_dst.clear()
        self._pending_fresh_dst.clear()
        return self._mark_w

    def wake_stats(self, last_n: Optional[int] = None) -> List[dict]:
        """The sweep counters of the last ``last_n`` wakes (all that are
        kept, at most STATS_KEPT, when None), oldest first, read back
        from the device now: per wake ``closure_sweeps``,
        ``closure_spent``, ``closure_bailed`` and ``gated_tiles`` (the
        closure's chunk walks, whether it gave up at its price, and the
        supertiles the first repair sweep was forced through),
        ``n_sweeps`` (repair), ``kernel_steps`` of ``kernel_steps_full``
        (the grid steps its kernels took, of launches x blocks) and
        ``kernel_contractions`` (those of the steps that gathered a new
        bit and paid for their contraction), ``kernel_chunk_walks`` (the
        chunk-iterations the steps' walks took) in ``kernel_walk_trips``
        (the trips of the walks' loops, two chunks a trip),
        ``jump_sweeps`` (the repair sweeps that ran the pointer jump),
        ``jump_spent`` (the ``auto`` policy's sparse chunk walks at exit;
        against :attr:`jump_price`) and, for the repair's first
        ``pt.MAX_SWEEP_STATS`` sweeps, ``dirty_chunks``,
        ``tiles_skipped``, ``pull_on`` and ``jump_on``.
        Waits for a wake still in flight; costs the wakes nothing."""
        kept = list(self._stats)
        if last_n is not None:
            kept = kept[max(0, len(kept) - last_n):]
        return read_counters(kept)

    def last_counters(self):
        """The last wake's counters as its program left them on the
        device, not read back: for :func:`read_counters`, later and on
        whatever thread (the ``decremental`` backend hands them to the
        wake's profiler record, which outlives the STATS_KEPT here)."""
        return self._stats[-1]

    def invalidate(self) -> None:
        """Drop the previous-fixpoint device state (after a failed or
        poisoned wake, or any external doubt about it): the next wake
        re-derives everything from the current seeds."""
        self._mark_w = self._seed_w = self._halted_w = None
        self._iu_w = self._table = self._walks = None
        self._pending_del_dst.clear()
        self._pending_fresh_dst.clear()

    def unpack_marks(self, mark_w) -> np.ndarray:
        """Packed mark words -> the oracle's (n,) bool mark vector.

        This is the readback point where an async-poisoned wake (the
        dispatch succeeded, the transport died before the result
        landed) first surfaces.  The tracer auto-invalidates before
        re-raising, so a caller that catches and retries without its
        own invalidate() still gets a clean full re-derivation instead
        of tracing from corrupt committed state."""
        import jax
        import jax.numpy as jnp

        if self._unpack is None:

            @jax.jit
            def unpack(words):
                return pt.unpack_table(words, self.n, jnp)

            self._unpack = unpack
        try:
            return np.asarray(self._unpack(mark_w))  # readback: host boundary: packed wake marks -> np for the caller
        except Exception:
            self.invalidate()
            raise

    def verdict_words(self, mark_w) -> tuple:
        """What a sweep needs of the last wake's verdicts, without the
        (n,) vector: ``(garbage_w, marked)``, the packed words of the
        slots in use and unmarked (flat uint32, bit ``i & 31`` of word
        ``i >> 5`` is slot ``i``; marks never leave the in-use set, so a
        slot in use is marked iff its bit is clear) and the number of
        marks.  ``mark_w`` is what :meth:`wake_device` returned, taken
        before the next wake or ``invalidate()``: the in-use words are
        the ones that wake left.  A reduce of its own on the device, not
        a part of the wake program; 4 bytes an 32 slots cross to the
        host.  Poisoned results invalidate as in :meth:`unpack_marks`."""
        import jax

        require(
            mark_w is self._mark_w, "decremental.verdict_words",
            "verdict words of a wake that is no longer the tracer's last",
        )
        try:
            garbage_w, marked = jax.device_get(  # readback: host boundary: the wake's verdict words -> np for the sweep
                verdict_reduce()(mark_w, self._iu_w)
            )
        except Exception:
            self.invalidate()
            raise
        return garbage_w.reshape(-1).view(np.uint32), int(marked)

    def marks(self, flags, recv_count) -> np.ndarray:
        """Wake + unpack to the oracle's (n,) bool mark vector."""
        import jax

        return self.unpack_marks(
            self.wake_device(jax.device_put(flags), jax.device_put(recv_count))
        )
