"""Decompose the Pallas trace's per-sweep cost at graph scale.

Times three things the full fixpoint mixes together (a wake reports only
their sum across ~12 sweeps):

- a **full-dirty** propagation sweep (every chunk dirty: worst-case walk
  + every block's one-hot contraction), and the same walk over a table
  with no new bit, in which every block skips its contraction: the
  difference is what a contraction costs a step;
- a **no-dirty** sweep (empty dirty list: pure grid/stream overhead —
  every block still streams its row_pos/emeta and runs the skip branch);
- the **word-space pack2d** of per-sweep hits into the word table (the
  per-sweep XLA cost outside the kernel), plus the legacy O(n)
  bool-space pack (now paid only once per trace, for seed/gate vectors);
- one **pointer-jump sweep** (``pallas_trace.jump_sweep``: 1 + 2 *
  JUMP_STEPS gathers over all n actors, over the graph's own jump
  parents), and from it and the full-dirty sweep the ns per gathered
  element and per streamed pair slot whose ratio is
  ``pallas_trace.JUMP_GATHER_COST``: re-measure it here on a new chip.

Plus, per trace mode (uigc.crgc.trace-mode: push/pull/jump/auto), the
**per-sweep frontier decomposition** of the real fixpoint, the wake
program deriving from nothing (``pallas_decremental.derive``) — sweep
count, dirty-chunk density, tiles pull-skipped, and the auto mode's
per-sweep pull and jump decisions, as ``wake_stats()`` gives them —
emitted through the telemetry wake profiler (telemetry/profile.py), so the pull-density
threshold is tuned from recorded wake data instead of guessed.

``--simulate`` instead runs the numpy sweep-count simulation at the
same graph geometry: sweep counts are hardware-independent, so the
push-vs-jump convergence (O(diameter) vs O(log diameter) sweeps) is
measurable without a chip — the number the ISSUE-6 acceptance
criterion is judged against.  ``auto`` is simulated with the program's
own policy helper (``pallas_trace.auto_jump_policy``) on the layout's
chip geometry, and ``jump_sweeps`` says per mode how many sweeps ran
the pointer jump: whether ``auto`` would engage it on a new graph
shape can be had without a chip.

Prints one JSON line.  Usage: python tools/sweep_profile.py [--n 10000000]
       [--simulate] [--modes auto,push,pull,jump] [--skip-probes]
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


#: what a grid step that walks nothing new costs beside its walk on the
#: v5e, in us: the per-sweep kernel times of a derivation fitted over the
#: program's own counters (PERF.md section 6; PR 41: 0.338, PR 44: 0.335,
#: PR 47: 0.343)
SKIP_US_PER_STEP = 0.343


def _sync(out):
    """Wait for every output (``block_until_ready`` synchronises on the
    chip: PERF.md section 6, PR 24)."""
    import jax

    jax.block_until_ready(out)


def timed(fn, *args, reps=5):
    out = fn(*args)
    _sync(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        _sync(out)
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e3


def simulate_sweeps(graph, n, modes, jump_steps=None, geometry=None,
                    suspects=None, layout=None):
    """Hardware-independent fixpoint sweep counts per trace mode, by
    direct numpy simulation of the kernel's per-sweep semantics
    (the wake's repair loop: table = mark & ~halted, hits gated by
    in_use, jump parents squared ``JUMP_STEPS`` times per engaged sweep
    through transparent intermediates, the loop running while the table
    changed).  Pull gating changes per-sweep WORK, never the sweep
    count, so pull reports push's count.  ``auto`` is simulated with the
    program's own policy (``pt.auto_jump_policy``) on the dirty walk
    chunks of ``geometry`` = (n_slots, n_chunks, chunk_nodes): the slots
    and chunks of the layout as the decremental backend packs it.

    With ``layout`` (the pairs packed, ``chip_layout`` at the chip's
    geometry; it then gives ``geometry`` too) every sweep also counts,
    by the propagate kernel's own rules, the grid ``steps`` it takes (the
    blocks with a dirty chunk in their span whose tile the pull gate does
    not skip), how many of them are ``contracting`` (a slot's source
    bit is new since the sweep before), the ``chunk_iterations`` of their
    walks and the ``walk_trips`` of the walks' loops (two chunks a trip):
    the oracle of ``wake_stats()``'s ``kernel_steps``,
    ``kernel_contractions``, ``kernel_chunk_walks`` and
    ``kernel_walk_trips`` on a derivation from nothing.  Pull gating changes the steps, so ``pull`` is then
    simulated on its own.

    With ``suspects`` (actor ids: the targets of released references,
    say) the decremental wake's closure over the derived marks is
    simulated too, with the program's own policy
    (``pt.closure_gives_up``, priced from each mode's derivation walks):
    the closure is push-only whatever the mode.

    Returns {mode: {"sweeps", "jump_sweeps", "dirty_chunks"}} (with
    ``layout`` also "steps", "contracting", "chunk_iterations" and
    "walk_trips", per sweep), under
    "auto" also the policy's "price", and with ``suspects`` under every
    mode "closure": {"price", "sweeps", "spent", "bailed"} as the wake
    would run it, and "full_sweeps", "sizes" (closure members after each
    sweep) and "marks" as it would end unpriced."""
    from uigc_tpu.ops import pallas_trace as pt
    from uigc_tpu.ops import trace as trace_ops

    F = trace_ops
    if jump_steps is None:
        jump_steps = pt.JUMP_STEPS
    flags = graph["flags"]
    recv = graph["recv_count"]
    live = graph["edge_weight"] > 0
    psrc = graph["edge_src"][live].astype(np.int64)
    pdst = graph["edge_dst"][live].astype(np.int64)
    sup = graph["supervisor"]
    sup_src = np.nonzero(sup >= 0)[0].astype(np.int64)
    psrc = np.concatenate([psrc, sup_src])
    pdst = np.concatenate([pdst, sup[sup_src].astype(np.int64)])

    in_use = (flags & F.FLAG_IN_USE) != 0
    halted = (flags & F.FLAG_HALTED) != 0
    seed = (
        ((flags & F.FLAG_ROOT) != 0)
        | ((flags & F.FLAG_BUSY) != 0)
        | (recv != 0)
        | ((flags & F.FLAG_INTERNED) == 0)
    )
    mark0 = in_use & (~halted) & seed
    trans = in_use & (~halted)
    trans_pad = np.concatenate([trans, [False]])
    if geometry is None:
        if layout is None:
            layout = chip_layout(psrc, pdst, n)
        geometry = layout_geometry(layout)
    n_slots, n_chunks, chunk_nodes = geometry
    pull_cut = max(1, int(round(pt.DEFAULT_PULL_DENSITY * n_chunks)))
    bounds = np.arange(0, n, chunk_nodes)
    walk = _BlockWalk(layout, n, in_use) if layout is not None else None

    out = {}
    for mode in modes:
        if mode == pt.MODE_PULL and pt.MODE_PUSH in out and walk is None:
            out[mode] = out[pt.MODE_PUSH]
            continue
        engaged, spent, decide = mode == pt.MODE_JUMP, 0, None
        if mode == pt.MODE_AUTO:
            decide = pt.auto_jump_policy(
                n, n_slots, n_chunks, pull_cut, steps=jump_steps
            )
        use_jump = mode in (pt.MODE_JUMP, pt.MODE_AUTO)
        j = pt.jump_parents(psrc, pdst, n) if use_jump else None
        mark = mark0.copy()
        table, table_prev = mark & ~halted, np.zeros(n, bool)
        dirty, jump_sweeps, per_sweep = [], 0, []
        while True:
            n_dirty = _dirty_chunks(table, table_prev, bounds)
            dirty.append(n_dirty)
            if walk is not None:
                pull_on = mode == pt.MODE_PULL or (
                    mode == pt.MODE_AUTO and n_dirty >= pull_cut
                )
                per_sweep.append(walk.sweep(table, table_prev, mark, pull_on))
            if decide is not None:
                engaged, spent = decide(engaged, spent, n_dirty)
            new = mark.copy()
            hit_dst = pdst[table[psrc]]
            new[hit_dst] |= in_use[hit_dst]
            if engaged:
                jump_sweeps += 1
                active_pad = np.concatenate([table, [False]])
                new |= active_pad[j[:n]] & in_use
                for _ in range(jump_steps):
                    j2 = j[j]
                    can = trans_pad[j] & (j2 < n)
                    j = np.where(can, j2, j)
            mark, table_prev, table = new, table, new & ~halted
            # the device fixpoint's sweep count includes the final
            # no-change sweep that proves convergence: same convention
            if np.array_equal(table, table_prev):
                break
        out[mode] = {"sweeps": len(dirty), "jump_sweeps": jump_sweeps,
                     "dirty_chunks": dirty}
        if walk is not None:
            steps, contracting, iters, trips = (
                list(c) for c in zip(*per_sweep)
            )
            out[mode].update(steps=steps, contracting=contracting,
                             chunk_iterations=iters, walk_trips=trips)
        if decide is not None:
            out[mode]["price"] = decide.price
    if suspects is not None:
        # the fixpoint's marks are the same in every mode
        c_dirty, sizes = _simulate_closure(psrc, pdst, mark, suspects, bounds)
        for res in out.values():
            walks = sum(res["dirty_chunks"])
            sweeps = spent = 0
            while (sweeps < len(c_dirty)
                   and not pt.closure_gives_up(spent, walks)):
                spent += c_dirty[sweeps]
                sweeps += 1
            res["closure"] = {
                "price": pt.closure_price(walks), "sweeps": sweeps,
                "spent": spent, "bailed": sweeps < len(c_dirty),
                "full_sweeps": len(c_dirty), "sizes": sizes,
                "marks": int(mark.sum()),
            }
    return out


def _dirty_chunks(table, table_prev, bounds) -> int:
    """Walk chunks (starting at ``bounds``) in which the two differ."""
    return int(np.add.reduceat(table != table_prev, bounds).astype(bool).sum())


def _simulate_closure(psrc, pdst, mark, suspects, bounds):
    """The wake's closure loop run to its end: the previously marked
    suspects, then per sweep the marked successors of the closure, while
    a sweep changed it.  Returns (dirty walk chunks, closure members)
    per sweep; the last sweep is the one that finds nothing new."""
    closure = np.zeros_like(mark)
    closure[np.asarray(suspects, np.int64)] = True
    closure &= mark
    prev = np.zeros_like(mark)
    dirty, sizes = [], []
    while not np.array_equal(closure, prev):
        dirty.append(_dirty_chunks(closure, prev, bounds))
        new = closure.copy()
        hit_dst = pdst[closure[psrc]]
        new[hit_dst] |= mark[hit_dst]
        prev, closure = closure, new
        sizes.append(int(closure.sum()))
    return dirty, sizes


class _BlockWalk:
    """One propagate launch of a derivation from nothing, counted from
    the packed layout by the kernel's own rules (``build_propagate``:
    ``block_iters``, the gather, the test around the contraction)."""

    def __init__(self, prep, n, in_use):
        from uigc_tpu.ops import pallas_trace as pt

        # a slot's source actor; an empty slot reads the sink, never set
        self.slot_src = pt.slot_sources(prep, n)
        self.tile = prep["bmeta1"] >> 1
        self.c_lo = prep["bmeta2"] >> pt._SPAN_BITS
        self.c_hi = self.c_lo + (prep["bmeta2"] & ((1 << pt._SPAN_BITS) - 1))
        self.chunk_nodes = pt.ROWS * prep["group"] * pt.LANE * pt.WORD_BITS
        self.n_chunks = prep["r_rows"] // (pt.ROWS * prep["group"])
        self.tile_nodes = prep["s_rows"] * pt.LANE
        # a destination shard's layout (``sharded_trace.shard_layout``)
        # numbers its own tiles, every D-th of the graph's: ``tiles``
        # names them in the whole graph's
        tiles = prep.get("tiles", np.arange(prep["n_super"]))
        self.tile = tiles[self.tile]
        self.n_tiles = max(int(tiles.max()) + 1, -(-n // self.tile_nodes))
        self.in_use = in_use

    def _per(self, flags, size, count):
        """``any`` over runs of ``size`` nodes, ``count`` runs."""
        padded = np.zeros(size * count, bool)
        padded[: flags.size] = flags
        return padded.reshape(count, size).any(axis=1)

    def sweep(self, table, table_prev, mark, pull_on):
        """(grid steps, steps that contract, chunk-iterations, loop trips
        of the walks: two chunks a trip) of the sweep that walks ``table``
        with the dirty lists taken against ``table_prev``."""
        dirty = self._per(table != table_prev, self.chunk_nodes, self.n_chunks)
        d = np.concatenate([[0], np.cumsum(dirty)])
        n_iter = d[self.c_hi] - d[self.c_lo]
        active = n_iter > 0
        if pull_on:  # a saturated tile: no unmarked in-use node left
            unmarked = self._per(
                self.in_use & ~mark, self.tile_nodes, self.n_tiles
            )
            active &= unmarked[self.tile]
        new = np.concatenate([table & ~table_prev, [False]])
        gathers = new[self.slot_src[active]].any(axis=1)
        walked = n_iter[active]
        return (int(active.sum()), int(gathers.sum()), int(walked.sum()),
                int(((walked + 1) // 2).sum()))


def chip_layout(psrc, pdst, n):
    """The pairs packed as the decremental backend packs them on the
    chip (``IncrementalPallasLayout.rebuild``: pow2/quantum-padded
    blocks, the chip's walk geometry).  One host pack (44 s at 10M
    actors)."""
    from uigc_tpu.ops import pallas_trace as pt

    return pt.prepare_pairs(
        psrc, pdst, n, pad_blocks_pow2=True, sub=pt.SUB_TPU,
        group=pt.GROUP_TPU,
    )


def layout_geometry(prep):
    """(n_slots, n_chunks, chunk_nodes) of a packed layout: what AUTO's
    price of a jump sweep is built from."""
    from uigc_tpu.ops import pallas_trace as pt

    group_rows = pt.ROWS * prep["group"]
    return (
        pt.kernel_slots((pt.layout_spec(prep),)),
        prep["r_rows"] // group_rows,
        group_rows * pt.LANE * pt.WORD_BITS,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--small", action="store_true")
    ap.add_argument(
        "--simulate", action="store_true",
        help="numpy sweep-count simulation per mode (no device work)",
    )
    ap.add_argument(
        "--suspects", type=int, default=0,
        help="with --simulate: also close over this many suspects (targets "
        "of live references, drawn with seed 0) under the wake's policy",
    )
    ap.add_argument(
        "--modes", default="push,pull,jump,auto",
        help="comma-separated trace modes for the fixpoint decomposition",
    )
    ap.add_argument(
        "--skip-probes", action="store_true",
        help="skip the isolated-sweep probes (fixpoint decomposition only)",
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from uigc_tpu.models import powerlaw_actor_graph
    from uigc_tpu.ops import pallas_decremental
    from uigc_tpu.ops import pallas_trace as pt
    from uigc_tpu.utils.platform import enable_compile_cache, is_tpu_platform

    enable_compile_cache()
    on_tpu = is_tpu_platform(jax.devices()[0].platform)
    n = args.n or (10_000_000 if on_tpu and not args.small else 1 << 16)
    seed, frac = 0, 0.5

    if args.simulate:
        # Sweep counts are hardware-independent: pure numpy, no device.
        graph = powerlaw_actor_graph(n, seed=seed, garbage_fraction=frac)
        modes = [m.strip() for m in args.modes.split(",") if m.strip()]
        suspects = None
        if args.suspects:
            live = np.flatnonzero(graph["edge_weight"] > 0)
            pick = np.random.default_rng(0).choice(
                live, args.suspects, replace=False
            )
            suspects = graph["edge_dst"][pick]
        sim = simulate_sweeps(graph, n, modes, suspects=suspects)
        print(
            json.dumps(
                {
                    "bench": "sweep_profile_simulate",
                    "n_actors": n,
                    "n_pairs": int(
                        (graph["edge_weight"] > 0).sum()
                        + (graph["supervisor"] >= 0).sum()
                    ),
                    "jump_steps": pt.JUMP_STEPS,
                    "sweeps": {m: sim[m]["sweeps"] for m in modes},
                    "jump_sweeps": {m: sim[m]["jump_sweeps"] for m in modes},
                    "dirty_chunks": {m: sim[m]["dirty_chunks"] for m in modes},
                    # grid steps per sweep, and those that contract
                    "steps": {m: sim[m]["steps"] for m in modes},
                    "contracting": {m: sim[m]["contracting"] for m in modes},
                    "chunk_iterations": {
                        m: sim[m]["chunk_iterations"] for m in modes
                    },
                    # the trips of the walks' loops, two chunks a trip
                    "walk_trips": {m: sim[m]["walk_trips"] for m in modes},
                    "auto_jump_price": sim.get(pt.MODE_AUTO, {}).get("price"),
                    **({"closure": {m: sim[m]["closure"] for m in modes}}
                       if suspects is not None else {}),
                }
            )
        )
        return

    graph = powerlaw_actor_graph(n, seed=seed, garbage_fraction=frac)
    t0 = time.perf_counter()
    prep = pt.prepare_chunks(
        graph["edge_src"].astype(np.int32),
        graph["edge_dst"].astype(np.int32),
        graph["edge_weight"],
        graph["supervisor"],
        n,
    )
    pack_host_s = time.perf_counter() - t0
    r_rows, s_rows, n_super = prep["r_rows"], prep["s_rows"], prep["n_super"]
    n_blocks = prep["n_blocks"]
    n_chunks = r_rows // (pt.ROWS * prep["group"])

    jp = pt.jump_parents_from_graph(
        graph["edge_src"], graph["edge_dst"],
        graph["edge_weight"], graph["supervisor"], n,
    )
    full_ms = none_ms = half_ms = pack_ms = pack2d_ms = jump_ms = None
    if not args.skip_probes:
        # jitted: the kernel's launch is the list of active blocks (XLA)
        # and a grid as long as the list, one program as in the wake
        propagate = jax.jit(
            pt.build_propagate(
                n_blocks, n_super, r_rows, s_rows, pt.default_interpret(),
                sub=prep["sub"], group=prep["group"],
            ).with_steps
        )
        dev = {
            k: jax.device_put(prep[k])
            for k in ("bmeta1", "bmeta2", "row_pos", "emeta")
        }

        rng = np.random.default_rng(0)
        table = jax.device_put(
            rng.integers(0, 1 << 31, (r_rows, pt.LANE), dtype=np.int32)
        )
        d_full = jax.device_put(np.arange(n_chunks + 1, dtype=np.int32))
        l_full = jax.device_put(np.arange(n_chunks, dtype=np.int32))
        d_none = jax.device_put(np.zeros(n_chunks + 1, dtype=np.int32))

        probes = {"full": (d_full, l_full), "none": (d_none, l_full)}

        # half the chunks dirty (even ids): the mid-fixpoint regime
        diff = np.zeros(n_chunks, bool)
        diff[::2] = True
        dd = np.concatenate([[0], np.cumsum(diff)]).astype(np.int32)
        ll = np.zeros(n_chunks, np.int32)
        ll[dd[:-1][diff]] = np.nonzero(diff)[0].astype(np.int32)
        probes["half"] = (jax.device_put(dd), jax.device_put(ll))
        # the kernel's table operand is the table over its new bits
        # (pt.walk_tables): every bit new, so every walked block
        # contracts; "full_nothing_new" walks the same chunks over no new
        # bit, so every block skips its contraction
        zeros = jnp.zeros_like(table)
        operands = {
            k: (*dl, dev["bmeta1"], dev["bmeta2"],
                pt.walk_tables(table, zeros, jnp), dev["row_pos"],
                dev["emeta"])
            for k, dl in probes.items()
        }
        operands["full_nothing_new"] = (
            *operands["full"][:4], pt.walk_tables(table, table, jnp),
            *operands["full"][5:],
        )
        probe_ms = {k: timed(propagate, *ops) for k, ops in operands.items()}
        full_ms, none_ms, half_ms = (
            probe_ms[k] for k in ("full", "none", "half")
        )
        # the grid steps each probe took (its blocks with work), those
        # of them that contracted, the chunk-iterations they walked and
        # the trips of the walks' loops
        probe_steps = {k: [int(c) for c in propagate(*ops)[1:]]
                       for k, ops in operands.items()}

        shifts = jnp.arange(pt.WORD_BITS, dtype=jnp.int32)

        @jax.jit
        def pack(active):
            a = jnp.zeros(r_rows * pt.LANE * pt.WORD_BITS, jnp.int32)
            a = a.at[:n].set(active.astype(jnp.int32))
            w = (a.reshape(-1, pt.WORD_BITS) << shifts[None, :]).sum(
                axis=1, dtype=jnp.int32
            )
            return w.reshape(r_rows, pt.LANE)

        active = jax.device_put(np.ones(n, bool))
        pack_ms = timed(pack, active)

        # The per-sweep pack actually on the fixpoint path now: word-space
        # pack of a (t_rows, LANE) hits plane (pt.pack_hits_table).
        t_rows = n_super * s_rows

        @jax.jit
        def pack2d(hits2d):
            return pt.pack_hits_table(hits2d, r_rows, jnp)

        hits2d = jax.device_put(np.ones((t_rows, pt.LANE), bool))
        pack2d_ms = timed(pack2d, hits2d)

        # One pointer-jump sweep over the graph's own jump parents: the
        # 1 + 2 * JUMP_STEPS gathers over all n actors that AUTO's price
        # of engaging the jump is built from (pt.JUMP_GATHER_COST).
        @jax.jit
        def jump(table, jump_j, trans_w):
            return pt.jump_sweep(table, jump_j, trans_w, n, jnp)

        jump_ms = timed(jump, table, jax.device_put(jp), table)
        # what the host's dispatch and wait cost a probe, whatever it runs
        floor_ms = timed(jax.jit(lambda x: x + 1), d_none)

    # --- per-mode fixpoint decomposition, through the wake profiler -- #
    # The same per-wake fields the engine notes into its active wake
    # (engines/crgc/arrays.py _read_sweep_stats) flow through a real
    # WakeProfiler here, so this tool exercises — and its JSON matches —
    # the telemetry pipeline the pull-density threshold is tuned from.
    modes = [m.strip() for m in args.modes.split(",") if m.strip()]
    mode_out = {}
    wake_records = None
    if modes:
        from uigc_tpu.telemetry.profile import WakeProfiler

        profiler = WakeProfiler(node="sweep_profile")
        flags_h, recv_h = graph["flags"], graph["recv_count"]
        for mode in modes:
            use_jump = mode in (pt.MODE_JUMP, pt.MODE_AUTO)

            def run():
                return pallas_decremental.derive(
                    flags_h, recv_h, [prep], mode=mode,
                    jump_parent=jp if use_jump else None,
                )

            wk = profiler.begin_wake()
            with wk.phase("trace"), wk.part("device_s"):
                run()  # compile + warmup
                t0 = time.perf_counter()
                _, stats = run()
                fix_ms = (time.perf_counter() - t0) * 1e3
                rows = {
                    k: stats[k]
                    for k in ("dirty_chunks", "tiles_skipped",
                              "pull_on", "jump_on")
                }
                wk.note(
                    trace_mode=mode,
                    n_sweeps=stats["n_sweeps"],
                    jump_sweeps=stats["jump_sweeps"],
                    **{"sweep_" + k: v for k, v in rows.items()},
                )
            wk.end(mode=mode)
            mode_out[mode] = {
                "n_sweeps": stats["n_sweeps"],
                "fixpoint_ms": round(fix_ms, 2),
                "jump_sweeps": stats["jump_sweeps"],
                "kernel_steps": stats["kernel_steps"],
                "kernel_contractions": stats["kernel_contractions"],
                "kernel_chunk_walks": stats["kernel_chunk_walks"],
                "kernel_walk_trips": stats["kernel_walk_trips"],
                **rows,
            }
        wake_records = profiler.to_json()["recent"]

    out = {
        "bench": "sweep_profile",
        "n_actors": n,
        "n_blocks": n_blocks,
        "n_chunks": n_chunks,
        "n_pairs": prep["n_pairs"],
        "host_pack_s": round(pack_host_s, 2),
        "modes": mode_out,
        "wake_profile_recent": wake_records,
    }
    if not args.skip_probes:
        gathered = (1 + 2 * pt.JUMP_STEPS) * n
        slots = pt.kernel_slots((pt.layout_spec(prep),))
        out.update(
            {
                "sweep_full_dirty_ms": round(full_ms, 2),
                "sweep_half_dirty_ms": round(half_ms, 2),
                "sweep_no_dirty_ms": round(none_ms, 2),
                "sweep_full_dirty_nothing_new_ms": round(
                    probe_ms["full_nothing_new"], 2
                ),
                # [grid steps, steps that contracted, chunk-iterations,
                # walk trips] per probe
                "sweep_grid_steps": probe_steps,
                # what the contraction costs a walked block that needs it
                "contraction_us_per_step": round(
                    (full_ms - probe_ms["full_nothing_new"]) * 1e3
                    / max(probe_steps["full"][1], 1), 3
                ),
                # what the walk costs a chunk-iteration: the walk that
                # contracts nothing, less the launch (the no-dirty probe)
                # and its steps at the skip price
                "walk_us_per_chunk": round(
                    ((probe_ms["full_nothing_new"] - none_ms) * 1e3
                     - probe_steps["full_nothing_new"][0] * SKIP_US_PER_STEP)
                    / max(probe_steps["full_nothing_new"][2], 1), 4
                ),
                "skip_us_per_step": SKIP_US_PER_STEP,
                "dispatch_floor_ms": round(floor_ms, 2),
                "pack_seed_ms": round(pack_ms, 2),
                "pack2d_per_sweep_ms": round(pack2d_ms, 2),
                "jump_sweep_ms": round(jump_ms, 2),
                # the two costs pt.JUMP_GATHER_COST is the ratio of
                "gather_ns_per_element": round(jump_ms * 1e6 / gathered, 3),
                "stream_ns_per_slot": round(full_ms * 1e6 / slots, 3),
                "stream_ns_per_pair": round(
                    full_ms * 1e6 / prep["n_pairs"], 3
                ),
                "n_slots": slots,
                "jump_gather_cost": pt.JUMP_GATHER_COST,
            }
        )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
