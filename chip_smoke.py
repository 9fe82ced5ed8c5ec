"""Chip smoke: the collector's device path, end to end, on a TPU v5e.

One process, JAX imported once, no child that needs the chip.  The first
failure ends the run with a non-zero exit; no phase is retried and none
falls back.  Default run (one chip):

1. device     — JAX must report a TPU.
2. data plane — BASELINE config 5 at full width: a 10M-actor power-law
   graph held on the device by the object the ``decremental`` backend
   holds (``DecrementalTracer`` over its ``IncrementalPallasLayout``),
   default trace-mode: the first wake, and a second from
   ``invalidate()``, both derivations from nothing with verdicts equal
   to the numpy oracle.  Wakes under churn are the benchmark's
   (``benchmark/drivers/tracer_wake.py``).
3. served path — ``ActorSystem`` -> CRGC engine -> Bookkeeper -> device
   backend through ``models/workloads.py`` with uigcsan attached: the
   10k-actor tree, 100 rings x 100 and a 100k-actor tree on
   ``decremental``.

``--chips 4`` runs ONLY the mesh phase and what it is compared with: the
sharded trace and sharded decremental wake over a 4-device mesh on the
same 10M graph (verdicts equal to the oracle and to the one-device
derivation), then the served path on ``mesh`` and ``mesh-decremental``.

``--rehearse`` is the CPU rehearsal (interpret-mode kernels, tiny sizes,
no platform check); it refuses to run on anything but the CPU platform
and says so in its last line.

Seconds and bytes printed along the way are set-up information, not
metrics.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import argparse
import json
import os
import time

T_START = time.perf_counter()


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:7.1f}s] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {msg}")


class Clock:
    """``with Clock() as c: ...`` then ``c.s`` is the seconds it took."""

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self.t0


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--actors", type=int, default=10_000_000,
        help="data-plane graph size (default: BASELINE config 5's 10M)",
    )
    ap.add_argument(
        "--rehearse", action="store_true",
        help="CPU rehearsal: tiny sizes, interpreted kernels, no chip",
    )
    return ap.parse_args()


# --------------------------------------------------------------------- #
# Phase 1: device
# --------------------------------------------------------------------- #


def phase_device(args):
    import jax

    from uigc_tpu.ops import i64map
    from uigc_tpu.utils.platform import enable_compile_cache, is_tpu_platform

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    info = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
    }
    say(f"device: {info}; jax {jax.__version__}; compile cache {cache_dir}")
    if args.rehearse:
        check(dev.platform == "cpu", "--rehearse runs on the CPU platform only")
        say("REHEARSAL: sizes shrunk, platform check skipped, kernels interpreted")
    else:
        check(
            is_tpu_platform(dev.platform),
            f"no TPU: JAX reports platform {dev.platform!r}",
        )
    check(
        len(devices) == args.chips,
        f"--chips {args.chips} but JAX sees {len(devices)} device(s)",
    )
    say(f"i64map probes: {i64map.probe_backend()}")
    return info


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


# --------------------------------------------------------------------- #
# Phase 2: data plane at full width
# --------------------------------------------------------------------- #


def make_graph(args, n):
    import numpy as np

    from uigc_tpu.models import powerlaw_actor_graph
    from uigc_tpu.ops import trace as trace_ops
    from uigc_tpu.ops.pallas_incremental import IncrementalPallasLayout

    with Clock() as c:
        graph = powerlaw_actor_graph(n, seed=args.seed, garbage_fraction=0.5)
        psrc, pdst, _ = IncrementalPallasLayout.pairs_from_graph(
            graph["edge_src"], graph["edge_dst"], graph["edge_weight"],
            graph["supervisor"],
        )
    say(
        f"graph: n={n} edges={graph['edge_src'].size} pairs={psrc.size} "
        f"seed={args.seed} generate {c.s:.1f}s"
    )
    with Clock() as c:
        oracle = trace_ops.trace_marks_np(
            graph["flags"], graph["recv_count"], graph["supervisor"],
            graph["edge_src"], graph["edge_dst"], graph["edge_weight"],
        )
    in_use = (graph["flags"] & trace_ops.FLAG_IN_USE) != 0
    check(
        np.array_equal(in_use & ~oracle, graph["expected_garbage"]),
        "numpy oracle disagrees with the generator's garbage partition",
    )
    say(f"oracle (numpy) on the initial graph: {c.s:.1f}s")
    return graph, (psrc, pdst), oracle


def phase_data_plane(args, n):
    import jax
    import numpy as np

    from uigc_tpu.ops import pallas_trace as pt
    from uigc_tpu.ops import trace as trace_ops
    from uigc_tpu.ops.pallas_decremental import DecrementalTracer

    graph, _, oracle = make_graph(args, n)
    flags, recv = graph["flags"], graph["recv_count"]

    tracer = DecrementalTracer(n)  # trace-mode default (auto), as the engine
    layout = tracer.layout
    check(layout.mode == pt.MODE_AUTO, f"default trace-mode is {layout.mode}")
    check(
        pt.default_interpret() == args.rehearse,
        f"interpret resolved to {pt.default_interpret()}",
    )
    with Clock() as c:
        tracer.rebuild(
            graph["edge_src"], graph["edge_dst"], graph["edge_weight"],
            graph["supervisor"],
        )
    base = layout.base
    operand_bytes = sum(
        base[k].nbytes for k in ("bmeta1", "bmeta2", "row_pos", "emeta")
    )
    say(
        f"pack: {c.s:.1f}s; base n_blocks={base['n_blocks']} "
        f"r_rows={base['r_rows']} n_super={base['n_super']} "
        f"sub={layout.sub} group={layout.group} trace-mode={layout.mode} "
        f"interpret={pt.default_interpret()} operand bytes={operand_bytes} (+ jump parents "
        f"{layout.jump_parent.nbytes})"
    )

    # -- first wake: the derivation from nothing ------------------------ #
    flags_dev, recv_dev = jax.device_put(flags), jax.device_put(recv)
    with Clock() as c:
        marks = tracer.unpack_marks(tracer.wake_device(flags_dev, recv_dev))
    say(f"wake 0 (cold: compile + upload + derivation): {c.s:.1f}s")
    check(np.array_equal(marks, oracle), "wake != oracle (first wake)")

    # -- again from invalidate(), operands resident, and what
    #    block_until_ready does here ----------------------------------- #
    tracer.invalidate()
    t0 = time.perf_counter()
    mark_w = tracer.wake_device(flags_dev, recv_dev)
    t1 = time.perf_counter()
    mark_w.block_until_ready()
    t2 = time.perf_counter()
    int(mark_w[0, 0])
    t3 = time.perf_counter()
    say(
        f"wake 1 (from invalidate(), resident): dispatch {t1 - t0:.4f}s, "
        f"block_until_ready {t2 - t1:.4f}s, 1-element readback after "
        f"{t3 - t2:.4f}s"
    )
    check(
        np.array_equal(tracer.unpack_marks(mark_w), oracle),
        "wake != oracle (second derivation)",
    )
    stats = tracer.wake_stats()
    check(
        [s["closure_sweeps"] for s in stats] == [0, 0]
        and stats[0]["dirty_chunks"] == stats[1]["dirty_chunks"],
        f"a derivation from nothing ran a closure or did not repeat: {stats}",
    )
    check(layout.stats["anomalies"] == 0, f"layout anomalies: {layout.stats}")
    in_use = (flags & trace_ops.FLAG_IN_USE) != 0
    say(
        f"data plane OK: garbage {int((in_use & ~oracle).sum())}; repair sweeps "
        f"{stats[1]['n_sweeps']} (jumping {stats[1]['jump_sweeps']}), dirty "
        f"chunks {stats[1]['dirty_chunks']}; layout stats "
        f"{ {k: (round(v, 2) if isinstance(v, float) else v) for k, v in layout.stats.items()} }; "
        f"device peak bytes {peak_bytes()}"
    )


# --------------------------------------------------------------------- #
# Phase 3: served path
# --------------------------------------------------------------------- #


def make_inspector(args, backend, extra=None):
    """The checks run on the live system once every released actor has
    had its PostStop (workloads.run_* assert that) and before it
    terminates."""

    def inspect(system):
        bookkeeper = system.engine.bookkeeper
        graph = bookkeeper.shadow_graph
        # The last PostStop is not the collector's last wake: the death
        # flushes still fold and trace.  Wait until it has gone quiet
        # (not inside a wake, nothing folded, no device wake, for
        # 0.3 s), so that a wake that raised after the collection has
        # stopped the cell by now.
        cell = system.engine.bookkeeper_cell
        seen, quiet_since = None, time.monotonic()
        deadline = quiet_since + 120.0
        while time.monotonic() - quiet_since < 0.3:
            check(time.monotonic() < deadline, f"{backend}: collector never quiet")
            now = (bookkeeper.total_entries, graph.device_wakes)
            if now != seen or (cell._scheduled and cell.is_active):
                seen, quiet_since = now, time.monotonic()
            time.sleep(0.02)
        check(graph.device_wakes > 0, f"{backend}: no device wake ran")
        # the compiled kernel on the chip, the interpreted one off it
        check(
            graph.trace_impl
            == ("pallas-interpret" if args.rehearse else "pallas"),
            f"{backend}: trace resolved to {graph.trace_impl!r}",
        )
        # A device trace that raises stops the Bookkeeper cell while the
        # application runs on (runtime/cell.py: unmanaged system cells):
        # collection observed above, and the collector still alive here.
        check(
            system.engine.bookkeeper_cell.is_active,
            f"{backend}: the Bookkeeper cell is dead",
        )
        check(
            system.sanitizer.violations == [],
            f"{backend}: sanitizer violations {system.sanitizer.violations[:3]}",
        )
        if extra is not None:
            extra(graph)
        say(
            f"  {backend}: device wakes {graph.device_wakes}, impl "
            f"{graph.trace_impl}, sanitizer clean, Bookkeeper alive"
        )

    return inspect


def served(args, backends, shapes, extra_config=None, extra=None):
    from uigc_tpu.models import workloads

    for backend in backends:
        cfg = {
            "uigc.crgc.shadow-graph": backend,
            "uigc.analysis.sanitizer": True,
        }
        cfg.update(extra_config or {})
        for kind, size in shapes:
            inspect = make_inspector(args, backend, extra)
            if kind == "tree":
                r = workloads.run_tree(
                    n_actors=size, fanout=8, config=cfg, inspect=inspect
                )
            else:
                r = workloads.run_rings(
                    n_rings=size, ring_size=size, config=cfg, inspect=inspect
                )
            say(
                f"served {backend} {kind} {size}: collected "
                f"{r['n_collected']} (every released actor got PostStop), "
                f"build {r['build_s']:.1f}s collect {r['collect_s']:.1f}s"
            )


# --------------------------------------------------------------------- #
# --chips 4: the mesh phase
# --------------------------------------------------------------------- #


def phase_mesh_data_plane(args, n):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from uigc_tpu.ops import pallas_decremental as pd
    from uigc_tpu.ops import pallas_trace as pt
    from uigc_tpu.ops import trace as trace_ops
    from uigc_tpu.parallel import sharded_trace as st

    D = args.chips
    graph, (psrc, pdst), oracle = make_graph(args, n)
    mesh = st.build_mesh(D)
    check(mesh.devices.size == D, "mesh is short of devices")

    chunk = D * pt.S_ROWS * pt.LANE
    n_pad = -(-n // chunk) * chunk
    with Clock() as c:
        stacked, meta, _ = st.pack_shard_layouts(psrc, pdst, n_pad, D)
        jump = pt.jump_parents(psrc, pdst, n_pad)
    say(
        f"shard pack: {c.s:.1f}s; n_pad={n_pad} shard={meta['shard_size']} "
        f"n_blocks/shard={meta['n_blocks']} r_rows={meta['r_rows']}"
    )
    bucket_m = 1024  # empty insert buckets (sink-padded), as after a rebuild
    nodes_s = NamedSharding(mesh, P("gc"))
    dev_s = NamedSharding(mesh, P("gc", None))
    dev3_s = NamedSharding(mesh, P("gc", None, None))
    repl_s = NamedSharding(mesh, P())
    # the node arrays as a shard holds them: its supertiles, dealt round-robin
    part = st.Partition(D)
    flags = jax.device_put(part.owner_major(st.pad_to(graph["flags"], n_pad)), nodes_s)
    recv = jax.device_put(part.owner_major(st.pad_to(graph["recv_count"], n_pad)), nodes_s)
    operands = [
        jax.device_put(stacked["bmeta1"], dev_s),
        jax.device_put(stacked["bmeta2"], dev_s),
        jax.device_put(stacked["row_pos"], dev3_s),
        jax.device_put(stacked["emeta"], dev3_s),
        jax.device_put(np.full((D, bucket_m), n_pad, np.int32), dev_s),
        jax.device_put(np.zeros((D, bucket_m), np.int32), dev_s),
    ]
    jump_dev = jax.device_put(jump, repl_s)
    for x in [flags, recv] + operands:
        check(len(x.sharding.device_set) == D, "an operand is not on every chip")
        check(not x.sharding.is_fully_replicated, "a sharded operand is replicated")
    check(
        jump_dev.sharding.is_fully_replicated
        and len(jump_dev.sharding.device_set) == D,
        "jump parents are not replicated over the mesh",
    )
    geom = (
        mesh, n_pad, meta["shard_size"], meta["n_blocks"], meta["r_rows"],
        meta["s_rows"], bucket_m,
    )
    kw = dict(sub=meta["sub"], group=meta["group"], mode=pt.MODE_AUTO)

    with Clock() as c:
        traced = st.make_sharded_pallas_trace(*geom, **kw)
        mark = traced(flags, recv, *operands, jump_dev)
        mark.block_until_ready()
    say(f"sharded trace (compile + run): {c.s:.1f}s")
    check(len(mark.sharding.device_set) == D, "sharded marks are on one chip")
    marks_mesh = np.asarray(mark)[:n]
    check(np.array_equal(marks_mesh, oracle), "sharded trace != oracle")

    with Clock() as c:
        wake = st.make_sharded_decremental_wake(*geom, **kw)
        zeros = jax.device_put(np.zeros(n_pad // 32, np.int32), nodes_s)
        no_walks = jax.device_put(np.zeros((), np.int32), repl_s)
        *out, stats = wake(
            flags, recv, zeros, zeros, *([zeros] * 5), no_walks,
            *operands, jump_dev,
        )
        out[0].block_until_ready()
    say(f"sharded decremental wake, cold (compile + run): {c.s:.1f}s")
    mark_w, _, _, iu_w, _, walks = out
    for o in out[:5]:
        check(len(o.sharding.device_set) == D, "a wake output is on one chip")
    # the verdict as the mesh backend reads it: packed words put back in
    # slot order on the device, a D-th a chip, laid end to end
    garbage_w, marked = st.make_sharded_verdict(mesh)(mark_w, iu_w)
    shards = st.shards_in_order(garbage_w)
    check(
        len(shards) == D
        and all(sh.shape[0] * 32 == meta["shard_size"] for sh in shards),
        "a chip's verdict words are not a D-th of the slot space",
    )
    words = np.concatenate([np.asarray(sh) for sh in shards]).view(np.uint32)
    garbage = np.unpackbits(words.view(np.uint8), bitorder="little")[:n] > 0
    in_use = (graph["flags"] & trace_ops.FLAG_IN_USE) != 0
    check(
        np.array_equal(garbage, in_use & ~oracle),
        "sharded decremental wake's verdict words != oracle",
    )
    check(int(marked) == int(oracle.sum()), "sharded wake's mark count != oracle")
    # one wake's counters, present on all the shards
    stats = {k: np.asarray(v) for k, v in stats.items()}
    check(set(stats) == set(pd.WAKE_STATS) | {"gathers"}, "a counter is missing")
    check(
        all(v.shape[0] == D for v in stats.values()),
        "a counter is not reported by every shard",
    )
    for k in ("n_sweeps", "closure_sweeps", "closure_bailed", "gathers"):
        check((stats[k] == stats[k][0]).all(), f"the shards disagree on {k}")
    check(
        int(stats["n_sweeps"][0]) >= 1 and int(stats["closure_sweeps"][0]) == 0
        and int(walks) == int(stats["dirty_chunks"][0].sum()),
        "a derivation from nothing with a closure, or without sweeps",
    )
    check(int(stats["kernel_steps"].sum()) > 0, "no shard's kernel took a step")
    say(
        f"sharded wake counters: sweeps {int(stats['n_sweeps'][0])} gathers "
        f"{int(stats['gathers'][0])} kernel steps by shard "
        f"{stats['kernel_steps'].tolist()} of {stats['kernel_steps_full'].tolist()}, "
        f"contractions {stats['kernel_contractions'].tolist()}"
    )
    del operands, stacked, out, mark

    # -- what it is compared with: the one-device derivation, same graph #
    with Clock() as c:
        prep = pt.prepare_chunks(
            graph["edge_src"].astype(np.int32),
            graph["edge_dst"].astype(np.int32),
            graph["edge_weight"], graph["supervisor"], n,
        )
    say(f"one-device pack: {c.s:.1f}s")
    with Clock() as c:
        marks_one, _ = pd.derive(
            graph["flags"], graph["recv_count"], [prep], mode=pt.MODE_AUTO,
            jump_parent=pt.jump_parents(psrc, pdst, n),
        )
    say(f"one-device derivation (compile + upload + run): {c.s:.1f}s")
    check(np.array_equal(marks_one, oracle), "one-device derivation != oracle")
    check(np.array_equal(marks_one, marks_mesh), "one-device != sharded verdict")
    say(f"mesh data plane OK; device peak bytes (device 0) {peak_bytes()}")


def mesh_sharding_checks(D):
    """Per-run checks on a live MeshShadowGraph; ``extra.totals`` sums
    wakes and full rebuilds over the runs (a wake that is not a rebuild
    went through the O(churn) device sync: donated fold/mask/scatter)."""

    def extra(graph):
        sharded = {
            "_dev_flags": graph._dev_flags,
            "_dev_recv": graph._dev_recv,
            "_dev_psrc": graph._dev_psrc,
            "_dev_pdst": graph._dev_pdst,
            **{f"_dev_stacked[{k}]": v for k, v in graph._dev_stacked.items()},
        }
        for name, x in sharded.items():
            check(
                len(x.sharding.device_set) == D
                and not x.sharding.is_fully_replicated,
                f"mesh operand {name} does not span {D} devices: {x.sharding}",
            )
        jd = graph._jump_dev
        check(
            jd is not None
            and jd.sharding.is_fully_replicated
            and len(jd.sharding.device_set) == D,
            "mesh jump parents are not replicated over the mesh",
        )
        check(graph.stats["wakes"] > 0, "mesh backend never traced")
        for k in extra.totals:
            extra.totals[k] += graph.stats[k]

    extra.totals = {"wakes": 0, "rebuilds": 0, "anomalies": 0}
    return extra


# --------------------------------------------------------------------- #


def main() -> None:
    args = parse_args()
    if args.rehearse:
        # before JAX loads: the rehearsal is a CPU run on virtual devices
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}"
        ).strip()
    info = phase_device(args)

    if args.rehearse:
        args.actors = min(args.actors, 1 << 15)
    n = args.actors
    check(args.rehearse or n >= 1_000_000, "--actors below 1M is a rehearsal size")
    if args.chips == 1:
        phase_data_plane(args, n)
        shapes = [("tree", 10_000), ("rings", 100), ("tree", 100_000)]
        if args.rehearse:
            shapes = [("tree", 300), ("rings", 6), ("tree", 1_500)]
        served(args, ["decremental"], shapes)
    else:
        phase_mesh_data_plane(args, n)
        shapes = [("tree", 10_000), ("rings", 100)]
        if args.rehearse:
            shapes = [("tree", 300), ("rings", 6)]
        extra = mesh_sharding_checks(args.chips)
        served(
            args, ["mesh", "mesh-decremental"], shapes,
            extra_config={"uigc.crgc.mesh-devices": args.chips},
            extra=extra,
        )
        say(f"mesh backends over all runs: {extra.totals}")
        check(
            args.rehearse or extra.totals["wakes"] > extra.totals["rebuilds"],
            "no mesh wake went through the incremental device sync",
        )

    say(f"all phases passed in {time.perf_counter() - T_START:.0f}s")
    result = {"ok": True, "device": info}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
