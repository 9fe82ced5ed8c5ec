"""Per-wake detection latency at graph scale under churn.

Models the collector's steady state (reference: LocalGC.scala:144-186, a
50ms-cadence incremental collect): a long-lived 10M-actor graph, and per
wake a batch of pair transitions (ref releases + new refs) folded into the
incremental Pallas layout in O(churn), then a device trace to fixpoint and
a compacted on-device reduction of garbage ids.  The full O(E log E) pack
runs once at startup; wakes pay only layout maintenance + the trace — the
layout's operand arrays stay device-resident between wakes
(IncrementalPallasLayout.trace_device) and sync in O(churn).

The JSON output reports p50/p90 of the host-maintenance, device-trace and
end-to-end wake times against BASELINE.md's <=10ms target, with the
device verdicts cross-checked against the numpy oracle on the first and
last wake.

Usage: python tools/wake_bench.py [--actors N] [--wakes 20]
       [--churn 20000] [--small]
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--actors", type=int, default=None)
    ap.add_argument("--wakes", type=int, default=20)
    ap.add_argument("--churn", type=int, default=20_000)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--no-oracle", action="store_true")
    ap.add_argument(
        "--mode",
        choices=["full", "decremental"],
        default="full",
        help=(
            "full: re-trace to fixpoint from seeds every wake "
            "(IncrementalPallasLayout.trace_device); decremental: "
            "closure+repair from the previous fixpoint "
            "(pallas_decremental.DecrementalTracer) — per-wake cost "
            "proportional to the churn's affected region"
        ),
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from uigc_tpu.models import powerlaw_actor_graph
    from uigc_tpu.ops import pallas_incremental as pinc
    from uigc_tpu.ops import trace as trace_ops
    from uigc_tpu.ops.slotmap import pack_keys
    from uigc_tpu.utils.platform import enable_compile_cache, is_tpu_platform

    enable_compile_cache()
    platform = jax.devices()[0].platform
    on_tpu = is_tpu_platform(platform)
    n = args.actors or (10_000_000 if on_tpu and not args.small else 1 << 16)

    rng = np.random.default_rng(7)
    graph = powerlaw_actor_graph(n, seed=0, garbage_fraction=0.5)
    flags = graph["flags"]
    recv = graph["recv_count"]

    t0 = time.perf_counter()
    if args.mode == "decremental":
        from uigc_tpu.ops.pallas_decremental import DecrementalTracer

        tracer = DecrementalTracer(n)
        layout = tracer.layout
        tracer.rebuild(
            graph["edge_src"], graph["edge_dst"], graph["edge_weight"],
            graph["supervisor"],
        )
    else:
        tracer = None
        layout = pinc.IncrementalPallasLayout(n)
        layout.rebuild(
            graph["edge_src"], graph["edge_dst"], graph["edge_weight"],
            graph["supervisor"],
        )
    rebuild_s = time.perf_counter() - t0

    # Base pair arrays (the churn population) + an oracle weight mask.
    psrc, pdst, kinds = pinc.IncrementalPallasLayout.pairs_from_graph(
        graph["edge_src"], graph["edge_dst"], graph["edge_weight"],
        graph["supervisor"],
    )
    base_keys_sorted = np.sort(pack_keys(psrc, pdst, kinds))
    removable = np.nonzero(kinds == 0)[0]  # churn stays edge-kind only
    removed = np.zeros(psrc.size, dtype=bool)
    ins_src: list = []
    ins_dst: list = []
    ins_seen: dict = {}

    in_use = (flags & trace_ops.FLAG_IN_USE) != 0
    id_cap = 1 << 17  # compacted garbage-id readback capacity

    @jax.jit
    def finish(mark, flags_dev):
        in_use_d = (flags_dev & trace_ops.FLAG_IN_USE) != 0
        garbage = in_use_d & (~mark)
        ids = jnp.nonzero(garbage, size=id_cap, fill_value=n)[0]
        return jnp.count_nonzero(garbage), ids

    flags_dev = jax.device_put(flags)
    recv_dev = jax.device_put(recv)

    if tracer is not None:
        from uigc_tpu.ops import pallas_trace as pt

        @jax.jit
        def unpack_marks(words):
            return pt.unpack_table(words, n, jnp)

    def run_wake():
        if tracer is not None:
            mark = unpack_marks(tracer.wake_device(flags_dev, recv_dev))
        else:
            mark = layout.trace_device(flags_dev, recv_dev)
        count, ids = finish(mark, flags_dev)
        return int(count), np.asarray(ids)

    def oracle_garbage():
        src = np.concatenate([psrc, np.asarray(ins_src, np.int64)])
        dst = np.concatenate([pdst, np.asarray(ins_dst, np.int64)])
        w = np.concatenate(
            [
                np.where(removed, 0, 1).astype(np.int64),
                np.ones(len(ins_src), np.int64),
            ]
        )
        m = trace_ops.trace_marks_np(
            flags, recv, np.full(n, -1, np.int32), src, dst, w
        )
        return int((in_use & ~m).sum())

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    # Warmup (compiles trace + readback; includes the first wake's pack).
    log(f"rebuild done in {rebuild_s:.1f}s; warmup trace...")
    count0, _ = run_wake()
    log(f"warmup done, garbage={count0}")
    checks = []
    if not args.no_oracle:
        checks.append(
            {"wake": "initial", "device": count0, "oracle": oracle_garbage()}
        )

    host_ms, trace_ms, wake_ms = [], [], []
    count = count0
    k = args.churn
    for w in range(args.wakes):
        # -- churn: half removals of live base pairs, half fresh inserts --
        cand = rng.choice(removable, k // 2, replace=False)
        cand = cand[~removed[cand]]
        new_s = rng.integers(0, n, k // 2, dtype=np.int64)
        new_d = rng.integers(0, n, k // 2, dtype=np.int64)
        new_keys = pack_keys(new_s, new_d, np.zeros(k // 2, np.int64))
        # skip inserts colliding with base pairs or earlier inserts
        pos = np.searchsorted(base_keys_sorted, new_keys)
        pos = np.minimum(pos, base_keys_sorted.size - 1)
        fresh = base_keys_sorted[pos] != new_keys

        log_batch = [
            (False, int(s), int(d), 0)
            for s, d in zip(psrc[cand].tolist(), pdst[cand].tolist())
        ]
        for key, s, d, f in zip(
            new_keys.tolist(), new_s.tolist(), new_d.tolist(), fresh.tolist()
        ):
            if not f or key in ins_seen:
                continue
            ins_seen[key] = None
            log_batch.append((True, s, d, 0))

        t0 = time.perf_counter()
        (tracer or layout).apply_log(log_batch)
        t1 = time.perf_counter()
        count, ids = run_wake()
        t2 = time.perf_counter()
        host_ms.append((t1 - t0) * 1e3)
        trace_ms.append((t2 - t1) * 1e3)
        wake_ms.append((t2 - t0) * 1e3)

        # mirror into the oracle state
        removed[cand] = True
        for ins, s, d, kind in log_batch:
            if ins:
                ins_src.append(s)
                ins_dst.append(d)
        log(
            f"wake {w}: host {host_ms[-1]:.1f}ms trace {trace_ms[-1]:.1f}ms "
            f"garbage={count}"
        )

    if not args.no_oracle:
        checks.append(
            {"wake": "final", "device": count, "oracle": oracle_garbage()}
        )

    ok = all(c["device"] == c["oracle"] for c in checks)
    p50 = statistics.median(wake_ms)
    result = {
        "bench": "per_wake_detection",
        "mode": args.mode,
        "n_actors": n,
        "n_pairs": int(layout.base["n_pairs"]),
        "wakes": args.wakes,
        "churn_per_wake": k,
        "platform": platform,
        "rebuild_s": round(rebuild_s, 2),
        "p50_wake_ms": round(p50, 2),
        "p90_wake_ms": round(sorted(wake_ms)[int(0.9 * len(wake_ms))], 2),
        "p50_host_maintenance_ms": round(statistics.median(host_ms), 2),
        "p50_trace_ms": round(statistics.median(trace_ms), 2),
        "layout_stats": {
            kk: (round(v, 3) if isinstance(v, float) else v)
            for kk, v in layout.stats.items()
        },
        "oracle_checks": checks,
        "oracle_ok": ok,
        "target_p50_ms": 10.0,
        "vs_target": round(10.0 / p50, 4),
    }
    print(json.dumps(result))
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
