"""Measure per-wake Pallas-layout maintenance: full repack vs incremental.

Round 1 re-ran prepare_chunks (a full lexsort over every live pair)
before nearly every collector wake on a churning graph.  The incremental
layout (ops/pallas_incremental.py) replaces that with O(changes)
maintenance: in-place masking for deletes plus a
small delta pack for inserts.  This tool measures both costs on the same
synthetic power-law graph and churn stream — host-side work only, so the
numbers are platform-independent (the kernel itself is benchmarked by
bench.py).

Usage: python tools/pack_bench.py [--n 1000000] [--churn 10000] [--wakes 5]
Prints one JSON line; committed artifacts live in BENCH_PACK_r*.json.
"""

import argparse
import json
import statistics
import sys
import time

sys.path.insert(0, ".")

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--churn", type=int, default=10_000, help="pair transitions per wake")
    ap.add_argument("--wakes", type=int, default=5)
    args = ap.parse_args()

    from uigc_tpu.models import powerlaw_actor_graph
    from uigc_tpu.ops import pallas_incremental as pinc
    from uigc_tpu.ops import pallas_trace
    from uigc_tpu.ops.slotmap import PairLog

    graph = powerlaw_actor_graph(args.n, seed=0, garbage_fraction=0.5)
    src = graph["edge_src"].astype(np.int32)
    dst = graph["edge_dst"].astype(np.int32)
    w = graph["edge_weight"]
    sup = graph["supervisor"]
    rng = np.random.default_rng(1)

    # What round 1 paid on every wake whose interval saw any edge insert:
    full_times = []
    for _ in range(args.wakes):
        t0 = time.perf_counter()
        pallas_trace.prepare_chunks(src, dst, w, sup, args.n, pad_blocks_pow2=True)
        full_times.append(time.perf_counter() - t0)

    # What the incremental layout pays per wake for the same churn:
    layout = pinc.IncrementalPallasLayout(args.n)
    layout.rebuild(src, dst, w, sup)
    rebuild_s = layout.stats["pack_s"]

    live = np.nonzero(w > 0)[0]
    seen_inserts = set()
    inc_times = []
    apply_times = []
    for _ in range(args.wakes):
        # Half deletes of existing live edges, half fresh inserts.  Kill
        # candidates are removed from the live pool so a later wake never
        # re-deletes the same edge (which would hit the layout's anomaly
        # path instead of doing real deletion work); inserts are deduped
        # for the same reason.
        kill = rng.choice(live, size=args.churn // 2, replace=False)
        live = np.setdiff1d(live, kill, assume_unique=True)
        fresh = []
        while len(fresh) < args.churn // 2:
            pair = (int(rng.integers(0, args.n)), int(rng.integers(0, args.n)))
            if pair not in seen_inserts:
                seen_inserts.add(pair)
                fresh.append(pair)
        # the production path: the fold's batches go into the log as the
        # arrays they are (arrays.py _log_pairs_batch), and apply_log
        # folds the columns
        log = PairLog()
        log.extend(False, src[kill], dst[kill], pinc.EDGE)
        log.extend(True, *np.array(fresh, np.int64).reshape(-1, 2).T, pinc.EDGE)
        t0 = time.perf_counter()
        layout.apply_log(log)
        apply_times.append(time.perf_counter() - t0)
        # everything trace() does on the host except the kernel launch
        layout.prepare_wake()
        inc_times.append(time.perf_counter() - t0)

    result = {
        "metric": "pack_ms_per_wake",
        "n_actors": args.n,
        "n_pairs": int((w > 0).sum() + (sup >= 0).sum()),
        "churn_per_wake": args.churn,
        "full_repack_ms_p50": round(statistics.median(full_times) * 1e3, 2),
        "incremental_ms_p50": round(statistics.median(inc_times) * 1e3, 2),
        "speedup": round(
            statistics.median(full_times) / statistics.median(inc_times), 1
        ),
        "one_time_rebuild_ms": round(rebuild_s * 1e3, 2),
        # apply_log alone over the columns, and the host's unit cost
        "apply_log_ms_p50": round(statistics.median(apply_times) * 1e3, 2),
        "apply_log_us_per_row": round(
            statistics.median(apply_times) * 1e6 / args.churn, 3
        ),
        "anomalies": layout.stats["anomalies"],
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
