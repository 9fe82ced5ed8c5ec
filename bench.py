"""Benchmark: garbage detection throughput on a power-law actor graph.

BASELINE config 5: a synthetic power-law refob graph, batched device trace.
Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The north-star target (BASELINE.json) is >=10M garbage actors/sec with
<=10ms p50 detection latency at a 10M-actor graph; vs_baseline is
throughput relative to that 10M/s target (no published reference numbers
exist — BASELINE.md documents the absence).

``--config`` selects the other BASELINE workloads, which drive the live
actor runtime end to end instead of the raw device kernel:
  churn    (1) CRGC, acyclic ownership tree of 10k actors
  mac      (2) MAC weighted-refcount, flat acyclic garbage
  rings    (3) CRGC cyclic garbage: 100 rings of 100 actors
  cluster  (4) CRGC 3-node crash recovery with injected message drops
  powerlaw (5) the default: batched device trace on a 10M-actor graph
Configs 1-4 report end-to-end collected actors/sec; no reference numbers
exist to normalize against, so their vs_baseline is null.
"""

import argparse
import json
import statistics
import time


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--n", type=int, default=None, help="number of actors")
    parser.add_argument("--reps", type=int, default=None)
    parser.add_argument("--garbage-fraction", type=float, default=0.5)
    parser.add_argument("--small", action="store_true", help="quick CPU-sized run")
    parser.add_argument(
        "--impl",
        choices=["pallas", "xla"],
        default=None,
        help="trace implementation (default: pallas on TPU, xla elsewhere)",
    )
    parser.add_argument(
        "--layout",
        choices=["static", "incremental"],
        default="static",
        help=(
            "pallas pair layout: one static pack, or the live collector's "
            "incremental base+delta layout with device-resident operands "
            "(ops/pallas_incremental.trace_device)"
        ),
    )
    parser.add_argument(
        "--sub",
        type=int,
        default=None,
        help="kernel walk geometry override: slot sub-blocks per grid step",
    )
    parser.add_argument(
        "--group",
        type=int,
        default=None,
        help="kernel walk geometry override: 8-row chunks per walk iteration",
    )
    parser.add_argument(
        "--config",
        choices=["powerlaw", "churn", "mac", "rings", "cluster"],
        default="powerlaw",
        help="BASELINE workload config (default: powerlaw, config 5)",
    )
    args = parser.parse_args()

    if args.config != "powerlaw":
        run_live_config(args)
        return

    import jax

    from uigc_tpu.utils.platform import enable_compile_cache, is_tpu_platform

    enable_compile_cache()

    import numpy as np

    # The benchmark runs on the platform JAX gives it and says which: a
    # backend that fails to initialise, or a kernel that fails to
    # compile, ends the run with the error and a non-zero exit.
    device = jax.devices()[0]
    platform = device.platform
    is_tpu = is_tpu_platform(platform)
    if args.n is not None:
        n = args.n
    elif args.small:
        n = 1 << 16
    else:
        n = 10_000_000

    from uigc_tpu.models import powerlaw_actor_graph
    from uigc_tpu.ops import trace as trace_ops

    impl = args.impl or ("pallas" if is_tpu else "xla")
    if args.layout == "incremental" and impl != "pallas":
        parser.error("--layout incremental requires the pallas impl")

    graph = powerlaw_actor_graph(n, seed=0, garbage_fraction=args.garbage_fraction)

    if impl == "pallas" and args.layout == "incremental":
        from uigc_tpu.ops import pallas_incremental

        layout = pallas_incremental.IncrementalPallasLayout(
            n, sub=args.sub, group=args.group
        )
        layout.rebuild(
            graph["edge_src"],
            graph["edge_dst"],
            graph["edge_weight"],
            graph["supervisor"],
        )

        def fn(flags_dev, recv_dev):
            return layout.trace_device(flags_dev, recv_dev)

        host_args = (graph["flags"], graph["recv_count"])
    elif impl == "pallas":
        from uigc_tpu.ops import pallas_trace

        prep = pallas_trace.prepare_chunks(
            graph["edge_src"].astype(np.int32),
            graph["edge_dst"].astype(np.int32),
            graph["edge_weight"],
            graph["supervisor"],
            n,
            sub=args.sub,
            group=args.group,
        )
        fn = pallas_trace.get_trace_fn(prep)
        host_args = (
            graph["flags"],
            graph["recv_count"],
        ) + pallas_trace.device_args(prep)
    else:
        if "fn" not in trace_ops._jax_trace_cache:
            trace_ops._jax_trace_cache["fn"] = trace_ops._build_jax_trace()
        fn = trace_ops._jax_trace_cache["fn"]
        host_args = (
            graph["flags"],
            graph["recv_count"],
            graph["supervisor"],
            graph["edge_src"].astype(np.int32),
            graph["edge_dst"].astype(np.int32),
            graph["edge_weight"],
        )
    dev_args = [jax.device_put(x) for x in host_args]

    # Warmup / compile, and verify verdicts.
    mark = fn(*dev_args)
    in_use = (graph["flags"] & trace_ops.FLAG_IN_USE) != 0
    garbage = in_use & ~np.asarray(mark)
    n_garbage = int(garbage.sum())
    assert np.array_equal(garbage, graph["expected_garbage"]), "wrong verdicts"

    # One-shot wall latency, host clock around a value readback (which
    # includes one host round-trip).
    t0 = time.perf_counter()
    one = fn(*dev_args)
    int(one.sum())
    one_shot = time.perf_counter() - t0

    # Sustained collector throughput.  Two regimes:
    #
    # - Fast traces (one-shot under 250 ms): chain reps inside one jit
    #   with an optimization barrier between them, so the per-call
    #   dispatch and readback cost is paid once per chain.  The chain
    #   length is capped so one device program stays a few seconds long.
    # - Slow traces: per-call timing with readback.  Never enqueue a
    #   multi-minute mega-program.
    budget_s = 20.0
    # The incremental layout's wake fn does host-side layout maintenance,
    # so it cannot be chained inside one jitted program.
    chainable = args.layout != "incremental"
    if one_shot < 0.25 and chainable:
        import jax.numpy as jnp

        @jax.jit
        def chained(chain_len, *state0):
            def body(_, carry):
                acc, state = carry
                mark = fn(*state)
                # Real data dependency so no trace can be elided or fused
                # away across iterations.
                acc = acc + jnp.count_nonzero(mark)
                state = jax.lax.optimization_barrier(state)
                return acc, state

            # Dynamic bound (lowered to while_loop): one compile covers
            # every chain length, so calibration costs no extra compiles.
            acc, _ = jax.lax.fori_loop(0, chain_len, body, (0, state0))
            return acc

        int(chained(2, *dev_args))  # compile
        # Calibrate per-trace cost from the *difference* of two chain
        # lengths, which cancels the fixed per-call cost (dispatch +
        # readback) — sizing reps from the one-shot wall latency would
        # fold that cost into the estimate.  The median of three pairs
        # guards against one noisy sample producing a near-zero estimate
        # (which would size a minutes-long chain); the one-shot-derived
        # floor is a second, independent guard.
        cal_len = 34
        estimates = []
        for _ in range(3):
            t0 = time.perf_counter()
            int(chained(2, *dev_args))
            t_short = time.perf_counter() - t0
            t0 = time.perf_counter()
            int(chained(cal_len, *dev_args))
            t_long = time.perf_counter() - t0
            estimates.append(max((t_long - t_short) / (cal_len - 2), 1e-6))
        per_trace = max(statistics.median(estimates), one_shot / 1000.0)

        n_chains = 3
        # Fill the budget, but keep any single device program a few
        # seconds long.
        max_chain_s = 6.0
        reps_cap = args.reps if args.reps is not None else 100_000
        reps = max(
            2,
            min(
                reps_cap,
                int(budget_s / n_chains / per_trace),
                int(max_chain_s / per_trace) + 1,
            ),
        )

        # Median of per-chain means, so the reported statistic matches the
        # slow regime's median (one chain can be skewed by host noise).
        times = []
        for _ in range(n_chains):
            t0 = time.perf_counter()
            int(chained(reps, *dev_args))  # forces full completion via readback
            times.append((time.perf_counter() - t0) / reps)
        p50 = statistics.median(times)
        reps = reps * n_chains
    else:
        reps = max(1, min(args.reps or 20, int(budget_s / one_shot) + 1))
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            m = fn(*dev_args)
            int(m.sum())
            times.append(time.perf_counter() - t0)
        p50 = statistics.median(times)

    throughput = n_garbage / p50
    target = 10_000_000.0  # north-star garbage actors/sec (BASELINE.json)

    result = {
        "metric": "garbage_actors_per_sec",
        "value": round(throughput, 1),
        "unit": "actors/s",
        "vs_baseline": round(throughput / target, 4),
        "p50_detection_ms": round(p50 * 1e3, 3),
        "one_shot_ms": round(one_shot * 1e3, 3),
        "n_actors": n,
        "n_garbage": n_garbage,
        "n_edges": int(graph["edge_src"].shape[0]),
        "timing_reps": reps,
        "platform": platform,
        "device_kind": device.device_kind,
        "impl": impl,
        "layout": args.layout,
    }
    print(json.dumps(result))


def run_live_config(args) -> None:
    """BASELINE configs 1-4: end-to-end collection through the live
    runtime (see uigc_tpu/models/workloads.py)."""
    from uigc_tpu.models import workloads

    n = args.n
    if args.config == "churn":
        r = workloads.run_tree(n_actors=n or 10_000, fanout=8, engine="crgc")
    elif args.config == "mac":
        r = workloads.run_tree(n_actors=n or 10_000, fanout=1 << 30, engine="mac")
    elif args.config == "rings":
        rings = max(1, (n or 10_000) // 100)
        r = workloads.run_rings(n_rings=rings, ring_size=100)
    else:  # cluster
        r = workloads.run_cluster_recovery(n_workers=n or 200)

    throughput = r["n_collected"] / r["collect_s"]
    result = {
        "metric": f"{args.config}_collected_actors_per_sec",
        "value": round(throughput, 1),
        "unit": "actors/s",
        "vs_baseline": None,  # no reference numbers exist (BASELINE.md)
        "collect_s": round(r["collect_s"], 3),
        "build_s": round(r["build_s"], 3),
        "n_collected": r["n_collected"],
        "config": args.config,
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
