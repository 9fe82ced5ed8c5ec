"""BASELINE configs 1-4: end-to-end collection through the live runtime.

``--config`` names the workload (``uigc_tpu/models/workloads.py``):
  churn    (1) CRGC, acyclic ownership tree of 10k actors
  mac      (2) MAC weighted-refcount, flat acyclic garbage
  rings    (3) CRGC cyclic garbage: 100 rings of 100 actors
  cluster  (4) CRGC 3-node crash recovery with injected message drops
Prints ONE JSON line with end-to-end collected actors/sec; no reference
numbers exist to normalize against (BASELINE.md), so vs_baseline is null.

Config 5, the device trace of a 10M-actor power-law graph, is the
benchmark's (``BENCHMARK.json``, ``benchmark/run.py``: the
``powerlaw-10m`` cells).
"""

import argparse
import json


def main() -> None:
    from uigc_tpu.models import workloads

    parser = argparse.ArgumentParser()
    parser.add_argument("--n", type=int, default=None, help="number of actors")
    parser.add_argument(
        "--config",
        choices=["churn", "mac", "rings", "cluster"],
        required=True,
        help="BASELINE workload config",
    )
    args = parser.parse_args()

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
