"""Cross-process delta-gossip throughput (runtime/node.py transport).

Two OS processes: a churn node continuously spawns and releases actors,
its collector folds the entries into DeltaGraphs and gossips them over
the real TCP link (reference: LocalGC.scala:159-165,191-196); the
measuring node counts delta frames, wire bytes, and shadow merges for a
fixed window.

Prints one JSON object; commit as ``BENCH_GOSSIP_r{N}.json``.

Usage: python tools/gossip_bench.py [--seconds 5]
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

BASE = {
    # Two OS processes (parent + Popen'd child), and a chip belongs to
    # one: both collectors stay on the host backend and neither touches
    # a JAX backend.
    "uigc.crgc.shadow-graph": "array",
    "uigc.crgc.wakeup-interval": 10,
    "uigc.crgc.egress-finalize-interval": 10,
    "uigc.crgc.num-nodes": 2,
}


def child(port: int, seconds: float) -> None:
    from uigc_tpu import AbstractBehavior, Behaviors, NoRefs
    from uigc_tpu.runtime.node import NodeFabric
    from uigc_tpu.runtime.system import ActorSystem

    class Tick(NoRefs):
        pass

    class Churner(AbstractBehavior):
        """Every tick: spawn a batch of children, share refs between
        them (cross-shadow edges for the delta), then release — a
        steady stream of created/released facts for the delta plane."""

        def __init__(self, context):
            super().__init__(context)
            self.n = 0

        def on_message(self, msg):
            ctx = self.context
            if isinstance(msg, Tick):
                kids = [
                    ctx.spawn(
                        Behaviors.setup(lambda c: Sink(c)), f"k{self.n}-{i}"
                    )
                    for i in range(8)
                ]
                self.n += 1
                refs = [ctx.create_ref(kids[i], kids[i - 1]) for i in range(8)]
                ctx.release(kids)
                ctx.release(refs)
            return self

    class Sink(AbstractBehavior):
        def on_message(self, msg):
            return self

    fabric = NodeFabric()
    system = ActorSystem(
        None, name="gossipChurn", config=dict(BASE), fabric=fabric
    )
    fabric.listen()
    fabric.connect("127.0.0.1", port)
    root = system.spawn_root(
        Behaviors.setup_root(lambda ctx: Churner(ctx)), "churner"
    )
    deadline = time.monotonic() + seconds + 2
    while time.monotonic() < deadline:
        root.tell(Tick())
        time.sleep(0.002)
    import os

    os._exit(0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--child-port", type=int, default=0)
    args = ap.parse_args()
    if args.child_port:
        child(args.child_port, args.seconds)
        return

    from uigc_tpu.runtime.node import NodeFabric
    from uigc_tpu.runtime.system import ActorSystem

    fabric = NodeFabric()
    system = ActorSystem(
        None, name="gossipMeasure", config=dict(BASE), fabric=fabric
    )
    stats = {"deltas": 0, "delta_bytes": 0, "ringress": 0, "frames": 0}
    orig = fabric._on_frame

    def counting(addr, frame):
        stats["frames"] += 1
        if frame[0] == "delta":
            stats["deltas"] += 1
            stats["delta_bytes"] += len(frame[2])
        elif frame[0] == "ringress":
            stats["ringress"] += 1
        orig(addr, frame)

    fabric._on_frame = counting
    port = fabric.listen()

    proc = subprocess.Popen(
        [
            sys.executable,
            str(Path(__file__).resolve()),
            "--child-port",
            str(port),
            "--seconds",
            str(args.seconds),
        ]
    )
    # wait for the peer to join
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not fabric._conns:
        time.sleep(0.05)
    if not fabric._conns:
        raise RuntimeError("churn child never connected")

    baseline = dict(stats)
    t0 = time.perf_counter()
    time.sleep(args.seconds)
    dt = time.perf_counter() - t0
    deltas = stats["deltas"] - baseline["deltas"]
    dbytes = stats["delta_bytes"] - baseline["delta_bytes"]
    merged = system.engine.bookkeeper.shadow_graph.total_actors_seen

    proc.wait(timeout=30)
    print(
        json.dumps(
            {
                "bench": "cross-process delta gossip (tools/gossip_bench.py)",
                "seconds": round(dt, 2),
                "deltas_received": deltas,
                "deltas_per_sec": round(deltas / dt, 1),
                "delta_bytes_per_sec": round(dbytes / dt, 1),
                "remote_shadows_interned": int(merged),
                "frames_total": stats["frames"],
            }
        )
    )
    system.terminate()
    import os

    os._exit(0)


if __name__ == "__main__":
    main()
