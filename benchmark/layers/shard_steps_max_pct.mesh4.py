"""``shard_steps_max_pct.mesh4``: how evenly the kernel's work falls on the shards: 100 x the largest shard's
``kernel_steps`` over the shards' mean, median over the window's wakes.  100 is balance; 100 x D (400 on four chips) is
one shard doing everything while the others wait for it in every all-gather.  ``kernel_steps`` is counted by the sharded
wake itself, per shard, in the carries of its two loops (``make_sharded_decremental_wake``: the grid steps its propagate
kernel took), and read after the window through ``MeshShadowGraph.wake_stats()``, which
``pallas_decremental.live_tracers()`` finds.  Nothing on a program whose counters are one chip's."""

from harness.mesh_trace import shard_wake_stats
from harness.stats import percentile


def read(obs):
    stats = shard_wake_stats(obs)
    if not stats:
        return None
    shares = [
        100.0 * max(w["kernel_steps"]) * len(w["kernel_steps"]) / sum(w["kernel_steps"])
        for w in stats if sum(w["kernel_steps"])
    ]
    return percentile(shares, 50) if shares else None
