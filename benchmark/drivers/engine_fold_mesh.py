"""Driver ``engine_fold_mesh``: ``engine_fold`` over a shadow graph that is
sharded across the chips of one host (``shadow-graph: mesh-decremental``).

Everything that is timed is ``drivers/engine_fold.py``'s, byte for byte:
this file loads it (``harness.cell.load_driver``), subclasses its
``Driver`` and changes what names the one-chip backend's tracer
(``graph._dec``), which a ``MeshShadowGraph`` does not have:

- before anything is built, the program must have the sharded wake's
  counters (``MeshShadowGraph.wake_stats``): the parent of the PR that
  brought the mesh road level with the one-chip wake fails here, in
  seconds, with another exit code than 0;
- the mesh's geometry is left in ``obs.facts["mesh"]`` (devices, padded
  slots) for the readers that count bytes from shapes; the wakes'
  counters are found by the readers themselves, where the one-chip cells'
  are (``pallas_decremental.live_tracers()``: the mesh graph is one);
- ``correct`` is ``engine_fold``'s, by uid, plus: the layout's anomalies
  are the mesh's own (``graph.stats``); the mesh has as many devices as
  the cell has chips; the layout was not rebuilt inside the window
  (``graph.stats["rebuilds"]``); every wake of the window left its
  counters; and the last wake's verdict words, as each shard holds them
  for its own slot range and laid end to end, are the whole verdict the
  sweep took, with as many slots in them as that wake delivered uids.

A rehearsal needs four devices before the harness loads this file
(``run.py`` counts them first): ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from harness.cell import load_driver
from harness.report import exact as exact_check

base = load_driver("engine_fold")


class Driver(base.Driver):
    def setup(self) -> None:
        from uigc_tpu.engines.crgc import mesh

        if not hasattr(mesh.MeshShadowGraph, "wake_stats"):
            # before anything is built: the parent fails in seconds
            raise SystemExit("the program's sharded wake carries no counters "
                             "(MeshShadowGraph.wake_stats): it cannot run this cell")
        super().setup()
        graph = self.graph
        if not isinstance(graph, mesh.MeshShadowGraph) or not graph.decremental:
            raise SystemExit(f"backend {type(graph).__name__}: the configuration's "
                             "uigc.crgc.shadow-graph must be mesh-decremental")
        self.obs.facts["mesh"] = {"devices": graph.n_devices, "n_pad": graph._n_pad}
        self.ctx.say(f"engine_fold_mesh: {graph.n_devices} devices, {graph._n_pad} padded slots, "
                     f"{graph._shard_size} a shard, {graph._layout_meta['n_blocks']} blocks a shard; "
                     f"layout packs in set-up {graph.stats['rebuilds']}")

    def window(self, seconds: float) -> None:
        self.rebuilds_before = self.graph.stats["rebuilds"]
        super().window(seconds)
        self.rebuilds_in_window = self.graph.stats["rebuilds"] - self.rebuilds_before
        wakes = len(self.released) - self.window_first
        self.window_stats = self.graph.wake_stats(wakes)
        if self.window_stats:
            steps = np.array([w["kernel_steps"] for w in self.window_stats], dtype=np.float64)
            last = self.window_stats[-1]
            self.ctx.say(
                "engine_fold_mesh: kernel steps a wake by shard (median) "
                f"{np.median(steps, axis=0).tolist()}; last wake: closure sweeps "
                f"{last['closure_sweeps']} bailed {last['closure_bailed']} repair sweeps "
                f"{last['n_sweeps']} gathers {last['gathers']} contractions "
                f"{last['kernel_contractions']} of steps {last['kernel_steps']} of "
                f"{last['kernel_steps_full']}")

    def check(self) -> List[Dict[str, object]]:
        graph = self.graph
        out = [c for c in super().check() if c["name"] != "layout_anomalies"]

        def exact(name, value):
            out.append(exact_check(name, value))

        exact("layout_anomalies", graph.stats["anomalies"])
        exact("mesh_devices_differing_from_the_cells_chips", graph.n_devices != self.ctx.chips)
        exact("layout_packs_inside_the_window", self.rebuilds_in_window)
        wakes = len(self.released) - self.window_first
        exact("window_wakes_without_counters_from_every_shard", sum(
            1 for w in self.window_stats
            if len(w["kernel_steps"]) != graph.n_devices) + abs(wakes - len(self.window_stats)))
        shards = graph.shard_verdict_words()
        whole = graph.last_verdict_words
        ranges_wrong = len(shards) != graph.n_devices or any(
            w.size * 32 != graph._shard_size for w in shards)
        exact("shard_verdict_words_not_of_the_shards_slot_ranges", ranges_wrong)
        exact("verdict_words_laid_end_to_end_differing_from_the_whole_verdict",
              1 if ranges_wrong else np.count_nonzero(np.concatenate(shards) != whole))
        # the last wake's words name the slots its sweep freed: one a uid
        # it delivered
        last = self.reported[-1].size if self.reported else self.garbage0.sum()
        bits = int(np.unpackbits(whole.view(np.uint8)).sum())
        exact("last_verdict_slots_differing_from_the_uids_delivered", bits - int(last))
        return out
