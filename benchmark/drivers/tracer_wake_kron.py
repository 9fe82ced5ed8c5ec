"""Driver ``tracer_wake_kron``: ``tracer_wake`` over a graph whose slot
order knows nothing of liveness (the Graph500 Kronecker graph,
``graphgen_kron.py``).

Everything that is timed is ``drivers/tracer_wake.py``'s, byte for byte:
this file loads it (``harness.cell.load_driver``), subclasses its
``Driver`` and changes what lies outside the window, as
``drivers/tracer_wake_chain.py`` does:

- the generator ``kron`` is put into the ``GENERATORS`` dict that
  ``tracer_wake`` looks its generator up in (the dict object, at import;
  no file changes), with what ``tracer_wake`` expects every generator to
  say about liveness filled in from the reference: the other generators
  build ``expected_garbage`` into the slot order (slots ``[0, n_live)``
  live, the rest garbage), here it is whatever ``reference_bfs.trace_marks``
  finds unreached, and ``n_live`` is a count and no slot boundary;
- the reference is ``reference_bfs.trace_marks``, linear in the pairs
  (``reference.trace_marks`` would do here too: the graph is shallow);
- the control (``--control``) draws from the seed ONE reference whose
  release changes the verdict: the only reference a live actor holds to a
  live actor that supervises no live one.  ``tracer_wake``'s control draws
  its releases below the slot boundary this graph does not have.

Only re-derivation traffic runs here (``"rederive": true``):
``tracer_wake`` draws a churn batch's ends below ``n_live``.

The file starts by importing the program's copy of the generator, which
set-up holds to the benchmark's at rehearsal size: a program without it
cannot run the configuration and fails at once.

How the configuration was added without editing a file: ``README-kron.md``.
"""

from __future__ import annotations

import time

import numpy as np

import reference
import reference_bfs
from graphgen_kron import kron
from harness.cell import load_driver
from uigc_tpu.models.graphgen import kron_actor_graph

base = load_driver("tracer_wake")

GRAPH_KEYS = ("flags", "recv_count", "supervisor", "edge_src", "edge_dst", "edge_weight")


def reference_garbage(g, weight=None) -> np.ndarray:
    """The linear reference's garbage on the graph ``g``, its references
    counted as ``weight`` where that is given."""
    marks = reference_bfs.trace_marks(
        g["flags"], g["recv_count"], g["supervisor"], g["edge_src"], g["edge_dst"],
        g["edge_weight"] if weight is None else weight,
    )
    return reference.garbage(g["flags"], marks)


def kron_with_verdict(**params):
    """``kron`` with the liveness ``tracer_wake`` reads off a generator,
    from the reference."""
    g = kron(**params)
    g["expected_garbage"] = reference_garbage(g)
    g["n_garbage"] = int(g["expected_garbage"].sum())
    g["n_live"] = g["flags"].shape[0] - g["n_garbage"]
    return g


base.GENERATORS.setdefault("kron", kron_with_verdict)


class Driver(base.Driver):
    def setup(self) -> None:
        ctx = self.ctx
        if not ctx.traffic.get("rederive"):
            # tracer_wake draws a batch's ends among slots [0, n_live)
            raise SystemExit("driver tracer_wake_kron runs re-derivation traffic only")
        t0 = time.perf_counter()
        small = {k: v for k, v in ctx.config["graph"].items() if k != "generator"}
        small.update(ctx.config["rehearse"]["graph"], seed=int(ctx.config["graph_seed"]))
        mine, theirs = kron_with_verdict(**small), kron_actor_graph(**small)
        differing = [k for k in GRAPH_KEYS + ("expected_garbage",)
                     if not np.array_equal(mine[k], theirs[k])]
        if differing:
            raise SystemExit(f"the program's kron_actor_graph and the benchmark's kron differ "
                             f"at scale {small['scale']} in {differing}")
        ctx.phase("the program's generator against the benchmark's", time.perf_counter() - t0,
                  f"scale {small['scale']}: equal")
        super().setup()
        # the layout's walk chunks, for layers/dirty_chunks_pct.kron.py
        from uigc_tpu.ops import pallas_trace as pt

        layout = self.tracer.layout.base
        self.obs.facts["walk_chunks"] = layout["r_rows"] // (pt.ROWS * layout["group"])
        ctx.say(f"walk chunks {self.obs.facts['walk_chunks']}; garbage {self.g['n_garbage']} "
                f"of {self.n} actors")

    def _reference_garbage(self, upto: int, extra_release=None) -> np.ndarray:
        """The linear reference's garbage on the resident graph (no batch
        ever churns it), less the control's release."""
        weight = None
        if extra_release is not None:
            weight = self.g["edge_weight"].copy()
            weight[extra_release] = 0
        return reference_garbage(self.g, weight)

    def _control_batch(self):
        """One reference, released for the reference alone, that a live
        actor owes its life to: the only reference from a live actor to a
        live actor that is no root and supervises no live one."""
        if not self.ctx.control:
            return None
        g, n = self.g, self.n
        live = self.in_use & ~g["expected_garbage"]
        src, dst, sup = g["edge_src"], g["edge_dst"], g["supervisor"]
        held = np.flatnonzero(live[src] & (g["edge_weight"] > 0))
        holders = np.bincount(dst[held], minlength=n)
        child = np.flatnonzero(live & (sup >= 0))
        kids = np.bincount(sup[child], minlength=n)
        is_root = (g["flags"] & reference.FLAG_ROOT) != 0
        owing = live & ~is_root & (holders == 1) & (kids == 0)
        last = held[owing[dst[held]]]
        pick = np.random.default_rng([self.ctx.seed, 13])
        return last[pick.integers(0, last.size, 1)]
