"""Driver ``tracer_wake_chain``: ``tracer_wake`` over a graph that comes
from a generator of its own and is too deep for ``reference.trace_marks``.

Everything that is timed is ``drivers/tracer_wake.py``'s, byte for byte:
this file loads it (``harness.cell.load_driver``), subclasses its
``Driver`` and changes two things that lie outside the window:

- the generator ``chain`` (``graphgen_chain.py``) is put into the
  ``GENERATORS`` dict that ``tracer_wake`` looks its generator up in (the
  dict object, at import; no file changes);
- the reference is ``reference_bfs.trace_marks``, the same fixpoint
  computed in time linear in the pairs: ``reference.trace_marks`` needs
  one pass over whole arrays per hop, 500,000 of them on ``chain-1m``.

Only re-derivation traffic runs here (``"rederive": true``): the graph is
never churned, so the reference is of the resident graph.

The control (``--control``) gives the reference one release the program
never saw, as ``tracer_wake``'s does, but draws it from the seed among
the references of the live chain's middle half: the reference then calls
everything below it garbage (a quarter to three quarters of the chain),
where ``tracer_wake``'s last-reference releases would find one actor on
a chain (its tail).

How a configuration with its own generator and reference was added
without editing a file: ``README-chain.md``.
"""

from __future__ import annotations

import numpy as np

import reference
import reference_bfs
from graphgen_chain import chain
from harness.cell import load_driver

base = load_driver("tracer_wake")
base.GENERATORS.setdefault("chain", chain)


class Driver(base.Driver):
    def setup(self) -> None:
        if not self.ctx.traffic.get("rederive"):
            # the closure phase is push-only: a release in mid-chain
            # costs one closure sweep per hop (PERF.md section 7)
            raise SystemExit("driver tracer_wake_chain runs re-derivation traffic only")
        super().setup()

    def _reference_garbage(self, upto: int, extra_release=None) -> np.ndarray:
        """The linear reference's garbage on the resident graph (no batch
        ever churns it), less the control's release."""
        g = self.g
        weight = g["edge_weight"].copy()
        if extra_release is not None:
            weight[extra_release] = 0
        marks = reference_bfs.trace_marks(
            g["flags"], g["recv_count"], g["supervisor"],
            g["edge_src"], g["edge_dst"], weight,
        )
        return reference.garbage(g["flags"], marks)

    def _control_batch(self):
        """One reference of the live chain's middle half, released for the
        reference alone."""
        if not self.ctx.control:
            return None
        g = self.g
        on_chain = np.nonzero(
            (g["edge_src"] >= self.n_live // 4) & (g["edge_dst"] < self.n_live - self.n_live // 4)
        )[0]
        pick = np.random.default_rng([self.ctx.seed, 13])
        return on_chain[pick.integers(0, on_chain.size, 1)]
