"""Driver ``served_fold``: the served runtime beside a large resident graph
that its collector holds by uid alone.

One node.  ``drivers/served.py``'s deployment whole and unchanged (the
``ActorSystem`` with the configuration's ``uigc.*`` keys, the resident
tree, the ping pairs, one owner per session slot, the Bookkeeper on its own
``wakeup-interval`` timer, the sessions and pings of the traffic file, its
samples, its ``correct`` and its control), and in the SAME shadow graph
the actors of mutator processes this collector does not host
(``drivers/engine_fold.py``: rows in through ``PackedPlane.write_foreign``,
uids out through ``CRGC.set_foreign_sink``).  The driver stands for those
processes in set-up only: it ships the configuration's graph once, takes
the first verdict, and writes no row after it.  The residents hold still;
the sessions are the traffic.  No reference ties the two sides: the
runtime has no refob to an actor held by uid.

Set-up, each step a ``set-up`` line:

- the program must have ``CRGC.hold_traces``, its one way to fold without
  tracing while a bulk load is in progress; without it the run exits
  non-zero before anything is generated (the parent of the PR that added
  it fails in seconds);
- ``engine_fold.keep_the_heap()``, before any thread of the system exists;
- the foreign graph is generated (``graphgen.GENERATORS``, ``graph_seed``);
- ``served.py``'s set-up builds the system, the resident tree, the pairs
  and the owners, the collector tracing on its timer as it does there;
- before that set-up's warm-up, under ``hold_traces()``: the sink is
  registered and the graph goes through the plane in blocks of
  ``load_rows_per_batch`` rows, by ``engine_fold``'s own loop (its
  ``encode_rows``; its ``FOLD`` after each block, which under the hold only
  saves the wait for the timer).  Every wake-up in between folds and does
  not trace;
- the hold is left and the driver waits: the timer's next wake-up drains
  what is left, builds the layout at the graph's capacity, compiles or
  loads the wake program, traces, and its sweep hands the garbage half of
  the graph to the sink, the first verdict;
- ``served.py``'s warm-up (sessions and pings as in the window), which
  here also absorbs the second pack (the mass death overflows the pair
  log, ``PERF.md`` section 5);
- ``gc.collect(); gc.freeze()``, as ``engine_fold`` ends its set-up: the
  graph's per-slot lists have 2^24 entries and every full collection of
  CPython's collector would walk them.

The window is ``served.py``'s.  The driver sends the Bookkeeper nothing in
it; the one thing it adds is a ``bench:wake`` span around the backend's
``compute_marks`` (the device call of a wake that traces), written from
the collector's own thread, so that the readers that count wakes by that
span (``kernel_ms``, ``closure_ms``, the sweep counters) read this cell
as they read the tracer cells.

``correct``: ``served.py``'s numbers, and of the foreign side, every limit
0, against ``reference.trace_marks`` on the generator's arrays: the uids
the sink was handed up to the first verdict are the reference's garbage,
each once; those to stop are the garbage whose supervisor lives; no uid
reached the sink after; after the window the foreign uids the engine
holds are the reference's live set, their ``flags``, ``recv_count`` and
supervisors the generator's, the references among them with their counts
the generator's among live actors; no reference ties a local and a
foreign slot; the layout reports no anomaly.
"""

from __future__ import annotations

import gc
import threading
import time
from contextlib import contextmanager
from typing import Dict, List

import numpy as np

import reference
from graphgen import GENERATORS
from harness.cell import load_driver
from harness.obs import ANNOTATION_PREFIX
from harness.report import exact as exact_check

served = load_driver("served")
fold = load_driver("engine_fold")

_NO_UIDS = np.empty(0, np.int64)
_keys = fold.base._keys  # (src << 32 | dst), as engine_fold's comparison packs a reference


@contextmanager
def wake_span(obs):
    """``obs.span("wake")`` for the collector's thread.  The harness starts
    and stops the profiler, and with it sets and clears ``obs.annotate``, on
    the driver's ticker thread; ``Obs.span`` reads that attribute twice,
    which is safe only on the thread that ticks.  Here it is read once."""
    annotate = obs.annotate
    note = annotate(ANNOTATION_PREFIX + "wake") if annotate is not None else None
    if note is not None:
        note.__enter__()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        t1 = time.perf_counter()
        if note is not None:
            note.__exit__(None, None, None)
        if obs.recording:
            with obs._lock:
                obs.spans.setdefault("wake", []).append((t0, t1))


class Driver(served.Driver):
    # the loader: engine_fold's own loop over its encoder
    _load_graph = fold.Driver._load_graph
    _await_folded = fold.Driver._await_folded
    graph = fold.Driver.graph

    # ----------------------------------------------------------------- #
    # set-up
    # ----------------------------------------------------------------- #

    def setup(self) -> None:
        from uigc_tpu.engines.crgc.engine import CRGC

        if not hasattr(CRGC, "hold_traces"):
            raise SystemExit("the program has no way to hold traces during a bulk load "
                             "(CRGC.hold_traces): on its timer the collector would trace "
                             "a part-loaded graph")
        ctx, cfg = self.ctx, self.ctx.config
        ctx.say("served_fold: " + fold.keep_the_heap())
        t0 = time.perf_counter()
        params = dict(cfg["graph"])
        g = self.g = GENERATORS[params.pop("generator")](seed=int(cfg["graph_seed"]), **params)
        self.n = g["flags"].shape[0]
        #: a row's busy/root bits, by uid: nobody is busy at a flush
        self.bits = np.where((g["flags"] & reference.FLAG_ROOT) != 0, 2, 0).astype(np.int64)
        ctx.phase("generate", time.perf_counter() - t0,
                  f"actors={self.n} edges={g['edge_src'].size} live={int(g['n_live'])} "
                  f"graph_seed={cfg['graph_seed']}")
        self.answers: List[tuple] = []
        self.first_verdict = threading.Event()
        self.loaded = False
        super().setup()  # its warm-up goes through _drive, which loads first
        self.answers_in_setup = len(self.answers)
        gc.collect()
        gc.freeze()

    def _drive(self, seconds: float, warm: bool) -> None:
        if not self.loaded:
            self.loaded = True
            self._load_the_foreign_side()
        super()._drive(seconds, warm)

    def _load_the_foreign_side(self) -> None:
        from uigc_tpu.engines.crgc import collector

        ctx = self.ctx
        engine = self.system.engine
        self.keeper = engine.bookkeeper
        self.plane = engine.packed_plane
        self.E = self.plane.entry_field_size
        self.fold = collector.FOLD
        graph = self.graph
        compute_marks = graph.compute_marks

        def spanned():
            # on the collector's thread, around the device call of a wake
            # that traces: what the tracer cells' drivers bracket themselves
            with wake_span(self.obs):
                return compute_marks()

        graph.compute_marks = spanned
        local = len(graph.slot_of)
        with engine.hold_traces():
            engine.set_foreign_sink(self._sink)
            self._load_graph(int(ctx.config["load_rows_per_batch"]))
            wakes = graph.device_wakes
        t0 = time.perf_counter()
        if not self.first_verdict.wait(fold.FIRST_WAKE_TIMEOUT_S):
            raise RuntimeError("no verdict on the foreign graph within "
                               f"{fold.FIRST_WAKE_TIMEOUT_S:.0f}s of the hold's end")
        freed = sum(f.size for _, f in self.answers)
        ctx.phase("first trace after the hold (layout build, compile or load, first verdict, "
                  "the sweep)", time.perf_counter() - t0,
                  f"freed uids={freed} local actors={local} capacity={graph.capacity} "
                  f"impl={graph.trace_impl} device wakes since the hold={graph.device_wakes - wakes}")

    def _sink(self, kill_uids: np.ndarray, freed_uids: np.ndarray) -> None:
        """On the Bookkeeper's thread, once per trace."""
        self.answers.append((kill_uids, freed_uids))
        if freed_uids.size:
            self.first_verdict.set()

    # ----------------------------------------------------------------- #
    # correct
    # ----------------------------------------------------------------- #

    def _reference_garbage(self) -> np.ndarray:
        g = self.g
        marks = reference.trace_marks(g["flags"], g["recv_count"], g["supervisor"],
                                      g["edge_src"], g["edge_dst"], g["edge_weight"])
        return reference.garbage(g["flags"], marks)

    def _foreign_checks(self, ref_garbage: np.ndarray) -> List[Dict[str, object]]:
        """The foreign side against the reference's verdict ``ref_garbage``
        (bool, by uid): what the sink was told, and what the engine holds
        after the window, by uid."""
        from uigc_tpu.engines.crgc.packed import FOREIGN_BIT

        out = []

        def exact(name, value):
            out.append(exact_check(name, value))

        g, n, graph = self.g, self.n, self.graph
        first = self.answers[: self.answers_in_setup]
        freed = np.concatenate([f for _, f in first] or [_NO_UIDS])
        told = np.zeros(n, dtype=bool)
        told[freed] = True
        exact("first_verdict_freed_uids_differing_from_reference_garbage",
              np.count_nonzero(told != ref_garbage) + freed.size - int(told.sum()))
        sup = g["supervisor"]
        under = sup[freed]
        want = freed[(under >= 0) & ~ref_garbage[np.maximum(under, 0)]]
        kills = np.concatenate([k for k, _ in first] or [_NO_UIDS])
        exact("first_verdict_kill_uids_not_the_garbage_under_a_live_supervisor",
              0 if np.array_equal(np.sort(kills), np.sort(want)) else 1)
        exact("uids_handed_to_the_sink_after_the_first_verdict",
              sum(k.size + f.size for k, f in self.answers[self.answers_in_setup:]))

        slot = graph._fuid_to_slot[:n]
        held = slot >= 0
        live = ~ref_garbage
        exact("uids_held_differing_from_reference_live_set", np.count_nonzero(held != live))
        uids = np.nonzero(held & live)[0]
        at = slot[uids]
        exact("flags_differing_from_reference", np.count_nonzero(graph.flags[at] != g["flags"][uids]))
        exact("recv_counts_differing_from_reference",
              np.count_nonzero(graph.recv_count[at] != g["recv_count"][uids]))
        code = graph._slot_uid
        uid_of = np.where(code >= FOREIGN_BIT, code ^ FOREIGN_BIT, -1)  # -1: a local or free slot
        at_sup = graph.supervisor[at]
        exact("supervisors_differing_from_reference",
              np.count_nonzero(np.where(at_sup >= 0, uid_of[np.maximum(at_sup, 0)], -1) != sup[uids]))

        eids = np.nonzero(graph.edge_weight != 0)[0]
        s, d = uid_of[graph.edge_src[eids]], uid_of[graph.edge_dst[eids]]
        exact("references_between_a_local_and_a_foreign_actor", np.count_nonzero((s >= 0) != (d >= 0)))
        both = (s >= 0) & (d >= 0)
        have = np.stack([_keys(s[both], d[both]), graph.edge_weight[eids][both]], axis=1)
        have = have[np.argsort(have[:, 0])]
        keep = np.nonzero((g["edge_weight"] != 0) & live[g["edge_src"]] & live[g["edge_dst"]])[0]
        keys, inverse = np.unique(_keys(g["edge_src"][keep], g["edge_dst"][keep]), return_inverse=True)
        want = np.stack([
            keys, np.bincount(inverse, weights=g["edge_weight"][keep], minlength=keys.size).astype(np.int64),
        ], axis=1)
        if have.shape == want.shape:
            wrong = np.count_nonzero((have != want).any(axis=1))
        else:
            wrong = abs(have.shape[0] - want.shape[0]) or 1
        exact("reference_counts_differing_from_reference", wrong)
        dec = getattr(graph, "_dec", None)
        exact("layout_anomalies", dec.layout.stats["anomalies"] if dec is not None else 1)
        self.ctx.say(f"served_fold: foreign actors held {graph.actors_foreign}, local "
                     f"{len(graph.slot_of)}, capacity {graph.capacity}, sink calls "
                     f"{len(self.answers)} ({self.answers_in_setup} in set-up)")
        return out

    def check(self) -> List[Dict[str, object]]:
        # the foreign side first: the probes of served's check are traffic
        foreign = self._foreign_checks(self._reference_garbage())
        return super().check() + foreign

    def close(self) -> None:
        gc.unfreeze()
        super().close()
