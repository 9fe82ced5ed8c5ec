"""Driver ``engine_fold``: a large graph through the engine's own path.

    packed rows -> ``PackedPlane`` ring -> Bookkeeper mailbox -> ``collect()``
    -> ``merge_packed`` -> ``ArrayShadowGraph.trace()`` -> the engine's sink

The actors are FOREIGN: they live in mutator processes the collector does
not host, which ship it their entry flushes as packed rows
(``uigc_tpu/engines/crgc/packed.py``); the collector knows them by uid
alone and answers, once per wake, with the uids to stop and the uids it
freed (``CRGC.set_foreign_sink``).  The driver stands for those processes:
it owns the graph in uid space (the generator's ids are the uids), encodes
it and its churn as rows, and keeps what the sink was told.  It builds an
``ActorSystem`` from the configuration's ``uigc.*`` keys, constructs no
tracer of its own and calls nothing under ``uigc_tpu/ops/``.

Set-up, each step a ``set-up`` line: generate the graph
(``graphgen.GENERATORS``, the configuration's ``graph_seed``); the churn
population (``drivers/tracer_wake.py``'s, whose draw of a wake's releases
and new references this driver inherits, so that the two drivers differ in
the path and not in the load); encode the graph as rows, one row per actor
(its bits, its references as created ``(owner, target)`` pairs and the
children it supervises as spawned uids, four of each to a row, more rows
where an actor has more) and hand them to the plane in blocks of
``load_rows_per_batch`` rows, each folded by the Bookkeeper (its ``FOLD``
message: drain and fold, no trace) while the next is encoded; the first
wake (layout build, compile or cache load, the first verdict: every
garbage uid of the generator's partition through the sink); the warm-up
wakes through the window's own call.  The driver paces the wakes: the
system is built with the collector's ``wakeup-interval`` beyond any run
(``NO_TIMER_MS``) and its timers are stopped after start-up, so the only
wake-ups are the driver's and the one the Bookkeeper sends itself after a
wake that found garbage (it folds nothing and does not trace).  An answer
that no batch asked for fails the run at once.

Three things steady a run, all in this file and none in the program.
Before anything else ``keep_the_heap`` has glibc keep large blocks (what
``MALLOC_MMAP_THRESHOLD_``, ``MALLOC_TRIM_THRESHOLD_`` and
``MALLOC_ARENA_MAX=1`` do from a start-up script): the wake's large numpy
temporaries are otherwise mapped, faulted in and unmapped every wake.  The
set-up ends with ``gc.collect(); gc.freeze()``: what it built (the graph's
per-slot lists, the layout's maps) is out of CPython's collector's sight
for the window.  And each wake is preceded, outside the clock, by a
``gc.collect()``, so that no full collection falls due inside one.

Window, closed loop, one batch per wake: the batch's rows into the ring
(``PackedPlane.write_foreign``), a ``_Wakeup`` to the Bookkeeper's mailbox,
wait for the sink.  ``detect_ms`` is the host clock from just before the
rows are handed to the plane until the wake's uids are in the driver's
hands; the cell's end-to-end metric is the throughput (``collected_per_s``),
and the latency stands beside it as a per-layer metric
(``layers/detect_ms.engine.py`` says why).  A batch (``traffic/<mix>.json``) is ``tracer_wake``'s releases and
new references as flushes of their owners (a release is an updated field
``(target, deactivated)``, a new reference a created pair), plus
``message_pairs_per_wake`` messages, each counted by its sender (an updated
field with the send count, on a resident reference it still holds) and by
its receiver (the row's ``recv``) in the same batch, so every receive count
nets to zero at every wake.  One row per acting actor, more where one has
more than four facts of a kind.

A traced run sets ``uigc.telemetry.wake-profile`` (as ``drivers/served.py``
does) and leaves the profiler's wake records in
``obs.facts["program_wakes"]``; a timed run (``--trace 0``) runs without the
profiler, so the per-layer numbers come from the slower run.

``correct`` (outside the window, limit 0 on every number): the first wake's
freed uids against the generator's partition; the uids delivered up to a
wake drawn from the seed, and up to the last, against
``reference.trace_marks`` on the driver's own copy of the graph as churned;
no uid delivered twice; none of a live actor; the engine's ``flags``,
``recv_count``, ``supervisor`` and reference counts after the last wake
against the driver's copy, by uid; the kill uids (garbage whose supervisor
lives) likewise; ``trace_impl``; no layout anomaly; no wake that timed out.
The control (``--control``) gives the reference one more batch of releases
than the program was given.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List

import numpy as np

import reference
from harness.cell import load_driver
from harness.report import exact as exact_check

base = load_driver("tracer_wake")

#: glibc's ``mallopt`` parameters (``malloc.h``)
M_TRIM_THRESHOLD, M_MMAP_THRESHOLD, M_ARENA_MAX = -1, -3, -8


def keep_the_heap() -> str:
    """Have glibc's allocator keep large blocks: what the environment
    ``MALLOC_MMAP_THRESHOLD_=1073741824 MALLOC_TRIM_THRESHOLD_=2147483647
    MALLOC_ARENA_MAX=1`` does, set from inside because the harness starts
    the process.  The collector's wake allocates a dozen temporaries of
    16 to 134 MB (the sweep's masks over 2^24 slots and 2^26 edge slots,
    the upload's staging copies); by default each is ``mmap``ed, faulted
    in page by page and unmapped again, every wake, and how long that
    takes swings with the host.  One arena, because only the main one
    grows past 64 MB without ``mmap`` and the collector runs on a thread
    of its own; so this is called before that thread exists."""
    import ctypes

    try:
        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except (OSError, AttributeError) as e:
        return f"not glibc ({e}): allocator left as it is"
    ok = [mallopt(M_MMAP_THRESHOLD, 1 << 30), mallopt(M_TRIM_THRESHOLD, (1 << 31) - 1),
          mallopt(M_ARENA_MAX, 1)]
    return f"mallopt mmap-threshold 1 GiB, trim-threshold 2 GiB, one arena: {ok}"


#: ``uigc.crgc.wakeup-interval`` for the run, a day: the Bookkeeper's timer is
#: armed when the system starts and can be stopped only after, and at the
#: default 50 ms it fires in between once start-up is slow (a traced start,
#: which attaches the telemetry after the engine, takes 80-90 ms on the chip's
#: host; a first run in a fresh checkout imports uncompiled).  That wake traces the
#: empty graph and answers into the sink, and every later answer is then
#: taken for the batch after its own.
NO_TIMER_MS = 86_400_000
#: a wake that has not answered by then has failed (the Bookkeeper died)
WAKE_TIMEOUT_S = 600.0
#: the first one builds the layout and may compile
FIRST_WAKE_TIMEOUT_S = 1000.0


def _grouped(index: np.ndarray, m: int):
    """``index`` (which of ``m`` actors each item belongs to) as an
    order that groups the items by actor, each item's place within its
    actor's group, and the group sizes."""
    order = np.argsort(index, kind="stable")
    counts = np.bincount(index, minlength=m)
    starts = np.cumsum(counts) - counts
    grouped = index[order]
    return order, grouped, np.arange(index.size) - starts[grouped], counts


def encode_rows(uids, bits, recv, created, spawned, updated, field_size: int) -> np.ndarray:
    """Packed rows (``packed.py``'s layout, plain uids, column 0 unset)
    for the actors ``uids`` (each once): ``bits`` and ``recv`` per actor,
    and three kinds of facts, each a tuple of arrays whose first names
    the acting actor by its INDEX into ``uids``: ``created`` (index,
    owner uid, target uid), ``spawned`` (index, child uid), ``updated``
    (index, target uid, packed refob info).  An actor gets as many rows
    as its most numerous kind needs at ``field_size`` a row, at least
    one; ``recv`` rides on its first."""
    E, m = field_size, uids.shape[0]
    groups = [_grouped(kind[0], m) for kind in (created, spawned, updated)]
    n_rows = np.ones(m, dtype=np.int64)
    for _, _, _, counts in groups:
        np.maximum(n_rows, -(-counts // E), out=n_rows)
    first = np.cumsum(n_rows) - n_rows
    rows = np.full((int(n_rows.sum()), 4 + 5 * E), -1, dtype=np.int64)
    rows[:, 1] = np.repeat(uids, n_rows)
    rows[:, 2] = np.repeat(bits, n_rows)
    rows[:, 3] = 0
    rows[first, 3] = recv
    for (order, grouped, place, _), kind, col0, width in zip(
        groups, (created, spawned, updated), (4, 4 + 2 * E, 4 + 3 * E), (2, 1, 2)
    ):
        at = first[grouped] + place // E
        col = col0 + width * (place % E)
        for k, values in enumerate(kind[1:]):
            rows[at, col + k] = values[order]
    return rows


class Driver(base.Driver):
    system = None  # until set-up has built it

    # ----------------------------------------------------------------- #
    # set-up
    # ----------------------------------------------------------------- #

    def setup(self) -> None:
        from uigc_tpu.engines.crgc import collector, packed

        if not hasattr(packed.PackedPlane, "write_foreign"):
            # before anything is built: the parent of the PR that added
            # the path fails in seconds, not after a 10M-iteration loop
            raise SystemExit("the program has no foreign path (PackedPlane.write_foreign): "
                             "it cannot hold actors by uid alone")
        from uigc_tpu.runtime.system import ActorSystem

        ctx, cfg, tr = self.ctx, self.ctx.config, self.ctx.traffic
        ctx.say("engine_fold: " + keep_the_heap())
        t0 = time.perf_counter()
        params = dict(cfg["graph"])
        g = self.g = base.GENERATORS[params.pop("generator")](seed=int(cfg["graph_seed"]), **params)
        self.n = n = g["flags"].shape[0]
        self.n_live = int(g["n_live"])
        ctx.phase("generate", time.perf_counter() - t0,
                  f"actors={n} edges={g['edge_src'].size} live={self.n_live} "
                  f"graph_seed={cfg['graph_seed']} traffic seed={ctx.seed}")

        t0 = time.perf_counter()
        self._churn_population()
        ctx.phase("churn population", time.perf_counter() - t0,
                  f"last-reference releases to draw from: {self.orphan_order.size}, others: "
                  f"{self.release_order.size}")

        t0 = time.perf_counter()
        config = dict(cfg["uigc"], **{"uigc.crgc.wakeup-interval": NO_TIMER_MS})
        if ctx.traced:
            # per-layer numbers come from the traced run only
            config["uigc.telemetry.wake-profile"] = True
        self.system = ActorSystem(None, name="bench", config=config)
        engine = self.engine = self.system.engine
        self.keeper = engine.bookkeeper
        self.keeper.stop_timers()  # the driver paces the wakes
        self.plane = engine.packed_plane
        self.E = self.plane.entry_field_size
        self.wakeup, self.fold = collector.WAKEUP, collector.FOLD
        self.answers: List[tuple] = []
        self.answered = threading.Event()
        engine.set_foreign_sink(self._sink)
        tel = self.system.telemetry
        self.profiler = tel.profiler if tel is not None else None
        self.program_wakes: List[dict] = []
        self._polled = time.time()
        ctx.phase("actor system", time.perf_counter() - t0,
                  f"engine={type(engine).__name__} backend={type(self.graph).__name__} "
                  f"entry-field-size={self.E} wake-profile={self.profiler is not None}")

        self._load_graph(int(cfg["load_rows_per_batch"]))

        # history, for the reference
        self.released: List[np.ndarray] = []
        self.inserted: List[np.ndarray] = []
        self.reported: List[np.ndarray] = []
        self.kills: List[np.ndarray] = []
        self.is_garbage = np.zeros(n, dtype=bool)
        self.edge_released = np.zeros(g["edge_src"].size, dtype=bool)

        t0 = time.perf_counter()
        self._nobody_answered_unasked()
        self.keeper.cell.tell(self.wakeup)
        kills, freed = self._await_answer(FIRST_WAKE_TIMEOUT_S)
        self.garbage0 = np.zeros(n, dtype=bool)
        self.garbage0[freed] = True
        self.garbage0_twice = freed.size - int(self.garbage0.sum())
        self.is_garbage[freed] = True
        self.kills0 = kills
        ctx.phase("wake 0 (layout build, compile or load, first verdict, the sweep)",
                  time.perf_counter() - t0,
                  f"freed uids={freed.size} kill uids={kills.size} impl={self.graph.trace_impl} "
                  f"capacity={self.graph.capacity}")
        if (self.graph.trace_impl == "pallas-interpret") != ctx.rehearse:
            raise SystemExit(f"trace_impl={self.graph.trace_impl} in a "
                             f"{'rehearsal' if ctx.rehearse else 'chip run'}")

        t0 = time.perf_counter()
        warm = int(tr["warmup_wakes"])
        for _ in range(warm):
            self._wake()
        ctx.phase(f"warm-up ({warm} wakes through the window's own call)",
                  time.perf_counter() - t0)
        # what set-up built stays; CPython's collector need not walk it
        # again in every full collection of the window
        gc.collect()
        gc.freeze()

    @property
    def graph(self):
        return self.keeper.shadow_graph

    def _churn_population(self) -> None:
        """``tracer_wake``'s churn population (its ``setup``, which builds
        a tracer in the same breath, cannot be called for it):
        references between live actors, each ``(src, dst)`` held once;
        of those, the ones whose release orphans exactly their target,
        and the ones whose target keeps its supervisor's."""
        ctx, tr, g, n = self.ctx, self.ctx.traffic, self.g, self.n
        self.rng = np.random.default_rng([ctx.seed, 7])
        self.R = int(tr["releases_per_wake"])
        self.K = int(tr.get("last_reference_releases_per_wake", 0))
        self.N = int(tr["new_refs_per_wake"])
        self.M = int(tr.get("message_pairs_per_wake", 0))
        self.lifetime = int(tr["new_ref_lifetime_wakes"])
        self.rederive = False
        src, dst, sup = g["edge_src"], g["edge_dst"], g["supervisor"]
        live_edge = self.live_edge = np.nonzero(src < self.n_live)[0]
        keys = base._keys(src[live_edge], dst[live_edge])
        order = np.argsort(keys, kind="stable")
        self.base_keys = keys[order]
        single = np.ones(order.size, bool)
        same = self.base_keys[1:] == self.base_keys[:-1]
        single[1:] &= ~same
        single[:-1] &= ~same
        single_edge = live_edge[order[single]]
        indeg = np.bincount(dst[live_edge], minlength=n)
        kids = np.bincount(sup[: self.n_live][sup[: self.n_live] >= 0], minlength=n)
        is_root = (g["flags"] & reference.FLAG_ROOT) != 0
        self.last_ref = (indeg == 1) & (kids == 0) & ~is_root
        self.last_ref[self.n_live:] = False
        from_sup = src[single_edge] == sup[dst[single_edge]]
        orphaning = single_edge[self.last_ref[dst[single_edge]] & from_sup]
        harmless = single_edge[~from_sup]
        self.orphan_order = self.rng.permutation(orphaning)
        self.release_order = self.rng.permutation(harmless)
        self.orphan_at = self.release_at = 0
        #: a row's busy/root bits, by uid: nobody is busy at a flush
        self.bits = np.where(is_root, 2, 0).astype(np.int64)

    def _load_graph(self, rows_per_batch: int) -> None:
        """The whole graph as rows through the plane, a block at a time;
        the Bookkeeper folds one block while the next is encoded."""
        ctx, g, n, E = self.ctx, self.g, self.n, self.E
        t0 = time.perf_counter()
        src, dst, sup = g["edge_src"], g["edge_dst"], g["supervisor"]
        by_src = np.argsort(src, kind="stable")
        src_at = np.searchsorted(src[by_src], np.arange(n + 1))
        child = np.nonzero(sup >= 0)[0]
        by_sup = child[np.argsort(sup[child], kind="stable")]
        sup_at = np.searchsorted(sup[by_sup], np.arange(n + 1))
        n_rows = np.maximum(1, np.maximum(-(-np.diff(src_at) // E), -(-np.diff(sup_at) // E)))
        row_at = np.concatenate([[0], np.cumsum(n_rows)])
        total = int(row_at[-1])
        cuts = np.unique(np.concatenate([
            np.searchsorted(row_at, np.arange(0, total, rows_per_batch)), [n]]))
        ctx.phase("index the graph by owner and by supervisor", time.perf_counter() - t0,
                  f"rows={total} blocks={cuts.size - 1}")

        t0 = time.perf_counter()
        encode_s = wait_s = 0.0
        sent = self.keeper.total_entries
        none = np.empty(0, np.int64)
        for a0, a1 in zip(cuts[:-1].tolist(), cuts[1:].tolist()):
            t = time.perf_counter()
            e = by_src[src_at[a0]:src_at[a1]]
            c = by_sup[sup_at[a0]:sup_at[a1]]
            rows = encode_rows(
                np.arange(a0, a1, dtype=np.int64), self.bits[a0:a1], g["recv_count"][a0:a1],
                (src[e].astype(np.int64) - a0, src[e], dst[e]),
                (sup[c].astype(np.int64) - a0, c),
                (none, none, none), E,
            )
            encode_s += time.perf_counter() - t
            t = time.perf_counter()
            self._await_folded(sent)  # the block before this one
            wait_s += time.perf_counter() - t
            self.plane.write_foreign(rows)
            sent += rows.shape[0]
            self.keeper.cell.tell(self.fold)
        t = time.perf_counter()
        self._await_folded(sent)
        wait_s += time.perf_counter() - t
        graph = self.graph
        ctx.phase("encode and fold (rows through the plane, the Bookkeeper folds each block)",
                  time.perf_counter() - t0,
                  f"rows={total} encode={encode_s:.2f}s waited for folds={wait_s:.2f}s "
                  f"actors seen={graph.total_actors_seen} capacity={graph.capacity} "
                  f"references={len(graph.edge_of)}")

    def _await_folded(self, rows: int, timeout_s: float = WAKE_TIMEOUT_S) -> None:
        deadline = time.perf_counter() + timeout_s
        while self.keeper.total_entries < rows:
            if time.perf_counter() > deadline or self.keeper.cell.is_terminated:
                raise RuntimeError(f"the Bookkeeper folded {self.keeper.total_entries} of "
                                   f"{rows} rows and stopped")
            time.sleep(0.002)

    # ----------------------------------------------------------------- #
    # the sink, on the Bookkeeper's thread
    # ----------------------------------------------------------------- #

    def _sink(self, kill_uids: np.ndarray, freed_uids: np.ndarray) -> None:
        self.answers.append((kill_uids, freed_uids))
        self.answered.set()

    def _nobody_answered_unasked(self) -> None:
        """Before a batch is handed over: an answer waiting here belongs
        to no batch (a wake the driver did not pace), and taking it for
        the next batch's would shift every verdict by one wake."""
        if self.answers or self.answered.is_set():
            raise RuntimeError(f"{len(self.answers)} answer(s) that no batch asked for: "
                               "the collector traced on its own")

    def _await_answer(self, timeout_s: float = WAKE_TIMEOUT_S):
        if not self.answered.wait(timeout_s):
            raise RuntimeError(f"no answer from the collector within {timeout_s:.0f}s")
        self.answered.clear()
        if len(self.answers) != 1:
            raise RuntimeError(f"{len(self.answers)} answers to one batch")
        return self.answers.pop()

    # ----------------------------------------------------------------- #
    # one wake
    # ----------------------------------------------------------------- #

    def _draw_messages(self):
        """``M`` messages, each over a resident reference its sender
        still holds, between actors that are live now: senders,
        receivers and how many messages each such pair exchanged."""
        if not self.M:
            none = np.empty(0, np.int64)
            return none, none, none
        g = self.g
        pick = self.live_edge[self.rng.integers(0, self.live_edge.size, int(self.M * base.OVERDRAW))]
        a, b = g["edge_src"][pick], g["edge_dst"][pick]
        ok = ~self.edge_released[pick] & ~self.is_garbage[a] & ~self.is_garbage[b]
        pairs, count = np.unique(base._keys(a[ok][: self.M], b[ok][: self.M]), return_counts=True)
        if int(count.sum()) < self.M:
            raise RuntimeError("too few references to send over: raise OVERDRAW")
        return pairs >> 32, pairs & 0xFFFFFFFF, count

    def _encode_batch(self, rel: np.ndarray, new: np.ndarray) -> np.ndarray:
        """A wake's churn as the flushes of the actors that acted."""
        g = self.g
        drop_s, drop_d = [g["edge_src"][rel]], [g["edge_dst"][rel]]
        if self.lifetime and len(self.inserted) >= self.lifetime:
            old = self.inserted[-self.lifetime]
            drop_s.append(old[0])
            drop_d.append(old[1])
        drop_s = np.concatenate(drop_s).astype(np.int64)
        drop_d = np.concatenate(drop_d).astype(np.int64)
        send_a, send_b, count = self._draw_messages()
        uids = np.unique(np.concatenate([drop_s, new[0], send_a, send_b]))
        index = lambda who: np.searchsorted(uids, who)
        recv = np.zeros(uids.size, dtype=np.int64)
        np.add.at(recv, index(send_b), count)
        return encode_rows(
            uids, self.bits[uids], recv,
            (index(new[0]), new[0], new[1]),
            (np.empty(0, np.int64), np.empty(0, np.int64)),
            (np.concatenate([index(drop_s), index(send_a)]),
             np.concatenate([drop_d, send_b]),
             np.concatenate([np.ones(drop_s.size, np.int64), count << 1])),
            self.E,
        )

    def _wake(self) -> None:
        obs = self.obs
        self.attempted += 1
        with obs.span("generate"):
            self._poll_profiler()
            _, rel, new = self._draw_batch()
            rows = self._encode_batch(rel, new)
            gc.collect()  # here, not wherever inside the wake it falls due
            self._nobody_answered_unasked()
        n_rows = rows.shape[0]
        t0 = time.perf_counter()
        with obs.span("wake"):
            self.plane.write_foreign(rows)
            self.keeper.cell.tell(self.wakeup)
            try:
                kills, freed = self._await_answer()
            except RuntimeError:
                self.failed += 1
                raise
        obs.sample("detect_ms", (time.perf_counter() - t0) * 1e3)
        obs.count("collected", int(freed.size))
        obs.count("rows", n_rows)
        self.is_garbage[freed] = True
        self.edge_released[rel] = True
        self.released.append(rel)
        self.inserted.append(new)
        self.reported.append(freed)
        self.kills.append(kills)

    def _poll_profiler(self) -> None:
        """The wake records the program's profiler has finished since the
        last look (it keeps the last 256 only)."""
        if self.profiler is None:
            return
        new = self.profiler.wakes_since(self._polled)
        if new:
            self._polled = new[-1]["t"]
            self.program_wakes.extend(new)

    # ----------------------------------------------------------------- #
    # the window
    # ----------------------------------------------------------------- #

    def window(self, seconds: float) -> None:
        self._poll_profiler()
        self.program_wakes = []
        super().window(seconds)
        if self.profiler is not None:
            wakes = len(self.released) - self.window_first
            deadline = time.perf_counter() + 5.0
            while time.perf_counter() < deadline:  # the last wake's record
                self._poll_profiler()
                if sum(1 for r in self.program_wakes if r["device_s"] > 0) >= wakes:
                    break
                time.sleep(0.01)
            self.obs.facts["program_wakes"] = self.program_wakes
            self.ctx.say(f"engine_fold: {len(self.program_wakes)} collector wakes read from the "
                         f"program's profiler, "
                         f"{sum(1 for r in self.program_wakes if r['device_s'] > 0)} called the device")

    # ----------------------------------------------------------------- #
    # correct
    # ----------------------------------------------------------------- #

    def _engine_against_copy(self, ref_garbage: np.ndarray, exact) -> None:
        """The engine's graph after the last wake against the driver's
        own copy, by uid."""
        from uigc_tpu.engines.crgc.packed import FOREIGN_BIT

        g, n, graph = self.g, self.n, self.graph
        slot = graph._fuid_to_slot[:n]
        held = slot >= 0
        live = ~ref_garbage
        exact("uids_held_differing_from_reference_live_set", np.count_nonzero(held != live))
        uids = np.nonzero(held & live)[0]
        at = slot[uids]
        exact("flags_differing_from_copy", np.count_nonzero(graph.flags[at] != g["flags"][uids]))
        exact("recv_counts_differing_from_copy",
              np.count_nonzero(graph.recv_count[at] != g["recv_count"][uids]))
        uid_of = graph._slot_uid ^ FOREIGN_BIT  # of a foreign slot; else negative
        sup = graph.supervisor[at]
        sup_uid = np.where(sup >= 0, uid_of[np.maximum(sup, 0)], -1)
        exact("supervisors_differing_from_copy",
              np.count_nonzero(sup_uid != g["supervisor"][uids]))

        eids = np.nonzero(graph.edge_weight != 0)[0]
        have = np.stack([
            base._keys(uid_of[graph.edge_src[eids]], uid_of[graph.edge_dst[eids]]),
            graph.edge_weight[eids],
        ], axis=1)
        weight = g["edge_weight"].copy()
        weight[self.edge_released] = 0
        keep = np.nonzero((weight != 0) & live[g["edge_src"]] & live[g["edge_dst"]])[0]
        keys, w = base._keys(g["edge_src"][keep], g["edge_dst"][keep]), weight[keep]
        alive = self.inserted[-self.lifetime:] if self.lifetime else self.inserted
        for a in alive:
            ok = live[a[0]] & live[a[1]]
            keys = np.concatenate([keys, base._keys(a[0][ok], a[1][ok])])
            w = np.concatenate([w, np.ones(int(ok.sum()), np.int64)])
        keys, inverse = np.unique(keys, return_inverse=True)
        want = np.stack([keys, np.bincount(inverse, weights=w, minlength=keys.size).astype(np.int64)],
                        axis=1)
        have = have[np.argsort(have[:, 0])]
        if have.shape == want.shape:
            wrong = np.count_nonzero((have != want).any(axis=1))
        else:
            wrong = abs(have.shape[0] - want.shape[0]) or 1
        exact("reference_counts_differing_from_copy", wrong)

    def check(self) -> List[Dict[str, object]]:
        out = []

        def exact(name, value):
            out.append(exact_check(name, value))

        g, graph = self.g, self.graph
        exact("first_wake_verdicts_differing_from_partition",
              np.count_nonzero(self.garbage0 != g["expected_garbage"]) + self.garbage0_twice)
        exact("wakes_failed", self.failed)
        dec = getattr(graph, "_dec", None)
        exact("layout_anomalies", dec.layout.stats["anomalies"] if dec is not None else 1)
        exact("trace_not_the_compiled_kernel",
              graph.trace_impl != ("pallas-interpret" if self.ctx.rehearse else "pallas"))
        exact("bookkeeper_dead", self.keeper.cell.is_terminated)
        exact("answers_left_over", len(self.answers))

        extra = self._control_batch()
        total = len(self.released)
        ref_last = self._reference_garbage(total, extra)
        ids = np.concatenate(self.reported) if self.reported else np.empty(0, np.int64)
        exact("uids_delivered_twice",
              ids.size - np.unique(ids).size + np.count_nonzero(self.garbage0[ids]))
        exact("uids_delivered_of_live_actors", np.count_nonzero(~ref_last[ids]))
        told = self.garbage0.copy()
        told[ids] = True
        exact("delivered_uids_differing_from_reference_at_last_wake",
              np.count_nonzero(told != ref_last))
        if total - self.window_first >= 2:
            pick = np.random.default_rng([self.ctx.seed, 11])
            mid = int(pick.integers(self.window_first + 1, total))
            told = self.garbage0.copy()
            told[np.concatenate(self.reported[:mid])] = True
            exact(f"delivered_uids_differing_from_reference_at_wake_{mid - self.window_first}"
                  f"_of_{total - self.window_first}",
                  np.count_nonzero(told != self._reference_garbage(mid)))
        # to stop: the garbage of a wake whose supervisor lives after it
        # (the stop cascades from there)
        sup, gone, wrong = g["supervisor"], np.zeros(self.n, dtype=bool), 0
        for kills, freed in zip([self.kills0] + self.kills,
                                [np.nonzero(self.garbage0)[0]] + self.reported):
            gone[freed] = True
            under = sup[freed]
            want = freed[(under >= 0) & ~gone[np.maximum(under, 0)]]
            wrong += int(not np.array_equal(np.sort(kills), np.sort(want)))
        exact("wakes_whose_kill_uids_are_not_the_garbage_under_a_live_supervisor", wrong)
        self._engine_against_copy(ref_last, exact)
        return out

    def close(self) -> None:
        gc.unfreeze()
        if self.system is not None:
            self.system.terminate(timeout_s=5.0)
