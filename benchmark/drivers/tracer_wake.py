"""Driver ``tracer_wake``: a resident shadow graph on the device, one wake
at a time, as the ``decremental`` backend holds it.

The driver owns a ``DecrementalTracer`` (``ops/pallas_decremental.py``)
over an ``IncrementalPallasLayout`` (``ops/pallas_incremental.py``), as
``chip_smoke.py:183-236`` does, and drives it in a closed loop: one
batch of pair transitions per wake.  A wake is

    apply_log(batch) -> wake_device -> reduction on the device to the
    count and the compacted ids of NEW garbage -> readback

or, where the traffic says ``rederive``, ``invalidate() -> wake_device
-> unpack_marks`` (every verdict on the host).  Detection latency is the
host clock from the batch being handed over to the ids being on the
host.

The resident graph comes from the configuration's ``graph_seed``, the
same in every run; ``--seed`` draws the traffic.  (A graph from ``--seed``
changes the work: one seed in six gave a graph that took a sweep more,
and its wakes 11% longer.)

Traffic (``traffic/<mix>.json``): ``releases_per_wake`` references of
the resident graph are released and ``new_refs_per_wake`` created, each
new one released again ``new_ref_lifetime_wakes`` wakes later (0: never).
All four ends are drawn by the seed among actors that are live NOW: the
live partition less what the collector has reported as garbage.  That is
the one change from ``chip_smoke.py:240-270``, whose ends range over all
slots and so hand live actors references to garbage, which no
application can do.  With it garbage only grows, and the ids reported
over the wakes must add up to the reference's verdict.

Every seed makes the same amount of garbage.  Of a wake's releases,
``last_reference_releases_per_wake`` drop the ONLY reference to an actor
that supervises nobody (so exactly that actor becomes garbage); the rest
drop a reference whose target keeps the one from its supervisor, and new
references never point at an actor of the first kind.  Left to chance the
garbage per 10,000 releases came out at 215 with a heavy tail (a release
near a root orphans a subtree), and ``collected_per_s`` spread by 6.6%
from seed to seed.

``correct`` (outside the window, limit 0 on every number): the first
wake's verdict against the generator's partition, the last wake's
verdict and the ids reported up to a wake drawn from the seed and up to
the last against ``reference.trace_marks`` on the graph as churned, no
id reported twice, no layout anomaly.  The control (``--control``) gives
the reference one more batch of releases than the program was given:
the verdict is then one batch stale, which the comparison has to catch.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

import reference
from graphgen import GENERATORS
from harness.report import exact as exact_check

#: releases and new references are drawn this much over, then cut to
#: size after those that do not fit (reported garbage, taken pairs, ...)
OVERDRAW = 2.0


def _keys(src, dst) -> np.ndarray:
    return (np.asarray(src, np.int64) << 32) | np.asarray(dst, np.int64)


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.obs = ctx.obs
        self.attempted = 0
        self.failed = 0

    # ----------------------------------------------------------------- #
    # set-up
    # ----------------------------------------------------------------- #

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from uigc_tpu.ops import pallas_trace as pt
        from uigc_tpu.ops.pallas_decremental import DecrementalTracer

        ctx, cfg, tr = self.ctx, self.ctx.config, self.ctx.traffic
        t0 = time.perf_counter()
        params = dict(cfg["graph"])
        g = self.g = GENERATORS[params.pop("generator")](seed=int(cfg["graph_seed"]), **params)
        self.n = n = g["flags"].shape[0]
        self.n_live = int(g["n_live"])
        self.in_use = (g["flags"] & reference.FLAG_IN_USE) != 0
        ctx.phase("generate", time.perf_counter() - t0,
                  f"actors={n} edges={g['edge_src'].size} live={self.n_live} "
                  f"graph_seed={cfg['graph_seed']} traffic seed={ctx.seed}")

        # the churn population: references between live actors, each
        # (src, dst) held once (a second reference src -> dst would make
        # a release no pair transition)
        t0 = time.perf_counter()
        self.rng = np.random.default_rng([ctx.seed, 7])
        self.R = int(tr["releases_per_wake"])
        self.K = int(tr.get("last_reference_releases_per_wake", 0))
        self.N = int(tr["new_refs_per_wake"])
        self.lifetime = int(tr["new_ref_lifetime_wakes"])
        self.rederive = bool(tr.get("rederive", False))
        if self.R or self.N or ctx.control:
            src, dst, sup = g["edge_src"], g["edge_dst"], g["supervisor"]
            live_edge = np.nonzero(src < self.n_live)[0]
            keys = _keys(src[live_edge], dst[live_edge])
            order = np.argsort(keys, kind="stable")
            self.base_keys = keys[order]
            single = np.ones(order.size, bool)
            same = self.base_keys[1:] == self.base_keys[:-1]
            single[1:] &= ~same
            single[:-1] &= ~same
            single_edge = live_edge[order[single]]
            # actors whose one reference is their supervisor's and who
            # supervise nobody: releasing it orphans exactly them
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
            ctx.phase("churn population", time.perf_counter() - t0,
                      f"last-reference releases to draw from: {orphaning.size}, others: "
                      f"{harmless.size}")

        t0 = time.perf_counter()
        mode = cfg["uigc"]["uigc.crgc.trace-mode"]
        self.tracer = DecrementalTracer(n, mode=mode)
        self.tracer.rebuild(g["edge_src"], g["edge_dst"], g["edge_weight"], g["supervisor"])
        layout = self.tracer.layout
        base = layout.base
        ctx.phase("pack", time.perf_counter() - t0,
                  f"n_blocks={base['n_blocks']} r_rows={base['r_rows']} "
                  f"n_super={base['n_super']} trace-mode={layout.mode} "
                  f"interpret={pt.default_interpret()}")
        if pt.default_interpret() != ctx.rehearse:
            raise SystemExit(f"kernels interpreted={pt.default_interpret()} in a "
                             f"{'rehearsal' if ctx.rehearse else 'chip run'}")

        # wake 0: upload, compile, the full derivation, every verdict read
        t0 = time.perf_counter()
        self.flags_dev = jax.device_put(g["flags"])
        self.recv_dev = jax.device_put(g["recv_count"])
        mark_w = self.tracer.wake_device(self.flags_dev, self.recv_dev)
        mark_w.block_until_ready()
        self.words_shape = tuple(mark_w.shape)
        n_words = int(np.prod(self.words_shape))
        marks0 = self.tracer.unpack_marks(mark_w)
        self.garbage0 = self.in_use & ~marks0
        self.is_garbage = self.garbage0.copy()  # what the collector has reported
        ctx.phase("wake 0 (compile or load, upload, full derivation, unpack)",
                  time.perf_counter() - t0, f"garbage={int(self.garbage0.sum())}")

        # the benchmark's own reduction, on packed words (bit i of word
        # i >> 5): new garbage = in use, unmarked, not reported before
        cap = self.id_cap = int(tr.get("id_capacity", 1 << 17))
        self.in_use_w = jax.device_put(
            self._pack_bits(self.in_use, n_words).reshape(self.words_shape)
        )
        self.prev_garbage_w = jax.device_put(
            self._pack_bits(self.garbage0, n_words).reshape(self.words_shape)
        )

        @jax.jit
        def finish(mark_w, in_use_w, prev_w):
            garbage = in_use_w & ~mark_w
            new = (garbage & ~prev_w).reshape(-1)
            count = jnp.sum(jax.lax.population_count(new))
            widx = jnp.nonzero(new != 0, size=cap, fill_value=n_words)[0]
            wval = new.at[widx].get(mode="fill", fill_value=0)
            return count, widx, wval, garbage

        self.finish = finish
        self.n_words = n_words

        # history, for the reference
        self.released: List[np.ndarray] = []  # per wake: indices into the edge arrays
        self.inserted: List[np.ndarray] = []  # per wake: (2, k) new references
        self.reported: List[np.ndarray] = []  # per wake: new garbage ids
        self.counts: List[int] = []           # rederive: garbage per wake
        self.first_marks = self.last_marks = None
        self.last_mark_w = mark_w

        t0 = time.perf_counter()
        warm = int(tr["warmup_wakes"])
        for _ in range(warm):
            self._wake()
        self.warm_wakes = len(self.released) if not self.rederive else warm
        ctx.phase(f"warm-up ({warm} wakes through the window's own call)",
                  time.perf_counter() - t0)

    @staticmethod
    def _pack_bits(flags: np.ndarray, n_words: int) -> np.ndarray:
        packed = np.zeros(n_words * 4, np.uint8)
        bits = np.packbits(flags, bitorder="little")
        packed[: bits.size] = bits
        return packed.view(np.int32)

    # ----------------------------------------------------------------- #
    # one wake
    # ----------------------------------------------------------------- #

    def _draw_batch(self):
        """The next batch, from the seed and from what has been reported."""
        g, R, N = self.g, self.R, self.N
        log = []
        rel = np.empty(0, np.int64)
        if R:
            K = self.K
            last = self.orphan_order[self.orphan_at : self.orphan_at + K]
            self.orphan_at += K
            take = int((R - K) * OVERDRAW)
            cand = self.release_order[self.release_at : self.release_at + take]
            self.release_at += take
            cand = cand[~self.is_garbage[g["edge_src"][cand]]][: R - K]
            if last.size < K or cand.size < R - K:
                raise RuntimeError("the churn population is used up: shorten the window")
            rel = np.concatenate([last, cand])
            log += [(False, s, d, 0) for s, d in
                    zip(g["edge_src"][rel].tolist(), g["edge_dst"][rel].tolist())]
        if self.lifetime and len(self.inserted) >= self.lifetime:
            old = self.inserted[-self.lifetime]
            log += [(False, s, d, 0) for s, d in zip(old[0].tolist(), old[1].tolist())]
        new = np.empty((2, 0), np.int64)
        if N:
            take = int(N * OVERDRAW)
            s = self.rng.integers(0, self.n_live, take, dtype=np.int64)
            d = self.rng.integers(0, self.n_live, take, dtype=np.int64)
            keys = _keys(s, d)
            pos = np.minimum(np.searchsorted(self.base_keys, keys), self.base_keys.size - 1)
            ok = (s != d) & ~self.is_garbage[s] & ~self.is_garbage[d] & ~self.last_ref[d]
            ok &= self.base_keys[pos] != keys
            alive = self.inserted[-self.lifetime:] if self.lifetime else self.inserted
            if alive:
                ok &= ~np.isin(keys, np.concatenate([_keys(a[0], a[1]) for a in alive]))
            _, first = np.unique(keys, return_index=True)
            once = np.zeros(take, bool)
            once[first] = True
            pick = np.nonzero(ok & once)[0][:N]
            if pick.size < N:
                raise RuntimeError("too few fresh references drawn: raise OVERDRAW")
            new = np.stack([s[pick], d[pick]])
            log += [(True, a, b, 0) for a, b in zip(new[0].tolist(), new[1].tolist())]
        return log, rel, new

    def _wake(self) -> None:
        obs = self.obs
        self.attempted += 1
        if self.rederive:
            t0 = time.perf_counter()
            with obs.span("wake"):
                self.tracer.invalidate()
                mark_w = self.tracer.wake_device(self.flags_dev, self.recv_dev)
                mark_w.block_until_ready()
            with obs.span("readback"):
                marks = self.tracer.unpack_marks(mark_w)
            obs.sample("detect_ms", (time.perf_counter() - t0) * 1e3)
            count = int(np.count_nonzero(self.in_use & ~marks))
            obs.count("collected", count)
            self.counts.append(count)
            if obs.recording and self.first_marks is None:
                self.first_marks = marks
            self.last_marks = marks
            self.last_mark_w = mark_w
            return

        with obs.span("generate"):
            log, rel, new = self._draw_batch()
        t0 = time.perf_counter()
        with obs.span("layout"):
            self.tracer.apply_log(log)
        with obs.span("wake"):
            mark_w = self.tracer.wake_device(self.flags_dev, self.recv_dev)
            mark_w.block_until_ready()
        with obs.span("readback"):
            count, widx, wval, self.prev_garbage_w = self.finish(
                mark_w, self.in_use_w, self.prev_garbage_w
            )
            count = int(count)
            ids = self._expand(np.asarray(widx), np.asarray(wval))
        obs.sample("detect_ms", (time.perf_counter() - t0) * 1e3)
        if count != ids.size:  # more new garbage than the id buffer holds
            self.failed += 1
        obs.count("collected", count)
        self.is_garbage[ids] = True
        self.released.append(rel)
        self.inserted.append(new)
        self.reported.append(ids)
        self.last_mark_w = mark_w

    def _expand(self, widx: np.ndarray, wval: np.ndarray) -> np.ndarray:
        keep = widx < self.n_words
        widx = widx[keep].astype(np.int64)
        wval = wval[keep].view(np.uint32)
        lanes = np.arange(32, dtype=np.uint32)
        bits = ((wval[:, None] >> lanes[None, :]) & 1).astype(bool)
        return (widx[:, None] * 32 + lanes[None, :].astype(np.int64))[bits]

    # ----------------------------------------------------------------- #
    # the window
    # ----------------------------------------------------------------- #

    def window(self, seconds: float) -> None:
        self.attempted = self.failed = 0
        self.window_first = len(self.released)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.obs.tick()
            self._wake()
        self.obs.tick()

    # ----------------------------------------------------------------- #
    # correct
    # ----------------------------------------------------------------- #

    def _reference_garbage(self, upto: int, extra_release=None) -> np.ndarray:
        """The reference's garbage on the graph after batches ``[0, upto)``."""
        g = self.g
        weight = g["edge_weight"].copy()
        gone = self.released[:upto] + ([extra_release] if extra_release is not None else [])
        if gone:
            weight[np.concatenate(gone)] = 0
        if self.lifetime:
            alive = self.inserted[max(0, upto - self.lifetime) : upto]
        else:
            alive = self.inserted[:upto]
        src, dst = [g["edge_src"]], [g["edge_dst"]]
        for a in alive:
            src.append(a[0].astype(np.int32))
            dst.append(a[1].astype(np.int32))
            weight = np.concatenate([weight, np.ones(a.shape[1], np.int64)])
        marks = reference.trace_marks(
            g["flags"], g["recv_count"], g["supervisor"],
            np.concatenate(src), np.concatenate(dst), weight,
        )
        return reference.garbage(g["flags"], marks)

    def _control_batch(self):
        """One more batch of releases, which the program never saw."""
        if not self.ctx.control:
            return None
        k = int(self.ctx.traffic["control_releases"])
        return self.orphan_order[self.orphan_at : self.orphan_at + k]

    def check(self) -> List[Dict[str, object]]:
        out = []

        def exact(name, value):
            out.append(exact_check(name, value))

        exact("first_wake_verdicts_differing_from_partition",
              np.count_nonzero(self.garbage0 != self.g["expected_garbage"]))
        exact("wakes_failed", self.failed)
        exact("layout_anomalies", self.tracer.layout.stats["anomalies"])
        extra = self._control_batch()
        if self.rederive:
            ref = self._reference_garbage(0, extra)
            last = self.in_use & ~self.last_marks
            exact("last_wake_verdicts_differing_from_reference", np.count_nonzero(last != ref))
            if self.first_marks is not None:
                exact("first_window_wake_verdicts_differing_from_reference",
                      np.count_nonzero((self.in_use & ~self.first_marks) != ref))
            exact("wakes_with_another_garbage_count",
                  sum(1 for c in self.counts if c != int(ref.sum())))
            return out

        total = len(self.released)
        last_marks = self.tracer.unpack_marks(self.last_mark_w)
        ref_last = self._reference_garbage(total, extra)
        exact("last_wake_verdicts_differing_from_reference",
              np.count_nonzero((self.in_use & ~last_marks) != ref_last))
        ids = np.concatenate(self.reported) if self.reported else np.empty(0, np.int64)
        exact("ids_reported_twice", ids.size - np.unique(ids).size)
        told = self.garbage0.copy()
        told[ids] = True
        exact("reported_ids_differing_from_reference_at_last_wake",
              np.count_nonzero(told != ref_last))
        if total - self.window_first >= 2:
            pick = np.random.default_rng([self.ctx.seed, 11])
            mid = int(pick.integers(self.window_first + 1, total))
            told = self.garbage0.copy()
            told[np.concatenate(self.reported[:mid])] = True
            exact(f"reported_ids_differing_from_reference_at_wake_{mid - self.window_first}"
                  f"_of_{total - self.window_first}",
                  np.count_nonzero(told != self._reference_garbage(mid)))
        return out

    def close(self) -> None:
        pass
