"""Differential test: decremental wakes vs the from-scratch numpy oracle.

Every wake applies a random batch of pair insertions/removals and flag
mutations (busy/root toggles, recv drains, halts — the events a live
collector produces), runs the closure+repair wake from the previous
fixpoint, and compares the marks against trace_marks_np re-run from
scratch on the current graph (the reference semantics of
ShadowGraph.java:205-289).  Covers exactly the non-monotone cases the
full re-trace never exercises: deletion cascades, released cycles,
de-seeded hubs, crash-style halts.
"""

import numpy as np
import pytest

from uigc_tpu.ops import pallas_decremental as pd
from uigc_tpu.ops import pallas_trace as pt
from uigc_tpu.ops import trace as trace_ops
from uigc_tpu.ops.pallas_incremental import EDGE, SUP

F = trace_ops
CHUNK = 8 * 128 * 32  # actors in one walk chunk at the interpreted geometry


class OracleGraph:
    """Host-side mutable truth the tracer's wakes are diffed against."""

    def __init__(self, rng, n, n_edges):
        self.n = n
        self.flags = np.zeros(n, dtype=np.uint8)
        in_use = rng.random(n) < 0.9
        self.flags[in_use] |= F.FLAG_IN_USE
        self.flags[rng.random(n) < 0.85] |= F.FLAG_INTERNED
        self.flags[rng.random(n) < 0.1] |= F.FLAG_BUSY
        self.flags[rng.random(n) < 0.05] |= F.FLAG_ROOT
        self.flags[rng.random(n) < 0.05] |= F.FLAG_HALTED
        self.recv = np.zeros(n, dtype=np.int64)
        self.recv[rng.random(n) < 0.1] = rng.integers(1, 5)
        # pair set: (src, dst, kind) -> None, kind EDGE only for edges
        # plus per-node supervisor pointers as SUP pairs
        self.pairs = {}
        src = rng.integers(0, n, n_edges)
        dst = rng.integers(0, n, n_edges)
        for s, d in zip(src.tolist(), dst.tolist()):
            self.pairs[(s, d, EDGE)] = None
        sup_child = np.nonzero(rng.random(n) < 0.3)[0]
        for c in sup_child.tolist():
            self.pairs[(c, int(rng.integers(0, n)), SUP)] = None

    def arrays(self):
        """(edge_src, edge_dst, weight, supervisor): EDGE pairs as the
        edge arrays, SUP pairs as the supervisor vector — the tracer's
        rebuild must see the kinds it will later get removals for."""
        ek = [k for k in self.pairs if k[2] == EDGE]
        src = np.array([k[0] for k in ek] or [0], dtype=np.int32)
        dst = np.array([k[1] for k in ek] or [0], dtype=np.int32)
        w = np.ones(len(ek) or 1, dtype=np.int64)
        if not ek:
            w[0] = 0
        sup = np.full(self.n, -1, np.int32)
        for k in self.pairs:
            if k[2] == SUP:
                sup[k[0]] = k[1]
        return src, dst, w, sup

    def oracle_marks(self):
        src, dst, w, sup = self.arrays()
        return trace_ops.trace_marks_np(
            self.flags, self.recv, sup, src, dst, w
        )


def _rand_schedule(rng, g, tracer, k):
    """One wake's worth of random churn, applied to both sides."""
    log = []
    keys = list(g.pairs)
    # removals
    for _ in range(min(k, len(keys))):
        key = keys[rng.integers(0, len(keys))]
        if key in g.pairs:
            del g.pairs[key]
            log.append((False, key[0], key[1], key[2]))
    # insertions
    for _ in range(k):
        key = (int(rng.integers(0, g.n)), int(rng.integers(0, g.n)), EDGE)
        if key not in g.pairs:
            g.pairs[key] = None
            log.append((True, key[0], key[1], key[2]))
    tracer.apply_log(log)
    # flag churn: seeds appear and disappear, nodes halt, slots free
    # and get reused — both additive (iu & ~prev_iu supertile gate)
    # and subtractive (~iu & prev_mark freed-slot suspects) in_use
    # transitions must hit the wake's suspect paths.
    for _ in range(k // 2):
        i = int(rng.integers(0, g.n))
        r = rng.random()
        if r < 0.25:
            g.flags[i] ^= F.FLAG_BUSY
        elif r < 0.4:
            g.flags[i] ^= F.FLAG_ROOT
        elif r < 0.55:
            g.recv[i] = 0 if g.recv[i] else 3
        elif r < 0.7:
            g.flags[i] |= F.FLAG_HALTED
        elif r < 0.85:
            g.flags[i] |= F.FLAG_IN_USE | F.FLAG_INTERNED
        else:
            # free the slot; a later iteration's IN_USE set is a reuse
            g.flags[i] &= ~(F.FLAG_IN_USE | F.FLAG_HALTED)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_decremental_wakes_match_oracle(seed):
    rng = np.random.default_rng(seed)
    n = 1 << 11
    g = OracleGraph(rng, n, n_edges=4 * n)
    tracer = pd.DecrementalTracer(n, freeze_threshold=64, max_frozen=2)
    _drive_random_wakes(rng, g, tracer, seed, wakes=8)


@pytest.mark.parametrize("mode", ["push", "pull", "jump"])
def test_decremental_modes_match_oracle(mode):
    """Every repair-fixpoint propagation strategy under the same random
    churn schedule (released cycles, halt cascades, de-seeded hubs,
    freed/reused slots) stays oracle-identical.  Auto is the default
    and covered by the seed-sweep test above plus the backends suite;
    here the pure strategies are pinned explicitly."""
    rng = np.random.default_rng(7)
    n = 1 << 10
    g = OracleGraph(rng, n, n_edges=4 * n)
    tracer = pd.DecrementalTracer(
        n, freeze_threshold=64, max_frozen=2, mode=mode
    )
    _drive_random_wakes(rng, g, tracer, 7, wakes=4)


def _drive_random_wakes(rng, g, tracer, seed, wakes):
    src, dst, w, sup = g.arrays()
    tracer.rebuild(src, dst, w, sup)

    # cold-start wake = full derivation
    got = tracer.marks(g.flags, g.recv)
    assert np.array_equal(got, g.oracle_marks())

    for wake in range(wakes):
        _rand_schedule(rng, g, tracer, k=40)
        got = tracer.marks(g.flags, g.recv)
        expected = g.oracle_marks()
        assert np.array_equal(got, expected), (
            f"seed {seed} wake {wake}: "
            f"{int((got != expected).sum())} mismatched marks"
        )
        s = tracer.wake_stats(1)[0]
        assert 0 <= s["kernel_contractions"] <= s["kernel_steps"]
    # SUP removals must have matched their packed kind (a key-kind
    # mismatch shows up as a silently-dropped anomaly)
    assert tracer.layout.stats["anomalies"] == 0


def _island_graph(rng, n, chain, density=0.6):
    """An :class:`OracleGraph` whose suspects close over islands: few
    references and hardly a supervisor, so no giant component ties the
    marks together, and a rooted chain that no other pair enters, which
    makes the derivation from nothing deep and so the closure's price (a
    share of that derivation's chunk walks) high enough to stay under."""
    g = OracleGraph(rng, n, n_edges=int(density * n))
    for key in list(g.pairs):
        if key[1] < chain or (key[2] == SUP and rng.random() > 0.07):
            del g.pairs[key]
    g.flags[:chain] = F.FLAG_IN_USE | F.FLAG_INTERNED
    g.flags[0] |= F.FLAG_ROOT
    g.recv[:chain] = 0
    for hop in range(chain - 1):
        g.pairs[(hop, hop + 1, EDGE)] = None
    return g


@pytest.mark.parametrize(
    "seed,mode,n,density,chain",
    [
        # ``auto`` jumps down the chain, so its derivation is cheap and its
        # price the floor: one walk chunk, a walk a closure sweep
        (0, pt.MODE_AUTO, CHUNK, 0.4, 60),
        (1, pt.MODE_AUTO, CHUNK, 0.4, 60),
        (1, pt.MODE_PULL, 2 * CHUNK + 500, 0.6, 160),
        (2, pt.MODE_PUSH, 2 * CHUNK + 500, 0.6, 160),
    ],
)
def test_warm_wakes_that_gather_only_new_bits_match_oracle(
    seed, mode, n, density, chain
):
    """The kernels' blocks gather the bits that are NEW since the sweep
    before (forced tiles the full table), and contract only if they found
    one.  The random schedule above is no test of that: since the closure
    is priced its wakes all give up and derive from nothing.  Here the
    closures are islands (``_island_graph``), so most wakes stay on the
    warm road: a closure that ends, tiles forced through their full span,
    a first repair sweep against the previous wake's table.  Over up to
    three walk chunks and 65 tiles, with every event of the generator
    (deletions, fresh inserts, halts, seeds dropped, slots freed and
    reused, tiers frozen), the marks equal the oracle's after every wake
    and the steps that contracted stay under the steps taken."""
    rng = np.random.default_rng(seed)
    g = _island_graph(rng, n, chain, density)
    tracer = pd.DecrementalTracer(
        n, freeze_threshold=64, max_frozen=2, mode=mode, s_rows=8
    )
    _drive_random_wakes(rng, g, tracer, seed, wakes=8)
    stats = tracer.wake_stats()
    warm = [s for s in stats if s["gated_tiles"]]
    assert len(warm) >= 3 and not any(s["closure_bailed"] for s in warm)
    assert all(0 < s["kernel_contractions"] < s["kernel_steps"] for s in stats)


def _sweep_profile():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        import sweep_profile
    finally:
        sys.path.pop(0)
    return sweep_profile


@pytest.mark.parametrize("mode", [pt.MODE_AUTO, pt.MODE_PUSH, pt.MODE_PULL])
def test_contractions_of_a_derivation_with_an_unreachable_half(mode):
    """On the benchmark's graph shape (``powerlaw_actor_graph``: half the
    actors released and unreachable) a derivation from nothing walks
    blocks that have nothing to contribute: sources never marked, or
    marked sweeps ago.  ``wake_stats()``'s ``kernel_contractions`` is
    under ``kernel_steps``, and both, and the ``kernel_chunk_walks`` of
    the steps' walks and the ``kernel_walk_trips`` they take them in
    (two chunks a trip), equal what
    ``tools/sweep_profile.py simulate_sweeps`` counts per sweep from the
    tracer's own packed layout (here at the interpreted geometry; the same
    code is the counter's oracle at the chip's)."""
    from uigc_tpu.models import powerlaw_actor_graph

    n = 4 * CHUNK
    g = powerlaw_actor_graph(n, seed=0, garbage_fraction=0.5)
    tracer = pd.DecrementalTracer(n, mode=mode)
    tracer.rebuild(g["edge_src"], g["edge_dst"], g["edge_weight"], g["supervisor"])
    got = tracer.marks(g["flags"], g["recv_count"])
    assert np.array_equal(got, trace_ops.trace_marks_np(
        g["flags"], g["recv_count"], g["supervisor"],
        g["edge_src"], g["edge_dst"], g["edge_weight"],
    ))
    s = tracer.wake_stats(1)[0]
    assert 0 < s["kernel_contractions"] < s["kernel_steps"]

    preps, _ = tracer.layout.prepare_device_wake()
    (layout,) = [p for p in preps if "xla_src" not in p]
    sim = _sweep_profile().simulate_sweeps(g, n, [mode], layout=layout)[mode]
    assert sim["sweeps"] == s["n_sweeps"] == len(sim["steps"])
    assert sim["dirty_chunks"] == s["dirty_chunks"]
    assert sum(sim["steps"]) == s["kernel_steps"]
    assert sum(sim["contracting"]) == s["kernel_contractions"]
    assert sum(sim["chunk_iterations"]) == s["kernel_chunk_walks"] >= s["kernel_steps"]
    assert sum(sim["walk_trips"]) == s["kernel_walk_trips"]
    assert all(c <= t for c, t in zip(sim["contracting"], sim["steps"]))
    # a trip walks two chunks, or one where a block's count is odd
    assert all(
        t <= r <= i <= 2 * r
        for t, r, i in zip(sim["steps"], sim["walk_trips"], sim["chunk_iterations"])
    )
    assert s["kernel_walk_trips"] < s["kernel_chunk_walks"]  # some trip walked two


def test_released_cycle_dies():
    """The canonical non-monotone case: a marked cycle loses its last
    external support and must be fully unmarked by one wake."""
    n = 256
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, dtype=np.uint8)
    flags[0] |= F.FLAG_ROOT
    recv = np.zeros(n, dtype=np.int64)
    # root -> 10, cycle 10 -> 11 -> ... -> 19 -> 10
    pairs = [(0, 10, EDGE)] + [
        (10 + i, 10 + ((i + 1) % 10), EDGE) for i in range(10)
    ]
    src = np.array([p[0] for p in pairs], np.int32)
    dst = np.array([p[1] for p in pairs], np.int32)
    w = np.ones(len(pairs), np.int64)
    tracer = pd.DecrementalTracer(n)
    tracer.rebuild(src, dst, w, np.full(n, -1, np.int32))
    got = tracer.marks(flags, recv)
    assert got[0] and got[10:20].all()

    # cut the root's edge: the whole cycle is suspect and dies
    tracer.apply_log([(False, 0, 10, EDGE)])
    got = tracer.marks(flags, recv)
    assert got[0] and not got[10:20].any()


def test_halt_cascade():
    """Crash-style wake: halting a relay node kills everything only it
    kept alive, while a second support path survives."""
    n = 128
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, dtype=np.uint8)
    flags[0] |= F.FLAG_ROOT
    recv = np.zeros(n, dtype=np.int64)
    # 0 -> 1 -> 2 -> 3 (chain through relay 1); 0 -> 4 -> 3 (second path
    # to 3 only)
    pairs = [(0, 1), (1, 2), (2, 3), (0, 4), (4, 3)]
    src = np.array([p[0] for p in pairs], np.int32)
    dst = np.array([p[1] for p in pairs], np.int32)
    w = np.ones(len(pairs), np.int64)
    tracer = pd.DecrementalTracer(n)
    tracer.rebuild(src, dst, w, np.full(n, -1, np.int32))
    got = tracer.marks(flags, recv)
    assert got[[0, 1, 2, 3, 4]].all()

    flags = flags.copy()
    flags[1] |= F.FLAG_HALTED
    got = tracer.marks(flags, recv)
    # 1 stays marked (reachable), 2 dies (only via halted 1), 3 survives
    # via 4
    assert got[0] and got[1] and not got[2] and got[3] and got[4]


def test_additive_only_wakes():
    """Pure insertions never enter the closure path; marks only grow."""
    n = 512
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, dtype=np.uint8)
    flags[0] |= F.FLAG_ROOT
    recv = np.zeros(n, dtype=np.int64)
    tracer = pd.DecrementalTracer(n)
    src = np.array([0], np.int32)
    dst = np.array([1], np.int32)
    tracer.rebuild(src, dst, np.ones(1, np.int64), np.full(n, -1, np.int32))
    got = tracer.marks(flags, recv)
    assert got[0] and got[1] and not got[2]

    tracer.apply_log([(True, 1, 2, EDGE), (True, 2, 3, EDGE)])
    got = tracer.marks(flags, recv)
    assert got[[0, 1, 2, 3]].all()


@pytest.mark.parametrize("seed", [0, 1])
def test_decremental_wide_geometry(seed):
    """The TPU walk geometry through the closure+repair wake, in
    interpret mode (the compiled tier re-checks on hardware)."""
    rng = np.random.default_rng(seed)
    n = 1 << 11
    g = OracleGraph(rng, n, n_edges=4 * n)
    tracer = pd.DecrementalTracer(
        n, freeze_threshold=64, max_frozen=2, sub=4, group=8
    )
    src, dst, w, sup = g.arrays()
    tracer.rebuild(src, dst, w, sup)
    got = tracer.marks(g.flags, g.recv)
    assert np.array_equal(got, g.oracle_marks())
    for wake in range(4):
        _rand_schedule(rng, g, tracer, k=40)
        got = tracer.marks(g.flags, g.recv)
        expected = g.oracle_marks()
        assert np.array_equal(got, expected), f"seed {seed} wake {wake}"
    assert tracer.layout.stats["anomalies"] == 0


def test_freed_relay_unmarks_downstream():
    """Clearing FLAG_IN_USE on a previously-marked relay must unmark it
    AND everything only it supported (the oracle gates marks on in_use)."""
    n = 128
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, np.uint8)
    flags[0] |= F.FLAG_ROOT
    recv = np.zeros(n, np.int64)
    pairs = [(0, 1), (1, 2)]
    src = np.array([p[0] for p in pairs], np.int32)
    dst = np.array([p[1] for p in pairs], np.int32)
    tracer = pd.DecrementalTracer(n)
    tracer.rebuild(src, dst, np.ones(2, np.int64), np.full(n, -1, np.int32))
    got = tracer.marks(flags, recv)
    assert got[[0, 1, 2]].all()

    flags = flags.copy()
    flags[1] = 0  # freed
    got = tracer.marks(flags, recv)
    assert got[0] and not got[1] and not got[2]


def test_rebuild_invalidates_previous_fixpoint():
    """A second rebuild() that drops pairs outside the removal log must
    not leave stale marks from the first fixpoint."""
    n = 128
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, np.uint8)
    flags[0] |= F.FLAG_ROOT
    recv = np.zeros(n, np.int64)
    tracer = pd.DecrementalTracer(n)
    src = np.array([0, 1], np.int32)
    dst = np.array([1, 2], np.int32)
    tracer.rebuild(src, dst, np.ones(2, np.int64), np.full(n, -1, np.int32))
    got = tracer.marks(flags, recv)
    assert got[[0, 1, 2]].all()

    tracer.rebuild(
        np.array([0], np.int32),
        np.array([1], np.int32),
        np.ones(1, np.int64),
        np.full(n, -1, np.int32),
    )
    got = tracer.marks(flags, recv)
    assert got[0] and got[1] and not got[2]


def test_newly_in_use_node_gets_marked():
    """Gaining FLAG_IN_USE (slot reuse) is an additive event with no
    word change anywhere; the wake must still pick the mark up."""
    n = 128
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, np.uint8)
    flags[0] |= F.FLAG_ROOT
    flags[2] = 0  # not yet in use
    recv = np.zeros(n, np.int64)
    src = np.array([0, 1], np.int32)
    dst = np.array([1, 2], np.int32)
    tracer = pd.DecrementalTracer(n)
    tracer.rebuild(src, dst, np.ones(2, np.int64), np.full(n, -1, np.int32))
    got = tracer.marks(flags, recv)
    assert got[0] and got[1] and not got[2]

    flags = flags.copy()
    flags[2] = F.FLAG_IN_USE | F.FLAG_INTERNED  # slot comes alive
    got = tracer.marks(flags, recv)
    assert got[[0, 1, 2]].all()


@pytest.mark.parametrize(
    # One seed guards the property in tier-1 (~100s of interpret-mode
    # kernel eval per seed); the second rides in the slow tier.
    "seed", [0, pytest.param(1, marks=pytest.mark.slow)]
)
def test_selective_gating_at_scale(seed):
    """Many supertiles, little churn: the suspect/fresh gates cover only
    a small fraction of the graph, so an under-approximated suspect set
    cannot hide behind whole-graph re-derivation (s_rows=1 gives
    128-node supertiles -> 256 supertiles at n=2^15, ~6% gated)."""
    rng = np.random.default_rng(seed)
    n = 1 << 15
    g = OracleGraph(rng, n, n_edges=2 * n)
    tracer = pd.DecrementalTracer(
        n, s_rows=1, freeze_threshold=64, max_frozen=2
    )
    src, dst, w, sup = g.arrays()
    tracer.rebuild(src, dst, w, sup)
    assert np.array_equal(tracer.marks(g.flags, g.recv), g.oracle_marks())
    for wake in range(4):
        _rand_schedule(rng, g, tracer, k=8)
        got = tracer.marks(g.flags, g.recv)
        expected = g.oracle_marks()
        assert np.array_equal(got, expected), (
            f"seed {seed} wake {wake}: "
            f"{int((got != expected).sum())} mismatched marks"
        )
        s = tracer.wake_stats(1)[0]
        assert 0 <= s["kernel_contractions"] <= s["kernel_steps"]
    assert tracer.layout.stats["anomalies"] == 0


# ------------------------------------------------------------------- #
# the closure's price and the cold road (PR 30)
# ------------------------------------------------------------------- #


def supervised_tree(n, fan=8):
    """A tree as a runtime builds it: every actor referenced from and
    supervised by its parent, so every live actor reaches the root by its
    supervisor chain and the marks are one strongly connected component.
    Returns (flags, recv, src, dst, supervisor)."""
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, np.uint8)
    flags[0] |= F.FLAG_ROOT
    child = np.arange(1, n, dtype=np.int32)
    parent = ((child - 1) // fan).astype(np.int32)
    sup = np.full(n, -1, np.int32)
    sup[child] = parent
    return flags, np.zeros(n, np.int64), parent, child, sup


def _walks(w):
    assert w["n_sweeps"] <= len(w["dirty_chunks"])  # every sweep kept its slot
    return sum(w["dirty_chunks"])


@pytest.mark.parametrize(
    # every trace mode on one walk chunk, and the default on three, where
    # a sweep costs as many walks as it has dirty chunks
    "mode,n",
    [(m, 4096) for m in pt.TRACE_MODES] + [(pt.MODE_AUTO, 2 * CHUNK + 4000)],
)
def test_closure_that_swallows_the_marks_gives_up(mode, n):
    """Released references among live, supervised actors: every suspect's
    closure is every mark, so the wake leaves the closure once it has cost
    its share of a derivation and re-derives from the seeds, ungated, with
    the oracle's verdicts."""
    flags, recv, src, dst, sup = supervised_tree(n, fan=8 if n > CHUNK else 2)
    n_chunks = -(-n // CHUNK)
    w = np.ones(src.size, np.int64)
    tracer = pd.DecrementalTracer(n, mode=mode)
    assert tracer.closure_price is None  # no previous fixpoint, no closure
    tracer.rebuild(src, dst, w, sup)
    assert np.array_equal(tracer.marks(flags, recv), np.ones(n, bool))
    rng = np.random.default_rng(30)
    for wake in range(2):
        price = tracer.closure_price
        assert price == pt.closure_price(_walks(tracer.wake_stats(1)[0]))
        cut = rng.choice(np.flatnonzero(w), 24, replace=False)
        w[cut] = 0
        tracer.apply_log([(False, int(src[i]), int(dst[i]), EDGE) for i in cut])
        got = tracer.marks(flags, recv)
        expected = trace_ops.trace_marks_np(flags, recv, sup, src, dst, w)
        assert np.array_equal(got, expected) and not expected.all(), wake
        s = tracer.wake_stats(1)[0]
        assert s["closure_bailed"] == 1 and s["closure_spent"] >= price
        # it left as soon as it could: without its last sweep it was under
        assert s["closure_spent"] - n_chunks < price and s["closure_sweeps"] <= price
        # the cold road: sweep 1 walks the root's chunk and forces no tile
        assert s["gated_tiles"] == 0 and s["dirty_chunks"][0] == 1
        assert max(s["dirty_chunks"]) == min(n_chunks, 2)  # a level spans two chunks


@pytest.mark.parametrize("mode", [pt.MODE_AUTO, pt.MODE_PUSH])
@pytest.mark.parametrize("event", ["release", "halt"])
def test_closure_of_an_island_keeps_the_regional_repair(event, mode):
    """Suspects among actors that no supervisor chain ties to the live
    set: the closure is the island, finishes under its price, and the
    repair forces the island's supertile and no other."""
    n, n_live = 1024, 100
    # the live set: a chain, every actor referenced from and supervised by
    # the one before it (a derivation of a hundred walks under push)
    flags = np.zeros(n, np.uint8)
    flags[:n_live] = F.FLAG_IN_USE | F.FLAG_INTERNED
    flags[0] |= F.FLAG_ROOT
    recv = np.zeros(n, np.int64)
    sup = np.full(n, -1, np.int32)
    sup[1:n_live] = np.arange(n_live - 1)
    # the island, in another supertile: a busy actor outside every
    # supervisor chain and two references hanging off it in a row
    isle = np.arange(900, 903, dtype=np.int32)
    flags[isle] = F.FLAG_IN_USE | F.FLAG_INTERNED
    flags[isle[0]] |= F.FLAG_BUSY
    src = np.concatenate([np.arange(n_live - 1, dtype=np.int32), isle[:-1]])
    dst = np.concatenate([np.arange(1, n_live, dtype=np.int32), isle[1:]])
    w = np.ones(src.size, np.int64)
    tracer = pd.DecrementalTracer(n, mode=mode, s_rows=1)
    tracer.rebuild(src, dst, w, sup)
    assert tracer.marks(flags, recv).sum() == n_live + 3
    price = tracer.closure_price
    assert price >= pt.CLOSURE_MIN_WALKS
    if event == "release":  # the island's first reference
        w[-2] = 0
        tracer.apply_log([(False, int(isle[0]), int(isle[1]), EDGE)])
        dead = isle[1:]
    else:  # its second actor halts: marked still, but passes nothing on
        flags = flags.copy()
        flags[isle[1]] |= F.FLAG_HALTED
        dead = isle[2:]
    got = tracer.marks(flags, recv)
    assert np.array_equal(got, trace_ops.trace_marks_np(flags, recv, sup, src, dst, w))
    assert not got[dead].any() and got.sum() == n_live + 3 - dead.size
    s = tracer.wake_stats(1)[0]
    # the suspect, the one after it, and the sweep that finds no more
    assert (s["closure_sweeps"], s["closure_spent"], s["closure_bailed"]) == (2, 2, 0)
    assert s["gated_tiles"] == 1  # of 8
    assert tracer.closure_price == price  # no derivation from nothing, no new price


@pytest.mark.parametrize("how", ["first", "invalidate", "rebuild"])
def test_a_derivation_from_nothing_is_not_gated(how):
    """With no previous fixpoint the first repair sweep walks the chunks
    that hold seeds and forces no supertile; the warm wake after it gates
    the one tile of a newly-in-use actor, as before."""
    n = CHUNK + 4000  # two walk chunks, the root in the first
    flags, recv, src, dst, sup = supervised_tree(n)
    flags[n - 1] = 0  # a slot not yet in use
    w = np.ones(src.size, np.int64)
    tracer = pd.DecrementalTracer(n)
    tracer.rebuild(src, dst, w, sup)
    if how != "first":
        tracer.marks(flags, recv)
        if how == "invalidate":
            tracer.invalidate()
        else:
            tracer.rebuild(src, dst, w, sup)
        assert tracer.closure_price is None
    got = tracer.marks(flags, recv)
    assert got[: n - 1].all() and not got[n - 1]
    s = tracer.wake_stats(1)[0]
    assert (s["closure_sweeps"], s["closure_bailed"], s["gated_tiles"]) == (0, 0, 0)
    assert s["dirty_chunks"][0] == 1 and max(s["dirty_chunks"]) == 2
    assert tracer.closure_price == pt.closure_price(_walks(s))
    # the warm road is what it was: no suspects, one tile forced
    flags = flags.copy()
    flags[n - 1] = F.FLAG_IN_USE | F.FLAG_INTERNED
    assert tracer.marks(flags, recv).all()
    s = tracer.wake_stats(1)[0]
    assert (s["closure_sweeps"], s["closure_bailed"], s["gated_tiles"]) == (0, 0, 1)


@pytest.mark.parametrize("walks", [0, 1, 8, 9, 192, 1000])
def test_closure_policy_on_ints(walks):
    """``pt.closure_gives_up`` on Python ints: never under the price,
    always at it, the price a share of the carried derivation walks."""
    price = pt.closure_price(walks)
    assert price == max(pt.CLOSURE_MIN_WALKS, -(-walks // round(1 / pt.CLOSURE_SHARE)))
    assert not any(pt.closure_gives_up(spent, walks) for spent in range(price))
    assert all(pt.closure_gives_up(spent, walks) for spent in range(price, price + 40))
    # at the 10M geometry: 192 walks a derivation, 20 a closure sweep
    if walks == 192:
        assert price == 24 and not pt.closure_gives_up(20, walks) and pt.closure_gives_up(40, walks)


def test_derive_is_the_tracers_first_wake():
    """``pd.derive`` over a layout's tiers and a tracer's first wake are
    one program on one set of operands: the same marks, the same
    counters, and the same cached wake fn."""
    rng = np.random.default_rng(9)
    n = 3000
    g = OracleGraph(rng, n, n_edges=3 * n)
    src, dst, w, sup = g.arrays()
    tracer = pd.DecrementalTracer(n, s_rows=8, freeze_threshold=16)
    tracer.rebuild(src, dst, w, sup)
    _rand_schedule(rng, g, tracer, k=60)
    tracer.marks(g.flags, g.recv)  # freezes the 60
    _rand_schedule(rng, g, tracer, k=6)  # and a live tier beside them
    layout = tracer.layout
    preps = layout.prepare_wake()
    assert len(preps) >= 3 and "xla_src" in preps[-1]
    fns_before = len(pd._fn_cache)
    marks, stats = pd.derive(
        g.flags, g.recv, preps, mode=layout.mode,
        pull_density=layout.pull_density, jump_parent=layout.jump_parent,
    )
    assert np.array_equal(marks, g.oracle_marks())
    tracer.invalidate()
    assert np.array_equal(tracer.marks(g.flags, g.recv), marks)
    assert tracer.wake_stats(1)[0] == stats
    assert len(pd._fn_cache) == fns_before + 1
    assert (stats["closure_sweeps"], stats["gated_tiles"]) == (0, 0)


def test_derive_refuses_a_jump_mode_without_parents():
    from uigc_tpu.utils.validation import InvariantViolation

    n = 200
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, np.uint8)
    e = np.zeros(0, np.int32)
    prep = pt.prepare_chunks(e, e, np.zeros(0, np.int64), np.full(n, -1, np.int32), n)
    with pytest.raises(InvariantViolation):
        pd.derive(flags, np.zeros(n, np.int64), [prep], mode=pt.MODE_AUTO)


def test_graft_entry_derives_the_oracles_marks():
    """``__graft_entry__.entry()``: a jittable step and its example
    arguments; the step's first output is the packed marks of its graph."""
    import jax
    import jax.numpy as jnp

    import __graft_entry__ as graft
    from uigc_tpu.models import powerlaw_actor_graph

    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    g = powerlaw_actor_graph(4096, seed=0, garbage_fraction=0.5)
    expected = trace_ops.trace_marks_np(
        g["flags"], g["recv_count"], g["supervisor"],
        g["edge_src"], g["edge_dst"], g["edge_weight"],
    )
    n = g["flags"].shape[0]
    assert np.array_equal(np.asarray(pt.unpack_table(out[0], n, jnp)), expected)
    in_use = (g["flags"] & F.FLAG_IN_USE) != 0
    assert np.array_equal(in_use & ~expected, g["expected_garbage"])


def _simulated_kernel_steps(preps, flags, src, dst, pull):
    """Blocks with work summed over the sweeps of a derivation from the
    seeds, in numpy: per sweep, the blocks whose chunk span holds a chunk
    whose table words changed, less (``pull``) those of a saturated tile."""
    n = flags.size
    packed = [p for p in preps if "xla_src" not in p]
    (p0,) = packed  # the base layout; no churn, so no tier
    super_sz = p0["s_rows"] * pt.LANE
    in_use = (flags & F.FLAG_IN_USE) != 0
    c_lo = p0["bmeta2"] >> pt._SPAN_BITS
    span = p0["bmeta2"] & ((1 << pt._SPAN_BITS) - 1)
    tile = p0["bmeta1"] >> 1
    n_chunks = -(-n // CHUNK)
    mark = in_use & ((flags & F.FLAG_ROOT) != 0)
    prev = np.zeros(n, bool)
    steps, sweeps = 0, 0
    while not np.array_equal(mark, prev):
        changed = np.flatnonzero(mark != prev) // CHUNK
        d = np.concatenate([[0], np.cumsum(np.bincount(changed, minlength=n_chunks) > 0)])
        active = d[c_lo + span] - d[c_lo] > 0
        if pull:
            unmarked = np.zeros(p0["n_super"] * super_sz, bool)
            unmarked[:n] = in_use & ~mark
            saturated = ~unmarked.reshape(p0["n_super"], super_sz).any(axis=1)
            active &= ~saturated[tile]
        steps += int(active.sum())
        sweeps += 1
        new = mark.copy()
        new[dst[mark[src]]] = True
        prev, mark = mark, new & in_use
    return steps, sweeps


@pytest.mark.parametrize("mode", [pt.MODE_PUSH, pt.MODE_PULL])
def test_kernel_steps_count_the_blocks_with_work(mode):
    """``wake_stats()``: ``kernel_steps`` is the blocks with work summed
    over the wake's sweeps, as a numpy run of the same sweeps counts them,
    and ``kernel_steps_full`` what as many launches over every block take."""
    n = 2 * CHUNK + 4000  # three walk chunks
    flags, recv, src, dst, sup = supervised_tree(n)
    w = np.ones(src.size, np.int64)
    tracer = pd.DecrementalTracer(n, mode=mode, s_rows=8)
    tracer.rebuild(src, dst, w, sup)
    assert tracer.marks(flags, recv).all()
    preps, _ = tracer.layout.prepare_device_wake()
    n_blocks = sum(p["n_blocks"] for p in preps if "xla_src" not in p)
    # a child marks its supervisor too, but a parent is marked before it
    steps, sweeps = _simulated_kernel_steps(
        preps, flags, src, dst, pull=mode == pt.MODE_PULL
    )
    s = tracer.wake_stats(1)[0]
    assert (s["closure_sweeps"], s["n_sweeps"]) == (0, sweeps)
    assert s["kernel_steps_full"] == sweeps * n_blocks
    assert s["kernel_steps"] == steps and 0 < steps < s["kernel_steps_full"]

    # a warm wake: the closure loop's launches and the forced tiles count too
    cut = np.random.default_rng(32).choice(src.size, 24, replace=False)
    w[cut] = 0
    tracer.apply_log([(False, int(src[i]), int(dst[i]), EDGE) for i in cut])
    got = tracer.marks(flags, recv)
    assert np.array_equal(got, trace_ops.trace_marks_np(flags, recv, sup, src, dst, w))
    s = tracer.wake_stats(1)[0]
    preps, _ = tracer.layout.prepare_device_wake()
    n_blocks = sum(p["n_blocks"] for p in preps if "xla_src" not in p)
    assert s["closure_sweeps"] > 0
    assert s["kernel_steps_full"] == (s["closure_sweeps"] + s["n_sweeps"]) * n_blocks
    assert 0 < s["kernel_steps"] <= s["kernel_steps_full"]


@pytest.mark.parametrize("seed", [0, 1])
def test_the_log_as_tuples_and_as_columns_is_one_wake(seed):
    """Two tracers over one graph, one handed each wake's log as a list
    of tuples and one as a ``PairLog``: the same verdict words, the same
    ``wake_stats()`` counter for counter over three churn wakes, and the
    wake program of the first serves the second (no new cache key)."""
    import jax
    from uigc_tpu.ops.slotmap import PairLog

    rng = np.random.default_rng(seed)
    n = 1 << 11
    g = OracleGraph(rng, n, n_edges=4 * n)
    kw = dict(freeze_threshold=64, max_frozen=2)
    as_tuples, as_columns = pd.DecrementalTracer(n, **kw), pd.DecrementalTracer(n, **kw)
    src, dst, w, sup = g.arrays()
    for tracer in (as_tuples, as_columns):
        tracer.rebuild(src, dst, w, sup)

    class Tee:
        """What ``_rand_schedule`` takes for a tracer: both, each in its form."""

        def apply_log(self, log):
            as_tuples.apply_log(log)
            columns = PairLog()
            ins, s, d, kind = (np.array(c) for c in zip(*log))
            for lo in range(0, len(log), 7):  # the fold's batches, as arrays
                for op in (False, True):
                    pick = np.flatnonzero(ins[lo:lo + 7] == op) + lo
                    if pick.size and (kind[pick] == kind[pick[0]]).all():
                        columns.extend(op, s[pick], d[pick].astype(np.int32), int(kind[pick[0]]))
                    else:
                        for i in pick.tolist():
                            columns.append(log[i])
            assert sorted(zip(*[c.tolist() for c in columns.columns()])) == \
                sorted((int(a), b, c, e) for a, b, c, e in log)
            as_columns.apply_log(columns)

    def wake(tracer):
        mark_w = tracer.wake_device(jax.device_put(g.flags), jax.device_put(g.recv))
        words, marked = tracer.verdict_words(mark_w)
        return words.copy(), marked

    assert np.array_equal(wake(as_tuples)[0], wake(as_columns)[0])
    for _ in range(3):
        _rand_schedule(rng, g, Tee(), k=40)
        words_t, marked_t = wake(as_tuples)
        keys = set(pd._fn_cache)
        words_c, marked_c = wake(as_columns)
        assert set(pd._fn_cache) == keys  # the same tiers, so the same program
        assert np.array_equal(words_t, words_c) and marked_t == marked_c
        assert np.array_equal(as_columns.unpack_marks(as_columns._mark_w), g.oracle_marks())
    stats_t, stats_c = as_tuples.wake_stats(), as_columns.wake_stats()
    assert len(stats_t) == 4 and stats_t == stats_c
    for tracer in (as_tuples, as_columns):
        assert tracer.layout.stats["anomalies"] == 0
    assert as_tuples.layout.stats["log_rows"] == as_columns.layout.stats["log_rows"] > 0
    assert as_tuples.layout.stats["log_keys"] == as_columns.layout.stats["log_keys"]
