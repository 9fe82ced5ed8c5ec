"""The wake traced from inside: named scopes in the wake program, its
sweep counters, and the collector's phases as trace annotations.

- every phase of the wake program (``uigc.wake/<phase>``) and every
  helper's scope is in the lowered program's text, per trace mode;
- the counters of the ONE wake program equal, per mode, what the
  ``with_stats`` variant of the commit before gave on a small chain and
  a small power-law graph (the numbers below were produced by that
  commit, with ``collect_stats``) wherever the wake still takes the
  regional repair, and a derivation from nothing where its closure gave
  up; ``closure_sweeps``, ``closure_spent`` and ``closure_bailed`` equal
  a numpy closure loop over the same deletions under the program's own
  policy (``pt.closure_gives_up``), and the verdicts equal the oracle's;
- ``WakeProfiler`` records hold the new phases, exclusive and adding up
  to ``wall_s``; an ``annotate`` hook sees ``uigc:wake`` enclose every
  phase on the collector's thread, and nothing without a profiler;
- the life of a release on the wake's record: what the drain's oldest
  flush had waited (``ingest_wait_s``, all three planes), the gap since
  the wake before, ``stage_s`` and ``dispatch_s`` inside their phases,
  the stop cascade of what the sweep freed counted in from the
  dispatchers' threads; the counters' readback is nobody's phase; the
  profiler listens to no event; and none of it exists without one;
- who had the host: thread CPU beside the wall on the wake and on every
  phase and part, the workers' CPU clocks read from outside over the
  wake, its sweep and the gap before it, CPython's collections counted
  into the wake they paused, and a stall of the whole process told from
  a thread that held the GIL by the process's CPU time across it.
"""

import gc
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from uigc_tpu.ops import pallas_decremental as pd
from uigc_tpu.ops import pallas_trace as pt
from uigc_tpu.ops import trace as F
from uigc_tpu.ops.pallas_incremental import EDGE
from uigc_tpu.telemetry import profile
from uigc_tpu.utils import events


# ------------------------------------------------------------------- #
# two small graphs and their deletions
# ------------------------------------------------------------------- #


def chain(n=256):
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, np.uint8)
    flags[0] |= F.FLAG_ROOT
    src = np.arange(n - 1, dtype=np.int32)
    # the third cut's closure (46..50) is small beside the derivation of
    # the 51 that are left: under push and pull it stays under its price
    return flags, src, src + 1, [[(150, 151)], [(50, 51), (220, 221)], [(45, 46)]]


def powerlaw(n=2048):
    rng = np.random.default_rng(5)
    e = 4 * n
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, np.uint8)
    flags[:4] |= F.FLAG_ROOT
    src = (n * rng.random(e) ** 3).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = (src.astype(np.int64) << 32) | dst
    _, first = np.unique(key, return_index=True)
    first.sort()
    src, dst = src[first], dst[first]
    pick = rng.permutation(src.size)
    cuts = [[(int(src[i]), int(dst[i])) for i in pick[:96]],
            [(int(src[i]), int(dst[i])) for i in pick[96:192]]]
    return flags, src, dst, cuts


GRAPHS = {"chain": chain, "powerlaw": powerlaw}

#: repair sweeps of wake 0 (full derivation) and of the two churn wakes,
#: and the marked actors after each, as the commit before PR 27
#: counted them with its ``with_stats`` wake program.  The two ``auto``
#: rows changed in PR 28 (they were the ``jump`` rows: [6, 1, 1] and
#: [4, 4, 5]): ``auto`` no longer jumps from sweep 0, it engages once
#: the push fixpoint has stayed sparse for the price of a jump sweep,
#: 5 one-chunk sweeps on this chain (then the 6 sweeps ``jump`` needs)
#: and 1 on this power-law graph (64 blocks for 8k pairs: a sweep
#: streams 65k slots, as much as the 10k gathers of a jump sweep cost).
PARENT_SWEEPS = {
    ("chain", "auto"): [11, 1, 1, 1], ("chain", "jump"): [6, 1, 1, 1],
    ("chain", "push"): [256, 1, 1, 1], ("chain", "pull"): [256, 1, 1, 1],
    ("powerlaw", "auto"): [5, 5, 5], ("powerlaw", "jump"): [4, 4, 5],
    ("powerlaw", "push"): [5, 5, 5], ("powerlaw", "pull"): [5, 5, 5],
}
#: the repair sweeps of a wake whose closure gave up (PR 30): those of a
#: derivation from nothing of the graph as that wake finds it
COLD_SWEEPS = {
    ("chain", "auto"): [11, 11, 10, 10], ("chain", "jump"): [6, 6, 5, 5],
    ("chain", "push"): [256, 151, 51, 46], ("chain", "pull"): [256, 151, 51, 46],
    ("powerlaw", "auto"): [5, 5, 5], ("powerlaw", "jump"): [4, 4, 5],
    ("powerlaw", "push"): [5, 5, 5], ("powerlaw", "pull"): [5, 5, 5],
}
#: the repair sweeps of those wakes that ran the pointer jump under
#: ``auto`` (``jump``: all of them; ``push`` and ``pull``: none), on the
#: regional road and on the cold one
AUTO_JUMP_SWEEPS = {"chain": [6, 0, 0, 0], "powerlaw": [4, 4, 4]}
AUTO_JUMP_SWEEPS_COLD = {"chain": [6, 6, 5, 5], "powerlaw": [4, 4, 4]}
PARENT_MARKED = {"chain": [256, 151, 51, 46], "powerlaw": [2005, 2003, 2003]}
#: tiles_skipped of wake 0's last kept sweep: the chain saturates its one
#: tile, which only the pull gates count
PARENT_LAST_SKIP = {("chain", "auto"): 1, ("chain", "pull"): 1}


def closure_sweeps_np(src, dst, alive, deleted_dst, prev_mark, derivation_walks):
    """The closure loop in numpy: suspects are the previously marked
    destinations of deleted pairs; every sweep adds the previously marked
    successors of the closure; the loop runs while a sweep changed it and
    the policy has not given up (one walk chunk at these sizes: a sweep
    costs one walk).  Returns (sweeps, gave up)."""
    closure = np.zeros_like(prev_mark)
    closure[deleted_dst] = True
    closure &= prev_mark
    sweeps, changed = 0, bool(closure.any())
    while changed and not pt.closure_gives_up(sweeps, derivation_walks):
        hits = np.zeros_like(closure)
        hits[dst[alive & closure[src]]] = True
        new = closure | (hits & prev_mark)
        changed = bool((new != closure).any())
        closure = new
        sweeps += 1
    return sweeps, changed


@pytest.mark.parametrize("mode", pt.TRACE_MODES)
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_wake_counters_match_the_parents_stats_variant(graph, mode):
    flags, src, dst, cuts = GRAPHS[graph]()
    n = flags.shape[0]
    recv = np.zeros(n, np.int64)
    sup = np.full(n, -1, np.int32)
    alive = np.ones(src.size, bool)
    tracer = pd.DecrementalTracer(n, mode=mode)
    tracer.rebuild(src, dst, np.ones(src.size, np.int64), sup)
    prev, marked, closures = None, [], []
    for batch in [None] + cuts:
        if batch is not None:
            tracer.apply_log([(False, s, d, EDGE) for s, d in batch])
            gone = {(s, d) for s, d in batch}
            alive &= np.array([(s, d) not in gone for s, d in zip(src.tolist(), dst.tolist())])
            closures.append((alive.copy(), [d for _, d in batch], prev))
        prev = tracer.marks(flags, recv)
        oracle = F.trace_marks_np(flags, recv, sup, src, dst, alive.astype(np.int64))
        assert np.array_equal(prev, oracle)
        marked.append(int(prev.sum()))
    stats = tracer.wake_stats()
    assert marked == PARENT_MARKED[graph]
    # wake 0 is a derivation from nothing; every later wake closes over
    # its suspects until the closure is done or has cost its share of the
    # last such derivation, and then repairs the region or re-derives
    want = {pt.MODE_JUMP: None, pt.MODE_AUTO: AUTO_JUMP_SWEEPS[graph]}.get(mode, [0] * len(stats))
    want_cold = {pt.MODE_JUMP: None, pt.MODE_AUTO: AUTO_JUMP_SWEEPS_COLD[graph]}.get(mode, want)
    walks, bails = None, []
    for i, w in enumerate(stats):
        sweeps, bailed = closure_sweeps_np(src, dst, *closures[i - 1], walks) if i else (0, False)
        cold = bailed or i == 0
        bails.append(int(bailed))
        assert (w["closure_sweeps"], w["closure_spent"], w["closure_bailed"]) == (sweeps, sweeps, bailed), i
        assert w["n_sweeps"] == (COLD_SWEEPS if cold else PARENT_SWEEPS)[graph, mode][i], i
        assert w["gated_tiles"] == (0 if cold else 1), i
        jumps = want_cold if cold else want
        assert w["jump_sweeps"] == (w["n_sweeps"] if jumps is None else jumps[i]), i
        if cold:
            walks = w["n_sweeps"]
    assert tracer.closure_price == pt.closure_price(walks)
    # the power-law graph's closures swallow its marks; the chain's last
    # one is five actors long and stays under a push derivation's price
    assert bails == {"powerlaw": [0, 1, 1]}.get(
        graph, [0, 1, 1, int(mode in (pt.MODE_JUMP, pt.MODE_AUTO))]
    )
    use_pull = mode in (pt.MODE_PULL, pt.MODE_AUTO)
    for w in stats:
        k = min(w["n_sweeps"], pt.MAX_SWEEP_STATS)
        # one walk chunk at these sizes, dirty in every sweep that ran
        assert w["dirty_chunks"] == [1] * k
        assert w["pull_on"] == [1 if use_pull else 0] * k
        assert len(w["tiles_skipped"]) == k
        # the jump: never in push and pull, every sweep in jump, and in
        # auto from the sweep it engages on to the end of the fixpoint
        assert len(w["jump_on"]) == k
        assert w["jump_sweeps"] == sum(w["jump_on"])
        assert w["jump_on"] == sorted(w["jump_on"])
    assert stats[0]["tiles_skipped"][-1] == PARENT_LAST_SKIP.get((graph, mode), 0)
    assert all(not any(w["tiles_skipped"]) for w in stats[1:])
    assert tracer.wake_stats(1) == stats[-1:]


@pytest.mark.parametrize("mode", pt.TRACE_MODES)
def test_scopes_in_the_lowered_wake_program(mode):
    import jax

    flags, src, dst, _ = chain(64)
    tracer = pd.DecrementalTracer(64, mode=mode)
    tracer.rebuild(src, dst, np.ones(src.size, np.int64), np.full(64, -1, np.int32))
    fn, del_w, fresh_w, args = tracer.stage_wake()
    text = fn.lower(
        jax.device_put(flags), jax.device_put(np.zeros(64, np.int32)), del_w, fresh_w,
        tracer._mark_w, tracer._seed_w, tracer._halted_w, tracer._iu_w, tracer._table,
        tracer._walks, *args,
    ).as_text(debug_info=True)
    for phase in pd.WAKE_PHASES:
        assert f"{pd.WAKE_SCOPE}/{phase}" in text, phase
    helpers = ["push", "hits", "dirty"]
    if mode in (pt.MODE_JUMP, pt.MODE_AUTO):
        helpers.append("jump")
    if mode in (pt.MODE_PULL, pt.MODE_AUTO):
        helpers.append("sat")
    for loop, helper in [("closure", h) for h in ("push", "hits", "dirty")] + [
        ("repair", h) for h in helpers
    ]:
        assert f"{pd.WAKE_SCOPE}/{loop}/while/body/{helper}" in text, (loop, helper)
    assert f"push/{pt.KERNEL_NAME}" in text
    assert "push/active/" in text  # the list of blocks with work, beside the kernel
    if "jump" not in helpers:
        assert "/jump" not in text
    else:  # the jump's parts carry scopes of their own
        for part in ("hits", "double", "pack"):
            assert f"/jump/{part}/" in text, part
    if "sat" not in helpers:
        assert "/sat" not in text


def test_stats_handles_are_bounded_and_tracers_are_found():
    flags, src, dst, _ = chain(64)
    tracer = pd.DecrementalTracer(64, mode="jump")
    assert tracer in pd.live_tracers()
    tracer.rebuild(src, dst, np.ones(src.size, np.int64), np.full(64, -1, np.int32))
    assert tracer.wake_stats() == []
    tracer._stats = type(tracer._stats)(maxlen=3)
    for _ in range(5):
        tracer.invalidate()
        tracer.marks(flags, np.zeros(64, np.int64))
    stats = tracer.wake_stats()
    assert len(stats) == 3 and len(tracer.wake_stats(2)) == 2
    assert all(w["n_sweeps"] == stats[0]["n_sweeps"] > 0 for w in stats)
    assert all(w["closure_sweeps"] == 0 for w in stats)  # from invalidate(): no suspects
    ident = id(tracer)
    del tracer
    gc.collect()  # its jitted unpack closes over it
    assert ident not in {id(t) for t in pd.live_tracers()}


def test_sweep_profile_prints_the_wakes_own_rows(monkeypatch, capsys):
    """``tools/sweep_profile.py``'s measured mode reads the wake program:
    its per-sweep rows, per mode, are ``wake_stats()`` of a tracer's first
    wake over the same graph, and its profiler records carry them."""
    import json
    import os
    import sys

    from uigc_tpu.models import powerlaw_actor_graph

    n, modes = 4096, ["auto", "jump", "pull"]
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        import sweep_profile
    finally:
        sys.path.pop(0)
    # the tool's compile cache is the chip's business, not this process's
    monkeypatch.setattr("uigc_tpu.utils.platform.enable_compile_cache", lambda: None)
    monkeypatch.setattr(
        sys, "argv",
        ["sweep_profile.py", "--n", str(n), "--skip-probes", "--modes", ",".join(modes)],
    )
    sweep_profile.main()
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert sorted(out["modes"]) == sorted(modes)

    g = powerlaw_actor_graph(n, seed=0, garbage_fraction=0.5)
    rows = ("dirty_chunks", "tiles_skipped", "pull_on", "jump_on")
    for mode, record in zip(modes, out["wake_profile_recent"]):
        tracer = pd.DecrementalTracer(n, mode=mode)
        tracer.rebuild(g["edge_src"], g["edge_dst"], g["edge_weight"], g["supervisor"])
        tracer.marks(g["flags"], g["recv_count"])
        want = tracer.wake_stats()[0]
        got = out["modes"][mode]
        assert got.pop("fixpoint_ms") > 0
        counts = ("n_sweeps", "jump_sweeps", "kernel_steps", "kernel_contractions",
                  "kernel_chunk_walks", "kernel_walk_trips")
        assert got == {k: want[k] for k in counts + rows}
        assert 0 < got["kernel_contractions"] <= got["kernel_steps"] <= got["kernel_chunk_walks"]
        assert got["kernel_steps"] <= got["kernel_walk_trips"] <= got["kernel_chunk_walks"]
        assert got["n_sweeps"] == len(got["dirty_chunks"]) > 1
        assert record["mode"] == mode and record["n_sweeps"] == want["n_sweeps"]
        assert all(record["sweep_" + k] == want[k] for k in rows)


# ------------------------------------------------------------------- #
# the profiler's phases and annotations
# ------------------------------------------------------------------- #


class FakeAnnotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records enter and
    exit with the thread they ran on."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **args):
        hook = self

        class Mark:
            def __enter__(self):
                hook.log.append(("enter", name, args, threading.get_ident()))
                return self

            def __exit__(self, *exc):
                hook.log.append(("exit", name, args, threading.get_ident()))

        return Mark()


#: the nested annotations and the phase each lies directly inside
PARTS = {"uigc:stage": "upload", "uigc:dispatch": "device"}


def test_phases_are_exclusive_and_add_up_to_the_wall():
    notes = FakeAnnotations()
    prof = profile.WakeProfiler("n", annotate=notes)
    wake = prof.begin_wake()
    with wake.phase("ingest"):
        time.sleep(0.01)
    with wake.phase("trace"):
        time.sleep(0.01)
        for name in ("layout", "upload", "device", "readback", "sweep"):
            with wake.phase(name):
                time.sleep(0.01)
        wake.note(kills=3, freed=4, n_sweeps=2, closure_sweeps=1)
    wake.end(entries=5, garbage=4)
    (rec,) = prof.wakes_since(0.0)
    assert set(rec["phases"]) == set(profile.PHASES)
    for name in ("ingest", "trace", "layout", "upload", "device", "readback", "sweep"):
        assert 0.009 < rec["phases"][name] < 0.5, (name, rec["phases"])
    assert rec["phases"]["fold"] == rec["phases"]["broadcast"] == 0.0
    total = sum(rec["phases"].values())
    assert total <= rec["wall_s"] and rec["wall_s"] - total < 0.05 * rec["wall_s"]
    assert (rec["kills"], rec["freed"], rec["n_sweeps"], rec["closure_sweeps"]) == (3, 4, 2, 1)
    assert rec["wake"] == 0
    # on the trace's clock: the wake encloses the phases, trace encloses its five
    names = [(kind, name) for kind, name, _, _ in notes.log]
    assert names[0] == ("enter", "uigc:wake")
    assert names[:3] == [("enter", "uigc:wake"), ("enter", "uigc:ingest"), ("exit", "uigc:ingest")]
    at = names.index
    assert at(("enter", "uigc:trace")) < at(("enter", "uigc:layout")) < at(("exit", "uigc:sweep")) \
        < at(("exit", "uigc:trace")) < at(("exit", "uigc:wake"))
    assert all(args == {"wake": 0} for _, _, args, _ in notes.log)
    assert prof.begin_wake().ordinal == 1


def _served(extra):
    from uigc_tpu import AbstractBehavior, ActorTestKit, Behaviors, NoRefs

    class Spawn(NoRefs):
        pass

    class Drop(NoRefs):
        pass

    class Worker(AbstractBehavior):
        def on_message(self, msg):
            return self

    class Root(AbstractBehavior):
        def __init__(self, context):
            super().__init__(context)
            self.held = []

        def on_message(self, msg):
            if isinstance(msg, Spawn):
                self.held = [
                    self.context.spawn(Behaviors.setup(Worker), f"w{i}-{time.time_ns()}")
                    for i in range(8)
                ]
            elif self.held:
                self.context.release(*self.held)
                self.held = []
            return self

    config = {"uigc.crgc.wakeup-interval": 10, "uigc.crgc.shadow-graph": "decremental"}
    config.update(extra)
    kit = ActorTestKit(config=config, name="waketrace")
    root = kit.spawn(Behaviors.setup_root(Root), "root")
    return kit, root, Spawn, Drop


def _churn(kit, root, Spawn, Drop, done):
    deadline = time.time() + 60
    while time.time() < deadline and not done():
        root.tell(Spawn())
        time.sleep(0.1)
        root.tell(Drop())
        time.sleep(0.2)
    assert done()


def test_annotations_enclose_every_phase_on_the_collectors_thread():
    kit, root, Spawn, Drop = _served({"uigc.telemetry.wake-profile": True})
    try:
        prof = kit.system.telemetry.profiler
        notes = prof.annotate = FakeAnnotations()
        device_events = []

        def on_event(name, fields):
            if name == events.DEVICE_TRACE:
                device_events.append(dict(fields))

        # the profiler alone leaves the recorder off: the test wants the event
        assert not events.recorder.enabled
        events.recorder.enable()
        events.recorder.add_listener(on_event)
        listening = time.time()

        def swept():
            return any(r.get("freed") for r in prof.wakes_since(0.0))

        _churn(kit, root, Spawn, Drop, swept)
        records = prof.wakes_since(0.0)
    finally:
        events.recorder.remove_listener(on_event)
        events.recorder.disable()
        kit.shutdown()
    log = list(notes.log)
    # the hook was swapped in while the collector ran: start at a whole wake
    first = next(i for i, (kind, name, _, _) in enumerate(log) if (kind, name) == ("enter", "uigc:wake"))
    # CPython's collector and the watchdog write theirs on whatever thread
    # they run on, with their own arguments: each entered and left, apart
    others = [e for e in log[first:] if e[1] in (profile.GC_ANNOTATION, profile.STALL_ANNOTATION)]
    assert [e[0] for e in others] == ["enter", "exit"] * (len(others) // 2)
    log = [e for e in log[first:] if e not in others]
    # one thread, properly nested, every phase inside a wake of its ordinal
    assert len({thread for *_, thread in log}) == 1
    stack, seen = [], set()
    for kind, name, args, _ in log:
        if kind == "enter":
            if name == "uigc:wake":
                assert not stack
            else:
                assert stack and stack[0][0] == "uigc:wake" and stack[0][1] == args
            # the two parts sit directly inside their phases
            if name in PARTS:
                assert stack[-1] == ("uigc:" + PARTS[name], args)
            stack.append((name, args))
            seen.add(name)
        else:
            assert stack.pop() == (name, args)
    assert {"uigc:wake", "uigc:ingest", "uigc:fold", "uigc:trace", "uigc:layout",
            "uigc:upload", "uigc:device", "uigc:readback", "uigc:sweep",
            "uigc:stage", "uigc:dispatch"} <= seen
    swept_rec = [r for r in records if r.get("freed")]
    # (a wake can free slots whose marks were gone already: no sweep then)
    assert swept_rec and all(r["device_s"] > 0 and r["n_sweeps"] >= 0 for r in swept_rec)
    assert any(r.get("n_sweeps", 0) >= 1 for r in records)
    for r in (r for r in records if r["device_s"] > 0):
        assert r["closure_sweeps"] >= 0 and len(r["sweep_dirty_chunks"]) == r["n_sweeps"]
        # auto, a shallow tree: the jump's decision is recorded per sweep
        assert len(r["sweep_jump_on"]) == r["n_sweeps"]
        assert r["jump_sweeps"] == sum(r["sweep_jump_on"])
        inside = sum(r["phases"][p] for p in ("layout", "upload", "device", "readback"))
        assert inside <= r["device_s"] * 1.001
        assert sum(r["phases"].values()) <= r["wall_s"]
        # parts, not phases: the phases around them keep their meaning
        assert 0 < r["stage_s"] <= r["phases"]["upload"]
        assert 0 < r["dispatch_s"] <= r["phases"]["device"]
        assert r["trace_mode"] == "auto"
    assert sorted(r["wake"] for r in records) == [r["wake"] for r in records]
    # device_s is the bracket of the device call's event, taken beside it
    # (the event closes around it), whose counters now come with the record
    assert device_events and all(
        e["trace_mode"] == "auto" and "upload_bytes" in e and "n_sweeps" not in e
        for e in device_events
    )
    called = [r for r in records if r["device_s"] > 0 and r["t"] > listening]
    assert called
    for r in called:
        assert any(0 <= e["duration_s"] - r["device_s"] < 0.05 for e in device_events), r


def test_no_annotation_and_one_program_without_a_profiler(monkeypatch):
    calls = []
    monkeypatch.setattr(profile, "trace_annotation", lambda *a, **k: calls.append(a))
    kit, root, Spawn, Drop = _served({})
    try:
        graph = kit.system.engine.bookkeeper.shadow_graph
        before = graph.device_wakes

        def traced_twice():
            return graph.device_wakes >= before + 2

        _churn(kit, root, Spawn, Drop, traced_twice)
        assert kit.system.telemetry is None or kit.system.telemetry.profiler is None
        assert graph.profile_wake is None
        stats = graph._dec.wake_stats()
    finally:
        kit.shutdown()
    assert calls == []
    # the program counted its sweeps all the same
    assert stats and all(w["n_sweeps"] >= 0 for w in stats)


# ------------------------------------------------------------------- #
# the life of a release: wait, gap, cascade, on the wake's own record
# ------------------------------------------------------------------- #

NO_TIMER_MS = 86_400_000  # the test wakes the collector by hand


def _wait(cond, timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


def _tree(extra, fanout=3, depth=2):
    """A kit whose root spawns, on ``Spawn``, a tree of
    ``(fanout ** (depth + 1) - 1) / (fanout - 1)`` actors (every
    constructor inside ``spawn``) and lets go of it on ``Drop``; the
    ``PostStop``s are counted in ``stopped``."""
    from uigc_tpu import AbstractBehavior, ActorTestKit, Behaviors, NoRefs
    from uigc_tpu.runtime.signals import PostStop

    class Spawn(NoRefs):
        pass

    class Drop(NoRefs):
        pass

    stopped = []

    def node(level):
        class Node(AbstractBehavior):
            def __init__(self, context):
                super().__init__(context)
                self.children = [
                    context.spawn(node(level + 1), f"c{i}") for i in range(fanout)
                ] if level < depth else []

            def on_message(self, msg):
                return self

            def on_signal(self, signal):
                if signal is PostStop:
                    stopped.append(self.context.name)
                return None

        return Behaviors.setup(Node)

    class Root(AbstractBehavior):
        def __init__(self, context):
            super().__init__(context)
            self.top = None

        def on_message(self, msg):
            if isinstance(msg, Spawn):
                self.top = self.context.spawn(node(0), f"top-{time.time_ns()}")
            elif self.top is not None:
                self.context.release(self.top)
                self.top = None
            return self

    # a slow timer: the records of three rounds stay among the 256 kept
    config = {"uigc.crgc.wakeup-interval": 100, "uigc.crgc.shadow-graph": "decremental"}
    config.update(extra)
    kit = ActorTestKit(config=config, name="cascade")
    root = kit.spawn(Behaviors.setup_root(Root), "root")
    size = (fanout ** (depth + 1) - 1) // (fanout - 1)
    return kit, root, Spawn, Drop, stopped, size


def test_a_released_tree_is_one_wakes_cascade_and_a_later_wake_leaves_it_alone():
    kit, root, Spawn, Drop, stopped, size = _tree({"uigc.telemetry.wake-profile": True})
    try:
        prof = kit.system.telemetry.profiler
        seen = {}  # ordinal -> the record as first seen whole

        def whole():
            for r in prof.wakes_since(0.0):
                if r.get("cascade_s") is not None:
                    seen.setdefault(r["wake"], r)
            return len(seen)

        # round 0 warms up: the first device wake compiles for seconds, and
        # what is flushed meanwhile reaches the collector in one heap
        for round_ in (0, 1, 2):
            root.tell(Spawn())
            time.sleep(0.4)  # every constructor's flush folded: the tree dies whole
            before = whole()
            root.tell(Drop())
            assert _wait(lambda: len(stopped) == (round_ + 1) * size), len(stopped)
            if round_ == 0:
                continue  # its tree may have died in pieces, or before a wake saw it
            assert _wait(lambda: whole() == before + 1), (round_, seen)
            if round_ == 1:
                first = max(seen)
        again = {r["wake"]: r for r in prof.wakes_since(0.0)}[first]
        last = seen[max(seen)]
    finally:
        kit.shutdown()
    for rec in (seen[first], last):
        # ONE wake frees the tree, stops its top, and is told of every PostStop
        assert rec["freed"] == rec["freed_local"] == rec["stopped"] == size
        assert rec["kills"] == 1
        assert 0 < rec["sweep_end_s"] <= rec["wall_s"] and 0 < rec["last_stop_s"] < 30
        assert rec["cascade_s"] == max(0.0, rec["last_stop_s"] - rec["sweep_end_s"])
    assert last["wake"] > first
    # the third tree's terminations went to the third wake's record alone
    assert again == seen[first]


def test_a_cascade_that_does_not_end_keeps_its_count_and_goes_with_its_record():
    prof = profile.WakeProfiler("n", max_recent=4, annotate=FakeAnnotations())

    def wake(freed_local):
        w = prof.begin_wake()
        with w.phase("sweep"):
            if freed_local:
                w.note(freed=freed_local, freed_local=freed_local)
        w.end(entries=0, garbage=freed_local)
        return w.ordinal

    first = wake(3)
    prof.cell_terminated(first, time.perf_counter())
    prof.cell_terminated(first, time.perf_counter())
    # a cell can stop before its wake has ended: counted in at the end
    early = prof.begin_wake()
    prof.cell_terminated(early.ordinal, time.perf_counter() + 1.0)
    prof.wakes_since(0.0)  # a reader looks in between: the count must not be lost
    with early.phase("sweep"):
        early.note(freed=1, freed_local=1)
    early.end(entries=0, garbage=1)
    recs = {r["wake"]: r for r in prof.wakes_since(0.0)}
    assert (recs[first]["stopped"], recs[first]["cascade_s"]) == (2, None)
    assert recs[early.ordinal]["stopped"] == 1 and 0.9 < recs[early.ordinal]["cascade_s"] < 1.1
    plain = wake(0)
    assert "cascade_s" not in {r["wake"]: r for r in prof.wakes_since(0.0)}[plain]
    # the open cascade is dropped when its record has left the recent ones
    assert first in prof._cascades
    for _ in range(4):
        wake(0)
    assert first not in prof._cascades and not prof._cascades


def _manual(extra):
    """A kit whose collector wakes only when the test tells it to, with
    one actor under its root that flushes when it has handled a
    message.  ``wake()`` returns the record of the wake it caused."""
    from uigc_tpu import AbstractBehavior, ActorTestKit, Behaviors, NoRefs
    from uigc_tpu.engines.crgc import collector

    class Poke(NoRefs):
        pass

    class Root(AbstractBehavior):
        def on_message(self, msg):
            return self

    config = {"uigc.crgc.wakeup-interval": NO_TIMER_MS, "uigc.telemetry.wake-profile": True}
    config.update(extra)
    kit = ActorTestKit(config=config, name="manual")
    root = kit.spawn(Behaviors.setup_root(Root), "root")
    engine = kit.system.engine
    prof = kit.system.telemetry.profiler

    def wake():
        before = prof.to_json()["wakes"]
        engine.bookkeeper_cell.tell(collector.WAKEUP)
        assert _wait(lambda: prof.to_json()["wakes"] > before)
        return prof.wakes_since(0.0)[before]

    return kit, root, Poke, engine, wake


@pytest.mark.parametrize("plane", ["packed", "entry", "foreign"])
def test_ingest_wait_is_the_age_of_the_oldest_flush_the_wake_drained(plane):
    packed = plane != "entry"
    kit, root, Poke, engine, wake = _manual({"uigc.crgc.packed-entries": packed})
    try:
        assert (engine.packed_plane is not None) == packed
        if packed:
            assert engine.packed_plane.timed
        time.sleep(0.1)
        wake()  # what the start-up flushed
        assert wake()["ingest_wait_s"] is None  # nothing flushed since: nothing waited

        def flushed():
            if packed:
                return engine.packed_plane.first_write is not None
            return engine.queue_since is not None

        assert not flushed()
        if plane == "foreign":
            # one flush of a foreign root actor, in its plain uid
            block = np.full((1, engine.packed_plane.width), -1, dtype=np.int64)
            block[0, 1:4] = (0, 2, 0)
            engine.packed_plane.write_foreign(block)
        else:
            root.tell(Poke())
        assert _wait(flushed)
        time.sleep(0.15)
        if plane != "foreign":
            root.tell(Poke())  # a younger flush does not move the clock
            time.sleep(0.05)
        rec = wake()
        assert 0.15 <= rec["ingest_wait_s"] < 5.0, rec
        assert rec["entries"] >= 1 and not flushed()
        assert wake()["ingest_wait_s"] is None
    finally:
        kit.shutdown()


def test_gap_is_the_pause_between_two_wakes():
    kit, root, Poke, engine, wake = _manual({})
    try:
        first = wake()
        time.sleep(0.2)
        second = wake()
        third = wake()
    finally:
        kit.shutdown()
    assert second["wake"] == first["wake"] + 1 and third["wake"] == second["wake"] + 1
    assert 0.2 <= second["gap_s"] < 5.0
    assert 0 <= third["gap_s"] < 0.2
    # and the very first wake of a profiler has no wake before it
    prof = profile.WakeProfiler("n", annotate=FakeAnnotations())
    prof.begin_wake().end(entries=0, garbage=0)
    assert prof.wakes_since(0.0)[0]["gap_s"] is None


def test_readback_does_not_wait_for_the_counters(monkeypatch):
    """The wake's counters stay on the device; whoever reads the records
    fetches them.  A tracer whose ``wake_stats`` is slow must cost the
    wake nothing (it was the second, synchronous ``device_get`` of
    every profiled wake, inside the ``readback`` bracket)."""
    real = pd.DecrementalTracer.wake_stats

    def slow(self, last_n=None):
        time.sleep(0.05)
        return real(self, last_n)

    monkeypatch.setattr(pd.DecrementalTracer, "wake_stats", slow)
    kit, root, Spawn, Drop = _served({"uigc.telemetry.wake-profile": True})
    try:
        prof = kit.system.telemetry.profiler

        def swept():
            return any(r.get("freed") for r in prof.wakes_since(0.0))

        _churn(kit, root, Spawn, Drop, swept)
        # inside the profiler: the record as the wake left it
        with prof._lock:
            raw = [dict(r) for r in prof._recent if r["device_s"] > 0]
        read = [r for r in prof.wakes_since(0.0) if r["device_s"] > 0]
    finally:
        kit.shutdown()
    assert raw and read
    assert all(r["phases"]["readback"] < 0.05 for r in read), [r["phases"] for r in read]
    assert all(r["n_sweeps"] >= 0 and len(r["sweep_dirty_chunks"]) == r["n_sweeps"] for r in read)


def test_deferred_fields_are_read_once_in_one_call_and_a_failing_read_is_survived():
    prof = profile.WakeProfiler("n", annotate=FakeAnnotations())
    calls = []

    def read(handles):
        calls.append(list(handles))
        return [{"n_sweeps": h} for h in handles]

    def broken(handles):
        raise RuntimeError("the device state is gone")

    for handle, reader in ((3, read), (5, read), (7, broken)):
        w = prof.begin_wake()
        w.defer(reader, handle)
        w.end(entries=0, garbage=0)
    w = prof.begin_wake()
    w.end(entries=0, garbage=0)
    recs = prof.wakes_since(0.0)
    assert [r.get("n_sweeps") for r in recs] == [3, 5, None, None]
    assert calls == [[3, 5]]
    assert [r.get("n_sweeps") for r in prof.to_json()["recent"]] == [3, 5, None, None]
    assert calls == [[3, 5]]  # nothing is read twice


def test_wake_profile_alone_leaves_the_recorder_off_and_still_fills_device_s():
    assert "__call__" not in vars(profile.WakeProfiler)
    was = events.recorder.enabled
    events.recorder.disable()
    try:
        kit, root, Spawn, Drop = _served({"uigc.telemetry.wake-profile": True})
        try:
            tel = kit.system.telemetry
            assert tel.profiler is not None and not callable(tel.profiler)
            assert not tel._listeners
            assert not events.recorder.enabled
            prof = tel.profiler

            def swept():
                return any(r.get("freed") for r in prof.wakes_since(0.0))

            _churn(kit, root, Spawn, Drop, swept)
            assert not events.recorder.enabled
            called = [r for r in prof.wakes_since(0.0) if r["device_s"] > 0]
            doc = prof.to_json()
        finally:
            kit.shutdown()
    finally:
        if was:
            events.recorder.enable()
    assert called and all(r["trace_mode"] == "auto" for r in called)
    for r in called:
        inside = sum(r["phases"][p] for p in ("layout", "upload", "device", "readback"))
        assert inside <= r["device_s"] * 1.001 <= r["wall_s"] * 1.001
    assert doc["phases"]["trace"]["device_total_s"] >= sum(r["device_s"] for r in called) > 0


# ------------------------------------------------------------------- #
# who had the host: CPU clocks beside the wall clock
# ------------------------------------------------------------------- #


def _spin(seconds):
    """Burn ``seconds`` of the calling thread's own CPU time."""
    until = time.thread_time() + seconds
    while time.thread_time() < until:
        pass


class Spinner:
    """A thread that stands for a dispatcher worker: it spins for
    ``seconds`` of its own CPU each time it is told to, and waits."""

    def __init__(self, seconds=0.05):
        self.seconds = seconds
        self.go, self.done, self.leave = threading.Event(), threading.Event(), False
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        while True:
            self.go.wait()
            self.go.clear()
            if self.leave:
                return
            _spin(self.seconds)
            self.done.set()

    def spin(self):
        """Have it spin, and wait (off the CPU) until it has."""
        self.done.clear()
        self.go.set()
        assert self.done.wait(30)

    def exit(self):
        self.leave = True
        self.go.set()
        self.thread.join(10)
        assert not self.thread.is_alive()


def _clocked(threads=None, **kw):
    """A started profiler on a fast watchdog, its annotations in a log."""
    notes = FakeAnnotations()
    prof = profile.WakeProfiler("n", annotate=notes, threads=threads,
                                watch_period_s=0.01, stall_threshold_s=0.05, **kw)
    prof.start()
    return prof, notes


def test_phases_cpu_is_exclusive_and_adds_up_to_cpu_s():
    prof, _ = _clocked()
    try:
        wake = prof.begin_wake()
        with wake.phase("trace"):
            _spin(0.02)
            with wake.phase("sweep"):  # pauses ``trace`` on both clocks
                _spin(0.03)
            _spin(0.01)
            with wake.part("stage_s", "stage"):
                _spin(0.01)
                time.sleep(0.03)
        time.sleep(0.02)  # between brackets: the wake's, no phase's
        wake.end(entries=0, garbage=0)
        (rec,) = prof.wakes_since(0.0)
    finally:
        prof.close()
    cpu = rec["phases_cpu"]
    assert set(cpu) == set(profile.PHASES)
    assert 0.04 <= cpu["trace"] < 0.07 and 0.03 <= cpu["sweep"] < 0.05, cpu
    assert all(cpu[name] == 0.0 for name in cpu if name not in ("trace", "sweep"))
    # as ``phases`` to ``wall_s``: the sum, less the statements between brackets
    assert 0 <= rec["cpu_s"] - sum(cpu.values()) < 0.005, rec
    assert 0 <= rec["wall_s"] - sum(rec["phases"].values())
    assert rec["cpu_s"] < rec["wall_s"] - 0.04  # the two sleeps
    # a part's twin: its thread CPU beside its wall, the phase's clock running on
    assert 0.01 <= rec["stage_cpu_s"] < 0.02 and rec["stage_s"] >= 0.04
    assert rec["process_cpu_s"] >= rec["cpu_s"] - 0.005


@pytest.mark.parametrize("how", ["sleeps", "spins"])
def test_a_phase_that_sleeps_stood_and_one_that_spins_ran(how):
    prof, _ = _clocked()
    try:
        wake = prof.begin_wake()
        with wake.phase("device"):
            if how == "sleeps":
                time.sleep(0.1)
            else:
                _spin(0.1)
        wake.end(entries=0, garbage=0)
        (rec,) = prof.wakes_since(0.0)
    finally:
        prof.close()
    wall, cpu = rec["phases"]["device"], rec["phases_cpu"]["device"]
    if how == "sleeps":
        assert wall >= 0.1 and cpu < 0.02, (wall, cpu)
    else:
        # beside five other workers the thread may be kept waiting: wide
        assert 0.1 <= cpu <= wall + 0.001 and cpu > 0.25 * wall, (wall, cpu)


def test_a_worker_spinning_inside_the_sweep_is_the_workers_cpu_not_the_collectors():
    busy, idle = Spinner(), Spinner()
    prof, _ = _clocked(lambda: {"workers": [busy.thread.ident, idle.thread.ident]})
    try:
        wake = prof.begin_wake()
        with wake.phase("trace"):
            with wake.phase("sweep"):
                busy.spin()
        wake.end(entries=0, garbage=0)
        (rec,) = prof.wakes_since(0.0)
    finally:
        prof.close()
        busy.exit(), idle.exit()
    assert 0.05 <= rec["workers_cpu_sweep_s"] < 0.08, rec
    assert rec["workers_cpu_sweep_s"] <= rec["workers_cpu_s"] < 0.08
    # one thread had all of it
    assert 0.05 <= rec["workers_busy_max_s"] <= rec["workers_cpu_s"]
    assert rec["workers_cpu_s"] - rec["workers_busy_max_s"] < 0.01
    # the collector stood meanwhile: the sweep's wall is not its CPU
    assert rec["phases"]["sweep"] >= 0.05 and rec["phases_cpu"]["sweep"] < 0.02
    assert rec["workers_cpu_gap_s"] is None  # no wake before it


def test_a_worker_spinning_between_two_wakes_is_the_gaps_alone_and_a_dead_one_is_dropped():
    worker = Spinner()
    prof, _ = _clocked(lambda: {"workers": [worker.thread.ident]})

    def wake():
        w = prof.begin_wake()
        with w.phase("sweep"):
            pass
        w.end(entries=0, garbage=0)

    try:
        wake()
        worker.spin()
        wake()
        worker.exit()
        wake()
        first, second, third = prof.wakes_since(0.0)
    finally:
        prof.close()
    assert 0.05 <= second["workers_cpu_gap_s"] < 0.08, second
    assert second["workers_cpu_s"] < 0.01 and second["workers_cpu_sweep_s"] < 0.01
    assert second["workers_busy_max_s"] < 0.01
    assert second["gap_s"] >= 0.05
    # its clock no longer reads (or reads what the exit took): no error
    assert 0 <= third["workers_cpu_s"] == third["workers_busy_max_s"] < 0.01
    assert 0 <= third["workers_cpu_gap_s"] < 0.01


def test_without_the_runtimes_threads_the_workers_fields_read_none():
    prof, _ = _clocked()
    try:
        wake = prof.begin_wake()
        with wake.phase("sweep"):
            pass
        wake.end(entries=0, garbage=0)
        (rec,) = prof.wakes_since(0.0)
    finally:
        prof.close()
    for field in ("workers_cpu_s", "workers_cpu_sweep_s", "workers_cpu_gap_s",
                  "workers_busy_max_s"):
        assert field in rec and rec[field] is None
    assert rec["cpu_s"] >= 0 and rec["process_cpu_s"] >= 0


def test_a_collection_inside_a_phase_is_that_wakes_pause_and_no_others():
    prof, notes = _clocked()
    gc.disable()  # the forced collections only
    try:
        def wake(collect):
            w = prof.begin_wake()
            with w.phase("fold"):
                if collect == "fold":
                    gc.collect()
                    gc.collect(1)
            with w.phase("sweep"):
                if collect == "sweep":
                    gc.collect()
                gc.collect(0)  # generation 0: nobody's finding
            w.end(entries=0, garbage=0)

        wake(None)
        wake("fold")
        gc.collect()  # between wakes: no wake's
        wake("sweep")
        wake(None)
        quiet, folded, swept, after = prof.wakes_since(0.0)
    finally:
        gc.enable()
        prof.close()
    for rec in (quiet, after):
        assert (rec["gc_s"], rec["gc_sweep_s"], rec["gc_full"]) == (0.0, 0.0, 0)
    assert folded["gc_s"] > 0 and folded["gc_full"] == 1 and folded["gc_sweep_s"] == 0.0
    assert folded["gc_s"] <= folded["phases"]["fold"]
    assert swept["gc_full"] == 1 and 0 < swept["gc_sweep_s"] == swept["gc_s"]
    assert swept["gc_s"] <= swept["phases"]["sweep"]
    # on the trace's clock: entered at start, left at stop, on the thread
    # that collected, with the generation; nothing for generation 0
    marks = [(kind, args) for kind, name, args, _ in notes.log if name == profile.GC_ANNOTATION]
    assert marks == [(kind, {"gen": gen}) for gen in (2, 1, 2, 2) for kind in ("enter", "exit")]
    assert prof._on_gc not in gc.callbacks


def test_the_profiler_is_started_by_telemetry_and_stopped_by_its_close():
    kit, root, Poke, engine, wake = _manual({})
    try:
        prof = kit.system.telemetry.profiler
        assert gc.callbacks.count(prof._on_gc) == 1
        watchdog = prof._watchdog
        assert watchdog.is_alive() and watchdog.name == "uigc-stallwatch"
        system = kit.system
        clocks = prof._clocks.clocks
        # the runtime's threads, learned once: the pool, the timer, the Bookkeeper's
        assert len(clocks["workers"]) == len(system.dispatcher.thread_idents()) > 0
        assert len(clocks["timer"]) == 1 and len(clocks["collector"]) == 1
        root.tell(Poke())
        rec = wake()
        assert rec["workers_cpu_s"] >= 0 and rec["workers_busy_max_s"] >= 0
        assert rec["cpu_s"] > 0 and set(rec["phases_cpu"]) == set(profile.PHASES)
        assert "stalls" in prof.to_json()
    finally:
        kit.shutdown()
    assert prof._on_gc not in gc.callbacks
    assert not watchdog.is_alive() and prof._watchdog is None


def _longest(stalls):
    assert stalls, "no stall was recorded"
    return max(stalls, key=lambda stall: stall["late_s"])


def test_a_thread_that_holds_the_gil_is_a_stall_with_the_processes_cpu_in_it(tmp_path):
    holder = Spinner()
    data = [random.random() for _ in range(2_000_000)]
    stacks = tmp_path / "stacks.txt"
    profile.WakeProfiler.dump_stalls_to(str(stacks))
    prof, notes = _clocked(lambda: {"workers": [holder.thread.ident]})
    sunk = []
    profile.record_sink = sunk.append
    try:
        time.sleep(0.1)  # the watchdog ticks freely
        wake = prof.begin_wake()
        with wake.phase("fold"):
            t0 = time.perf_counter()
            data.sort()  # one C call: the GIL is not let go of
            held = time.perf_counter() - t0
        wake.end(entries=0, garbage=0)
        time.sleep(0.1)
        stalls = prof.to_json()["stalls"]
    finally:
        profile.record_sink = None
        prof.close()
        profile.WakeProfiler.dump_stalls_to(None)
        holder.exit()
    assert held > 0.15, held  # else the case shows nothing
    stall = _longest(stalls)
    assert held - 0.1 < stall["late_s"] <= held + 0.1, (held, stall)
    # somebody RAN all the while: the program's, and not the workers'
    assert stall["process_cpu_s"] > 0.5 * stall["late_s"], stall
    assert stall["workers_cpu_s"] < 0.05 and stall["timer_cpu_s"] is None
    assert (stall["wake"], stall["phase"]) == (0, "fold")
    assert stall in [s for s in sunk if "late_s" in s] and any("wall_s" in r for r in sunk)
    marks = [args for kind, name, args, _ in notes.log
             if (kind, name) == ("enter", profile.STALL_ANNOTATION)]
    assert {"late_ms": round(stall["late_s"] * 1e3)} in marks
    # the stacks, written by a thread that needs no GIL WHILE it was held:
    # the dump begins where the record says, and names the call that held on
    text = stacks.read_bytes()[stall["dump_offset"]:].decode()
    assert text.startswith("Timeout (0:00:00.07"), text[:200]
    assert "test_a_thread_that_holds_the_gil" in text


_CHILD = r"""
import contextlib, json, sys, time
from uigc_tpu.telemetry import profile
prof = profile.WakeProfiler("child", annotate=lambda name, **args: contextlib.nullcontext(),
                            watch_period_s=0.01, stall_threshold_s=0.05)
prof.start()
print("ready", flush=True)
sys.stdin.readline()
print(json.dumps(prof.to_json()["stalls"]), flush=True)
prof.close()
"""


def test_a_stopped_process_is_a_stall_in_which_nobody_ran():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = subprocess.Popen(
        [sys.executable, "-c", _CHILD], cwd=root, text=True,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    try:
        assert child.stdout.readline().strip() == "ready"
        time.sleep(0.1)
        child.send_signal(signal.SIGSTOP)
        time.sleep(0.5)
        child.send_signal(signal.SIGCONT)
        time.sleep(0.1)
        child.stdin.write("\n")
        child.stdin.flush()
        stalls = json.loads(child.stdout.readline())
        assert child.wait(30) == 0
    finally:
        if child.poll() is None:
            child.kill()
    stall = _longest(stalls)
    assert 0.3 < stall["late_s"] < 5.0, stall
    # the host's: the process had no CPU across it
    assert stall["process_cpu_s"] < 0.1 * stall["late_s"], stall
    assert stall["wake"] is None and stall["workers_cpu_s"] is None


def test_a_sink_that_raises_does_not_take_the_wake_with_it():
    prof = profile.WakeProfiler("n", annotate=FakeAnnotations())
    seen = []

    def sink(record):
        seen.append(record["wake"])
        raise RuntimeError("a full disk")

    profile.record_sink = sink
    try:
        for _ in range(2):
            wake = prof.begin_wake()
            with wake.phase("sweep"):
                assert wake.stack  # the sink is called after the wake, outside every phase
            wake.end(entries=0, garbage=0)
    finally:
        profile.record_sink = None
    assert seen == [0, 1] and [r["wake"] for r in prof.wakes_since(0.0)] == [0, 1]
    assert prof._active is None


def test_telemetry_dump_renders_a_two_wake_document_and_a_sinks_lines(tmp_path, capsys):
    """``tools/telemetry_dump.py --wakes``: the record's way out of a run.
    A ``dump()`` document and the JSON lines a ``record_sink`` wrote give
    the same medians, the phases on and off the CPU, the sweep's wall split
    into who ran, and each stall with its reading."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools"))
    import telemetry_dump

    worker = Spinner(0.03)
    prof, _ = _clocked(lambda: {"workers": [worker.thread.ident]})
    lines = tmp_path / "sink.jsonl"
    with open(lines, "w") as fh:
        profile.record_sink = lambda record: fh.write(json.dumps(record) + "\n")
        try:
            for spin in (0.01, 0.03):
                wake = prof.begin_wake()
                assert not wake.stack
                with wake.phase("device"):
                    with wake.part("device_s"):
                        time.sleep(0.02)
                with wake.phase("sweep"):
                    _spin(spin)
                    worker.spin()
                    wake.note(freed=4, kills=1)
                wake.end(entries=2, garbage=4)
            # a made-up stall of each reading, as the watchdog hands them over
            for cpu, collector in ((0.01, 0.0), (1.9, 1.85)):
                profile.record_sink({
                    "t": 0.0, "at": 12.5, "late_s": 2.0, "process_cpu_s": cpu, "wake": 1,
                    "phase": "sweep", "workers_cpu_s": 0.0, "timer_cpu_s": 0.0,
                    "collector_cpu_s": collector})
            doc = prof.dump(str(tmp_path / "doc.json"))
        finally:
            profile.record_sink = None
            prof.close()
            worker.exit()
    first, second = doc["recent"]
    assert telemetry_dump.main(["--wakes", str(tmp_path / "doc.json"), "--format", "json"]) == 0
    from_doc = json.loads(capsys.readouterr().out)
    assert telemetry_dump.main(["--wakes", str(lines), "--format", "json"]) == 0
    from_lines = json.loads(capsys.readouterr().out)
    assert from_doc["fields"] == from_lines["fields"] and from_doc["sweep"] == from_lines["sweep"]
    assert from_doc["wakes"] == from_doc["called_the_device"] == 2

    def mid(read):
        return (read(first) + read(second)) / 2

    fields = from_doc["fields"]
    assert fields["wall_s"]["all"] == pytest.approx(mid(lambda r: r["wall_s"]))
    assert fields["workers_cpu_sweep_s"]["device"] == pytest.approx(
        mid(lambda r: r["workers_cpu_sweep_s"]))
    assert fields["freed"] == {"all": 4, "device": 4}
    # the phase that slept stood; the sweep: the collector spun 10 and 30 ms,
    # the worker 30 each time, while the collector waited for it
    device, sweep = from_doc["phases"]["device"], from_doc["sweep"]
    assert device["wall_ms"] >= 20 and device["cpu_ms"] < 5 and device["off_cpu_pct"] > 75
    assert device["wall_median_ms"] == pytest.approx(device["wall_ms"])  # of two
    assert from_doc["sweep_typical"] is None  # no full collection: the same wakes
    assert 0 < from_doc["cpu_tick_ms"] <= min(r["cpu_s"] for r in (first, second)) * 1e3
    assert 20 <= sweep["sweep_cpu_ms"] < 35 and 30 <= sweep["sweep_workers_cpu_ms"] < 40
    assert sweep["sweep_ms"] >= 50 and sweep["wakes"] == 2
    assert sweep["sweep_unrun_ms"] == pytest.approx(
        mid(lambda r: r["phases"]["sweep"] - r["phases_cpu"]["sweep"]
            - r["workers_cpu_sweep_s"]) * 1e3)
    assert sweep["gc_in_sweep_ms"] == 0 and from_doc["gc_in_wake_ms"] == 0
    assert 20 < from_doc["wake_off_cpu_pct"] < 95
    assert from_doc["stalls_in_window"] == 0 and from_lines["stalls_in_window"] == 2
    assert [s["reading"] for s in from_lines["stalls"]] == [
        "the host's: nobody ran", "the program's: the collector ran"]
    # and as text: the table and the stall list
    assert telemetry_dump.main(["--wakes", str(lines)]) == 0
    text = capsys.readouterr().out
    assert "wakes 2  called the device 2  stalls 2" in text
    assert "off-CPU %" in text and "collector's CPU" in text and "nobody ran" in text
    # the first wake left out: one wake is left
    assert telemetry_dump.main(["--wakes", str(lines), "--from-wake", "1", "--format", "json"]) == 0
    assert json.loads(capsys.readouterr().out)["wakes"] == 1
    assert telemetry_dump.main(
        ["--wakes", str(lines), "--to-wake", "1", "--format", "json"]) == 0
    assert json.loads(capsys.readouterr().out)["fields"]["wall_s"]["all"] == first["wall_s"]
    assert telemetry_dump.main(["--wakes", str(tmp_path / "none.json")]) == 1


@pytest.mark.parametrize("extra", [{}, {"uigc.telemetry.metrics": True}],
                         ids=["no-telemetry", "metrics-only"])
def test_without_a_profiler_no_cell_is_stamped_no_write_is_timed_nothing_is_called(
        extra, monkeypatch):
    called = []
    monkeypatch.setattr(profile.WakeProfiler, "cell_terminated",
                        lambda self, *a: called.append(a))
    from uigc_tpu.runtime import cell as cell_mod

    stamped = []
    real = cell_mod.ActorCell.note_freed
    monkeypatch.setattr(cell_mod.ActorCell, "note_freed",
                        lambda self, wake: stamped.append(wake) or real(self, wake))
    # the second clock: who of this system's threads reads a thread's CPU time
    clock_reads = []
    for name in ("thread_time", "pthread_getcpuclockid"):
        def counted(*a, _real=getattr(time, name), _name=name):
            clock_reads.append((_name, threading.current_thread().name))
            return _real(*a)
        monkeypatch.setattr(time, name, counted)
    watchdogs = {t for t in threading.enumerate() if t.name == "uigc-stallwatch"}
    callbacks = list(gc.callbacks)
    kit, root, Spawn, Drop, stopped, size = _tree(extra)
    try:
        tel = kit.system.telemetry
        assert (tel is None) == (not extra) and (tel is None or tel.profiler is None)
        assert {t for t in threading.enumerate() if t.name == "uigc-stallwatch"} <= watchdogs
        assert gc.callbacks == callbacks
        engine = kit.system.engine
        plane = engine.packed_plane
        root.tell(Spawn())
        time.sleep(0.3)
        top = next(iter(root.cell.children.values()))
        cells = [top] + list(top.children.values())
        root.tell(Drop())
        assert _wait(lambda: len(stopped) == size)
        assert all(c.is_terminated for c in cells)
    finally:
        kit.shutdown()
    assert not stamped and not called
    assert not [read for read in clock_reads if read[1].startswith("cascade")], clock_reads
    assert all(getattr(c, "_freed_wake", None) is None for c in cells)
    assert not plane.timed and plane.first_write is None and engine.queue_since is None
