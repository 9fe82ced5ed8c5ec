"""Correctness-tooling suite: uigcsan, the race detector, uigc-lint.

Mutation-style acceptance (ISSUE 2): each test seeds a deliberate
invariant break — double-release, dropped recv fact, reordered undo
fold, duplicate frame tally, premature terminate — and asserts uigcsan
flags it, under both the in-process Fabric and the socket NodeFabric.
Clean-run baselines guard against false positives: the sanitizer must
stay silent on a correct system doing the same churn.
"""

import importlib.util
import os
import sys
import threading
import time
from contextlib import contextmanager

import pytest

from uigc_tpu import AbstractBehavior, Behaviors, Message, NoRefs
from uigc_tpu.analysis import RaceDetector, Sanitizer, VectorClock
from uigc_tpu.engines.crgc.state import CrgcContext, CrgcState
from uigc_tpu.engines.engine import TerminationDecision
from uigc_tpu.runtime.fabric import Fabric
from uigc_tpu.runtime.node import NodeFabric
from uigc_tpu.runtime.system import ActorSystem
from uigc_tpu.runtime.testkit import ActorTestKit
from uigc_tpu.utils import events
from uigc_tpu.utils.validation import (
    CapacityError,
    GraphMismatchError,
    InvariantViolation,
    WireFormatError,
    require,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE = {
    "uigc.crgc.wakeup-interval": 10,
    "uigc.crgc.egress-finalize-interval": 5,
    "uigc.analysis.sanitizer": True,
}

FABRIC_KINDS = ["fabric", "node"]


# ------------------------------------------------------------------- #
# Shared actors
# ------------------------------------------------------------------- #


class Ping(NoRefs):
    pass


class Drop(NoRefs):
    pass


class DoubleDrop(NoRefs):
    pass


class Share(Message):
    def __init__(self, ref):
        self.ref = ref

    @property
    def refs(self):
        return (self.ref,) if self.ref is not None else ()


class Worker(AbstractBehavior):
    def on_message(self, msg):
        return self


class Owner(AbstractBehavior):
    """Root owning a worker: pings it locally, shares it to a peer
    root, releases it — once or (seeded mutation) twice."""

    def __init__(self, context, peer_root=None):
        super().__init__(context)
        self.worker = context.spawn(Behaviors.setup(lambda c: Worker(c)), "worker")
        self.peer_root = peer_root

    def on_message(self, msg):
        ctx = self.context
        if isinstance(msg, Ping) and self.worker is not None:
            self.worker.tell(Ping(), ctx)
        elif isinstance(msg, Share) and self.peer_root is not None:
            self.peer_root.tell(
                Share(ctx.create_ref(self.worker, self.peer_root)), ctx
            )
        elif isinstance(msg, Drop) and self.worker is not None:
            ctx.release(self.worker)
            self.worker = None
        elif isinstance(msg, DoubleDrop) and self.worker is not None:
            ctx.release(self.worker)
            ctx.release(self.worker)  # the seeded double release
            self.worker = None
        return self


class Holder(AbstractBehavior):
    """Peer root: receives a shared ref, pings through it, releases."""

    def __init__(self, context):
        super().__init__(context)
        self.held = None

    def on_message(self, msg):
        ctx = self.context
        if isinstance(msg, Share) and msg.ref is not None:
            self.held = msg.ref
        elif isinstance(msg, Ping) and self.held is not None:
            self.held.tell(Ping(), ctx)
        elif isinstance(msg, Drop) and self.held is not None:
            ctx.release(self.held)
            self.held = None
        return self


# ------------------------------------------------------------------- #
# Two-node cluster helper, parametrized over the fabric kind
# ------------------------------------------------------------------- #


class Cluster:
    def __init__(self, kind, names, overrides=None):
        config = dict(BASE)
        config["uigc.crgc.num-nodes"] = len(names)
        if overrides:
            config.update(overrides)
        self.kind = kind
        if kind == "fabric":
            fabric = Fabric()
            self.fabrics = [fabric] * len(names)
            self.systems = [
                ActorSystem(None, name=n, config=config, fabric=fabric)
                for n in names
            ]
        else:
            self.fabrics = [NodeFabric() for _ in names]
            self.systems = [
                ActorSystem(None, name=n, config=config, fabric=f)
                for n, f in zip(names, self.fabrics)
            ]
            ports = [f.listen() for f in self.fabrics]
            for i, fa in enumerate(self.fabrics):
                for j in range(i + 1, len(ports)):
                    fa.connect("127.0.0.1", ports[j])

    def sanitizer(self, idx) -> Sanitizer:
        return self.systems[idx].sanitizer

    def root_ref(self, from_idx, target_idx, raw_ref):
        """A refob usable on system ``from_idx`` naming a root actor on
        system ``target_idx`` (proxy under the node transport)."""
        src = self.systems[from_idx]
        if self.kind == "node":
            cell = self.fabrics[from_idx]._proxy(
                self.systems[target_idx].address, raw_ref.cell.uid
            )
        else:
            cell = raw_ref.cell
        return src.engine.to_root_refob(cell)

    def settle(self, predicate, timeout_s=15.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if predicate():
                return True
            time.sleep(0.05)
        return predicate()

    def terminate(self):
        for system in self.systems:
            try:
                system.terminate(timeout_s=5.0)
            except Exception:
                pass


@contextmanager
def cluster(kind, tag, overrides=None, n=2):
    names = [f"an{tag}{kind[0]}{i}" for i in range(n)]
    c = Cluster(kind, names, overrides)
    try:
        yield c
    finally:
        c.terminate()


def no_nonzero_recv(system):
    graph = system.engine.bookkeeper.shadow_graph
    return graph.investigate_live_set()["nonzero_recv"] == 0


# ------------------------------------------------------------------- #
# Structured validation errors (the de-asserted invariants)
# ------------------------------------------------------------------- #


class _StubSystem:
    address = "uigc://stub"


class _StubCell:
    _uid = 0

    def __init__(self):
        _StubCell._uid += 1
        self.uid = _StubCell._uid
        self.path = f"/stub/{self.uid}"
        self.system = _StubSystem()


def test_capacity_errors_survive_dash_O_and_carry_payload():
    from uigc_tpu.engines.crgc.refob import CrgcRefob

    context = CrgcContext(delta_graph_size=8, entry_field_size=1)
    cell = _StubCell()
    ref = CrgcRefob(cell)
    state = CrgcState(ref, context)
    state.record_new_refob(ref, ref)
    with pytest.raises(CapacityError) as exc:
        state.record_new_refob(ref, ref)
    assert exc.value.rule == "state.capacity"
    assert exc.value.payload["field"] == "created"
    assert exc.value.payload["capacity"] == 1


def test_delta_serialize_desync_is_structured():
    from uigc_tpu.engines.crgc.delta import DeltaGraph

    graph = DeltaGraph("uigc://stub", CrgcContext(8, 2))
    graph._encode(_StubCell())
    graph.compression_table[_StubCell()] = 7  # desync on purpose
    with pytest.raises(WireFormatError) as exc:
        graph.serialize(lambda cell: b"x")
    assert exc.value.rule == "delta.table_desync"
    assert exc.value.payload["table_size"] == 2
    assert exc.value.payload["shadow_count"] == 1


def test_shadow_assert_equals_reports_mismatching_entries():
    from uigc_tpu.engines.crgc.refob import CrgcRefob
    from uigc_tpu.engines.crgc.shadow import ShadowGraph
    from uigc_tpu.engines.crgc.state import Entry

    context = CrgcContext(8, 2)
    cell = _StubCell()
    entry = Entry(context)
    entry.self_ref = CrgcRefob(cell)
    entry.recv_count = 3
    a, b = ShadowGraph(context, "uigc://a"), ShadowGraph(context, "uigc://b")
    a.merge_entry(entry)
    entry.recv_count = 5
    b.merge_entry(entry)
    with pytest.raises(GraphMismatchError) as exc:
        a.assert_equals(b)
    assert exc.value.rule == "graph.mismatch"
    mismatch = exc.value.payload["mismatches"][0]
    assert mismatch["fields"]["recv_count"] == (3, 5)


def test_require_helper():
    require(True, "x.y", "fine")
    with pytest.raises(InvariantViolation) as exc:
        require(False, "x.y", "broken", a=1)
    assert exc.value.payload == {"a": 1}


# ------------------------------------------------------------------- #
# EventRecorder: exception isolation, thread safety, seq stamping
# ------------------------------------------------------------------- #


def test_event_listener_exceptions_are_isolated(capsys):
    rec = events.EventRecorder()
    rec.enable()
    seen = []

    def bad(name, fields):
        raise RuntimeError("listener boom")

    rec.add_listener(bad)
    rec.add_listener(lambda name, fields: seen.append((name, fields)))
    rec.commit("x.y", value=1)  # must not raise
    # The surviving listener saw the original event plus the structured
    # telemetry.listener_error the broken listener produced.
    names = [name for name, _ in seen]
    assert names.count("x.y") == 1
    assert names.count(events.LISTENER_ERROR) == 1
    snap = rec.snapshot()
    assert snap["counts"]["x.y"] == 1
    # Two errors were really raised: one on "x.y" and one on the error
    # event itself (the reentrancy guard counts the second silently
    # instead of recursing).
    assert snap["counts"][events.LISTENER_ERROR] == 2
    assert "listener boom" in capsys.readouterr().err


def test_event_commit_stamps_monotone_seq():
    rec = events.EventRecorder()
    rec.enable()
    seqs = []
    rec.add_listener(lambda name, fields: seqs.append(fields["seq"]))
    for _ in range(5):
        rec.commit("x.y")
    assert seqs == sorted(seqs) and len(set(seqs)) == 5


def test_event_listener_mutation_during_concurrent_commits():
    rec = events.EventRecorder()
    rec.enable()
    stop = threading.Event()
    errors = []

    def committer():
        while not stop.is_set():
            rec.commit("x.y")

    threads = [threading.Thread(target=committer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            fn = lambda name, fields: None  # noqa: E731
            rec.add_listener(fn)
            rec.remove_listener(fn)
    except Exception as exc:  # pragma: no cover
        errors.append(exc)
    finally:
        stop.set()
        for t in threads:
            t.join(5)
    assert not errors


# ------------------------------------------------------------------- #
# Vector clocks and the race detector
# ------------------------------------------------------------------- #


def test_vector_clock_ordering():
    a, b = VectorClock(), VectorClock()
    a.tick("t1")
    b.join(a)
    b.tick("t2")
    assert a.happened_before(b)
    assert not b.happened_before(a)
    c = VectorClock()
    c.tick("t3")
    assert a.concurrent_with(c)
    assert not a.concurrent_with(b)


def _ev(seq, name, **fields):
    fields["seq"] = seq
    return name, fields


def test_race_detector_flags_overlapping_batches():
    stream = [
        _ev(1, events.SCHED_BATCH_START, cell=1, path="/a", thread="t1"),
        _ev(2, events.SCHED_BATCH_START, cell=1, path="/a", thread="t2"),
        _ev(3, events.SCHED_BATCH_END, cell=1, path="/a", thread="t1"),
        _ev(4, events.SCHED_BATCH_END, cell=1, path="/a", thread="t2"),
    ]
    violations = RaceDetector().feed(stream).analyze()
    assert [v.rule for v in violations] == ["sched.overlap"]
    assert violations[0].payload["vc_concurrent"] is True


def test_race_detector_flags_app_before_pending_sys():
    stream = [
        _ev(1, events.SCHED_ENQUEUE, cell=1, path="/a", kind="sys", thread="t9"),
        _ev(2, events.SCHED_ENQUEUE, cell=1, path="/a", kind="app", thread="t9"),
        _ev(3, events.SCHED_BATCH_START, cell=1, path="/a", thread="t1"),
        # Mutated scheduler: app invoked while the earlier sys pends.
        _ev(4, events.SCHED_INVOKE, cell=1, path="/a", kind="app", thread="t1"),
        _ev(5, events.SCHED_INVOKE, cell=1, path="/a", kind="sys", thread="t1"),
        _ev(6, events.SCHED_BATCH_END, cell=1, path="/a", thread="t1"),
    ]
    violations = RaceDetector().feed(stream).analyze()
    assert [v.rule for v in violations] == ["sched.sys_after_app"]
    assert violations[0].payload["pending_sys_seqs"] == [1]


def test_race_detector_accepts_correct_sys_first_order():
    stream = [
        _ev(1, events.SCHED_ENQUEUE, cell=1, path="/a", kind="sys", thread="t9"),
        _ev(2, events.SCHED_ENQUEUE, cell=1, path="/a", kind="app", thread="t9"),
        _ev(3, events.SCHED_BATCH_START, cell=1, path="/a", thread="t1"),
        _ev(4, events.SCHED_INVOKE, cell=1, path="/a", kind="sys", thread="t1"),
        _ev(5, events.SCHED_INVOKE, cell=1, path="/a", kind="app", thread="t1"),
        _ev(6, events.SCHED_BATCH_END, cell=1, path="/a", thread="t1"),
        # A sys message landing mid-batch is NOT a violation.
        _ev(7, events.SCHED_BATCH_START, cell=1, path="/a", thread="t2"),
        _ev(8, events.SCHED_ENQUEUE, cell=1, path="/a", kind="sys", thread="t9"),
        _ev(9, events.SCHED_INVOKE, cell=1, path="/a", kind="app", thread="t2"),
        _ev(10, events.SCHED_BATCH_END, cell=1, path="/a", thread="t2"),
    ]
    assert RaceDetector().feed(stream).analyze() == []


def test_race_detector_flags_poststop_before_children():
    stream = [
        _ev(1, events.SCHED_SPAWN, cell=2, path="/a/kid", parent=1, thread="t1"),
        _ev(2, events.SCHED_POSTSTOP, cell=1, path="/a", thread="t1"),
        _ev(3, events.SCHED_TERMINATED, cell=2, path="/a/kid", thread="t1"),
    ]
    violations = RaceDetector().feed(stream).analyze()
    assert [v.rule for v in violations] == ["sched.poststop_before_children"]
    assert violations[0].payload["live_children"] == ["/a/kid"]


def test_race_detector_clean_on_real_run():
    """A live system with scheduling taps on: the detector must find no
    violations (the false-positive guard for the event instrumentation)."""
    events.recorder.enable()
    detector = RaceDetector().attach()
    try:
        kit = ActorTestKit(
            {
                "uigc.crgc.wakeup-interval": 10,
                "uigc.analysis.sched-events": True,
            }
        )
        try:
            owner = kit.spawn(
                Behaviors.setup_root(lambda c: Owner(c)), "owner"
            )
            for _ in range(30):
                owner.tell(Ping())
            time.sleep(0.3)
            owner.tell(Drop())
            time.sleep(0.5)
        finally:
            kit.shutdown()
    finally:
        detector.detach()
        events.recorder.disable()
        events.recorder.reset()
    assert detector.event_count() > 50
    violations = detector.analyze()
    assert violations == [], [str(v) for v in violations]


# ------------------------------------------------------------------- #
# uigcsan: clean baselines (false-positive guards)
# ------------------------------------------------------------------- #


def test_sanitizer_clean_single_system():
    kit = ActorTestKit(dict(BASE))
    san = kit.system.sanitizer
    try:
        owner = kit.spawn(Behaviors.setup_root(lambda c: Owner(c)), "owner")
        for _ in range(20):
            owner.tell(Ping())
        time.sleep(0.3)
        owner.tell(Drop())
        time.sleep(0.5)
        assert san.checks > 0
        assert san.violations == [], san.report()
        assert san.check_quiescent() == [], san.report()
    finally:
        kit.shutdown()


@pytest.mark.parametrize("backend", ["decremental", "mesh-decremental"])
def test_mutation_backend_keeps_garbage_alive(backend):
    """Every trace is ``trace()``, and the mirror holds each one's verdict
    to the oracle's, on the device backends too: marks that keep a
    released actor alive (the seeded fault) are a ``verdict.mismatch`` on
    that very trace, and the same system with its own marks was clean."""
    from uigc_tpu.ops.trace import FLAG_IN_USE

    kit = ActorTestKit(dict(BASE, **{"uigc.crgc.shadow-graph": backend}))
    san = kit.system.sanitizer
    try:
        graph = kit.system.engine.bookkeeper.shadow_graph  # the mirror
        owner = kit.spawn(Behaviors.setup_root(lambda c: Owner(c)), "owner")
        for _ in range(20):
            owner.tell(Ping())

        def settle(predicate, timeout_s=60.0):  # the first wake compiles
            deadline = time.monotonic() + timeout_s
            while not predicate() and time.monotonic() < deadline:
                time.sleep(0.05)

        settle(lambda: san.checks > 0)
        assert san.checks > 0 and graph.device_wakes >= san.checks
        assert san.violations == [], san.report()
        graph.compute_marks = lambda: (graph.flags & FLAG_IN_USE) != 0
        owner.tell(Drop())
        settle(lambda: san.by_rule("verdict.mismatch"))
        violation = san.by_rule("verdict.mismatch")[0]
        assert violation.payload["engine_garbage"] == 0
        assert violation.payload["oracle_garbage"] >= 1
    finally:
        kit.shutdown()


def test_sanitizer_tap_only_for_mac():
    kit = ActorTestKit(
        {"uigc.engine": "mac", "uigc.analysis.sanitizer": True}
    )
    san = kit.system.sanitizer
    try:
        assert san is not None and san.oracle is None
        owner = kit.spawn(Behaviors.setup_root(lambda c: Owner(c)), "owner")
        for _ in range(10):
            owner.tell(Ping())
        time.sleep(0.3)
        assert san.violations == [], san.report()
        assert san.check_quiescent() == []
        assert san.report()["tap"]["sends"] >= 10
    finally:
        kit.shutdown()


@pytest.mark.parametrize("kind", FABRIC_KINDS)
def test_sanitizer_clean_two_nodes(kind):
    with cluster(kind, "cl") as c:
        a, b = c.systems
        holder = a.spawn_root(Behaviors.setup_root(lambda ctx: Holder(ctx)), "holder")
        owner = b.spawn_root(
            Behaviors.setup_root(
                lambda ctx: Owner(ctx, peer_root=c.root_ref(1, 0, holder))
            ),
            "owner",
        )
        owner.tell(Share(None))
        time.sleep(0.3)
        for _ in range(15):
            holder.tell(Ping())
            time.sleep(0.005)
        holder.tell(Drop())
        owner.tell(Drop())
        assert c.settle(
            lambda: no_nonzero_recv(a) and no_nonzero_recv(b)
        ), "balances never converged — workload itself is broken"
        for i in (0, 1):
            san = c.sanitizer(i)
            assert san.checks > 0
            assert san.violations == [], san.report()
            assert san.check_quiescent() == [], san.report()


# ------------------------------------------------------------------- #
# uigcsan: the five seeded invariant mutations, on both fabrics
# ------------------------------------------------------------------- #


@pytest.mark.parametrize("kind", FABRIC_KINDS)
def test_mutation_double_release(kind):
    """Seeded break #1: a refob released twice in one batch."""
    with cluster(kind, "dr") as c:
        b = c.systems[1]
        owner = b.spawn_root(Behaviors.setup_root(lambda ctx: Owner(ctx)), "owner")
        owner.tell(Ping())
        time.sleep(0.2)
        owner.tell(DoubleDrop())
        assert c.settle(lambda: c.sanitizer(1).by_rule("release.double"))
        violation = c.sanitizer(1).by_rule("release.double")[0]
        assert violation.payload["target"].endswith("/worker")


@pytest.mark.parametrize("kind", FABRIC_KINDS)
def test_mutation_dropped_recv_fact(kind):
    """Seeded break #2: one receive fact silently lost at the worker —
    the folded balance can never return to zero, and the tap ground
    truth proves the facts (not the traffic) are wrong."""
    orig = CrgcState.record_message_received
    dropped = {"done": False}

    def mutated(self):
        if not dropped["done"] and self.self_ref.target.path.endswith("/worker"):
            dropped["done"] = True
            return
        orig(self)

    CrgcState.record_message_received = mutated
    try:
        with cluster(kind, "dv") as c:
            b = c.systems[1]
            owner = b.spawn_root(
                Behaviors.setup_root(lambda ctx: Owner(ctx)), "owner"
            )
            for _ in range(10):
                owner.tell(Ping())
                time.sleep(0.005)
            time.sleep(0.5)
            san = c.sanitizer(1)
            assert c.settle(
                lambda: bool(san.check_quiescent()), timeout_s=5.0
            )
            violation = san.by_rule("balance.nonzero_recv")[0]
            assert violation.payload["balance"] == -1
            assert violation.payload["tap_recvs"] == violation.payload["tap_sends"]
    finally:
        CrgcState.record_message_received = orig


@pytest.mark.parametrize("kind", FABRIC_KINDS)
def test_mutation_reordered_undo_fold(kind):
    """Seeded break #3: the collector folds a peer's undo log on every
    ingress entry — before the finalization quorum, and repeatedly."""
    with cluster(kind, "uf") as c:
        a, b = c.systems
        bookkeeper = b.engine.bookkeeper
        orig_merge = bookkeeper.merge_ingress_entry

        def mutated(entry):
            orig_merge(entry)
            log = bookkeeper.undo_logs.get(entry.egress_address)
            if log is not None:
                bookkeeper.shadow_graph.merge_undo_log(log)

        bookkeeper.merge_ingress_entry = mutated
        holder = a.spawn_root(Behaviors.setup_root(lambda ctx: Holder(ctx)), "holder")
        owner = b.spawn_root(
            Behaviors.setup_root(
                lambda ctx: Owner(ctx, peer_root=c.root_ref(1, 0, holder))
            ),
            "owner",
        )
        owner.tell(Share(None))
        for _ in range(10):
            holder.tell(Ping())
            time.sleep(0.005)
        san = c.sanitizer(1)
        assert c.settle(lambda: san.by_rule("undo.premature_fold"))
        assert c.settle(lambda: san.by_rule("undo.double_fold"))
        violation = san.by_rule("undo.premature_fold")[0]
        assert b.address in violation.payload["missing"]


@pytest.mark.parametrize("kind", FABRIC_KINDS)
def test_mutation_duplicate_frame_tally(kind):
    """Seeded break #4: one inbound app frame is tallied and delivered
    twice (a broken dedup layer) — the receiver's balance stays one
    receive ahead of the sender's claims forever."""
    with cluster(kind, "df") as c:
        a, b = c.systems
        state = {"duplicated": False}
        if kind == "fabric":
            fabric = c.fabrics[0]
            orig_deliver = fabric._deliver_now

            def mutated(link, target, payload):
                orig_deliver(link, target, payload)
                if not state["duplicated"] and link.dst is b:
                    state["duplicated"] = True
                    orig_deliver(link, target, payload)

            fabric._deliver_now = mutated
        else:
            # App frames are delivered in per-cell runs since the
            # batched transport (runtime/node.py _deliver_app_run) —
            # inject the duplicate tally at that seam.
            node_fabric = c.fabrics[1]
            orig_run = node_fabric._deliver_app_run

            def mutated(from_address, uid, frames):
                orig_run(from_address, uid, frames)
                if not state["duplicated"] and frames:
                    state["duplicated"] = True
                    orig_run(from_address, uid, frames)

            node_fabric._deliver_app_run = mutated

        holder = a.spawn_root(Behaviors.setup_root(lambda ctx: Holder(ctx)), "holder")
        owner = b.spawn_root(
            Behaviors.setup_root(
                lambda ctx: Owner(ctx, peer_root=c.root_ref(1, 0, holder))
            ),
            "owner",
        )
        owner.tell(Share(None))
        time.sleep(0.3)
        for _ in range(10):
            holder.tell(Ping())
            time.sleep(0.005)
        time.sleep(0.6)
        assert state["duplicated"], "mutation never fired"
        san = c.sanitizer(1)
        assert c.settle(lambda: bool(san.check_quiescent()), timeout_s=5.0)
        assert san.by_rule("balance.nonzero_recv"), san.report()


@pytest.mark.parametrize("kind", FABRIC_KINDS)
def test_mutation_premature_terminate(kind):
    """Seeded break #5: the engine decides a live, referenced worker
    SHOULD_STOP — the oracle still proves it reachable."""
    with cluster(kind, "pt") as c:
        b = c.systems[1]
        owner = b.spawn_root(Behaviors.setup_root(lambda ctx: Owner(ctx)), "owner")
        for _ in range(5):
            owner.tell(Ping())
        time.sleep(0.3)  # the worker is interned and provably live now

        from uigc_tpu.engines.crgc.messages import AppMsg

        engine = b.engine
        orig_on_idle = engine.on_idle

        def mutated(msg, state, ctx):
            if isinstance(msg, AppMsg) and ctx.cell.path.endswith("/worker"):
                return TerminationDecision.SHOULD_STOP
            return orig_on_idle(msg, state, ctx)

        engine.on_idle = mutated
        owner.tell(Ping())
        san = c.sanitizer(1)
        assert c.settle(lambda: san.by_rule("terminate.premature"))
        violation = san.by_rule("terminate.premature")[0]
        assert violation.payload["actor"].endswith("/worker")


# ------------------------------------------------------------------- #
# uigc-lint
# ------------------------------------------------------------------- #


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "uigc_lint", os.path.join(ROOT, "tools", "uigc_lint.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def lint():
    return _load_lint()


BAD_ACTOR_SRC = '''
import time
from uigc_tpu import AbstractBehavior, Behaviors, Message, NoRefs


class CarriesRef(NoRefs):
    def __init__(self, worker_ref):
        self.worker_ref = worker_ref


class HidesRef(Message):
    def __init__(self, worker_ref):
        self.worker_ref = worker_ref

    @property
    def refs(self):
        return ()


class Sloppy(AbstractBehavior):
    def __init__(self, context, friend_ref):
        super().__init__(context)
        self.friend_ref = friend_ref

    def on_message(self, msg):
        time.sleep(1.0)
        child = self.context.spawn(
            Behaviors.setup(lambda ctx: Sloppy(ctx, self.friend_ref)), "kid"
        )
        assert child is not None
        return self
'''

LOCK_ORDER_A = """
import threading

class A:
    def __init__(self):
        self.send_lock = threading.Lock()
        self.recv_lock = threading.Lock()

    def forward(self):
        with self.send_lock:
            with self.recv_lock:
                pass
"""

LOCK_ORDER_B = """
import threading

class B:
    def __init__(self):
        self.send_lock = threading.Lock()
        self.recv_lock = threading.Lock()

    def backward(self):
        with self.recv_lock:
            with self.send_lock:
                pass
"""


def test_lint_catches_each_rule(lint, tmp_path):
    bad = tmp_path / "bad_actor.py"
    bad.write_text(BAD_ACTOR_SRC)
    (tmp_path / "lock_a.py").write_text(LOCK_ORDER_A)
    (tmp_path / "lock_b.py").write_text(LOCK_ORDER_B)
    violations = lint.lint_paths([str(tmp_path)])
    rules = {v.rule for v in violations}
    assert {"UL001", "UL002", "UL003", "UL004", "UL005"} <= rules, sorted(
        v.render() for v in violations
    )
    # UL002 fires for both the NoRefs-with-ref and the empty-refs shapes.
    ul2 = [v for v in violations if v.rule == "UL002"]
    assert len(ul2) >= 2


def test_lint_suppression_comment(lint, tmp_path):
    src = (
        "class W:\n"
        "    def on_message(self, msg):\n"
        "        import time\n"
        "        time.sleep(1)  # uigc-lint: disable=UL003\n"
        "        assert msg  # uigc-lint: disable=all\n"
        "        return self\n"
    )
    f = tmp_path / "suppressed.py"
    f.write_text(src)
    violations = lint.lint_paths([str(f)])
    assert violations == [], [v.render() for v in violations]


def test_lint_allowlist_budget(lint, tmp_path):
    f = tmp_path / "legacy.py"
    f.write_text("def run(x):\n    assert x\n    assert x\n")
    violations = lint.lint_paths([str(f)])
    assert len(violations) == 2
    key = str(f).replace(os.sep, "/")
    grandfathered, fresh = lint.apply_allowlist(violations, {(key, "UL004"): 1})
    assert len(grandfathered) == 1 and len(fresh) == 1


def test_lint_ignores_test_trees_for_asserts(lint, tmp_path):
    tests_dir = tmp_path / "tests"
    tests_dir.mkdir()
    (tests_dir / "test_thing.py").write_text("def test_x():\n    assert 1\n")
    assert lint.lint_paths([str(tests_dir)]) == []


def test_lint_strict_clean_on_repo(lint):
    """The verify-path gate: the repo's own package must lint clean
    under --strict (grandfathered budget allowed)."""
    rc = lint.main(["--strict", os.path.join(ROOT, "uigc_tpu")])
    assert rc == 0
