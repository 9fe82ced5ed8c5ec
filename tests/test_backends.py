"""Run the live actor runtime against every shadow-graph backend.

The oracle is the reference-exact pointer graph; "array" folds into dense
numpy arrays; "decremental" additionally runs the trace on the device, as
the decremental wake (interpreted here); the mesh backends shard it.
All must produce identical lifecycle behavior.
"""

import pytest

from uigc_tpu import AbstractBehavior, ActorTestKit, Behaviors, Message, NoRefs, PostStop


class Share(Message):
    def __init__(self, ref):
        self.ref = ref

    @property
    def refs(self):
        return (self.ref,)


class Drop(NoRefs):
    pass


class Spawned(NoRefs):
    def __init__(self, name):
        self.name = name


class Stopped(NoRefs):
    def __init__(self, name):
        self.name = name


class Node(AbstractBehavior):
    def __init__(self, context, probe):
        super().__init__(context)
        self.probe = probe
        self.peer = None
        probe.ref.tell(Spawned(context.name))

    def on_message(self, msg):
        if isinstance(msg, Share):
            self.peer = msg.ref
        return self

    def on_signal(self, signal):
        if signal is PostStop:
            self.probe.ref.tell(Stopped(self.context.name))
        return None


class Root(AbstractBehavior):
    def __init__(self, context, probe):
        super().__init__(context)
        ctx = context
        self.a = ctx.spawn(Behaviors.setup(lambda c: Node(c, probe)), "a")
        self.b = ctx.spawn(Behaviors.setup(lambda c: Node(c, probe)), "b")
        # Mutual cycle a <-> b.
        self.a.tell(Share(ctx.create_ref(self.b, self.a)), ctx)
        self.b.tell(Share(ctx.create_ref(self.a, self.b)), ctx)

    def on_message(self, msg):
        if isinstance(msg, Drop):
            self.context.release(self.a, self.b)
        return self


from conftest import NATIVE_BACKEND


@pytest.mark.parametrize(
    "backend",
    [
        "oracle", "array", "mesh", "decremental", "mesh-decremental",
        NATIVE_BACKEND,
    ],
)
def test_cycle_collection_all_backends(backend):
    kit = ActorTestKit(
        {"uigc.crgc.wakeup-interval": 10, "uigc.crgc.shadow-graph": backend}
    )
    try:
        probe = kit.create_test_probe(timeout_s=30.0)
        root = kit.spawn(Behaviors.setup_root(lambda ctx: Root(ctx, probe)), "root")
        probe.expect_message_type(Spawned)
        probe.expect_message_type(Spawned)
        probe.expect_no_message(0.2)  # cycle alive while root holds refs
        root.tell(Drop())
        probe.expect_message_type(Stopped)
        probe.expect_message_type(Stopped)
    finally:
        kit.shutdown()


def test_device_backend_name_is_refused():
    """``shadow-graph: device`` (the full re-trace, gone with PR 31) is an
    unknown name like any other: the error names the valid ones."""
    from uigc_tpu.engines.crgc.engine import SHADOW_GRAPHS

    assert "device" not in SHADOW_GRAPHS and "decremental" in SHADOW_GRAPHS
    with pytest.raises(ValueError) as err:
        ActorTestKit({"uigc.crgc.shadow-graph": "device"})
    assert "'device'" in str(err.value)
    for name in SHADOW_GRAPHS:
        assert name in str(err.value)


def test_the_pipelined_road_left_no_handle_and_its_key_is_no_key():
    """The pipelined wake (gone with PR 48) left one road from a fold to
    a sweep, ``trace()``: no backend and not the sanitizer's mirror has
    a second, and ``uigc.crgc.pipelined`` is no key.  A configuration
    that still sets it builds as before (overrides are not checked
    against the defaults) and sets what nothing reads."""
    from uigc_tpu.analysis.sanitizer import _MirrorGraph
    from uigc_tpu.config import DEFAULTS, Config
    from uigc_tpu.engines.crgc import mesh
    from uigc_tpu.engines.crgc.arrays import ArrayShadowGraph

    gone = (
        "launch_trace", "harvest_trace", "harvest_ready", "expire_stalled_wake",
        "can_pipeline", "has_pending_wake", "_pending_wake", "_start_wake",
    )
    for owner in (ArrayShadowGraph, mesh.MeshShadowGraph, _MirrorGraph):
        assert [name for name in gone if name in dir(owner)] == []
        assert "trace" in dir(owner)
    assert not hasattr(mesh, "_MeshWakeHandle")
    assert "uigc.crgc.pipelined" not in DEFAULTS
    with pytest.raises(KeyError):
        Config().get("uigc.crgc.pipelined")
    kit = ActorTestKit({"uigc.crgc.pipelined": False, "uigc.crgc.shadow-graph": "decremental"})
    try:
        assert not hasattr(kit.system.engine, "pipelined")
        assert kit.system.engine.bookkeeper.shadow_graph.use_device
    finally:
        kit.shutdown()


class LoneRoot(AbstractBehavior):
    """A root that spawns workers, never releases them, then stops itself."""

    def __init__(self, context, probe):
        super().__init__(context)
        self.probe = probe
        self.kids = [
            context.spawn(Behaviors.setup(lambda c: Node(c, probe)), f"w{i}")
            for i in range(3)
        ]

    def on_message(self, msg):
        if isinstance(msg, Drop):
            return Behaviors.stopped()
        return self


def test_dead_root_does_not_leak_referents():
    """A stopped root must not pin its referents forever: its death flush
    clears root status, so the workers (and the root's zombie shadow)
    collapse on the next trace."""
    kit = ActorTestKit({"uigc.crgc.wakeup-interval": 10})
    try:
        probe = kit.create_test_probe(timeout_s=10.0)
        root = kit.spawn(
            Behaviors.setup_root(lambda ctx: LoneRoot(ctx, probe)), "root"
        )
        probe.expect_message_type(Spawned)
        probe.expect_message_type(Spawned)
        probe.expect_message_type(Spawned)
        root.tell(Drop())
        # Workers are children of the root, so the runtime cascade stops
        # them; the regression here is the SHADOW side: the collector must
        # also conclude they are garbage (root flag cleared), not keep
        # zombie pseudoroots. All three must report stopping.
        probe.expect_message_type(Stopped)
        probe.expect_message_type(Stopped)
        probe.expect_message_type(Stopped)
        import time

        time.sleep(0.2)  # let a few collection rounds run
        graph = kit.system.engine.bookkeeper.shadow_graph
        assert graph.num_in_use <= 1, (
            f"{graph.num_in_use} zombie shadows left after root death"
        )
    finally:
        kit.shutdown()


@pytest.mark.parametrize("backend", ["decremental", "mesh-decremental"])
def test_a_wakeup_with_nothing_folded_makes_no_device_call(backend):
    """A collector wake has one shape, drain -> fold -> ``trace()``, and
    the trace is skipped where nothing was folded since the last one: the
    timer's wake-ups on a quiet system dispatch no wake and answer no
    sink, and the first fold after them does both."""
    import time

    kit = ActorTestKit(
        {"uigc.crgc.wakeup-interval": 10, "uigc.crgc.shadow-graph": backend}
    )
    try:
        keeper = kit.system.engine.bookkeeper
        graph = keeper.shadow_graph
        answers, wakeups = [], []
        graph.foreign_sink = lambda kills, freed: answers.append(1)
        collect = keeper.collect
        keeper.collect = lambda trace=True: wakeups.append(trace) or collect(trace)
        probe = kit.create_test_probe(timeout_s=60.0)
        root = kit.spawn(Behaviors.setup_root(lambda ctx: Root(ctx, probe)), "root")
        probe.expect_message_type(Spawned)
        probe.expect_message_type(Spawned)

        def quiet():
            # the last flush is folded and traced: no entry for 10 wake-ups
            seen, folded = len(wakeups), keeper.total_entries
            time.sleep(0.15)
            return len(wakeups) >= seen + 10 and keeper.total_entries == folded

        assert any(quiet() for _ in range(40)), "the system never went quiet"
        assert not keeper._graph_dirty
        seen = len(wakeups)
        wakes, calls, folded = graph.device_wakes, len(answers), keeper.total_entries
        assert wakes >= 1 and calls == wakes  # one answer a trace
        time.sleep(0.3)
        assert len(wakeups) >= seen + 10 and all(wakeups[seen:])
        assert keeper.total_entries == folded
        assert (graph.device_wakes, len(answers)) == (wakes, calls)
        root.tell(Drop())
        probe.expect_message_type(Stopped)
        probe.expect_message_type(Stopped)
        assert graph.device_wakes > wakes
        deadline = time.monotonic() + 10  # the sink hears after the StopMsgs
        while len(answers) == calls and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(answers) > calls
    finally:
        kit.shutdown()
