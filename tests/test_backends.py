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


def test_pipelined_decremental_collection():
    """uigc.crgc.pipelined: the collector sweeps the previous wake's
    verdicts while the next runs; cyclic garbage still collapses (a
    consistent-snapshot verdict is never wrong — CRGC garbage is
    monotone)."""
    kit = ActorTestKit(
        {
            "uigc.crgc.wakeup-interval": 10,
            "uigc.crgc.shadow-graph": "decremental",
            "uigc.crgc.pipelined": True,
        }
    )
    try:
        probe = kit.create_test_probe(timeout_s=30.0)
        root = kit.spawn(Behaviors.setup_root(lambda ctx: Root(ctx, probe)), "root")
        probe.expect_message_type(Spawned)
        probe.expect_message_type(Spawned)
        probe.expect_no_message(0.2)
        root.tell(Drop())
        probe.expect_message_type(Stopped)
        probe.expect_message_type(Stopped)
    finally:
        kit.shutdown()


def test_pipelined_mesh_decremental_collection():
    """uigc.crgc.pipelined + shadow-graph=mesh-decremental: the mesh
    runs its OWN pipelined wake (launch syncs the shard layouts
    mesh-natively, then dispatches the sharded decremental wake
    asynchronously; the harvest sweeps the launch snapshot's verdicts).
    Cyclic garbage still collapses, and the regression this guards: the
    base-class path through the single-device tracer would have
    desynced the shard layouts."""
    kit = ActorTestKit(
        {
            "uigc.crgc.wakeup-interval": 10,
            "uigc.crgc.shadow-graph": "mesh-decremental",
            "uigc.crgc.pipelined": True,
        }
    )
    try:
        graph = kit.system.engine.bookkeeper.shadow_graph
        assert graph.can_pipeline is True
        probe = kit.create_test_probe(timeout_s=60.0)
        root = kit.spawn(Behaviors.setup_root(lambda ctx: Root(ctx, probe)), "root")
        probe.expect_message_type(Spawned)
        probe.expect_message_type(Spawned)
        root.tell(Drop())
        probe.expect_message_type(Stopped)
        probe.expect_message_type(Stopped)
    finally:
        kit.shutdown()


def test_pipelined_stalled_wake_expires():
    """A wake whose device result never lands must expire (tracer
    invalidated, pipeline freed) instead of deadlocking collection."""
    import time

    from uigc_tpu.engines.crgc.arrays import ArrayShadowGraph
    from uigc_tpu.engines.crgc.state import CrgcContext

    graph = ArrayShadowGraph(
        CrgcContext(delta_graph_size=64, entry_field_size=4),
        "uigc://test",
        use_device=True,
    )

    class NeverReady:
        def is_ready(self):
            return False

    class FakeDec:
        invalidated = False

        def invalidate(self):
            self.invalidated = True

    dec = FakeDec()
    graph._pending_wake = (dec, NeverReady(), None, None, time.monotonic() - 60)
    assert not graph.harvest_ready()
    assert not graph.expire_stalled_wake(max_age_s=120)  # too young
    assert graph.has_pending_wake
    assert graph.expire_stalled_wake(max_age_s=30)
    assert dec.invalidated and not graph.has_pending_wake
