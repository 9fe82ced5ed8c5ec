"""The deep chain (``models/graphgen.py chain_actor_graph``): a graph as
deep as it is long, where a push fixpoint needs a sweep per hop and the
pointer jump has to win.  Through ``DecrementalTracer`` in the modes
that jump, and through the served runtime on ``shadow-graph:
decremental``."""

import math

import numpy as np
import pytest

from uigc_tpu import AbstractBehavior, ActorTestKit, Behaviors, NoRefs, PostStop
from uigc_tpu.models.graphgen import chain_actor_graph
from uigc_tpu.ops import pallas_decremental as pd
from uigc_tpu.ops import pallas_trace as pt
from uigc_tpu.ops import trace as F

N = 4096


def test_chain_graph_shape():
    g = chain_actor_graph(N)
    n_live = g["n_live"]
    assert (n_live, g["n_garbage"]) == (N // 2, N // 2)
    assert np.flatnonzero(g["flags"] & F.FLAG_ROOT).tolist() == [0]
    sup = g["supervisor"]
    # every actor supervised by the one before it; the ring's head by slot 0
    assert sup[0] == -1 and sup[n_live] == 0
    others = np.setdiff1d(np.arange(1, N), [n_live])
    assert np.array_equal(sup[others], others - 1)
    src, dst = g["edge_src"], g["edge_dst"]
    assert src.size == (n_live - 1) + g["n_garbage"] and (g["edge_weight"] == 1).all()
    # one reference down each hop of the chain, the ring closed on its
    # head, and no reference from the chain into the ring (released)
    live = src < n_live
    assert np.array_equal(dst[live], src[live] + 1) and dst[live].max() == n_live - 1
    assert np.array_equal(dst[~live], np.where(src[~live] == N - 1, n_live, src[~live] + 1))
    assert np.array_equal(g["expected_garbage"], np.arange(N) >= n_live)
    # degenerate sizes still make a graph
    assert chain_actor_graph(1)["edge_src"].size == 0
    assert chain_actor_graph(8, garbage_fraction=0.0)["n_garbage"] == 0


@pytest.mark.parametrize("mode", [pt.MODE_AUTO, pt.MODE_JUMP])
def test_chain_through_the_tracer(mode):
    g = chain_actor_graph(N)
    oracle = F.trace_marks_np(
        g["flags"], g["recv_count"], g["supervisor"],
        g["edge_src"], g["edge_dst"], g["edge_weight"],
    )
    in_use = (g["flags"] & F.FLAG_IN_USE) != 0
    assert np.array_equal(in_use & ~oracle, g["expected_garbage"])
    tracer = pd.DecrementalTracer(N, mode=mode)
    assert tracer.jump_price is None  # no wake staged yet
    tracer.rebuild(g["edge_src"], g["edge_dst"], g["edge_weight"], g["supervisor"])
    for wake in range(2):
        if wake:
            tracer.invalidate()
        marks = tracer.marks(g["flags"], g["recv_count"])
        assert np.array_equal(marks, oracle), wake
    price = tracer.jump_price
    assert price >= 1
    stats = tracer.wake_stats()
    assert len(stats) == 2 and stats[0] == stats[1]
    w = stats[0]
    assert w["closure_sweeps"] == 0  # from nothing: no suspects
    assert w["jump_sweeps"] > 0
    # push alone would take n_live - 1 = 2047 sweeps
    waited = price if mode == pt.MODE_AUTO else 0
    assert w["n_sweeps"] <= waited + math.log2(N) / 2 + 3
    if mode == pt.MODE_AUTO:
        # the policy's carry at exit: it engaged because spent reached the
        # price, after `price` one-chunk sweeps
        assert w["jump_spent"] >= price
        assert w["jump_on"][:price] == [0] * price and all(w["jump_on"][price:])
    else:
        assert w["jump_spent"] == 0 and w["jump_sweeps"] == w["n_sweeps"]


def test_simulated_closure_of_a_supervised_chain_swallows_it():
    """``tools/sweep_profile.py simulate_sweeps`` with the wake's closure
    policy: one suspect in mid-chain closes over every mark (forward by
    the references, back by the supervisors), a hop a sweep, and each
    mode leaves the closure at its own price, a share of its own
    derivation."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        from sweep_profile import simulate_sweeps
    finally:
        sys.path.pop(0)
    g = chain_actor_graph(N)
    n_live = g["n_live"]
    modes = [pt.MODE_PUSH, pt.MODE_AUTO, pt.MODE_JUMP]
    # one walk chunk over all actors; slots enough for a price of a few sweeps
    sim = simulate_sweeps(g, N, modes, geometry=(8 * N, 1, N), suspects=[n_live // 2])
    assert sim[pt.MODE_PUSH]["sweeps"] == n_live  # a hop a sweep, and the one that finds no more
    assert sim[pt.MODE_JUMP]["sweeps"] <= math.log2(N) / 2 + 3
    assert sim[pt.MODE_AUTO]["sweeps"] <= sim[pt.MODE_AUTO]["price"] + math.log2(N) / 2 + 3
    for mode in modes:
        c, walks = sim[mode]["closure"], sum(sim[mode]["dirty_chunks"])
        assert walks == sim[mode]["sweeps"]  # one chunk: a walk a sweep
        assert c["marks"] == c["sizes"][-1] == n_live  # the closure is every mark
        assert c["full_sweeps"] == n_live // 2 + 1  # the longer way round, and the last look
        assert c["price"] == pt.closure_price(walks)
        assert c["bailed"] and c["sweeps"] == c["spent"] == c["price"]
    assert sim[pt.MODE_PUSH]["closure"]["price"] == n_live // 8
    # a suspect in the released ring was never marked: nothing to close over
    sim = simulate_sweeps(g, N, [pt.MODE_JUMP], geometry=(8 * N, 1, N), suspects=[n_live + 5])
    assert sim[pt.MODE_JUMP]["closure"]["sweeps"] == 0 and not sim[pt.MODE_JUMP]["closure"]["bailed"]


class Spawned(NoRefs):
    def __init__(self, name):
        self.name = name


class Stopped(NoRefs):
    def __init__(self, name):
        self.name = name


class Drop(NoRefs):
    pass


class Link(AbstractBehavior):
    """One hop of the chain: spawns, supervises and holds the next."""

    def __init__(self, context, probe, index, length):
        super().__init__(context)
        self.probe = probe
        self.index = index
        self.next = None
        if index + 1 < length:
            self.next = context.spawn(
                Behaviors.setup(lambda c: Link(c, probe, index + 1, length)), f"link{index + 1}"
            )
        probe.ref.tell(Spawned(index))

    def on_message(self, msg):
        return self

    def on_signal(self, signal):
        if signal is PostStop:
            self.probe.ref.tell(Stopped(self.index))
        return None


class Head(AbstractBehavior):
    def __init__(self, context, probe, length):
        super().__init__(context)
        self.head = context.spawn(
            Behaviors.setup(lambda c: Link(c, probe, 0, length)), "link0"
        )

    def on_message(self, msg):
        if isinstance(msg, Drop) and self.head is not None:
            self.context.release(self.head)
            self.head = None
        return self


def test_served_chain_is_collected_whole_after_the_release():
    length = 64
    kit = ActorTestKit(
        {"uigc.crgc.wakeup-interval": 10, "uigc.crgc.shadow-graph": "decremental"}
    )
    try:
        probe = kit.create_test_probe(timeout_s=120.0)
        root = kit.spawn(Behaviors.setup_root(lambda ctx: Head(ctx, probe, length)), "root")
        names = {probe.expect_message_type(Spawned).name for _ in range(length)}
        assert names == set(range(length))
        # the root holds the head: nothing stops, however many wakes run
        probe.expect_no_message(0.5)
        root.tell(Drop())
        stopped = [probe.expect_message_type(Stopped).name for _ in range(length)]
        assert sorted(stopped) == list(range(length))  # each exactly once
        probe.expect_no_message(0.3)
        # the verdicts came from the wake program, not from a host trace
        assert kit.system.engine.bookkeeper.shadow_graph.trace_impl == "pallas-interpret"
    finally:
        kit.shutdown()
