"""Soundness/completeness stress: random spawn/link/release/ping churn.

Analogue of the reference's RandomSpec (reference:
src/test/scala/edu/illinois/osl/uigc/RandomSpec.scala:14-125): spawn
MAX_ACTORS actors in a random topology (including cycles), then wait for
the GC to collect every one of them.  Unsound GC kills live actors (dead
letters / lost countdowns); incomplete GC times out.
"""

import os
import random
import threading
import time

from uigc_tpu import AbstractBehavior, ActorTestKit, Behaviors, Message, NoRefs, PostStop

MAX_ACTORS = int(os.environ.get("UIGC_RANDOM_SPEC_ACTORS", "10000"))
CONFIG = {"uigc.crgc.wakeup-interval": 20}


class Link(Message):
    def __init__(self, ref):
        self.ref = ref

    @property
    def refs(self):
        return (self.ref,)


class Ping(NoRefs):
    pass


class Latch:
    """CountDownLatch analogue."""

    def __init__(self, count):
        self._count = count
        self._cond = threading.Condition()

    def count_down(self):
        with self._cond:
            self._count -= 1
            if self._count <= 0:
                self._cond.notify_all()

    def await_zero(self, timeout_s):
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while self._count > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return self._count
                self._cond.wait(remaining)
            return 0


class Shared:
    def __init__(self):
        self.spawn_counter = 0
        self.lock = threading.Lock()
        self.latch = Latch(MAX_ACTORS)
        self.rng = random.Random(20260729)

    def try_reserve_spawn(self):
        with self.lock:
            self.spawn_counter += 1
            return self.spawn_counter <= MAX_ACTORS

    def reached_max(self):
        with self.lock:
            return self.spawn_counter >= MAX_ACTORS

    def rand(self):
        with self.lock:
            return self.rng.random()

    def randint(self, n):
        with self.lock:
            return self.rng.randrange(n)


class RandomActor(AbstractBehavior):
    def __init__(self, context, shared, timers):
        super().__init__(context)
        self.shared = shared
        self.timers = timers
        self.acquaintances = []

    def on_message(self, msg):
        if isinstance(msg, Link):
            self.acquaintances.append(msg.ref)
            self.do_some_actions()
        elif isinstance(msg, Ping):
            self.do_some_actions()
        return self

    def do_some_actions(self):
        if self.shared.reached_max():
            if self.timers is not None:
                # Root: stop the churn and release everything so the whole
                # population becomes garbage.
                self.timers.cancel_all()
                if self.acquaintances:
                    self.context.release(self.acquaintances)
                    self.acquaintances = []
            return
        self.do_something()
        self.do_something()

    def do_something(self):
        ctx = self.context
        shared = self.shared
        p = shared.rand()
        if p < 0.2:
            if shared.try_reserve_spawn():
                self.acquaintances.append(
                    ctx.spawn_anonymous(random_actor_factory(shared))
                )
        elif p < 0.4 and self.acquaintances:
            owner = self.acquaintances[shared.randint(len(self.acquaintances))]
            target = self.acquaintances[shared.randint(len(self.acquaintances))]
            owner.tell(Link(ctx.create_ref(target, owner)), ctx)
        elif p < 0.6 and self.acquaintances:
            i = shared.randint(len(self.acquaintances))
            actor = self.acquaintances.pop(i)
            ctx.release(actor)
        elif p < 0.8 and self.acquaintances:
            self.acquaintances[shared.randint(len(self.acquaintances))].tell(
                Ping(), ctx
            )

    def on_signal(self, signal):
        if signal is PostStop:
            if self.timers is None:  # root doesn't count
                self.shared.latch.count_down()
        return None


def random_actor_factory(shared):
    return Behaviors.setup(lambda ctx: RandomActor(ctx, shared, None))


import pytest


@pytest.mark.parametrize("backend", ["array", "decremental", "mesh-decremental"])
def test_random_churn_fully_collected(backend):
    """Unsound GC kills live actors; incomplete GC times out.  The
    decremental variants must detect every released subgraph (incl.
    cycles) by regional repair, never by luck of a full re-trace."""
    shared = Shared()
    kit = ActorTestKit(dict(CONFIG, **{"uigc.crgc.shadow-graph": backend}))
    try:
        def make_root(timers):
            def setup(ctx):
                timers.start_timer_at_fixed_rate("ping", Ping(), 0.001)
                return RandomActor(ctx, shared, timers)

            return Behaviors.setup_root(setup)

        kit.spawn(Behaviors.with_timers(make_root), "root")
        remaining = shared.latch.await_zero(timeout_s=300.0)
        assert remaining == 0, (
            f"{remaining} of {MAX_ACTORS} actors were never collected "
            "(GC incomplete)"
        )
    finally:
        kit.shutdown()

