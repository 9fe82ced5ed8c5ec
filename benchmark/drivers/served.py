"""Driver ``served``: the runtime as users touch it.

``ActorSystem`` -> CRGC engine -> Bookkeeper -> the shadow-graph backend
the configuration names, with the ``uigc.*`` keys of the configuration
file.  Set-up builds the resident set, which stays referenced for the
whole run: a tree of ``resident.actors`` actors (fanout
``resident.fanout``) under one root, ``ping_pairs`` pairs of actors that
hold references to each other, and one owner actor per session slot.

Traffic (``traffic/<mix>.json``), from one process and one load thread:

- sessions, closed loop, ``sessions_in_flight`` at a time: an owner
  spawns a subtree of ``session_actors`` actors (every constructor runs
  inside ``spawn``), sends its top one message, and releases it; the next
  session of that slot starts when the last ``PostStop`` of this one has
  arrived.  ``stop_ms`` is the host clock from just before
  ``context.release`` to that last ``PostStop``.  The shape is Savina's
  Fork-Join Create (create, use once, drop).
- pings, open loop, ``pings_per_s``, round robin over the pairs: the load
  thread kicks a pair's first actor when the ping is due, it sends
  ``Ping`` to its peer, the peer answers ``Pong``; ``app_rtt_ms`` runs
  from when the ping was DUE to the ``Pong``'s arrival.  ``ping_late_ms``
  says how late the generator itself ran.

A session or ping begun inside the window and finished inside the grace
period after it still gives its sample (a tail is the tail of all
requests); one not finished by then is failed.  ``stopped`` counts the
``PostStop`` signals that arrived inside the window.

The tree node and the latch idea are copied from
``uigc_tpu/models/workloads.py:64-91`` (``_tree_node``) and ``:27-47``
(``_Latch``); nothing is imported from there.

``correct`` is by construction (released sessions are garbage, residents
are live), every limit 0: sessions not stopped, session actors without
exactly one ``PostStop``, ``PostStop`` among residents, sampled residents
that do not answer a probe after the window, pings unanswered, a dead
Bookkeeper cell, a trace that is not the compiled kernel, no device wake.
The control (``--control``) has the resident root release one of its
subtrees in mid-window without telling the check: to the check that is
a collector that reaps live actors.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List

import numpy as np

from harness.report import exact as exact_check

from uigc_tpu.interfaces import Message, NoRefs
from uigc_tpu.runtime.behaviors import AbstractBehavior, Behaviors
from uigc_tpu.runtime.signals import PostStop
from uigc_tpu.runtime.system import ActorSystem


class _Start(NoRefs):
    def __init__(self, session):
        self.session = session


class _Use(NoRefs):
    pass


class _ReleaseOne(NoRefs):
    pass


class _Probe(NoRefs):
    def __init__(self, path, idx):
        self.path = path
        self.idx = idx


class _Kick(NoRefs):
    def __init__(self, seq):
        self.seq = seq


class _Ping(NoRefs):
    def __init__(self, seq):
        self.seq = seq


class _Pong(NoRefs):
    def __init__(self, seq):
        self.seq = seq


class _Share(Message):
    def __init__(self, ref):
        self.ref = ref

    @property
    def refs(self):
        return (self.ref,)


def _shares(size: int, fanout: int) -> List[int]:
    """Sizes of the subtrees under a node of a ``size``-actor tree."""
    remaining = size - 1
    k = min(fanout, remaining)
    return [remaining // k + (1 if i < remaining % k else 0) for i in range(k)] if k else []


class _Session:
    """One released subtree: who has stopped, and when the last did."""

    __slots__ = ("sid", "slot", "size", "stops", "left", "built", "t_release", "t_done", "lock")

    def __init__(self, sid: int, slot: int, size: int):
        self.sid, self.slot, self.size = sid, slot, size
        self.stops = [0] * size
        self.left = size
        self.built = 0
        self.t_release = self.t_done = None
        self.lock = threading.Lock()


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.obs = ctx.obs
        self.attempted = 0
        self.failed = 0
        self.system = None
        self._lock = threading.Lock()

    # ----------------------------------------------------------------- #
    # behaviours
    # ----------------------------------------------------------------- #

    def _session_node(self, session: _Session, size: int, fanout: int):
        driver = self

        class SessionNode(AbstractBehavior):
            def __init__(self, context):
                super().__init__(context)
                self.idx = session.built
                session.built += 1
                self.children = [
                    context.spawn(driver._session_node(session, share, fanout), f"c{i}")
                    for i, share in enumerate(_shares(size, fanout))
                ]

            def on_message(self, msg):
                return self

            def on_signal(self, signal):
                if signal is PostStop:
                    driver._session_actor_stopped(session, self.idx)
                return None

        return Behaviors.setup(SessionNode)

    def _resident_node(self, size: int, fanout: int, path: tuple, root: bool = False):
        driver = self

        class ResidentNode(AbstractBehavior):
            def __init__(self, context):
                super().__init__(context)
                driver.resident_paths.append(path)
                self.released = None  # the control's released child, by index
                self.children = [
                    context.spawn(driver._resident_node(share, fanout, path + (i,)), f"c{i}")
                    for i, share in enumerate(_shares(size, fanout))
                ]

            def on_message(self, msg):
                if isinstance(msg, _Probe):
                    if not msg.path:
                        driver._probe_answered(msg.idx)
                    elif msg.path[0] != self.released:
                        self.children[msg.path[0]].tell(
                            _Probe(msg.path[1:], msg.idx), self.context
                        )
                elif isinstance(msg, _ReleaseOne) and self.children:
                    self.released = len(self.children) - 1
                    self.context.release(self.children[self.released])
                return self

            def on_signal(self, signal):
                if signal is PostStop:
                    driver.resident_stops += 1
                return None

        return (Behaviors.setup_root if root else Behaviors.setup)(ResidentNode)

    def _owner(self):
        driver = self

        class Owner(AbstractBehavior):
            def on_message(self, msg):
                if isinstance(msg, _Start):
                    s, tr = msg.session, driver.ctx.traffic
                    top = self.context.spawn(
                        driver._session_node(s, s.size, int(tr["session_fanout"])), f"s{s.sid}"
                    )
                    top.tell(_Use(), self.context)
                    s.t_release = time.perf_counter()
                    self.context.release(top)
                return self

        return Behaviors.setup_root(Owner)

    def _pinger(self):
        driver = self

        class Ponger(AbstractBehavior):
            def __init__(self, context):
                super().__init__(context)
                self.back = None

            def on_message(self, msg):
                if isinstance(msg, _Share):
                    self.back = msg.ref
                elif isinstance(msg, _Ping):
                    self.back.tell(_Pong(msg.seq), self.context)
                return self

            def on_signal(self, signal):
                if signal is PostStop:
                    driver.resident_stops += 1
                return None

        class Pinger(AbstractBehavior):
            def __init__(self, context):
                super().__init__(context)
                self.peer = context.spawn(Behaviors.setup(Ponger), "peer")
                self.peer.tell(_Share(context.create_ref(context.self, self.peer)), context)

            def on_message(self, msg):
                if isinstance(msg, _Kick):
                    self.peer.tell(_Ping(msg.seq), self.context)
                elif isinstance(msg, _Pong):
                    driver._pong(msg.seq)
                return self

            def on_signal(self, signal):
                if signal is PostStop:
                    driver.resident_stops += 1
                return None

        return Behaviors.setup_root(Pinger)

    # ----------------------------------------------------------------- #
    # callbacks from dispatcher threads
    # ----------------------------------------------------------------- #

    def _session_actor_stopped(self, s: _Session, idx: int) -> None:
        now = time.perf_counter()
        with s.lock:
            s.stops[idx] += 1
            s.left -= 1
            last = s.left == 0
        if self.in_window:
            self.obs.count("stopped")
        if last:
            s.t_done = now
            with self._lock:
                self.done.append(s)
                start_next = self.in_window
            if start_next:
                self._start_session(s.slot)

    def _start_session(self, slot: int) -> None:
        with self._lock:
            s = _Session(self.next_sid, slot, int(self.ctx.traffic["session_actors"]))
            self.next_sid += 1
            self.sessions.append(s)
        self.owners[slot].tell(_Start(s))

    def _pong(self, seq: int) -> None:
        self.pong_at[seq] = time.perf_counter()

    def _probe_answered(self, idx: int) -> None:
        with self._lock:
            self.probe_answers.add(idx)

    # ----------------------------------------------------------------- #
    # set-up
    # ----------------------------------------------------------------- #

    def setup(self) -> None:
        ctx, cfg, tr = self.ctx, self.ctx.config, self.ctx.traffic
        self.resident_paths: List[tuple] = []
        self.resident_stops = 0
        self.sessions: List[_Session] = []
        self.done: List[_Session] = []
        self.next_sid = 0
        self.in_window = False
        self.probe_answers = set()
        self.pong_at: Dict[int, float] = {}
        self._gc_t0 = None
        self._gc_pauses: List[tuple] = []
        gc.callbacks.append(self._on_gc)

        config = dict(cfg["uigc"])
        if ctx.traced:
            # per-layer numbers come from the traced run only; the
            # attached profiler also switches the backend to its
            # with_stats programs (collector.py:396-400)
            config["uigc.telemetry.wake-profile"] = True
        t0 = time.perf_counter()
        self.system = ActorSystem(None, name="bench", config=config)
        res = cfg["resident"]
        self.resident_root = self.system.spawn_root(
            self._resident_node(int(res["actors"]), int(res["fanout"]), (), root=True), "resident"
        )
        ctx.phase("resident tree", time.perf_counter() - t0,
                  f"actors={len(self.resident_paths)} fanout={res['fanout']}")
        t0 = time.perf_counter()
        self.pingers = [
            self.system.spawn_root(self._pinger(), f"pair{i}") for i in range(int(cfg["ping_pairs"]))
        ]
        self.owners = [
            self.system.spawn_root(self._owner(), f"owner{i}")
            for i in range(int(tr["sessions_in_flight"]))
        ]
        ctx.phase("pairs and owners", time.perf_counter() - t0,
                  f"pairs={len(self.pingers)} owners={len(self.owners)}")

        # warm-up: the same sessions and pings as the window's, until the
        # collector has folded the resident set, compiled its programs
        # for this capacity and stopped a few rounds of sessions
        t0 = time.perf_counter()
        self._drive(0.0, warm=True)
        graph = self.system.engine.bookkeeper.shadow_graph
        ctx.phase("warm-up (sessions and pings as in the window)", time.perf_counter() - t0,
                  f"sessions stopped={len(self.done)} device wakes={graph.device_wakes} "
                  f"impl={graph.trace_impl}")
        if not self.done:
            raise SystemExit("no session was collected during the warm-up")

    # ----------------------------------------------------------------- #
    # load
    # ----------------------------------------------------------------- #

    def _on_gc(self, phase: str, info: dict) -> None:
        """Python's own collector stops every thread: its pauses explain
        stalls that no layer of the program shows (``py_gc_ms``).

        The interpreter calls this on whatever thread crosses its
        threshold, between any two bytecodes, also inside a thread that
        holds ``obs``'s lock: so it takes no lock and only appends to a
        list of the driver's own, which ``_drive`` hands over afterwards."""
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            if self.in_window:
                self._gc_pauses.append(
                    (info.get("generation"), (time.perf_counter() - self._gc_t0) * 1e3))
            self._gc_t0 = None

    def _tick_while_in_window(self) -> None:
        """Ticks for the profiler; and, once a second, the collector wakes
        the program's own profiler has recorded since (it keeps the last
        256 only, and a backlog of empty timer wakes flushes them)."""
        tel = self.system.telemetry
        profiler = tel.profiler if tel is not None else None
        last, polled = time.time(), time.perf_counter()
        while self.in_window:
            self.obs.tick()
            if profiler is not None and time.perf_counter() - polled >= 1.0:
                polled = time.perf_counter()
                new = profiler.wakes_since(last)
                if new:
                    last = new[-1]["t"]
                    self.program_wakes.extend(new)
            time.sleep(0.05)
        self.obs.tick()

    def _drive(self, seconds: float, warm: bool) -> None:
        """Sessions in flight plus the open loop of pings for ``seconds``,
        then the grace period for what is still in flight."""
        tr, obs = self.ctx.traffic, self.obs
        with self._lock:
            self.sessions, self.done = [], []
            self.pong_at = {}
        period = 1.0 / float(tr["pings_per_s"])
        self.program_wakes = []
        self._gc_pauses = []
        self.in_window = True
        # the profiler starts and stops on a thread of its own (seconds of
        # host work each), not on the one that has pings to send on time
        ticker = threading.Thread(target=self._tick_while_in_window, daemon=True)
        ticker.start()
        t0 = time.perf_counter()
        for slot in range(len(self.owners)):
            self._start_session(slot)
        due_at, late = [], []
        control_at = t0 + seconds / 2 if (self.ctx.control and not warm) else None
        k = 0
        while True:
            due = t0 + k * period
            if warm:
                # until the collector has caught up with the resident
                # set and stopped some rounds of sessions, then `seconds`
                if len(self.done) < int(tr["warmup_sessions"]):
                    seconds = due - t0 + float(tr["warmup_s"])
                    if due - t0 > float(tr["warmup_max_s"]):
                        raise SystemExit(
                            f"warm-up: {len(self.done)} sessions collected in "
                            f"{due - t0:.0f}s, wanted {tr['warmup_sessions']}")
            if due - t0 >= seconds:
                break
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent = time.perf_counter()
            self.pingers[k % len(self.pingers)].tell(_Kick(k))
            due_at.append(due)
            late.append((sent - due) * 1e3)
            if control_at is not None and sent >= control_at:
                self.resident_root.tell(_ReleaseOne())
                control_at = None
            k += 1
        wait = t0 + seconds - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        self.in_window = False
        if not warm:
            obs.close_window()
        ticker.join()

        # grace: what began inside the window may still finish
        deadline = time.perf_counter() + float(tr["grace_s"])
        while time.perf_counter() < deadline:
            with self._lock:
                open_sessions = len(self.sessions) - len(self.done)
            if open_sessions == 0 and len(self.pong_at) == len(due_at):
                break
            time.sleep(0.01)
        if warm:
            return
        with self._lock:
            sessions, done = list(self.sessions), list(self.done)
        for s in done:
            obs.late_sample("stop_ms", (s.t_done - s.t_release) * 1e3)
        answered = 0
        for seq, due in enumerate(due_at):
            at = self.pong_at.get(seq)
            if at is not None:
                answered += 1
                obs.late_sample("app_rtt_ms", (at - due) * 1e3)
        for ms in late:
            obs.late_sample("ping_late_ms", ms)
        for generation, ms in list(self._gc_pauses):
            obs.late_sample(f"py_gc_gen{generation}_ms", ms)
        self.window_sessions = sessions
        self.pings_unanswered = len(due_at) - answered
        self.attempted = len(sessions) + len(due_at)
        self.failed = (len(sessions) - len(done)) + self.pings_unanswered

    def window(self, seconds: float) -> None:
        self._drive(seconds, warm=False)
        if self.program_wakes:
            wakes = self.obs.facts["program_wakes"] = self.program_wakes
            self.ctx.say(f"served: {len(wakes)} collector wakes read from the program's profiler, "
                         f"{sum(1 for r in wakes if r['device_s'] > 0)} called the device")

    # ----------------------------------------------------------------- #
    # correct
    # ----------------------------------------------------------------- #

    def check(self) -> List[Dict[str, object]]:
        ctx, tr = self.ctx, self.ctx.traffic
        out = []

        def exact(name, value):
            out.append(exact_check(name, value))

        sessions = self.window_sessions
        exact("sessions_not_stopped", sum(1 for s in sessions if s.left > 0))
        exact("session_actors_without_exactly_one_poststop",
              sum(1 for s in sessions if s.left == 0 for c in s.stops if c != 1)
              + sum(1 for s in sessions if s.left > 0 for c in s.stops if c > 1))
        exact("pings_unanswered", self.pings_unanswered)

        # a sample of the residents, drawn from the seed, must still answer
        rng = np.random.default_rng([ctx.seed, 13])
        n = len(self.resident_paths)
        sample = rng.choice(n, size=min(int(tr["probe_residents"]), n), replace=False).tolist()
        for idx in sample:
            self.resident_root.tell(_Probe(self.resident_paths[idx], idx))
        deadline = time.perf_counter() + float(tr["grace_s"])
        while time.perf_counter() < deadline and len(self.probe_answers) < len(sample):
            time.sleep(0.01)
        exact(f"residents_not_answering_of_{len(sample)}", len(sample) - len(self.probe_answers))
        exact("resident_poststops", self.resident_stops)

        engine = self.system.engine
        graph = engine.bookkeeper.shadow_graph
        want = "pallas-interpret" if ctx.rehearse else "pallas"
        exact("bookkeeper_cell_dead", 0 if engine.bookkeeper_cell.is_active else 1)
        exact(f"trace_impl_is_not_{want}__it_is_{graph.trace_impl}", 0 if graph.trace_impl == want else 1)
        exact("no_device_wake", 0 if graph.device_wakes > 0 else 1)
        ctx.say(f"served: sessions {len(sessions)}, device wakes {graph.device_wakes}, "
                f"impl {graph.trace_impl}, live actors {self.system.live_actor_count}")
        return out

    def close(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if self.system is not None:
            self.system.terminate(timeout_s=float(self.ctx.traffic.get("terminate_s", 5.0)))
