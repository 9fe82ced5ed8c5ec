"""Driver ``served_mac``: the served runtime on the MAC engine, its cycle
detector on the chip.

One node.  ``ActorSystem`` -> MAC engine (weighted reference counts on the
actors, ``uigc_tpu/engines/mac/engine.py``) -> ``CycleDetector`` on its own
``uigc.mac.wakeup-interval`` timer (``engines/mac/detector.py``) -> the
shadow-graph backend ``uigc.mac.shadow-graph`` names, with the ``uigc.*``
keys of the configuration file.  Under MAC acyclic garbage is collected by
the counts alone, on the actors, with no collector and no device; rings
are the part that needs the detector, so everything here is rings.

Set-up, each step a ``set-up`` line:

- the program must read ``uigc.mac.shadow-graph``; without the key the run
  exits non-zero before anything is built (the parent of the PR that added
  it fails in seconds, and before that: it has no such driver file);
- ``engine_fold.keep_the_heap()``, before any thread of the system exists;
- the residents: ``resident.supervisors`` root actors share
  ``resident.rings`` rings of ``resident.ring_size`` members.  A supervisor
  spawns a ring's members (every constructor runs inside ``spawn``), hands
  each its successor's reference (``create_ref`` and one ``_Share``), then
  releases its own references to all but member 0.  So one weight keeps a
  ring: member 0 is a seed of the detector's trace and the others are live
  only because a mark went round the ring;
- ``ping_pairs`` pairs that hold each other and one owner per session slot,
  ``drivers/served.py``'s (loaded and subclassed; its load loop, its
  samples and its control's timing are used unchanged);
- the warm-up, sessions and pings as in the window, until the detector has
  folded the residents, compiled its programs at this capacity and stopped
  ``warmup_sessions`` sessions;
- ``gc.collect(); gc.freeze()``, as ``served_fold`` ends its set-up.

Traffic (``traffic/<mix>.json``; keys read: ``sessions_in_flight``,
``session_actors``, ``use_hops``, ``pings_per_s``, ``warmup_sessions``,
``warmup_s``, ``warmup_max_s``, ``grace_s``, ``probe_residents``,
``trace_seconds``, ``terminate_s``):

- ring sessions, closed loop, ``sessions_in_flight`` at a time: an owner
  spawns ``session_actors`` actors, wires them into one ring, sends member
  0 one message that is passed on ``use_hops`` times (once round), and
  releases all its references.  ``stop_ms`` is the host clock from just
  before that release to the last ``PostStop`` of the session; the slot's
  next session starts then.  Nothing of a session stops without the
  detector: every member stays held by its predecessor.
- pings, open loop, ``pings_per_s`` over the pairs, as ``served.py``: each
  is a ``UNB`` and a ``BLK`` at two residents, rows that free nothing.

The one thing the driver adds to the program's road is a ``bench:wake``
span around the backend's ``compute_marks`` on the detector's thread
(``served_fold.wake_span``), as ``served_fold.py`` has it.

``correct``, every limit 0: ``served.py``'s numbers by construction
(sessions not stopped, session actors without exactly one ``PostStop``,
pings unanswered, ``PostStop`` among residents, sampled residents that do
not answer: the probe enters a ring at member 0 and is passed on to the
member drawn, so it answers only if the references still hold), sessions
a member of which stopped before the ring's last member had reported the
``use_hops``-th hop (a detector that kills a ring with a message still in
flight), a dead detector cell, a trace that is not the compiled kernel, no
device wake; and the detector's verdicts against ``reference_mac.garbage``
as sets of uids (``CycleDetector.audit``, asked through the detector's
mailbox so that it runs on the detector's thread), twice: in mid-window,
straight after a wake that asked somebody, so with sessions in flight, a
token open and a set ``G`` that is not empty (the set that wake asked
against the reference's on the table with the other tokens pending; from
half time on the first such wake, at most ``AUDIT_TRIES`` looked at), and on
the table the window left at rest.
The control (``--control``) has a supervisor release the one reference it
kept to a resident ring in mid-window without telling the check: to the
check that is a detector that reaps live actors.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List

import numpy as np

import reference_mac
from harness.cell import load_driver
from harness.report import exact as exact_check

served_fold = load_driver("served_fold")
served = served_fold.served

from uigc_tpu.runtime.behaviors import AbstractBehavior, Behaviors
from uigc_tpu.runtime.signals import PostStop

_Share, _Probe, _Start, _ReleaseOne = served._Share, served._Probe, served._Start, served._ReleaseOne

AUDIT_TIMEOUT_S = 60.0
#: tracing wakes the mid-window audit looks at for one that asked somebody
AUDIT_TRIES = 10


class _Use(served.NoRefs):
    def __init__(self, hops: int, session):
        self.hops, self.session = hops, session


class _RingSession(served._Session):
    """``used``: the ring's last member has had the message that went
    round; ``early``: a member stopped before that."""

    __slots__ = ("used", "early")

    def __init__(self, sid: int, slot: int, size: int):
        super().__init__(sid, slot, size)
        self.used = self.early = False


def _wire_ring(context, members) -> None:
    """Hand every member its successor's reference."""
    k = len(members)
    for i, member in enumerate(members):
        member.tell(_Share(context.create_ref(members[(i + 1) % k], member)), context)


class Driver(served.Driver):
    # ----------------------------------------------------------------- #
    # behaviours
    # ----------------------------------------------------------------- #

    def _ring_member(self, on_stop):
        """A worker that holds its successor: passes a ``_Use`` on while it
        has hops left and a ``_Probe`` until its path's one number is 0."""
        driver = self

        class RingMember(AbstractBehavior):
            def __init__(self, context):
                super().__init__(context)
                self.next = None
                self.on_stop = on_stop(self)

            def on_message(self, msg):
                if isinstance(msg, _Share):
                    self.next = msg.ref
                elif isinstance(msg, _Use):
                    if msg.hops > 1:
                        self.next.tell(_Use(msg.hops - 1, msg.session), self.context)
                    else:
                        msg.session.used = True
                elif isinstance(msg, _Probe):
                    if msg.path[0] == 0:
                        driver._probe_answered(msg.idx)
                    else:
                        self.next.tell(_Probe((msg.path[0] - 1,), msg.idx), self.context)
                return self

            def on_signal(self, signal):
                if signal is PostStop:
                    self.on_stop()
                return None

        return Behaviors.setup(RingMember)

    def _resident_stopped(self, _member):
        def stopped():
            self.resident_stops += 1
        return stopped

    def _supervisor(self, first_ring: int, rings: int, ring_size: int):
        driver = self

        class Supervisor(AbstractBehavior):
            def __init__(self, context):
                super().__init__(context)
                self.kept = []  # the reference to each ring's member 0
                self.released = None  # the control's released ring, by index
                member = driver._ring_member(driver._resident_stopped)
                for r in range(rings):
                    members = [context.spawn(member, f"r{first_ring + r}m{i}")
                               for i in range(ring_size)]
                    _wire_ring(context, members)
                    context.release(*members[1:])
                    self.kept.append(members[0])

            def on_message(self, msg):
                if isinstance(msg, _Probe):
                    ring = msg.path[0] - first_ring
                    if ring != self.released:
                        self.kept[ring].tell(_Probe(msg.path[1:], msg.idx), self.context)
                elif isinstance(msg, _ReleaseOne):
                    self.released = len(self.kept) - 1
                    self.context.release(self.kept[self.released])
                return self

        return Behaviors.setup_root(Supervisor)

    def _owner(self):
        driver = self

        class Owner(AbstractBehavior):
            def on_message(self, msg):
                if isinstance(msg, _Start):
                    s, tr = msg.session, driver.ctx.traffic

                    def counted(member):
                        idx = s.built
                        s.built += 1

                        def stopped():
                            if not s.used:
                                s.early = True
                            driver._session_actor_stopped(s, idx)

                        return stopped

                    member = driver._ring_member(counted)
                    members = [self.context.spawn(member, f"s{s.sid}m{i}") for i in range(s.size)]
                    _wire_ring(self.context, members)
                    members[0].tell(_Use(int(tr["use_hops"]), s), self.context)
                    s.t_release = time.perf_counter()
                    self.context.release(*members)
                return self

        return Behaviors.setup_root(Owner)

    def _start_session(self, slot: int) -> None:
        with self._lock:
            s = _RingSession(self.next_sid, slot, int(self.ctx.traffic["session_actors"]))
            self.next_sid += 1
            self.sessions.append(s)
        self.owners[slot].tell(_Start(s))

    # ----------------------------------------------------------------- #
    # set-up
    # ----------------------------------------------------------------- #

    def setup(self) -> None:
        from uigc_tpu import config as program_config
        from uigc_tpu.runtime.system import ActorSystem

        if "uigc.mac.shadow-graph" not in program_config.DEFAULTS:
            raise SystemExit("the program's cycle detector has no shadow-graph backend "
                             "(uigc.mac.shadow-graph): there is no device path to measure")
        from uigc_tpu.engines.mac.detector import Audit

        #: asks the detector for ``CycleDetector.audit`` on its own thread
        self.audit_request = Audit
        ctx, cfg, tr = self.ctx, self.ctx.config, self.ctx.traffic
        ctx.say("served_mac: " + served_fold.fold.keep_the_heap())
        self.resident_stops = 0
        self.sessions: List = []
        self.done: List = []
        self.next_sid = 0
        self.in_window = False
        self.probe_answers = set()
        self.pong_at: Dict[int, float] = {}
        self._gc_t0 = None
        self._gc_pauses: List[tuple] = []
        gc.callbacks.append(self._on_gc)

        config = dict(cfg["uigc"])
        if ctx.traced:
            # per-layer numbers come from the traced run only
            config["uigc.telemetry.wake-profile"] = True
        t0 = time.perf_counter()
        self.system = ActorSystem(None, name="bench", config=config)
        detector = self.detector = self.system.engine.detector
        graph = detector.graph
        compute_marks = graph.compute_marks

        #: wakes the mid-window audit may still look at, 0 outside it
        self.audit_tries = 0
        self.audit_asked = False
        self.mid_audit = None

        def spanned():
            # on the detector's thread, around the device call of a wake
            # that traces; an audit asked for from here is answered
            # straight after this wake, before its ACKs are in (and
            # traces itself: that call asks for none)
            if self.audit_tries and not self.audit_asked:
                self.audit_tries -= 1
                self.audit_asked = True
                self.system.engine.detector_cell.tell(self.audit_request(self._mid_window_audit))
            with served_fold.wake_span(self.obs):
                return compute_marks()

        graph.compute_marks = spanned

        res = cfg["resident"]
        rings, size, n_sup = int(res["rings"]), int(res["ring_size"]), int(res["supervisors"])
        self.ring_size = size
        self.supervisors, self.first_ring = [], []
        first = 0
        for i in range(n_sup):
            share = rings // n_sup + (1 if i < rings % n_sup else 0)
            self.first_ring.append(first)
            self.supervisors.append(
                self.system.spawn_root(self._supervisor(first, share, size), f"sup{i}"))
            first += share
        self.rings = rings
        #: what ``served._drive``'s control tells ``_ReleaseOne``
        self.resident_root = self.supervisors[0]
        ctx.phase("resident rings", time.perf_counter() - t0,
                  f"actors={rings * size + n_sup} rings={rings} of {size} supervisors={n_sup}")
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

        t0 = time.perf_counter()
        self._drive(0.0, warm=True)
        ctx.phase("warm-up (ring sessions and pings as in the window)", time.perf_counter() - t0,
                  f"sessions stopped={len(self.done)} device wakes={graph.device_wakes} "
                  f"impl={graph.trace_impl} candidates={detector.candidates} "
                  f"capacity={graph.capacity}")
        if not self.done:
            raise SystemExit("no session was collected during the warm-up")
        gc.collect()
        gc.freeze()

    # ----------------------------------------------------------------- #
    # correct
    # ----------------------------------------------------------------- #

    def window(self, seconds: float) -> None:
        arm = threading.Timer(seconds / 2, setattr, (self, "audit_tries", AUDIT_TRIES))
        arm.daemon = True
        arm.start()
        super().window(seconds)
        arm.cancel()
        self.audit_tries = 0

    def _mid_window_audit(self, result: tuple) -> None:
        """On the detector's thread.  The one kept is the first of a wake
        that asked somebody, else the last looked at."""
        self.mid_audit = result
        if result[2]:
            self.audit_tries = 0
        self.audit_asked = False

    def _audit(self) -> tuple:
        got, answered = [], threading.Event()

        def reply(result):
            got.append(result)
            answered.set()

        self.system.engine.detector_cell.tell(self.audit_request(reply))
        if not answered.wait(AUDIT_TIMEOUT_S):
            raise RuntimeError(f"the detector did not answer an audit in {AUDIT_TIMEOUT_S:.0f}s")
        return got[0]

    def check(self) -> List[Dict[str, object]]:
        ctx, tr = self.ctx, self.ctx.traffic
        out = []

        def exact(name, value):
            out.append(exact_check(name, value))

        # the detector first: the probes below are traffic
        def held_to_reference(when, audit):
            table, pending, asked, garbage = audit
            # what the last wake asked, found with the other tokens open;
            # and what a trace of the table as it stood left unmarked
            want_asked = reference_mac.garbage(table, pending - asked)
            want = reference_mac.garbage(table, pending)
            exact(f"{when}_asked_differing_from_reference_mac", len(asked ^ want_asked))
            exact(f"{when}_garbage_differing_from_reference_mac", len(garbage ^ want))
            cands = reference_mac.candidates(table, pending)
            seeds = sum(1 for b in reference_mac.balances(table, cands).values() if b != 0)
            ctx.say(f"served_mac: {when}: blocked table {len(table)} actors, {len(cands)} "
                    f"candidates, {seeds} seeds, {len(pending)} in a pending confirmation; "
                    f"asked by the last wake: detector {len(asked)}, reference "
                    f"{len(want_asked)}; garbage: detector {len(garbage)}, reference {len(want)}")

        at_rest = self._audit()
        if self.mid_audit is None:
            exact("no_mid_window_audit", 1)
        else:
            exact("mid_window_audit_of_a_wake_that_asked_nobody", 0 if self.mid_audit[2] else 1)
            held_to_reference("mid_window", self.mid_audit)
        held_to_reference("at_rest", at_rest)

        sessions = self.window_sessions
        exact("sessions_not_stopped", sum(1 for s in sessions if s.left > 0))
        exact("sessions_with_a_stop_before_the_last_hop", sum(1 for s in sessions if s.early))
        exact("session_actors_without_exactly_one_poststop",
              sum(1 for s in sessions if s.left == 0 for c in s.stops if c != 1)
              + sum(1 for s in sessions if s.left > 0 for c in s.stops if c > 1))
        exact("pings_unanswered", self.pings_unanswered)

        # a sample of the ring members, drawn from the seed, must still
        # answer a message that reaches them round their ring
        rng = np.random.default_rng([ctx.seed, 13])
        n = self.rings * self.ring_size
        sample = rng.choice(n, size=min(int(tr["probe_residents"]), n), replace=False).tolist()
        for idx in sample:
            ring, member = divmod(idx, self.ring_size)
            sup = int(np.searchsorted(self.first_ring, ring, side="right")) - 1
            self.supervisors[sup].tell(_Probe((ring, member), idx))
        deadline = time.perf_counter() + float(tr["grace_s"])
        while time.perf_counter() < deadline and len(self.probe_answers) < len(sample):
            time.sleep(0.01)
        exact(f"residents_not_answering_of_{len(sample)}", len(sample) - len(self.probe_answers))
        exact("resident_poststops", self.resident_stops)

        engine = self.system.engine
        graph = self.detector.graph
        want_impl = "pallas-interpret" if ctx.rehearse else "pallas"
        exact("detector_cell_dead", 0 if engine.detector_cell.is_active else 1)
        exact(f"trace_impl_is_not_{want_impl}__it_is_{graph.trace_impl}",
              0 if graph.trace_impl == want_impl else 1)
        exact("no_device_wake", 0 if graph.device_wakes > 0 else 1)
        ctx.say(f"served_mac: sessions {len(sessions)}, device wakes {graph.device_wakes}, "
                f"impl {graph.trace_impl}, capacity {graph.capacity}, garbage sets collected "
                f"{self.detector.total_cycles_collected}, live actors {self.system.live_actor_count}")
        return out

    def close(self) -> None:
        gc.unfreeze()
        super().close()
