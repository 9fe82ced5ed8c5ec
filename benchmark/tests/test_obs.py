"""Recording is safe from a hook that the interpreter runs wherever it
likes.  The check of PR 26 lost a run of the served cell to this: the
``gc.callbacks`` hook recorded its pause through ``Obs.sample`` while the
thread it ran on was inside ``Obs.count``, waited for the lock it held
itself, and every dispatcher thread queued up behind it."""

import threading
import types

from harness import cell as cells
from harness.obs import Obs


def _finishes(fn, timeout=5.0):
    t = threading.Thread(target=fn, daemon=True)
    t.start()
    t.join(timeout)
    return not t.is_alive()


def test_recording_from_inside_a_recording_does_not_wait_for_itself():
    obs = Obs()
    obs.open_window()

    def nested():
        with obs._lock:  # as if a hook ran in the middle of count()
            obs.sample("hook_ms", 1.0)
            obs.count("stopped")

    assert _finishes(nested)
    assert obs.series("hook_ms") == [1.0] and obs.counter("stopped") == 1


def test_the_served_drivers_gc_hook_takes_no_lock():
    obs = Obs()
    obs.open_window()
    driver = cells.load_driver("served").Driver(types.SimpleNamespace(obs=obs))
    driver.in_window, driver._gc_t0, driver._gc_pauses = True, None, []
    held, release = threading.Event(), threading.Event()

    def holder():
        with obs._lock:
            held.set()
            release.wait(20)

    threading.Thread(target=holder, daemon=True).start()
    assert held.wait(5)
    try:
        def hook():
            driver._on_gc("start", {"generation": 2})
            driver._on_gc("stop", {"generation": 2})

        assert _finishes(hook), "the hook waited for obs's lock"
    finally:
        release.set()
    assert [g for g, _ in driver._gc_pauses] == [2]
    assert obs.samples == {}, "the pauses reach obs after the window, from the load thread"
