"""``py_gc_pause_pct.served``: share of the window in which Python's own
cyclic collector had every thread stopped, from the benchmark's
``gc.callbacks`` hook (all generations).  With 100,000 resident actors on
the heap a full collection takes over a second, and it is what the
application's messages wait behind."""


def read(obs):
    pauses = [ms for name in obs.samples if name.startswith("py_gc_gen") for ms in obs.samples[name]]
    if not pauses or not obs.window_s:
        return None
    return 100.0 * sum(pauses) / 1e3 / obs.window_s
