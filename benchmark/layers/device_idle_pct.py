"""``device_idle_pct``: 1 - (union of the device's operation intervals)
/ (traced interval), from the ``.xplane.pb``."""


def read(obs):
    trace = obs.trace
    if trace is None or not trace.n_devices or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
