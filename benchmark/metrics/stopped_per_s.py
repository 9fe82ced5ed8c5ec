"""``stopped_per_s``: released actors whose ``PostStop`` arrived inside the
window, over the whole window."""


def read(obs):
    return obs.counter("stopped") / obs.window_s
