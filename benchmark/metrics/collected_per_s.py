"""``collected_per_s``: garbage actors detected (or stopped) in the
window, over the whole window."""


def read(obs):
    return obs.counter("collected") / obs.window_s
