"""``setup_s``: everything before the window (imports, generate, pack,
compile or cache load, upload, warm-up), on the host clock."""


def read(obs):
    return obs.facts.get("setup_s")
