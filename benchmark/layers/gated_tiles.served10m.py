"""``gated_tiles.served10m``: supertiles whose blocks a wake's first repair sweep walked in full: the ones its suspect closure reached (``ops/pallas_decremental.py``: ``gated_tiles``, ``suspect_g.sum()``; 0 on the cold road, after a closure that gave up).
Counted by the wake program itself and left on the device; ``ArrayShadowGraph`` hands the profiler a handle and the wake's
``WakeProfiler`` record gets the number when it is read (``_Wake.defer``).  Median over the window's wakes that called the
device, from ``obs.facts["program_wakes"]``; nothing on a program whose records carry no such counter.  Of 4,096
supertiles at 2^24 slots: what a session's release forces the kernel through, beside 5M residents it should not touch."""

from harness.program_trace import device_wakes, percentile


def read(obs):
    values = [r["gated_tiles"] for r in device_wakes(obs) if "gated_tiles" in r]
    return percentile(values, 50) if values else None
