"""``actors_foreign.served10m``: slots of the shadow graph held by foreign actors (actors known by uid alone) after a wake's sweep: ``ArrayShadowGraph.actors_foreign``, a running count (up in ``_slots_for_foreign``, down where the sweep frees them), noted on the wake's ``WakeProfiler`` record by ``_sweep``.
Median over the window's wakes that called the device, from ``obs.facts["program_wakes"]``; nothing on a program whose
records carry no such count.  The residents hold still: it reads the generator's live partition, every wake."""

from harness.program_trace import device_wakes, percentile


def read(obs):
    values = [r["actors_foreign"] for r in device_wakes(obs) if "actors_foreign" in r]
    return percentile(values, 50) if values else None
