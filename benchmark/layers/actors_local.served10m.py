"""``actors_local.served10m``: slots of the shadow graph held by local actors (actors with a cell) after a wake's sweep: ``len(ArrayShadowGraph.slot_of)``, which ``slot_for`` and the sweep's frees keep, noted on the wake's ``WakeProfiler`` record by ``_sweep``.
Median over the window's wakes that called the device, from ``obs.facts["program_wakes"]``; nothing on a program whose
records carry no such count.  The node's residents, the sessions in flight, and the stopped actors whose last flush
interned them once more and who wait for the next sweep."""

from harness.program_trace import device_wakes, percentile


def read(obs):
    values = [r["actors_local"] for r in device_wakes(obs) if "actors_local" in r]
    return percentile(values, 50) if values else None
