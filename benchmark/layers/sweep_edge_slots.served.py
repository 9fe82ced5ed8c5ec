"""``sweep_edge_slots.served``: edge slots the wake's sweep examined to find the edges that hang on the dead (``ArrayShadowGraph._sweep`` notes ``sweep_edge_slots``): the graph's whole edge capacity where it scanned the edge arrays, the endpoint index's candidates (``uigc_tpu/ops/edgeindex.py``: the spans of the dead slots in its sorted runs plus its overlay) where it asked the index.
Median over the window's wakes that called the device and freed an actor
(a wake in which nothing died examines nothing), from the program's
``WakeProfiler`` records (``obs.facts["program_wakes"]``); nothing on a
program whose records carry no such counter."""

from harness.program_trace import device_wakes, percentile


def read(obs):
    values = [
        r["sweep_edge_slots"]
        for r in device_wakes(obs)
        if "sweep_edge_slots" in r and r.get("freed", 0) > 0
    ]
    return percentile(values, 50) * 1 if values else None
