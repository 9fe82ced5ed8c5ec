"""``kill_uids.engine``: foreign uids the wake's sweep handed the sink to stop (``ArrayShadowGraph._sweep`` notes ``kill_uids``): garbage whose supervisor lives, 250 under ``flush-20k``.
Median over the window's wakes that called the device, from the program's
``WakeProfiler`` records (``obs.facts["program_wakes"]``); nothing on a
program whose records carry no such counter."""

from harness.program_trace import device_wakes, percentile


def read(obs):
    values = [r["kill_uids"] for r in device_wakes(obs) if "kill_uids" in r]
    return percentile(values, 50) * 1 if values else None
