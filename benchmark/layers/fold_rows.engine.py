"""``fold_rows.engine``: packed rows the wake folded (``ArrayShadowGraph.merge_packed`` notes ``fold_rows`` on the wake's record): the batch the driver handed over, so about 60,000 under ``flush-20k``.
Median over the window's wakes that called the device, from the program's
``WakeProfiler`` records (``obs.facts["program_wakes"]``); nothing on a
program whose records carry no such counter."""

from harness.program_trace import device_wakes, percentile


def read(obs):
    values = [r["fold_rows"] for r in device_wakes(obs) if "fold_rows" in r]
    return percentile(values, 50) * 1 if values else None
