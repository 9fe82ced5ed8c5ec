"""``layout_rows.engine``: pair transitions the wake's layout phase folded (``ArrayShadowGraph._synced_dec`` notes ``layout_rows`` on the wake's record: the rows of the pair log it handed ``apply_log``): what ``layout_ms.engine`` is the price of, so about 45,000 under ``flush-20k``.
Median over the window's wakes that called the device, from the program's
``WakeProfiler`` records (``obs.facts["program_wakes"]``); nothing on a
program whose records carry no such counter."""

from harness.program_trace import device_wakes, percentile


def read(obs):
    values = [r["layout_rows"] for r in device_wakes(obs) if "layout_rows" in r]
    return percentile(values, 50) * 1 if values else None
