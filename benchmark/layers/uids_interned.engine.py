"""``uids_interned.engine``: uids the wake's fold interned, local or foreign (``merge_packed`` notes ``uids_interned``: the growth of ``total_actors_seen`` over the fold): 0 in a steady window, the floor that says interning stayed out of it.
Median over the window's wakes that called the device, from the program's
``WakeProfiler`` records (``obs.facts["program_wakes"]``); nothing on a
program whose records carry no such counter."""

from harness.program_trace import device_wakes, percentile


def read(obs):
    values = [r["uids_interned"] for r in device_wakes(obs) if "uids_interned" in r]
    return percentile(values, 50) * 1 if values else None
