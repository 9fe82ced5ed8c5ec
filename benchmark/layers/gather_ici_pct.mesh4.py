"""``gather_ici_pct.mesh4``: the all-gather's share of the interconnect's roofline: the bytes a chip receives per wake
(``roofline_gather.py gather_bytes``: ``(D - 1) / D x n_pad / 8`` an all-gather, times the all-gathers the program
counted, median ``gathers`` over the window's wakes) over ``gather_ms.mesh4``, against the device's published
``ici_bits_per_s / 8`` (``harness/peaks.json``).  The table is 2 MB: latency bounds the all-gather, not bandwidth, so this
reads far under 100%.  Nothing without a traced run, on a program without the counter or the scope, or on a device that
is not in the table of peaks; the mesh's shape (``obs.facts["mesh"]``) is left by ``drivers/engine_fold_mesh.py``."""

from harness.cell import reader_of
from harness.device import device_info, peaks_for
from harness.mesh_trace import shard_wake_stats
from harness.stats import percentile
from roofline_gather import gather_bytes

gather_ms = reader_of("layers", "gather_ms.mesh4")


def read(obs):
    ms = gather_ms(obs)
    stats = shard_wake_stats(obs)
    mesh = obs.facts.get("mesh")
    if not ms or not stats or not mesh or "gathers" not in stats[0]:
        return None
    try:
        peak = peaks_for(device_info()["kind"])["ici_bits_per_s"] / 8
    except SystemExit:  # the CPU of a rehearsal has no peak
        return None
    gathers = percentile([w["gathers"] for w in stats], 50)
    return 100.0 * gather_bytes(mesh["n_pad"], mesh["devices"], gathers) / (ms * 1e-3) / peak
