"""``layout_ms``: host clock around ``tracer.apply_log`` (layout
maintenance, ``ops/pallas_incremental.py``), median per wake."""

from harness.stats import percentile


def read(obs):
    return percentile(obs.span_ms("layout"), 50)
