"""``stop_p95_ms``: 95th percentile of the ``stop_ms`` samples; the window
holds thousands of sessions."""

from harness.stats import percentile


def read(obs):
    return percentile(obs.series("stop_ms"), 95)
