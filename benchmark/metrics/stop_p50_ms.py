"""``stop_p50_ms``: median of the ``stop_ms`` samples, each the host clock
from just before a session's ``context.release`` until the last
``PostStop`` of its actors."""

from harness.stats import percentile


def read(obs):
    return percentile(obs.series("stop_ms"), 50)
