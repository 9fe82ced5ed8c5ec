"""``detect_p50_ms``: median of the ``detect_ms`` samples, each the host
clock from a release being handed to the system until its consequence is
on the host side."""

from harness.stats import percentile


def read(obs):
    return percentile(obs.series("detect_ms"), 50)
