"""``wake_ms``: host clock from the dispatch of ``wake_device``
(``ops/pallas_decremental.py``) to ``block_until_ready``, median per
wake."""

from harness.stats import percentile


def read(obs):
    return percentile(obs.span_ms("wake"), 50)
