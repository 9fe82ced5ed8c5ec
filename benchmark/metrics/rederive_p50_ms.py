"""``rederive_p50_ms``: ``detect_p50_ms`` (``metrics/detect_p50_ms.py``) in the
re-derivation cell: host clock from ``invalidate()`` until all 10M verdicts
are on the host.  Named apart so that it keeps a bound of its own: this cell
repeats to 0.2%, the churn cell does not."""

from harness.cell import reader_of

read = reader_of("metrics", "detect_p50_ms")
