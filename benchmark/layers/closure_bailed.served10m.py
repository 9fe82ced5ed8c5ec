"""``closure_bailed.served10m``: ``closure_bailed`` (``layers/closure_bailed.py``) in the ``served-10m`` cell,
where the wake is the collector's own, on its timer, beside 5M residents held by uid (``drivers/served_fold.py``).  The wakes it counts are the ``bench:wake`` spans that driver writes around the backend's device call.  Lower is better HERE: a session is an island, so its closure should end under its price (0) and keep the regional repair; 1 says the wake paid for a derivation from the seeds."""

from harness.cell import reader_of

read = reader_of("layers", "closure_bailed")
