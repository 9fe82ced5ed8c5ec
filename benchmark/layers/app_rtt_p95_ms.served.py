"""``app_rtt_p95_ms.served``: 95th percentile of the ``app_rtt_ms`` samples,
the round trip of an application message between resident actors, timed
from when each was due (open loop): what collection, and Python's own
collector, cost the traffic beside them.  ISSUE 26 wanted it end to end;
it spread by 12.6% over six runs and its median moved 12% between two
sets of the same seeds (my chip runs, PR 26), more than any bound allows,
so it is read per layer."""

from harness.stats import percentile


def read(obs):
    return percentile(obs.series("app_rtt_ms"), 95)
