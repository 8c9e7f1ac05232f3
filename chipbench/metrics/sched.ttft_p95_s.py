"""Requests: 95th percentile of the time from each request's due time to
its first token (s), on the benchmark's clock.  Over a few tens of
requests near the knee this tail swings with the backlog, so it is
reported here beside the judged ``latency_p95_s``."""
from chipbench.tails import percentile


def read(run):
    return percentile(run.ttft(), 95)
