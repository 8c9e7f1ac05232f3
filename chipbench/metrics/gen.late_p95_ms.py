"""Load generator: 95th percentile of how late each request was submitted
after its due time (ms), on the benchmark's clock."""
from chipbench.tails import percentile


def read(run):
    return percentile(run.late_ms, 95)
