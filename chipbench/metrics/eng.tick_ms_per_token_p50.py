"""Step engine: median decode tick time per decode step (ms), from the
program's raw ``token_latency_s`` observations in the window."""
from chipbench.tails import percentile


def read(run):
    v = percentile(run.values("token_latency_s"), 50)
    return None if v is None else 1e3 * v
