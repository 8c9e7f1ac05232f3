"""Scheduler: 95th percentile of the program's raw ``queue_wait_s``
observations in the window (submit to admission into a slot)."""
from chipbench.tails import percentile


def read(run):
    return percentile(run.values("queue_wait_s"), 95)
