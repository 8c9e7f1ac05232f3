"""Scheduler: median host gap between two decode ticks (ms), from the
program's raw ``decode_stall_s`` observations in the window."""
from chipbench.tails import percentile


def read(run):
    v = percentile(run.values("decode_stall_s"), 50)
    return None if v is None else 1e3 * v
