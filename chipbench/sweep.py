#!/usr/bin/env python3
"""Find a cell's knee: offer its mix at several fixed rates, one process.

    python3 chipbench/sweep.py --workload ds15.chat --seconds 20 \\
        --rates 2 3 4 5 6

Builds and warms the cell's server once, then offers one window per rate
(the mix's ``rate_per_s``, or ``bursts_per_s`` for a burst mix, replaced)
and prints one line per rate: offered and completed requests, output
tokens per second, TTFT and latency tails, and how the backlog moved (the
latency of the window's last third against its first third, and how long
the last answers took after the window closed).  Below the knee the
backlog stays flat; above it latency grows through the window.  The
rate written into a cell's mix is about 0.8 of the knee.  No correctness
check runs here.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]


def sustained(row: dict) -> bool:
    """No growing backlog: every request answered, and
    - steady arrivals: the window's last third no slower than 1.5x its
      first third (plus half a second), and the last answers in within
      twice the p95 latency (plus 2 s) after the close;
    - bursts: the last answers in within half the median latency after
      the close.  A window holds a few bursts, so its thirds differ by
      where the bursts and their loads fall, not by a backlog."""
    if row["answered"] != row["requests"]:
        return False
    if row.get("kind") == "bursts":
        return row["drain_s"] <= 0.5 * row["latency_p50_s"]
    return (row["latency_last_third_s"]
            <= 1.5 * row["latency_first_third_s"] + 0.5
            and row["drain_s"] <= 2 * row["latency_p95_s"] + 2.0)


def knee(rows: list) -> float:
    """The highest swept rate below which every rate was sustained."""
    best = None
    for row in sorted(rows, key=lambda r: r["rate"]):
        if not sustained(row):
            break
        best = row["rate"]
    return best


def sweep(cell: str, seconds: float, rates: list, seed: int = 1) -> list:
    import numpy as np
    from chipbench import harness, traffic as traffic_mod
    from chipbench.spec import Benchmark
    from chipbench.tails import percentile
    from repro.serve.scheduler import ContinuousScheduler
    bench = Benchmark()
    c = bench.cell(cell)
    harness.device_info(c.chips, True)
    harness.enable_cache()
    cfg = harness.load_config(bench, c.config)
    tspec = bench.traffic(c.traffic)
    max_len = traffic_mod.max_len(tspec, harness.SCHED["page_size"])
    batch = cfg.batch(max_len)
    server, registry, _ = harness.build(cfg, seed, max_len)
    sched = ContinuousScheduler(server, batch_size=batch,
                                **harness.SCHED).start()
    harness.warm(sched, cfg, harness.SCHED["prefill_chunk"], cfg.dims.vocab,
                 tspec.get("models", [1.0]))
    key = ("rate_per_s" if tspec["arrivals"]["kind"] == "poisson"
           else "bursts_per_s")
    rows = []
    try:
        for i, rate in enumerate(rates):
            spec = copy.deepcopy(tspec)
            spec["arrivals"][key] = rate
            reqs, t0, t_end, ctx = harness.window(
                server, sched, cfg, spec, seed + i, seconds, None,
                harness._Compiles.listening())
            harness.first_tokens(reqs, [tv for tv in registry.raw["ttft_s"]
                                        if tv[0] >= t0])
            ok = [r for r in reqs if r.output is not None]
            lat = np.array([r.done - (t0 + r.due) for r in ok])
            third = max(1, len(ok) // 3)
            done_in = [r for r in ok if r.done <= t0 + seconds]
            row = {"rate": rate, "kind": spec["arrivals"]["kind"],
                   "requests": len(reqs), "answered": len(ok),
                   "req_s": len(reqs) / seconds,
                   "out_tok_s": sum(len(r.output) for r in done_in)
                   / seconds,
                   "ttft_p95_s": percentile(
                       [r.first - (t0 + r.due) for r in ok], 95),
                   "latency_p50_s": percentile(list(lat), 50),
                   "latency_p95_s": percentile(list(lat), 95),
                   "latency_first_third_s": float(np.mean(lat[:third])),
                   "latency_last_third_s": float(np.mean(lat[-third:])),
                   "drain_s": t_end - (t0 + seconds),
                   "loads": ctx["loads"]}
            row["sustained"] = sustained(row)
            print(json.dumps(row), flush=True)
            rows.append(row)
            if len(rows) >= 2 and not (rows[-1]["sustained"]
                                       or rows[-2]["sustained"]):
                break               # two rates past the knee: stop
    finally:
        sched.stop(drain=False)
        server.shutdown()
    return rows


def main(argv=None) -> int:
    t = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    rows = sweep(args.workload, args.seconds, args.rates)
    print(json.dumps({"workload": args.workload, "knee": knee(rows)}),
          flush=True)
    print(f"sweep wall {time.perf_counter() - t:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
