#!/usr/bin/env python3
"""Chip benchmark of the serving main path: one run of one cell.

    python3 chipbench/run.py --workload ds15.chat --seed 7 --seconds 40 \\
        --trace 0

Builds the cell's server from its configuration (weights drawn on the
device from ``--seed``), warms every program the cell's traffic uses,
offers ``--seconds`` of open-loop traffic from the cell's mix, waits for
every answer, then checks a seeded sample of the answers against a
float32 reference.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the same
window.  The last line of standard output is one JSON object; the last
lines of standard error give each compared number beside its limit.

Needs a TPU: without one (or with fewer chips than the cell asks for) it
exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench.harness import NoChip, measure
    try:
        result = measure(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    checks = result["checks"]
    for name, c in checks.items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
