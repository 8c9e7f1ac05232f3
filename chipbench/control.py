#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 chipbench/control.py --workload ds15.chat --seconds 10 \\
        --seeds 11 12 13 ... --control-seeds 11 12 13

Runs the cell's timed path at its own load for each seed (a short window,
long enough to finish the mix's longest requests), and prints, per seed,
the widest gap by which a served token's reference logit lies below the
reference's best; on the control seeds it also reads the same gap for the
token that the float8 control puts first, and judges the control by the
run's own checks (``control_correct``, which has to come out false).  The
limit in ``cells/<cell>.json`` lies between the largest program reading
and the smallest control reading.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]


def readings(cell: str, seconds: float, seeds: list, control_seeds: list,
             **kw) -> list[dict]:
    from chipbench.harness import measure
    out = []
    for seed in seeds:
        t = time.perf_counter()
        r = measure(cell, seed, seconds, False, t_start=t,
                    control=seed in control_seeds, **kw)
        ctl = r.get("control_checks", {}).get("logit_gap_max", {})
        row = {"seed": seed, "correct": r["correct"],
               "program_gap_max": r["checks"]["logit_gap_max"]["value"],
               "control_correct": r.get("control_correct"),
               "control_gap_max": ctl.get("value"),
               "failed": r["failed"], "attempted": r["attempted"],
               "memory_peak_bytes": r["device"]["memory_peak_bytes"],
               "wall_s": time.perf_counter() - t}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    rows = readings(args.workload, args.seconds, args.seeds,
                    set(args.control_seeds))
    prog = [r["program_gap_max"] for r in rows]
    ctrl = [r["control_gap_max"] for r in rows
            if r["control_gap_max"] is not None]
    print(json.dumps({"workload": args.workload, "lower": max(prog),
                      "upper": min(ctrl) if ctrl else None,
                      "program": prog, "control": ctrl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
