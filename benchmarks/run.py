"""Benchmark driver: one module per paper table/figure + ours.

``PYTHONPATH=src python -m benchmarks.run``   prints ``name,value,notes``
CSV; ``--only fig6`` filters by prefix; ``--json [DIR]`` additionally
writes one machine-readable ``BENCH_<name>.json`` per module (throughput
and latency fields pulled out of the rows, plus platform / device /
jax-version / git-sha provenance in ``meta``) so the perf trajectory can
be tracked across PRs — ``python -m benchmarks.compare OLD NEW`` diffs
two emitted files and prints per-key regressions.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def modules():
    from benchmarks import (bench_continuous, bench_multistep, bench_paged,
                            bench_prefill_chunk, bench_prefix,
                            bench_serve_queue, bench_sharded,
                            bench_speculative, bench_switch,
                            fig5_critical_path, fig5_primitives, fig6_cases,
                            fig6b_accuracy, figS1_pipeline, roofline_table)
    return [
        ("fig5_primitives", fig5_primitives.run),
        ("fig5_critical_path", fig5_critical_path.run),
        ("fig6b_accuracy", fig6b_accuracy.run),
        ("fig6_cases", fig6_cases.run),
        ("figS1_pipeline", figS1_pipeline.run),
        ("bench_switch", bench_switch.run),
        ("bench_serve_queue", bench_serve_queue.run),
        ("bench_continuous", bench_continuous.run),
        ("bench_speculative", bench_speculative.run),
        ("bench_prefill_chunk", bench_prefill_chunk.run),
        ("bench_paged", bench_paged.run),
        ("bench_prefix", bench_prefix.run),
        ("bench_sharded", bench_sharded.run),
        ("bench_multistep", bench_multistep.run),
        ("roofline_table", roofline_table.run),
    ]


def _metadata() -> dict:
    """Where these numbers came from: BENCH files are diffed across PRs
    and machines (``benchmarks.compare``), so each one records the
    platform, the JAX device/version, and the git revision it measured."""
    import platform
    import subprocess

    import jax
    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             timeout=10).stdout.strip() or "unknown"
    except Exception:
        sha = "unknown"
    dev = jax.devices()[0]
    from repro.core import env
    return {"platform": platform.platform(),
            "device": f"{dev.platform}:{dev.device_kind}",
            "jax_version": jax.__version__,
            "git_sha": sha,
            **env.describe()}


def _json_report(name: str, rows: list[tuple], wall_s: float) -> dict:
    """Shape a module's CSV rows into the tracked-metrics JSON: every row
    keyed by name, with throughput / latency / hidden-load convenience
    sections so cross-PR tooling doesn't parse notes strings."""
    report: dict = {"name": name, "wall_s": round(wall_s, 3),
                    "rows": {}, "throughput": {}, "latency": {}}
    for row in rows:
        n, v, note = (tuple(row) + ("",))[:3]
        report["rows"][str(n)] = {"value": v, "notes": str(note)}
        key = str(n)
        if "req_per_s" in key or "tok_per_s" in key or "per_s" in key:
            report["throughput"][key] = v
        if ("latency" in key or "ttft" in key or "stall" in key
                or key.endswith("_wall_s")):
            report["latency"][key] = v
        if "hidden_load_fraction" in key:
            report.setdefault("hidden_load", {})[key] = v
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", nargs="?", const=".", default=None,
                    metavar="DIR",
                    help="also write BENCH_<name>.json per module to DIR")
    args = ap.parse_args(argv)
    from repro.core import env
    env.enable_compile_cache()
    meta = None
    if args.json is not None:
        os.makedirs(args.json, exist_ok=True)
        meta = _metadata()
    failures = 0
    print("name,value,notes")
    for name, fn in modules():
        if args.only and not name.startswith(args.only):
            continue
        t0 = time.perf_counter()
        try:
            rows = list(fn())
            for row in rows:
                n, v, note = (tuple(row) + ("",))[:3]
                print(f"{n},{v},{note}")
        except Exception:
            failures += 1
            rows = None
            print(f"{name},ERROR,")
            traceback.print_exc()
        wall = time.perf_counter() - t0
        print(f"_{name}_wall_s,{wall:.2f},")
        if args.json is not None:
            path = os.path.join(args.json, f"BENCH_{name}.json")
            report = (_json_report(name, rows, wall) if rows is not None
                      else {"name": name, "error": True,
                            "wall_s": round(wall, 3)})
            report["meta"] = meta
            with open(path, "w") as f:
                json.dump(report, f, indent=1, sort_keys=True)
                f.write("\n")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
