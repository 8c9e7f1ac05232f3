#!/usr/bin/env python3
"""The serving program's own regions in a profiler trace.

With its telemetry tracing, the program opens ``Tracer.region`` spans
(``sched.*`` on the scheduler thread, ``eng.*`` inside its ticks,
``ctx.load*`` on the context engine's loader thread), and each lands in
the profile's host plane through ``jax.profiler.TraceAnnotation``, one
line per thread, on the device planes' clock.  From them:

  * ``prog_spans``: seconds per region name inside the window, every
    thread;
  * ``idle_by_region``: the device's idle time inside the window, split
    by the innermost region open on the scheduler thread (the host line
    that holds ``sched.tick`` events):

      load_wait     ``sched.activate`` (a switch, waiting for a load)
      no_work       ``sched.wait`` / ``sched.idle_sleep``
      sync          ``eng.sync`` (reading a program's outputs back)
      host          anything else inside ``sched.tick``: the host work
                    of a step boundary
      unattributed  no region open on the scheduler thread

    The classes add up to the idle time;
  * ``host_events``: for runtime events named in ``WATCH`` (trailing
    numbers dropped, as ``trace_reduce`` labels them), their seconds
    in the window, and the parts of them that lie inside a
    ``ctx.load.put`` region and inside a ``ctx.load`` region (summed over
    events, which may run on several threads at once).

A trace of a program without these regions gives empty dicts.

    python3 chipbench/regions.py --workload ds8x3.switch --seed 7 \\
        --seconds 51

runs one cell as ``run.py --trace 1`` does, with the program's tracer on
(its ring and its profiler regions), and prints one JSON object: the
numbers above, the per-layer metrics read from them, ``out_tok_s``, the
ring's events and drops in the window, and how ``harness.first_tokens``'
time pairing agrees with each request's own first-token stamp (the
widest gap, and how many requests it gave another's first token).  It
checks no answer against the reference.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import trace_reduce  # noqa: E402

PREFIXES = ("sched.", "eng.", "ctx.")
SCHED_LINE = "sched.tick"        # the region that marks the scheduler line
CLASSES = ("load_wait", "no_work", "sync", "host", "unattributed")
# region -> class; where regions of two classes are open, the first in
# CLASSES wins (eng.sync and sched.activate lie inside sched.tick)
CLASS_OF = {"sched.activate": "load_wait", "sched.wait": "no_work",
            "sched.idle_sleep": "no_work", "eng.sync": "sync",
            "sched.tick": "host"}
WATCH = ("XlaLinearize", "H2D_Dispatch")
LOAD, PUT = "ctx.load", "ctx.load.put"


@dataclass
class Regions:
    window_s: float = 0.0
    idle_s: float = 0.0
    idle_by_region: dict = field(default_factory=dict)    # class -> s
    prog_spans: dict = field(default_factory=dict)        # name -> s
    host_events: dict = field(default_factory=dict)   # name -> [s, s, s]


def _gaps(busy: list, w0: int, w1: int) -> list:
    """The idle intervals of ``[w0, w1)`` around merged busy intervals."""
    gaps, prev = [], w0
    for s, e in busy:
        if s > prev:
            gaps.append((prev, min(s, w1)))
        prev = max(prev, e)
    if w1 > prev:
        gaps.append((prev, w1))
    return [(s, e) for s, e in gaps if e > s]


def split_idle(gaps: list, regions: list) -> dict:
    """Seconds of ``gaps`` (ns intervals) under each class.  ``regions``
    are ``(start, end, name)`` of one thread; a gap instant goes to the
    first class in ``CLASSES`` among the regions open then."""
    rank = {c: i for i, c in enumerate(CLASSES)}
    evs = []
    for s, e in gaps:
        evs += [(s, 1, None), (e, -1, None)]
    for s, e, name in regions:
        c = CLASS_OF.get(name)
        if c is not None and e > s:
            evs += [(s, 1, c), (e, -1, c)]
    evs.sort(key=lambda t: t[0])
    open_gaps, prev = 0, None
    depth: dict = defaultdict(int)
    out = {c: 0.0 for c in CLASSES}
    for t, d, c in evs:
        if open_gaps and prev is not None and t > prev:
            live = [k for k, n in depth.items() if n > 0]
            cls = min(live, key=rank.get) if live else "unattributed"
            out[cls] += (t - prev) * 1e-9
        if c is None:
            open_gaps += d
        else:
            depth[c] += d
        prev = t
    return out


def _overlap(intervals: list, union: list) -> int:
    """ns of ``intervals`` that lie inside the merged ``union``."""
    tot = 0
    for s, e in intervals:
        for u0, u1 in union:
            if u0 >= e:
                break
            tot += max(0, min(e, u1) - max(s, u0))
    return tot


def reduce_lines(busy: list, w0: int, w1: int, lines: list) -> Regions:
    """``busy``: merged busy intervals of one device; ``lines``: each host
    line's events as ``(start, end, name)``, ns on the device's clock."""
    out = Regions(window_s=(w1 - w0) * 1e-9)
    gaps = _gaps(busy, w0, w1)
    out.idle_s = sum(e - s for s, e in gaps) * 1e-9
    spans: dict = defaultdict(float)
    watched: dict = defaultdict(list)
    puts, loads, sched = [], [], None
    for evs in lines:
        for s, e, name in evs:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            if name.startswith(PREFIXES):
                spans[name] += (e - s) * 1e-9
                if name == PUT:
                    puts.append((s, e))
                elif name == LOAD:
                    loads.append((s, e))
            elif name in WATCH:
                watched[name].append((s, e))
        if sched is None and any(n == SCHED_LINE for _, _, n in evs):
            sched = evs
    out.prog_spans = dict(spans)
    if sched is not None:
        out.idle_by_region = split_idle(gaps, sched)
    in_put, in_load = trace_reduce._union(puts), trace_reduce._union(loads)
    out.host_events = {k: [sum(e - s for s, e in v) * 1e-9,
                           _overlap(sorted(v), in_put) * 1e-9,
                           _overlap(sorted(v), in_load) * 1e-9]
                       for k, v in watched.items()}
    return out


def summarize(path, window: str = trace_reduce.WINDOW) -> Regions:
    """``reduce_lines`` over a ``.xplane.pb``: the window and device busy
    time as ``trace_reduce.summarize`` reads them (its first device), and
    every host line's events."""
    pd = trace_reduce._load(path)
    w0 = w1 = None
    lines = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            evs = []
            for ev in line.events:
                if ev.name == window:
                    w0, w1 = ev.start_ns, ev.end_ns
                elif ev.name.startswith(PREFIXES):
                    evs.append((ev.start_ns, ev.end_ns, ev.name))
                elif trace_reduce._NUM.sub("", ev.name) in WATCH:
                    evs.append((ev.start_ns, ev.end_ns,
                                trace_reduce._NUM.sub("", ev.name)))
            if evs:
                lines.append(evs)
    if w0 is None:
        raise ValueError(f"no {window!r} span in {path}")
    busy = []
    devs = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if devs:
        lines0 = {ln.name: ln for ln in devs[0].lines}
        ops = lines0.get(trace_reduce.OPS_LINE)
        busy = [(max(ev.start_ns, w0), min(ev.end_ns, w1))
                for ev in getattr(ops, "events", ())
                if min(ev.end_ns, w1) > max(ev.start_ns, w0)]
    return reduce_lines(trace_reduce._union(busy), w0, w1, lines)


# ------------------------------------------------- the metrics read from it
def idle_share(r: Regions, cls: str):
    """Device idle time under one class, as % of the window."""
    if not r.idle_by_region or r.window_s <= 0:
        return None
    return 100.0 * r.idle_by_region[cls] / r.window_s


def load_host_share(r: Regions):
    """``ctx.load.put`` seconds over ``ctx.load`` seconds (%)."""
    load = r.prog_spans.get(LOAD, 0.0)
    if load <= 0:
        return None
    return 100.0 * r.prog_spans.get(PUT, 0.0) / load


def tpot_s(futs: list) -> list:
    """Each request's time per output token after the first, from the
    stamps its future carries; requests without them are left out."""
    out = []
    for f in futs:
        t1 = getattr(f, "first_token_at", None)
        t2 = getattr(f, "done_at", None)
        n = getattr(f, "tokens", 0)
        if t1 is not None and t2 is not None and n > 1:
            out.append((t2 - t1) / (n - 1))
    return out


# ------------------------------------------------------------- one run
def profile(cell: str, seed: int, seconds: float, bench=None,
            require_chip: bool = True, t_start=None,
            trace_root=None) -> dict:
    """One window of a cell with the program's tracer on; the profile
    goes under ``trace_root`` (default: the checkout's
    ``.chipbench_trace``) and is deleted once read."""
    import json
    import math
    import shutil
    from chipbench import costs, harness, traffic as traffic_mod
    from chipbench.spec import ROOT, Benchmark
    from chipbench.tails import percentile
    from repro.serve.scheduler import ContinuousScheduler
    t_start = T_START if t_start is None else t_start
    bench = bench or Benchmark()
    c = bench.cell(cell)
    dev = harness.device_info(c.chips, require_chip)
    if require_chip:
        harness.enable_cache()
    compiles = harness._Compiles.listening()
    cfg = harness.load_config(bench, c.config)
    tspec = bench.traffic(c.traffic)
    max_len = traffic_mod.max_len(tspec, harness.SCHED["page_size"])
    server, registry, _ = harness.build(cfg, seed, max_len)
    sched = ContinuousScheduler(server, batch_size=cfg.batch(max_len),
                                **harness.SCHED).start()
    futs: list = []
    trace_dir = (Path(trace_root) if trace_root is not None
                 else ROOT / ".chipbench_trace") / f"{cell}.regions"
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        harness.warm(sched, cfg, harness.SCHED["prefill_chunk"],
                     cfg.dims.vocab, tspec.get("models", [1.0]))
        setup_s = time.perf_counter() - t_start
        tracer = server.telemetry.tracer
        tracer.enabled = True
        tracer.clear()
        submit = sched.submit

        def keep(*a, **kw):
            f = submit(*a, **kw)
            futs.append(f)
            return f
        sched.submit = keep
        reqs, t0, _, ctx = harness.window(server, sched, cfg, tspec, seed,
                                          seconds, trace_dir, compiles)
        ring, dropped = len(tracer), tracer.dropped
    finally:
        sched.stop(drain=False)
        server.shutdown()
    xplane = trace_reduce.xplane_file(trace_dir)
    summary = trace_reduce.summarize(xplane, cfg.family.KERNELS,
                                     harness.PROGRAMS)
    regions = summarize(xplane)
    shutil.rmtree(trace_dir, ignore_errors=True)
    run = harness.Run(cell=cell, seconds=seconds, t0=t0,
                      t_close=t0 + seconds, reqs=reqs, raw=registry.raw,
                      ctx=ctx, dims=cfg.dims, peak={}, work=costs.Work(),
                      trace=summary)
    harness.first_tokens(reqs, [tv for tv in registry.raw["ttft_s"]
                                if tv[0] >= t0])
    pairs = [(r.first, f.first_token_at) for r, f in zip(reqs, futs)
             if not math.isnan(r.first)
             and getattr(f, "first_token_at", None) is not None]
    paired = [abs(a - b) for a, b in pairs]
    # a request whose paired stamp lies nearer another request's own
    mispaired = sum(min(abs(a - b2) for _, b2 in pairs) < abs(a - b)
                    for a, b in pairs)
    tick = percentile(run.values("token_latency_s"), 50)
    tpot = percentile(tpot_s(futs), 50)
    idle = 100.0 * (1.0 - summary.busy_s / summary.window_s)
    classes = {k: 100.0 * v / regions.window_s
               for k, v in regions.idle_by_region.items()}
    out = {
        "cell": cell, "seed": seed, "seconds": seconds, "setup_s": setup_s,
        "out_tok_s": harness.end_to_end("out_tok_s", run, setup_s),
        "eng.tick_ms_per_token_p50": None if tick is None else 1e3 * tick,
        "device.idle_share": idle,
        "device.idle_load_wait_share": idle_share(regions, "load_wait"),
        "device.idle_host_share": idle_share(regions, "host"),
        "ctx.load_host_share": load_host_share(regions),
        "sched.tpot_p50_ms": None if tpot is None else 1e3 * tpot,
        "idle_by_region_pct": classes,
        "idle_by_region_s": regions.idle_by_region,
        "prog_spans_s": regions.prog_spans,
        "host_events_s": regions.host_events,
        "ring_events": ring, "dropped": dropped,
        "pairing_max_ms": 1e3 * max(paired) if paired else None,
        "paired": len(paired), "mispaired": mispaired,
        "requests": len(reqs),
        "ctx": ctx, "device": dev,
    }
    print("idle_by_region (% of window): " + " ".join(
        f"{k}={v:.4f}" for k, v in classes.items())
        + f" sum={sum(classes.values()):.4f} device.idle_share={idle:.4f}",
        file=sys.stderr)
    print("load phases (s): " + " ".join(
        f"{k}={regions.prog_spans.get(k, 0.0):.4f}"
        for k in (LOAD, "ctx.load.fetch", PUT, "ctx.load.wait")),
        file=sys.stderr)
    print(json.dumps(out), flush=True)
    return out


def main(argv=None) -> int:
    import argparse
    from chipbench.harness import NoChip
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        profile(args.workload, args.seed, args.seconds)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
