"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

From the device planes: the busy time (union of the intervals in which
an operation ran) inside the traced window, each kernel's summed time,
each jitted program's summed time, and the operations that took most
time.  From the host planes: what the host was doing in each idle gap of
the device.  The window is the host span the benchmark itself annotates
(``WINDOW``) around the traffic it offers.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host spans longer than this (a thread's whole life) label no idle gap
LABEL_SPAN_NS = 1_000_000_000
_NUM = re.compile(r"[.\-_]?\d+$")
# "%copy.84.remat = bf16[...] copy(...)" -> "copy"
_SUFFIX = re.compile(r"(\.\d+|\.remat\d*|\.clone)+$")
# module events read "jit__mstep(123)" or "jit__mstep"
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


@dataclass
class Summary:
    window_s: float
    busy_s: float                           # averaged over device planes
    kernel_s: dict = field(default_factory=dict)
    module_s: dict = field(default_factory=dict)
    device_ops: list = field(default_factory=list)   # [[name, s]] top 10
    idle_gaps: list = field(default_factory=list)    # [[label, s]] top 10
    devices: int = 0


def xplane_file(trace_dir) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb*"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def module_name(name: str) -> str:
    return _MODULE.match(name).group(1)


def op_name(name: str) -> str:
    """The HLO op's name without its number: the trace names an op by
    its whole HLO line."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


def _load(path):
    """A trace from ``.xplane.pb`` (or its gzip)."""
    import gzip
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    return ProfileData.from_serialized_xspace(raw)


def summarize(path, kernels: dict, modules: dict,
              window: str = WINDOW) -> Summary:
    """``kernels``: {key: the kernel's op name}; ``modules``: {key: set
    of jitted function names}."""
    pd = _load(path)
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    devs = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    w0 = w1 = None
    spans = []                                  # (start, end, name)
    for p in host:
        for line in p.lines:
            for ev in line.events:
                if ev.name == window:
                    w0, w1 = ev.start_ns, ev.end_ns
                elif 0 < ev.duration_ns <= LABEL_SPAN_NS:
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    if w0 is None:
        raise ValueError(f"no {window!r} span in {path}")
    out = Summary(window_s=(w1 - w0) * 1e-9, busy_s=0.0, devices=len(devs))
    ops_t: dict = defaultdict(float)
    busy_all = []
    for p in devs:
        lines = {line.name: line for line in p.lines}
        busy, stack = [], []        # stack: [end, name, self time]
        evs = sorted(((max(ev.start_ns, w0), min(ev.end_ns, w1), ev)
                      for ev in getattr(lines.get(OPS_LINE), "events", ())
                      if min(ev.end_ns, w1) > max(ev.start_ns, w0)),
                     key=lambda t: (t[0], -t[1]))
        for s, e, ev in evs:
            busy.append((s, e))
            while stack and stack[-1][0] <= s:
                _, name, own = stack.pop()
                ops_t[name] += own * 1e-9
            if stack:       # ops nest (a loop holds its body's ops)
                stack[-1][2] -= e - s
            stack.append([e, op_name(ev.name), e - s])
            for key, sub in kernels.items():
                if stack[-1][1].startswith(sub):
                    out.kernel_s[key] = (out.kernel_s.get(key, 0.0)
                                         + (e - s) * 1e-9)
        for _, name, own in stack:
            ops_t[name] += own * 1e-9
        for ev in getattr(lines.get(MODULES_LINE), "events", ()):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            base = module_name(ev.name)
            for key, names in modules.items():
                if base in names:
                    out.module_s[key] = (out.module_s.get(key, 0.0)
                                         + (e - s) * 1e-9)
        merged = _union(busy)
        out.busy_s += sum(e - s for s, e in merged) * 1e-9 / len(devs)
        busy_all.append(merged)
    out.device_ops = [[k, v] for k, v in
                      sorted(ops_t.items(), key=lambda kv: -kv[1])[:10]]
    if busy_all:
        out.idle_gaps = _label_gaps(busy_all[0], w0, w1, spans)
    return out


def _label_gaps(merged: list, w0: int, w1: int, spans: list) -> list:
    """Idle time of one device by what the host was doing: each gap goes
    to the shortest host span that covers at least half of it (the
    innermost, most specific), else to the span covering most of it;
    the gaps are summed per label."""
    gaps, prev = [], w0
    for s, e in merged:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if w1 > prev:
        gaps.append((prev, w1))
    spans = sorted(spans)
    starts = [s for s, _, _ in spans]
    total: dict = defaultdict(float)
    longest = max((e - s for s, e, _ in spans), default=0)
    for g0, g1 in gaps:
        half = (g1 - g0) / 2
        best, cover, inner = "host: no span", 0, None
        lo = bisect.bisect_left(starts, g0 - longest)
        hi = bisect.bisect_right(starts, g1)
        for s, e, name in spans[lo:hi]:
            c = min(e, g1) - max(s, g0)
            if c >= half and (inner is None or e - s < inner[0]):
                inner = (e - s, name)
            if c > cover:
                best, cover = name, c
        label = inner[1] if inner is not None else best
        total[_NUM.sub("", label)] += (g1 - g0) * 1e-9
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:10]]
