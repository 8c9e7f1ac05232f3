"""One run of one cell: build the server, warm it, offer the window's
traffic, measure, then check what was served against the reference.

The system under test is the serving main path as the launcher builds it
(``repro.launch.serve.build_server``): a ``SwitchableServer`` whose
context engine loads each model's host weights into a device slot, and a
``ContinuousScheduler`` (paged bank, 256-token pages and prefill chunks,
4 fused decode steps per tick, greedy) fed through ``submit``.  From the
program the benchmark takes only that, its raw telemetry observations and
counters, and its kernel and program names in the profiler trace.
"""
from __future__ import annotations

import bisect
import gc
import math
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Optional

import numpy as np

from chipbench import costs, traffic as traffic_mod, weights as weights_mod
from chipbench.spec import ROOT, Benchmark
from chipbench.tails import percentile

SCHED = dict(paged=True, page_size=256, prefill_chunk=256, multi_step=4)
# trace names of the jitted programs of each phase (a family names its
# kernels in its own ``KERNELS``)
PROGRAMS = {"decode": {"_step", "_mstep"},
            "prefill": {"_chunk", "_chunk_final"}}
# the raw observations the per-layer readers use (scheduler clock)
RAW = ("ttft_s", "queue_wait_s", "token_latency_s", "decode_stall_s")
DRAIN_S = 240.0            # longest wait for the window's last answers


class NoChip(RuntimeError):
    pass


@dataclass
class Config:
    """A configuration file, resolved by its family against the
    program's arch."""
    name: str
    raw: dict
    dims: object                   # the family's dims
    models: list
    arch: object                   # repro ArchConfig as served
    family: ModuleType             # families/<raw["family"]>.py

    @property
    def serving(self) -> dict:
        return self.raw["serving"]

    def batch(self, max_len: int) -> int:
        """Slots per engine: the KV budget over every model's bank."""
        s = self.serving
        per_slot = max_len * self.dims.kv_bytes_per_token
        b = s["kv_budget_bytes"] // (len(self.models) * per_slot)
        return int(max(1, min(s["max_batch"], b)))


def load_config(bench: Benchmark, name: str) -> Config:
    """Configuration ``name``, resolved by the family its file names."""
    raw = bench.config(name)
    family = bench.family(raw["family"])
    dims, arch = family.resolve(raw, name)
    return Config(name, raw, dims, list(raw["models"]), arch, family)


class Recorder:
    """A ``MetricRegistry`` that also keeps every raw observation with
    its clock time (the histograms keep only bucket counts)."""

    def __new__(cls):
        from repro.serve.telemetry import MetricRegistry

        class RecordingRegistry(MetricRegistry):
            def __init__(self):
                super().__init__()
                self.raw: dict = {k: [] for k in RAW}

            def observe(self, name, v, *a, **kw):
                lst = self.raw.get(name)
                if lst is not None:
                    lst.append((self.clock(), float(v)))
                super().observe(name, v, *a, **kw)
        return RecordingRegistry()


@dataclass
class Run:
    """What one window left behind; the metric readers take this."""
    cell: str
    seconds: float
    t0: float
    t_close: float
    reqs: list
    raw: dict
    ctx: dict                       # context-engine counter deltas
    dims: object                    # the family's dims
    peak: dict
    work: costs.Work
    trace: Optional[object] = None  # trace_reduce.Summary
    compiles: int = 0
    compile_s: float = 0.0
    traces: int = 0

    def values(self, name: str) -> list:
        return [v for t, v in self.raw.get(name, ()) if t >= self.t0]

    def ttft(self) -> list:
        """Seconds from each answered request's due time to its first
        token (see ``first_tokens``)."""
        return [r.first - (self.t0 + r.due) for r in self.reqs
                if not math.isnan(r.first)]

    @property
    def late_ms(self) -> list:
        return [1e3 * (r.submitted - (self.t0 + r.due)) for r in self.reqs]


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} jax={jax.__version__}", file=sys.stderr,
          flush=True)
    if require_chip:
        if jax.default_backend() != "tpu":
            raise NoChip(f"no TPU found (jax backend is "
                         f"{jax.default_backend()!r}); the benchmark does "
                         "not fall back to another device")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
        import repro.kernels as kernels
        if kernels.get_mode() != "auto" or not kernels.use_kernels():
            raise NoChip("compiled Pallas kernels are off (kernel mode "
                         f"{kernels.get_mode()!r}); unset REPRO_PALLAS")
    return info


def enable_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout, handed to the program through the variable it reads."""
    import jax
    path = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.core import env
    return env.enable_compile_cache()


class _Compiles:
    """Counts backend compiles and persistent-cache loads (``n``, ``s``)
    and jaxpr traces (``traces``) while ``on``: a program first used in
    the window shows as one of the first two, a retrace as the last."""
    _one = None
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        self.on, self.n, self.s, self.traces = False, 0, 0.0, 0

    @classmethod
    def listening(cls) -> "_Compiles":
        if cls._one is None:
            import jax
            cls._one = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._one._event)
        cls._one.n, cls._one.s, cls._one.traces = 0, 0.0, 0
        return cls._one

    def _event(self, event, duration, **_):
        if not self.on:
            return
        if event in self.EVENTS:
            self.n += 1
            self.s += duration
        elif event == self.TRACE:
            self.traces += 1


def build(cfg: Config, seed: int, max_len: int):
    """Weights from the seed, and the server the launcher would build."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import build_model
    from repro.serve.switching import ServedModel, SwitchableServer
    from repro.serve.telemetry import Telemetry
    registry = Recorder()
    server = SwitchableServer(num_slots=cfg.serving["slots"],
                              telemetry=Telemetry(registry=registry))
    hosts = {}
    for i, name in enumerate(cfg.models):
        model = build_model(cfg.arch, cache_dtype=jnp.bfloat16)
        dev = weights_mod.draw(cfg.family.layout(cfg.dims),
                               weights_mod.key_for(seed, i))
        weights_mod.check_layout(dev, model.abstract())
        host = jax.device_get(dev)
        del dev
        hosts[name] = host
        server.register(ServedModel(name=name, model=model,
                                    weights_fn=lambda p=host: p,
                                    max_len=max_len, temperature=0.0))
    return server, registry, hosts


def warm(sched, cfg: Config, chunk: int, vocab: int, shares) -> None:
    """One request per program per context: a one-chunk prompt that goes
    live at once, and a three-chunk prompt streaming in behind it (the
    streaming chunk, the final chunk, the single decode step taken while
    a chunk is pending, then the fused multi-step decode).  The models
    are warmed from the least popular to the most, so the window opens
    with the most popular ones resident, as on a server in steady state;
    every load in the window is then the traffic's own."""
    rng = np.random.default_rng(0)
    for i in np.argsort(shares, kind="stable"):
        name = cfg.models[i]
        futs = [sched.submit(name, rng.integers(0, vocab, (1, n)),
                             steps=8) for n in (chunk, 2 * chunk + 1)]
        for f in futs:
            f.result(timeout=1200)


def _offer(sched, reqs: list, names: list, t0: float) -> None:
    for r in reqs:
        delay = t0 + r.due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        r.submitted = time.perf_counter()
        fut = sched.submit(names[r.model], r.tokens[None], steps=r.steps)

        def done(f, r=r):
            r.done = time.perf_counter()
            r.output = (None if f.exception() is not None
                        else np.asarray(f.result())[0])
        fut.add_done_callback(done)


def first_tokens(reqs: list, observed: list, tol: float = 0.25) -> None:
    """Stamp each request with the moment its first token was produced,
    on the benchmark's clock.  The program observes ``ttft_s`` (seconds
    since its own submit stamp) right as the first token reaches the
    host; the recorder notes the clock at that call, so ``t - v`` is the
    program's submit stamp, taken just after the benchmark's own (one
    thread submits, so the two keep the same order).  Observations go,
    in that order, to the latest earlier request not yet matched."""
    subs = sorted((r.submitted, i) for i, r in enumerate(reqs))
    keys = [s for s, _ in subs]
    taken = set()
    for t, v in sorted(observed, key=lambda tv: tv[0] - tv[1]):
        k = bisect.bisect_right(keys, t - v + 1e-6) - 1
        while k >= 0 and subs[k][1] in taken:
            k -= 1
        if k >= 0 and t - v - keys[k] <= tol:
            taken.add(subs[k][1])
            reqs[subs[k][1]].first = t


def _ctx_counters(server) -> dict:
    st = server.engine.stats
    return {k: float(st[k]) for k in ("loads", "load_seconds",
                                      "bytes_loaded",
                                      "hidden_load_seconds")}


def window(server, sched, cfg: Config, tspec: dict, seed: int,
           seconds: float, trace_dir: Optional[Path], compiles: _Compiles):
    """Offer the window's traffic; wait for every answer."""
    import jax
    vocab = cfg.dims.vocab
    reqs = traffic_mod.schedule(tspec, seed, seconds, vocab,
                                len(cfg.models))
    c0 = _ctx_counters(server)
    ann = None
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
        ann = jax.profiler.TraceAnnotation("chipbench.window")
        ann.__enter__()
    compiles.on = True
    t0 = time.perf_counter()
    gen = threading.Thread(target=_offer, name="chipbench-offer",
                           args=(sched, reqs, cfg.models, t0))
    gen.start()
    gen.join()
    deadline = time.perf_counter() + DRAIN_S
    while (any(math.isnan(r.done) for r in reqs)
           and time.perf_counter() < deadline):
        time.sleep(0.01)
    t_end = time.perf_counter()
    compiles.on = False
    if ann is not None:
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    c1 = _ctx_counters(server)
    return reqs, t0, t_end, {k: c1[k] - c0[k] for k in c0}


def sample(reqs: list, seed: int, spec: dict) -> list:
    """The answered requests the reference checks, drawn from the seed:
    the longest answer and the longest prompt of each model first, then
    others until ``min_tokens`` served tokens or ``max_requests``."""
    ok = [r for r in reqs if r.output is not None]
    rng = np.random.default_rng([seed, 1])
    picked: list = []
    for m in sorted({r.model for r in ok}):
        mine = [r for r in ok if r.model == m]
        for key in (lambda r: r.steps, lambda r: len(r.tokens)):
            r = max(mine, key=key)
            if r not in picked:
                picked.append(r)
    rest = [ok[i] for i in rng.permutation(len(ok)) if ok[i] not in picked]
    for r in rest:
        if (sum(len(p.output) for p in picked) >= spec["min_tokens"]
                or len(picked) >= spec["max_requests"]):
            break
        picked.append(r)
    return picked


def served_gaps(picked: list, hosts: dict, cfg: Config,
                control: bool = False) -> dict:
    """Reference gaps of the picked requests, model by model."""
    from chipbench import reference
    out = {"served": [], "control": []}
    for m, name in enumerate(cfg.models):
        mine = [r for r in picked if r.model == m]
        if not mine:
            continue
        g = reference.served_gaps(
            weights_mod.flatten(hosts[name]), cfg.family, cfg.dims, cfg.raw,
            [r.tokens for r in mine], [r.output for r in mine],
            control=control)
        out["served"] += g["served"]
        out["control"] += g["control"]
    return out


def free(server, sched) -> None:
    """Drop the program's device state before the reference runs."""
    sched.stop()
    server.shutdown()
    for s in server.engine.slots:
        s.buffers = None
    server._step_engines.clear()
    gc.collect()


def judge(gaps: list, failed: int, limits: dict) -> tuple[list, bool]:
    """The numbers compared, each beside its limit, and whether all hold.
    The program's gaps and the control's go through this one test."""
    widest = max((float(g.max()) for g in gaps), default=math.inf)
    checks = [("logit_gap_max", widest, limits["logit_gap_max"]),
              ("failed_requests", failed, 0)]
    return checks, all(v <= lim for _, v, lim in checks)


def measure(cell: str, seed: int, seconds: float, trace: bool,
            bench: Optional[Benchmark] = None, require_chip: bool = True,
            t_start: Optional[float] = None,
            control: bool = False) -> dict:
    """One run; returns the result object the command prints.
    ``control`` also judges the float8 control, put in the program's
    place on the same sample, by the same checks (``control.py``)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = bench or Benchmark()
    c = bench.cell(cell)
    import jax
    dev = device_info(c.chips, require_chip)
    if require_chip:
        enable_cache()
    compiles = _Compiles.listening()
    cfg = load_config(bench, c.config)
    tspec = bench.traffic(c.traffic)
    limits = bench.limits(cell)
    page = SCHED["page_size"]
    max_len = traffic_mod.max_len(tspec, page)
    batch = cfg.batch(max_len)
    print(f"cell {cell}: {cfg.name} x{len(cfg.models)} on "
          f"{cfg.serving['slots']} slots, {cfg.dims.layers} layers, "
          f"batch {batch}, max_len {max_len}", file=sys.stderr, flush=True)

    from repro.serve.scheduler import ContinuousScheduler
    server, registry, hosts = build(cfg, seed, max_len)
    sched = ContinuousScheduler(server, batch_size=batch, **SCHED).start()
    try:
        warm(sched, cfg, SCHED["prefill_chunk"], cfg.dims.vocab,
             tspec.get("models", [1.0]))
        setup_s = time.perf_counter() - t_start
        trace_dir = None
        if trace:
            trace_dir = ROOT / ".chipbench_trace" / cell
            shutil.rmtree(trace_dir, ignore_errors=True)
        reqs, t0, _, ctx = window(server, sched, cfg, tspec, seed,
                                  seconds, trace_dir, compiles)
        stats = jax.devices()[0].memory_stats() or {}
        dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    except BaseException:
        sched.stop(drain=False)
        server.shutdown()
        raise
    peak = costs.peaks(dev["kind"]) if require_chip else \
        costs.PEAKS["TPU v5 lite"]
    work = costs.Work()
    for r in reqs:
        if r.output is not None:
            cfg.family.request_work(cfg.dims, len(r.tokens), len(r.output),
                                    SCHED["prefill_chunk"], peak, work)
    run = Run(cell=cell, seconds=seconds, t0=t0, t_close=t0 + seconds,
              reqs=reqs, raw=registry.raw, ctx=ctx,
              dims=cfg.dims, peak=peak, work=work, compiles=compiles.n,
              compile_s=compiles.s, traces=compiles.traces)
    first_tokens(reqs, [tv for tv in registry.raw["ttft_s"] if tv[0] >= t0])
    free(server, sched)
    del server, sched

    failed = sum(r.output is None or len(r.output) != r.steps
                 or not ((r.output >= 0) & (r.output < cfg.dims.vocab)).all()
                 for r in reqs)
    picked = sample(reqs, seed, tspec["check"])
    gaps = served_gaps(picked, hosts, cfg, control=control)
    checks, correct = judge(gaps["served"], failed, limits)
    compared = sum(len(g) for g in gaps["served"])

    if trace:
        from chipbench import trace_reduce
        run.trace = trace_reduce.summarize(
            trace_reduce.xplane_file(trace_dir), cfg.family.KERNELS,
            PROGRAMS)
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for m in bench.per_layer_for(cell):
            v = bench.reader(m.name)(run)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
    else:
        metrics = {m.name: {"value": v, "unit": m.unit}
                   for m in bench.end_to_end_for(cell)
                   for v in [end_to_end(m.name, run, setup_s)]
                   if v is not None}

    lat = [r.done - (run.t0 + r.due) for r in reqs if r.output is not None]
    print("tails: " + " ".join(
        f"{name}_p{q}={percentile(v, q):.4f}" for name, v in
        (("ttft", run.ttft()), ("latency", lat)) for q in (50, 75, 90, 95)
        if v), file=sys.stderr)
    print("ttft_p50 by third of the window: " + " ".join(
        f"{percentile(v, 50):.4f}" if v else "-"
        for v in thirds(run)), file=sys.stderr)
    print(f"context loads {run.ctx['loads']:g}, "
          f"{run.ctx['load_seconds']:.3f} s", file=sys.stderr)
    print(f"window: {len(reqs)} requests, {compared} served tokens "
          f"compared over {len(picked)} requests, {run.compiles} compiles "
          f"or cache loads ({run.compile_s:.3f} s) and {run.traces} traces "
          "inside the window", file=sys.stderr)
    result = {"correct": bool(correct), "attempted": len(reqs),
              "failed": int(failed), "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
    if control:
        c_checks, c_correct = judge(gaps["control"], failed, limits)
        result["control_correct"] = c_correct
        result["control_checks"] = {name: {"value": v, "limit": lim}
                                    for name, v, lim in c_checks}
    result["checks"] = {name: {"value": v, "limit": lim}   # comes last
                        for name, v, lim in checks}
    return result


def thirds(run: Run) -> list:
    """Each request's TTFT (from due), by the third of the window it was
    due in: a backlog that builds shows as the later thirds' rising."""
    out: list = [[], [], []]
    for r in run.reqs:
        if not math.isnan(r.first):
            k = min(2, int(3 * r.due / run.seconds))
            out[k].append(r.first - (run.t0 + r.due))
    return out


def end_to_end(name: str, run: Run, setup_s: float) -> Optional[float]:
    """The end-to-end metrics, all on the benchmark's own clock."""
    if name == "setup_s":
        return setup_s
    if name == "latency_p95_s":
        return percentile([r.done - (run.t0 + r.due) for r in run.reqs
                           if r.output is not None], 95)
    if name == "out_tok_s":
        done = [r for r in run.reqs if r.output is not None]
        if not done:
            return None
        return (sum(len(r.output) for r in done)
                / (max(r.done for r in done) - run.t0))
    raise KeyError(f"no end-to-end metric {name!r}")
