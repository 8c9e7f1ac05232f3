"""Unified serving telemetry: registry/view/histogram semantics, the
disabled-tracer overhead gate, Chrome trace schema validity, cross-layer
conservation invariants, and the headline acceptance check — the
hidden-load fraction recomputed from exported trace spans matches the
engine's own accounting."""
import json

import jax
import numpy as np
import pytest

from conftest import reduced_arch, tokens_for
from repro.core.scheduler import Run, simulate_dynamic
from repro.core.telemetry import (Histogram, ManualClock, MetricRegistry,
                                  Telemetry, Tracer, safe_ratio)
from repro.models.model import build_model
from repro.serve.engine import StepEngine
from repro.serve.scheduler import ContinuousScheduler
from repro.serve.switching import ServedModel, SwitchableServer


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = reduced_arch("supersub-sub")
    m = build_model(cfg)
    p = m.init(jax.random.key(0))
    return cfg, m, p


def _make_server(names, telemetry=None, max_len=48):
    server = SwitchableServer(num_slots=2, telemetry=telemetry)
    cfgs = {}
    for i, name in enumerate(names):
        cfg = reduced_arch(name)
        cfgs[name] = cfg
        m = build_model(cfg)
        p = m.init(jax.random.key(i))
        server.register(ServedModel(name=name, model=m,
                                    weights_fn=lambda p=p: p,
                                    max_len=max_len))
    return server, cfgs


# ---------------------------------------------------------------------------
# registry / view / histogram units
# ---------------------------------------------------------------------------

def test_histogram_buckets_and_percentiles():
    h = Histogram(buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 5
    assert h.counts == [2, 1, 1, 1]
    assert h.percentile(0.0) == 0.01          # first non-empty bucket edge
    assert h.percentile(0.5) == 0.1           # 3rd of 5 obs -> bucket edge
    assert h.percentile(1.0) == 5.0           # overflow reports the max
    s = h.summary()
    assert s["count"] == 5 and s["max"] == 5.0
    assert s["mean"] == pytest.approx(5.56 / 5, abs=1e-6)


def test_registry_scalars_histograms_and_keys():
    reg = MetricRegistry()
    reg.inc("a.n", doc="a counter")
    reg.inc("a.n", 2)
    reg.gauge("free", 7)
    reg.observe("lat_s", 0.02, doc="a histogram")
    assert reg.value("a.n") == 3
    assert "a.n" in reg and "lat_s" in reg and "nope" not in reg
    assert reg.keys() == ["a.n", "free", "lat_s"]
    snap = reg.snapshot()
    assert snap["a.n"] == 3 and snap["free"] == 7
    assert snap["lat_s"]["count"] == 1


def test_metric_view_is_dict_compatible():
    reg = MetricRegistry()
    va = reg.view("eng.0.")
    vb = reg.view("eng.1.")
    va.update({"ticks": 0, "busy": 0.0})
    va["ticks"] += 2
    vb["ticks"] = 5
    assert va["ticks"] == 2 and vb["ticks"] == 5      # namespaced values
    assert dict(va) == {"ticks": 2, "busy": 0.0}
    assert sorted(va.items()) == [("busy", 0.0), ("ticks", 2)]
    assert va.setdefault("ticks", 99) == 2
    assert "ticks" in va and "other" not in va        # local namespace only
    assert reg.value("eng.0.ticks") == 2              # shared store
    with pytest.raises(KeyError):
        va["missing"]
    del va["busy"]
    assert "busy" not in va and "eng.0.busy" not in reg


def test_scoped_telemetry_shares_store():
    tm = Telemetry()
    child = tm.scoped("eng.0.")
    child.view()["x"] = 1
    child.observe("lat_s", 0.5)               # histograms stay unprefixed
    assert tm.registry.value("eng.0.x") == 1
    assert tm.registry.histogram("lat_s").count == 1
    assert child.tracer is tm.tracer and child.clock is tm.clock


# ---------------------------------------------------------------------------
# zero-denominator guards (satellite: early snapshots report 0.0, never NaN)
# ---------------------------------------------------------------------------

def test_safe_ratio_zero_denominator():
    assert safe_ratio(3.0, 2.0) == 1.5
    assert safe_ratio(3.0, 0.0) == 0.0
    assert safe_ratio(3.0, 0) == 0.0
    assert safe_ratio(0.0, 0.0, default=1.0) == 1.0


def test_fresh_snapshot_ratios_are_zero_not_nan(tiny_lm):
    """A snapshot taken before any load/tick happened must report 0.0
    ratios (present, finite), not raise or emit NaN."""
    server, _ = _make_server(["supersub-sub"])
    try:
        assert server.engine.hidden_load_fraction() == 0.0
        sched = ContinuousScheduler(server, batch_size=2)   # never started
        snap = sched.snapshot()
        assert snap["steps_per_tick"] == 0.0
        assert snap["host_ticks"] == 0 and snap["device_steps"] == 0
        assert snap["hidden_load_fraction"] == 0.0
        eng = server.step_engine("supersub-sub", 2)
        assert eng.stats["host_ticks"] == 0
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# injected clock: simulator and live engine emit the same stream
# ---------------------------------------------------------------------------

def test_manual_clock_drives_registry_and_tracer():
    clk = ManualClock()
    tm = Telemetry(clock=clk, trace=True)
    clk.set(10.0)
    tm.tracer.instant("ev", "trk")
    clk.advance(2.5)
    tm.tracer.span("sp", "trk", 10.0, clk())
    evs = tm.tracer.events()
    assert evs[0]["t0"] == 10.0
    assert evs[1]["dur"] == 2.5


def test_simulate_dynamic_emits_live_engine_keys():
    """The simulator writes the very ``ctx.*`` counters the live
    ``ContextSwitchEngine`` writes, on virtual time, and its hidden-load
    accounting matches the closed-form expectation."""
    tm = Telemetry(clock=ManualClock(), trace=True)
    sched = [Run("a", 1.0), Run("b", 1.0), Run("a", 1.0), Run("b", 1.0)]
    load = {"a": 0.5, "b": 0.5}
    total = simulate_dynamic(sched, load, num_slots=2, telemetry=tm)
    # baseline path unchanged by telemetry
    assert total == simulate_dynamic(sched, load, num_slots=2)
    v = tm.view("ctx.")
    assert v["loads"] == 2                     # a and b load exactly once
    assert v["load_seconds"] == pytest.approx(1.0)
    # a's initial load is a visible stall; b's load hides behind a's run
    assert v["visible_stall_seconds"] == pytest.approx(0.5)
    assert v["hidden_load_seconds"] == pytest.approx(0.5)
    assert v["switches"] == 4 and v["context_changes"] == 4
    tracks = {e["track"] for e in tm.tracer.events()}
    assert tracks == {"sim-loader", "sim-exec"}


# ---------------------------------------------------------------------------
# disabled-tracer overhead gate
# ---------------------------------------------------------------------------

def test_disabled_tracer_allocates_nothing():
    """Disabled, span/instant must return without allocating — the hot
    decode loop pays one attribute test per record point and nothing
    else (no tuple, no deque append, no args dict).  ``region`` returns
    one shared no-op object, whose ``with`` records nothing."""
    import tracemalloc
    tr = Tracer(enabled=False)
    name, track = "eng.decode", "eng"
    for _ in range(4):                         # warm any lazy setup
        tr.span(name, track, 0.0, 1.0)
        tr.instant(name, track, ts=0.0)
        with tr.region(name, track):
            pass
    assert tr.region(name, track) is tr.region("sched.tick", "sched")
    # tracemalloc attributes every allocation to its source line, so
    # background-thread noise cannot produce a false positive: any
    # telemetry.py allocation during the loop is a real per-call cost
    tracemalloc.start()
    try:
        snap1 = tracemalloc.take_snapshot()
        for _ in range(1000):
            tr.span(name, track, 0.0, 1.0)
            tr.instant(name, track, ts=0.0)
            with tr.region(name, track) as reg:
                reg.set(rows=4)
        snap2 = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    import os
    impl = os.path.join("core", "telemetry.py")
    grown = [st for st in snap2.compare_to(snap1, "lineno")
             if st.size_diff > 0
             and st.traceback[0].filename.endswith(impl)]
    assert len(tr) == 0
    assert not grown, [str(st) for st in grown]


def test_traced_and_untraced_outputs_identical(tiny_lm):
    """Tracing is observational: enabling it changes no token."""
    cfg, m, p = tiny_lm
    prompt = np.asarray(tokens_for(cfg, batch=2, seq=8, seed=7))
    outs = []
    for trace in (False, True):
        eng = StepEngine(m, batch_size=2, max_len=32,
                         telemetry=Telemetry(trace=trace))
        gens = eng.admit(p, prompt, max_new=4)
        while eng.live_slots():
            eng.step(p)
        outs.append(np.stack([np.asarray(g.tokens) for g in gens]))
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# trace schema
# ---------------------------------------------------------------------------

def test_chrome_trace_schema(tiny_lm):
    """Exported JSON is valid Chrome trace-event format: metadata names
    every track, complete events carry non-negative ts/dur, instants
    carry a scope, and everything survives a json round-trip."""
    cfg, m, p = tiny_lm
    tm = Telemetry(trace=True)
    eng = StepEngine(m, batch_size=2, max_len=32, telemetry=tm)
    gens = eng.admit(p, np.asarray(tokens_for(cfg, batch=2, seq=8)),
                     max_new=4)
    while eng.live_slots():
        eng.step(p)
    assert all(g.done for g in gens)
    doc = json.loads(json.dumps(tm.tracer.chrome_trace()))
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    data = [e for e in evs if e["ph"] != "M"]
    assert data, "no events recorded"
    named_tids = {e["tid"] for e in meta if e["name"] == "thread_name"}
    assert {e["tid"] for e in data} <= named_tids
    for e in data:
        assert e["ph"] in ("X", "i")
        assert e["pid"] == 1 and e["ts"] >= 0.0
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        else:
            assert e["s"] == "t"
    kinds = {e["name"].split(":")[0] for e in data}
    assert {"eng.decode", "eng.dispatch", "eng.sync", "first-token",
            "req"} <= kinds


# ---------------------------------------------------------------------------
# conservation invariants across layers
# ---------------------------------------------------------------------------

def test_conservation_invariants_continuous():
    """submitted == admitted + rejected + queued; every token is counted
    exactly once; histogram counts equal their triggering events."""
    tm = Telemetry()
    server, cfgs = _make_server(["supersub-super", "supersub-sub"],
                                telemetry=tm)
    names = list(cfgs)
    steps = 3
    try:
        with ContinuousScheduler(server, batch_size=4) as sched:
            futs = []
            for i in range(6):
                nm = names[i % 2]
                toks = np.asarray(tokens_for(cfgs[nm], batch=1, seq=8,
                                             seed=i))
                futs.append(sched.submit(nm, toks, steps=steps))
            outs = [f.result(timeout=300) for f in futs]
        snap = sched.snapshot()
        assert snap["requests"] == 6
        assert snap["requests"] == (snap["admitted_requests"]
                                    + snap["rejected_requests"]
                                    + snap["queued_requests"])
        reg = tm.registry
        eng_sum = {k: 0 for k in ("tokens_out", "admitted_rows",
                                  "retired_rows")}
        for key in reg.keys():
            for stat in eng_sum:
                if key.startswith("eng.") and key.endswith("." + stat):
                    eng_sum[stat] += reg.value(key)
        total_tokens = sum(int(np.asarray(o).size) for o in outs)
        assert eng_sum["tokens_out"] == total_tokens == 6 * steps
        assert eng_sum["admitted_rows"] == eng_sum["retired_rows"] == 6
        # one TTFT and one gen-latency observation per retired row
        assert reg.histogram("ttft_s").count == 6
        assert reg.histogram("gen_latency_s").count == 6
        # queue-wait observed once per admitted row
        assert reg.histogram("queue_wait_s").count == 6
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# the acceptance criterion: trace spans reproduce the engine's hidden-load
# accounting, and the overlap is visible in the trace
# ---------------------------------------------------------------------------

def _hidden_from_trace(events):
    loads = [e for e in events if e["name"].startswith("load:")]
    runs = [e for e in events if e["name"].startswith("run:")]
    hidden = total = 0.0
    overlapped = 0
    for ld in loads:
        l0, l1 = ld["t0"], ld["t0"] + ld["dur"]
        ov = sum(max(0.0, min(l1, r["t0"] + r["dur"]) - max(l0, r["t0"]))
                 for r in runs)
        if ov > 0:
            overlapped += 1
        hidden += min(ov, ld["dur"])
        total += ld["dur"]
    return hidden, total, overlapped


def test_hidden_load_fraction_matches_trace():
    """Mixed-model continuous serving with emulated load latency: the
    hidden-load fraction recomputed from exported ``load:``/``run:``
    spans matches ``ContextSwitchEngine`` accounting to < 1%, and at
    least one context load overlaps an active decode span (the paper's
    hidden reconfiguration, visually provable in Perfetto)."""
    from repro.launch.serve import build_server
    tm = Telemetry(trace=True)
    server, cfgs = build_server(["supersub-super", "supersub-sub"],
                                slots=2, max_len=48, load_delay_s=0.05,
                                telemetry=tm)
    names = list(cfgs)
    try:
        with ContinuousScheduler(server, batch_size=4) as sched:
            futs = []
            for i in range(8):
                nm = names[i % 2]
                toks = np.asarray(tokens_for(cfgs[nm], batch=1, seq=8,
                                             seed=i))
                futs.append(sched.submit(nm, toks, steps=6))
            for f in futs:
                f.result(timeout=300)
        eng_frac = server.engine.hidden_load_fraction()
        hidden, total, overlapped = _hidden_from_trace(tm.tracer.events())
        assert total > 0 and eng_frac > 0
        assert overlapped >= 1, "no load span overlapped a run span"
        trace_frac = hidden / total
        assert trace_frac == pytest.approx(eng_frac, rel=0.01)
        # the engine's raw accumulators match the span sums too
        assert total == pytest.approx(
            server.engine.stats["load_seconds"], rel=1e-6)
        assert hidden == pytest.approx(
            server.engine.stats["hidden_load_seconds"], rel=1e-6)
    finally:
        server.shutdown()
