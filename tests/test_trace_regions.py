"""``Tracer.region`` spans on the profiler's clock: a continuous scheduler
serving two contexts on two slots under ``jax.profiler.start_trace``; the
``.xplane.pb`` read back with ``ProfileData`` holds every region, nested as
the serving layers nest, one host line per thread, each request's spans
carrying the id its future carries.  The same run checks the future's
stamps and the ring's Perfetto export."""
import json
import time
from pathlib import Path

import numpy as np
import pytest

from conftest import tokens_for
from repro.serve.engine import StepEngine
from repro.serve.scheduler import ContinuousScheduler
from repro.serve.telemetry import Telemetry

SCHED_REGIONS = {"sched.wait", "sched.tick", "sched.activate", "sched.admit",
                 "sched.resolve", "sched.idle_sleep"}
ENG_REGIONS = {"eng.prefill_chunk", "eng.decode", "eng.dispatch",
               "eng.sync"}
LOAD_REGIONS = {"ctx.load", "ctx.load.fetch", "ctx.load.put",
                "ctx.load.wait"}
STEPS = 5


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """(futures, tracer, host lines of the profile): each line a list of
    ``(start_ns, end_ns, name, stats)`` of the program's regions."""
    import jax
    from jax.profiler import ProfileData
    from repro.launch.serve import build_server
    tm = Telemetry(trace=True)
    server, cfgs = build_server(["supersub-super", "supersub-sub"],
                                slots=2, max_len=64, telemetry=tm)
    names = list(cfgs)
    out = tmp_path_factory.mktemp("profile")
    refused = []
    real = StepEngine.can_admit

    def refuse_first(self, tokens, max_new):
        # the first admission finds no room and no row is live: the
        # scheduler's tick sleeps (sched.idle_sleep) and tries again
        if not refused:
            refused.append(1)
            self.last_admit_block = "slots"
            return False
        return real(self, tokens, max_new)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(StepEngine, "can_admit", refuse_first)
            jax.profiler.start_trace(str(out))
            try:
                sched = ContinuousScheduler(
                    server, batch_size=4, paged=True, page_size=16,
                    prefill_chunk=8, multi_step=2).start()
                time.sleep(0.15)                 # no work: sched.wait
                futs = []
                for i in range(4):
                    nm = names[i % 2]
                    toks = np.asarray(tokens_for(cfgs[nm], batch=1,
                                                 seq=20, seed=i))
                    futs.append(sched.submit(nm, toks, steps=STEPS))
                for f in futs:
                    f.result(timeout=300)
                sched.stop()
            finally:
                jax.profiler.stop_trace()
    finally:
        server.shutdown()
    path = sorted(Path(out).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_serialized_xspace(path.read_bytes())
    lines = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            evs = [(ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
                   for ev in line.events
                   if ev.name.startswith(("sched.", "eng.", "ctx."))]
            if evs:
                lines.append(evs)
    return futs, tm.tracer, lines


def _line_with(lines, name):
    found = [ln for ln in lines if any(e[2] == name for e in ln)]
    assert len(found) == 1, f"{name} on {len(found)} host lines"
    return found[0]


def _inside(ev, outer):
    return any(o[0] <= ev[0] and ev[1] <= o[1] for o in outer)


def test_every_region_is_in_the_profile(profiled):
    _, _, lines = profiled
    names = {e[2] for ln in lines for e in ln}
    assert SCHED_REGIONS | ENG_REGIONS | LOAD_REGIONS <= names


def test_scheduler_regions_nest_on_one_thread_line(profiled):
    _, _, lines = profiled
    sched = _line_with(lines, "sched.tick")
    names = {e[2] for e in sched}
    assert SCHED_REGIONS | ENG_REGIONS <= names
    assert not names & LOAD_REGIONS               # the loader's own line
    ticks = [e for e in sched if e[2] == "sched.tick"]
    decodes = [e for e in sched if e[2] == "eng.decode"]
    for ev in sched:
        if ev[2] == "sched.wait":
            assert not _inside(ev, ticks)
        elif ev[2] in ("eng.dispatch", "eng.sync"):
            assert _inside(ev, decodes), ev
        elif ev[2] != "sched.tick":
            assert _inside(ev, ticks), ev
    # a tick that decodes dispatches, then reads the outputs back
    for d in decodes:
        kids = sorted(e for e in sched if e[2] in ("eng.dispatch",
                                                   "eng.sync")
                      and d[0] <= e[0] and e[1] <= d[1])
        assert [k[2] for k in kids] == ["eng.dispatch", "eng.sync"]
        assert {"steps", "rows"} <= set(d[3])


def test_loader_regions_nest_on_their_own_line(profiled):
    _, _, lines = profiled
    loader = _line_with(lines, "ctx.load")
    assert {e[2] for e in loader} == LOAD_REGIONS
    loads = [e for e in loader if e[2] == "ctx.load"]
    for ev in loader:
        if ev[2] != "ctx.load":
            assert _inside(ev, loads), ev
    for ld in loads:
        assert ld[3]["cause"] in ("demand", "prefetch")
        assert ld[3]["ctx"] in ("supersub-super", "supersub-sub")
        assert ld[3]["bytes"] > 0
        kids = sorted(e[2] for e in loader
                      if ld[0] <= e[0] and e[1] <= ld[1] and e is not ld)
        assert kids == sorted(LOAD_REGIONS - {"ctx.load"})


def test_request_spans_carry_the_futures_id(profiled):
    futs, tracer, lines = profiled
    ids = {f.req for f in futs}
    assert len(ids) == len(futs)
    sched = _line_with(lines, "sched.tick")
    admits = [e[3]["req"] for e in sched if e[2] == "sched.admit"]
    assert sorted(admits) == sorted(ids)
    chunks = [e[3] for e in sched if e[2] == "eng.prefill_chunk"]
    # 20-token prompts in 8-token chunks: 0-8, 8-16, 16-20 (final)
    for rid in ids:
        mine = sorted((c["start"], c["end"], c["final"]) for c in chunks
                      if c["req"] == rid)
        assert mine == [(0, 8, 0), (8, 16, 0), (16, 20, 1)], (rid, mine)
    acts = [e[3] for e in sched if e[2] == "sched.activate"]
    assert {a["ctx"] for a in acts} == {"supersub-super", "supersub-sub"}
    # the ring's per-request events use the same id
    evs = tracer.events()
    firsts = {int(e["name"].split(":")[1]) for e in evs
              if e["name"].startswith("first-token:")}
    retires = {int(e["name"].split(":")[1]) for e in evs
               if e["name"].startswith("req:")}
    assert firsts == retires == ids


def test_future_stamps_are_ordered(profiled):
    futs, _, _ = profiled
    for f in futs:
        assert f.submitted_at <= f.admitted_at <= f.first_token_at \
            <= f.done_at
        assert f.tokens == STEPS == f.result().shape[1]


def test_ring_spans_converted_and_perfetto_export_loads(profiled, tmp_path):
    _, tracer, lines = profiled
    doc = json.loads(Path(tracer.export(str(tmp_path / "t.json")))
                     .read_text())
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "M"
            and e["name"] == "thread_name"}
    assert evs and {e["tid"] for e in evs} <= tids
    names = {e["name"] for e in evs}
    assert SCHED_REGIONS | ENG_REGIONS | LOAD_REGIONS <= names
    # the old after-the-fact spans became regions, not extra copies
    assert not names & {"tick", "prefill-chunk"}
    profile = [e[2] for ln in lines for e in ln]
    ring = [e["name"] for e in evs if e["ph"] == "X"
            and e["name"] in SCHED_REGIONS | ENG_REGIONS | LOAD_REGIONS]
    assert sorted(ring) == sorted(profile)


def test_speculative_engine_records_its_rounds_and_chunks():
    """A ``SpecEngine``'s round is an ``eng.decode`` region and its chunk
    ticks ``eng.prefill_chunk`` regions, in the ring as in the profile."""
    import jax
    import jax.numpy as jnp
    from conftest import reduced_arch
    from repro.models.model import build_model
    from repro.serve.speculative import SpecEngine
    cfg = reduced_arch("supersub-sub")
    m = build_model(cfg, cache_dtype=jnp.float32)
    p = m.init(jax.random.key(0))
    tm = Telemetry(trace=True)
    eng = SpecEngine(m, m, batch_size=2, max_len=64, k=2, prefill_chunk=8,
                     telemetry=tm)
    eng.admit((p, p), np.asarray(tokens_for(cfg, 1, 12)), max_new=4)
    while eng.live_slots():
        eng.step((p, p))
    evs = tm.tracer.events()
    rounds = [e for e in evs if e["name"] == "eng.decode"]
    chunks = [e["args"] for e in evs if e["name"] == "eng.prefill_chunk"]
    assert rounds and all(e["args"]["rows"] == 1 for e in rounds)
    assert [(c["start"], c["end"], c["final"]) for c in chunks] == \
        [(0, 8, False), (8, 12, True)]
