"""Device idle time split by the scheduler thread's regions: by hand on
synthetic spans, on a recorded trace of a program without regions, and
on a whole CPU run of a toy cell with the program's tracer on, where the
benchmark's time pairing of first tokens is held against each request's
own first-token stamp."""
from chipbench_testkit import family, tiny_bench  # noqa: F401
from pathlib import Path

import pytest

from chipbench import regions, trace_reduce
from chipbench.harness import PROGRAMS

DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 31 + 91


def _split(gaps, sched):
    return {k: v for k, v in regions.split_idle(gaps, sched).items() if v}


def test_gap_under_activate_is_load_wait_whatever_the_loader_does():
    # the device idles over [100, 200): the scheduler thread sits in a
    # switch waiting for the load; the loader thread is in ctx.load.put
    sched = [(0, 300, "sched.tick"), (90, 210, "sched.activate")]
    loader = [(80, 220, "ctx.load"), (95, 205, "ctx.load.put")]
    r = regions.reduce_lines([[0, 100], [200, 300]], 0, 300,
                             [loader, sched])
    assert _split([(100, 200)], sched) == pytest.approx(
        {"load_wait": 100e-9})
    assert r.idle_by_region["load_wait"] == pytest.approx(100e-9)
    assert r.idle_by_region["host"] == 0.0
    assert r.prog_spans["ctx.load.put"] == pytest.approx(110e-9)


def test_gap_under_wait_is_no_work_not_host():
    sched = [(0, 40, "sched.tick"), (40, 90, "sched.wait"),
             (90, 100, "sched.tick"), (92, 95, "sched.idle_sleep")]
    got = _split([(30, 100)], sched)
    # [30,40) and [90,92) and [95,100): host; [40,90) and [92,95): no work
    assert got == pytest.approx({"host": 17e-9, "no_work": 53e-9})


def test_sync_host_and_unattributed():
    sched = [(0, 100, "sched.tick"), (10, 60, "eng.decode"),
             (10, 40, "eng.dispatch"), (40, 55, "eng.sync")]
    got = _split([(30, 50), (95, 130)], sched)
    # [30,40) dispatch -> host; [40,50) sync; [95,100) host; after the
    # tick no region is open
    assert got == pytest.approx({"host": 15e-9, "sync": 10e-9,
                                 "unattributed": 30e-9})


def test_classes_add_up_to_the_idle_time():
    sched = [(0, 50, "sched.tick"), (20, 45, "sched.activate"),
             (50, 70, "sched.wait"), (70, 200, "sched.tick"),
             (150, 160, "eng.sync")]
    busy = [[10, 20], [100, 150], [180, 190]]
    r = regions.reduce_lines(busy, 0, 220, [sched])
    assert r.idle_s == pytest.approx(220e-9 - 70e-9)
    assert sum(r.idle_by_region.values()) == pytest.approx(r.idle_s)
    assert regions.idle_share(r, "load_wait") == pytest.approx(
        100 * 25 / 220)


def test_prog_spans_clip_to_the_window_and_load_host_share():
    loader = [(-50, 50, "ctx.load"), (-40, 10, "ctx.load.put"),
              (60, 100, "ctx.load"), (65, 75, "ctx.load.put"),
              (66, 70, "XlaLinearize"), (80, 90, "XlaLinearize")]
    r = regions.reduce_lines([], 0, 100, [loader])
    assert r.prog_spans == pytest.approx({"ctx.load": 90e-9,
                                          "ctx.load.put": 20e-9})
    assert regions.load_host_share(r) == pytest.approx(100 * 20 / 90)
    # 14 ns of XlaLinearize, 4 of them inside a ctx.load.put, all of
    # them inside a ctx.load
    assert r.host_events["XlaLinearize"] == pytest.approx(
        [14e-9, 4e-9, 14e-9])
    # no scheduler line: nothing to split
    assert r.idle_by_region == {}
    assert regions.idle_share(r, "host") is None


def test_trace_of_a_program_without_regions_reads_nothing():
    path = DATA / "tiny_v5e.xplane.pb.gz"
    r = regions.summarize(path)
    s = trace_reduce.summarize(path, family("dense").KERNELS, PROGRAMS)
    assert r.idle_by_region == {} and r.prog_spans == {}
    assert r.window_s == pytest.approx(s.window_s)
    assert r.idle_s == pytest.approx(s.window_s - s.busy_s, rel=1e-9)
    assert regions.load_host_share(r) is None
    assert regions.tpot_s([object()]) == []


def test_traced_toy_run_reads_every_region_metric(tiny_bench, tmp_path):
    """A switching toy cell (three models on two slots, bursts) with the
    program's tracer on: the split covers the idle time, the loads have
    their phases, each request's TPOT comes from its future, and the
    benchmark's time pairing gives every request its own first token."""
    bench = tiny_bench(3, {"kind": "bursts", "bursts_per_s": 1.0,
                           "size": [4, 12], "span_s": 1.0})
    out = regions.profile("tiny.mix", SEED, 2.0, bench=bench,
                          require_chip=False, trace_root=tmp_path)
    classes = out["idle_by_region_pct"]
    assert set(classes) == set(regions.CLASSES)
    assert sum(classes.values()) == pytest.approx(
        out["device.idle_share"], abs=0.5)
    assert out["device.idle_host_share"] is not None
    assert out["device.idle_load_wait_share"] is not None
    assert out["ctx"]["loads"] >= 1
    assert 0 < out["ctx.load_host_share"] <= 100
    assert out["sched.tpot_p50_ms"] > 0
    assert out["ring_events"] > 0 and out["dropped"] == 0
    # the time pairing finds each request's own first token; its stamp
    # is the recorder's clock at the observation, a little after the
    # program's own (by up to a thread switch)
    assert out["paired"] == out["requests"] > 0
    assert out["mispaired"] == 0
    assert 0 <= out["pairing_max_ms"] < 50
