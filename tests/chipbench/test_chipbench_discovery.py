"""Every piece of a cell is found by its name: a configuration, a mix, a
cell's limits, a metric reader or a family of layers added as a file is
used with no edit of the harness.  The committed ``BENCHMARK.json``
keeps to its own shape."""
from chipbench_testkit import tiny_bench  # noqa: F401
import json
import re

import pytest

from chipbench.spec import ROOT, Benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_added_files_are_found_by_name(tiny_bench):
    bench = tiny_bench()
    d = bench.dir
    (d / "configs" / "other.json").write_text(json.dumps({"x": 1}))
    (d / "traffic" / "burst2.json").write_text(json.dumps({"y": 2}))
    (d / "cells" / "new.cell.json").write_text(
        json.dumps({"logit_gap_max": 0.5}))
    (d / "metrics" / "new.metric_ms.py").write_text(
        "def read(run):\n    return 42.0 if run else None\n")
    (d / "families" / "new_family.py").write_text("KERNELS = {'k': 'op'}\n")
    assert bench.config("other") == {"x": 1}
    assert bench.traffic("burst2") == {"y": 2}
    assert bench.limits("new.cell")["logit_gap_max"] == 0.5
    read = bench.reader("new.metric_ms")
    assert read(object()) == 42.0 and read(None) is None
    assert bench.family("new_family").KERNELS == {"k": "op"}


def test_a_file_is_loaded_once_per_process(tiny_bench):
    one, other = tiny_bench(), tiny_bench(family="moe_probe")
    dense = one.family("dense")
    assert Benchmark(one.path, one.dir).family("dense") is dense
    assert one.reader("mfu.decode") is one.reader("mfu.decode")
    # the same name in another tree is another module
    assert other.family("dense") is not dense
    assert other.family("dense").__name__ != dense.__name__


def test_a_missing_piece_names_what_is_missing(tiny_bench):
    bench = tiny_bench()
    with pytest.raises(FileNotFoundError, match="nope"):
        bench.traffic("nope")
    with pytest.raises(FileNotFoundError, match="no reader"):
        bench.reader("nope")
    with pytest.raises(FileNotFoundError, match="no family"):
        bench.family("nope")
    with pytest.raises(KeyError, match="unknown workload"):
        bench.cell("nope")


def test_metrics_of_a_cell_follow_workloads_and_moves():
    bench = Benchmark()
    per = {m.name for m in bench.per_layer_for("ds15.chat")}
    assert "ctx.load_gb_s" not in per
    assert "ctx.load_gb_s" in {m.name for m in
                               bench.per_layer_for("ds8x3.switch")}
    reported = {m.name for m in bench.end_to_end_for("ds15.chat")}
    assert all(m.moves in reported for m in bench.per_layer)


def test_committed_benchmark_is_complete_and_well_formed():
    raw = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(raw) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    bench = Benchmark()
    assert 1 <= bench.run_seconds <= 51
    names = [c["name"] for c in raw["configs"]]
    for c in raw["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert bench.config(c["name"])["reduced"] == c["reduced"]
        bench.family(bench.config(c["name"])["family"])
    used = {w["config"] for w in raw["workloads"]}
    assert used == set(names)
    pairs = [(w["config"], w["traffic"]) for w in raw["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in raw["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        bench.traffic(w["traffic"])
        assert bench.limits(w["name"])["logit_gap_max"] > 0
    e2e = {m["name"] for m in raw["end_to_end"]}
    assert "setup_s" in e2e
    for m in raw["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in raw["end_to_end"] + raw["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in raw["per_layer"]:
        assert m["moves"] in e2e
        bench.reader(m["name"])
        for w in m.get("workloads", ()):
            bench.cell(w)
    for cell in bench.cells:
        assert len(bench.end_to_end_for(cell)) >= 2
        assert bench.per_layer_for(cell)


def test_a_configuration_off_the_published_widths_is_refused():
    from chipbench import harness
    bench = Benchmark()
    raw = bench.config("deepseek-7b.l15")
    cfg = harness.load_config(bench, "deepseek-7b.l15")
    assert cfg.dims.layers == cfg.arch.num_layers == 15
    with pytest.raises(ValueError, match="widths differ"):
        cfg.family.resolve(dict(raw, intermediate_size=4096), "narrow")


def test_warm_up_leaves_the_most_popular_models_resident_last():
    from types import SimpleNamespace

    from chipbench import harness

    class Sched:
        names: list = []

        def submit(self, name, tokens, steps):
            self.names.append(name)
            return SimpleNamespace(result=lambda timeout: None)
    sched = Sched()
    cfg = SimpleNamespace(models=["chat", "coder", "math"])
    harness.warm(sched, cfg, 4, 100, [0.6, 0.3, 0.1])
    assert sched.names == ["math"] * 2 + ["coder"] * 2 + ["chat"] * 2
