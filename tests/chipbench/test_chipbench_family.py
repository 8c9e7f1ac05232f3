"""A family of layers enters the benchmark by adding a file: a test-only
mixture-of-experts family (``moe_probe_family.py``), copied into a tiny
benchmark tree beside the committed ones, is served through the
scheduler at toy widths and judged correct by its own reference, and its
kernel names drive the trace reduction."""
from chipbench_testkit import TINY_LIMIT, tiny_bench  # noqa: F401
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import costs, harness, trace_reduce

DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 31 + 53


def test_probe_family_is_served_and_judged_correct(tiny_bench):
    bench = tiny_bench(family="moe_probe")
    cfg = harness.load_config(bench, "tiny")
    assert cfg.arch.moe.num_experts == 4 and cfg.arch.moe.top_k == 2
    r = harness.measure("tiny.mix", SEED, 2.0, False, bench=bench,
                        require_chip=False)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    gap = r["checks"]["logit_gap_max"]
    assert gap["limit"] == TINY_LIMIT and 0 <= gap["value"] < TINY_LIMIT


def test_probe_family_off_the_program_widths_is_refused(tiny_bench):
    bench = tiny_bench(family="moe_probe")
    raw = dict(bench.config("tiny"), num_experts_per_tok=3)
    with pytest.raises(ValueError, match="widths differ"):
        bench.family("moe_probe").resolve(raw, "tiny")


def test_probe_kernels_drive_the_trace_reduction(tiny_bench):
    bench = tiny_bench(family="moe_probe")
    probe = bench.family("moe_probe")
    s = trace_reduce.summarize(DATA / "tiny_v5e.xplane.pb.gz",
                               probe.KERNELS, harness.PROGRAMS)
    # the recorded toy run ran both attention kernels and no expert kernel
    assert set(s.kernel_s) == {"paged_decode", "paged_verify"}
    metrics = bench.dir / "metrics"
    (metrics / "gmm_roofline.py").write_text(
        (metrics / "paged_decode_roofline.py").read_text()
        .replace('"paged_decode"', '"gmm"'))
    dims, _ = probe.resolve(bench.config("tiny"), "tiny")
    work = probe.request_work(dims, 200, 12, 64, costs.PEAKS["TPU v5 lite"],
                              costs.Work())
    work.kernel["gmm"] = 1e-3
    run = SimpleNamespace(trace=s, work=work)
    assert bench.reader("paged_decode_roofline")(run) > 0
    # the family names the expert kernel, the trace holds none: no reading
    assert bench.reader("gmm_roofline")(run) is None
