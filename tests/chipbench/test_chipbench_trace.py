"""Trace reduction: interval arithmetic by hand, and the whole reduction
on a small trace recorded on a TPU v5e (toy widths, 2 s window)."""
from chipbench_testkit import family
from pathlib import Path

import pytest

from chipbench import trace_reduce
from chipbench.harness import PROGRAMS

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps():
    got = trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 9), (10, 11)])
    assert got == [[0, 3], [5, 9], [10, 11]]


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    busy = [[10, 20], [40, 50]]
    spans = [(0, 100, "outer"), (18, 39, "dispatch"), (52, 60, "sleep")]
    gaps = dict(trace_reduce._label_gaps(busy, 0, 70, spans))
    # [0,10): only "outer"; [20,40): "dispatch" covers 19 of 20 ns and is
    # the innermost; [50,70): "sleep" covers less than half -> "outer"
    assert gaps == pytest.approx({"outer": 30e-9, "dispatch": 20e-9})


def test_module_names():
    assert trace_reduce.module_name("jit__mstep(123)") == "_mstep"
    assert trace_reduce.module_name("jit__chunk_final") == "_chunk_final"


def test_op_names():
    assert trace_reduce.op_name(
        "%copy.84.remat = bf16[49,32,256,128]{3,2,1,0} copy(%x)") == "copy"
    assert trace_reduce.op_name(
        "%paged_decode_attention.10 = bf16[6,32,1,128] custom-call()") == \
        "paged_decode_attention"


@pytest.fixture(scope="module")
def recorded():
    """``tiny_v5e.xplane.pb.gz``: the toy-width tiny cell (2 layers,
    d_model 64) traced for a 2 s window on one TPU v5e."""
    return trace_reduce.summarize(DATA / "tiny_v5e.xplane.pb.gz",
                                  family("dense").KERNELS, PROGRAMS)


def test_recorded_trace_window_and_busy(recorded):
    s = recorded
    assert s.devices == 1
    assert 1.5 < s.window_s < 3.0               # the 2 s window + drain
    assert 0 < s.busy_s < 0.1 * s.window_s      # toy widths: mostly idle


def test_recorded_trace_kernels_and_programs(recorded):
    s = recorded
    assert set(s.kernel_s) == {"paged_decode", "paged_verify"}
    assert set(s.module_s) == {"decode", "prefill"}
    # kernels run inside the programs; a program's span also holds the
    # short gaps between its ops, so it may pass the ops' union a little
    assert s.kernel_s["paged_decode"] < s.module_s["decode"]
    assert s.kernel_s["paged_verify"] < s.module_s["prefill"]
    assert sum(s.module_s.values()) <= s.busy_s * 1.05


def test_recorded_trace_breakdown(recorded):
    s = recorded
    ops = dict(s.device_ops)
    assert 0 < len(s.device_ops) <= 10 and "paged_decode_attention" in ops
    # exclusive times: the decode loop's own time excludes its body
    assert sum(ops.values()) <= s.busy_s * 1.001
    assert 0 < len(s.idle_gaps) <= 10
    idle = s.window_s - s.busy_s
    assert sum(v for _, v in s.idle_gaps) <= idle * 1.001
