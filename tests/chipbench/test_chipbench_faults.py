"""A whole run of the harness on the CPU at toy widths, with the look for
a chip skipped: sound, it is correct; with the timed path broken
underneath, ``correct`` comes out false for each fault a serving cell can
have (a token altered where it is produced, a decode step that returns
its cache unchanged, and in the switching cell a request served by
another slot's weights).  The float8 control, put in the program's
place, also fails the limit."""
from chipbench_testkit import TINY_LIMIT, tiny_bench  # noqa: F401
import numpy as np
import pytest

from chipbench import harness

SEED = 2 ** 31 + 77
BURSTS = {"kind": "bursts", "bursts_per_s": 1.0, "size": [4, 12],
          "span_s": 1.0}


def _run(bench, **kw):
    return harness.measure("tiny.mix", SEED, 2.0, False, bench=bench,
                           require_chip=False, **kw)


def test_sound_run_is_correct_and_reports_every_metric(tiny_bench):
    r = _run(tiny_bench())
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == 6
    assert set(r["metrics"]) == {"latency_p95_s", "out_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    gap = r["checks"]["logit_gap_max"]
    assert gap["limit"] == TINY_LIMIT and 0 <= gap["value"] < TINY_LIMIT


def test_altered_token_fails(tiny_bench, monkeypatch):
    from repro.serve.engine import StepEngine
    orig = StepEngine._call

    def altered(self, fn, params, *args):
        out = orig(self, fn, params, *args)
        if fn is self._mstep_fn:
            toks, n, state = out
            out = ((toks + 1) % self.model.cfg.vocab_size, n, state)
        return out
    monkeypatch.setattr(StepEngine, "_call", altered)
    r = _run(tiny_bench())
    assert not r["correct"]
    assert r["checks"]["logit_gap_max"]["value"] > TINY_LIMIT


def test_decode_that_keeps_its_cache_fails(tiny_bench, monkeypatch):
    from repro.models import layers

    def unchanged(cache, k, v, tables, positions, wmask=None):
        return cache
    monkeypatch.setattr(layers, "_page_write", unchanged)
    r = _run(tiny_bench())
    assert not r["correct"]


def test_switching_cell_is_correct_and_wrong_slot_fails(tiny_bench,
                                                        monkeypatch):
    bench = tiny_bench(3, BURSTS)
    assert _run(bench)["correct"]
    from repro.core.context import ContextState, ContextSwitchEngine
    orig = ContextSwitchEngine.run_step

    def other_slot(self, fn, *inputs, block=True, slot=None):
        act = self.active
        for s in self.slots:
            if s is not act and s.state == ContextState.READY:
                return orig(self, fn, *inputs, block=block, slot=s)
        return orig(self, fn, *inputs, block=block, slot=slot)
    monkeypatch.setattr(ContextSwitchEngine, "run_step", other_slot)
    assert not _run(bench)["correct"]


def test_float8_control_fails_the_limit(tiny_bench):
    r = _run(tiny_bench(), control=True)
    assert r["correct"] and r["control_correct"] is False
    ctl = r["control_checks"]["logit_gap_max"]
    assert ctl["limit"] == TINY_LIMIT
    assert np.isfinite(ctl["value"])
    assert ctl["value"] > TINY_LIMIT > r["checks"]["logit_gap_max"]["value"]


@pytest.mark.parametrize("chips", [1, 4])
def test_no_chip_means_no_result(tiny_bench, chips):
    with pytest.raises(harness.NoChip):
        harness.device_info(chips, require_chip=True)


def test_command_without_a_chip_exits_nonzero_and_prints_nothing():
    import os
    import subprocess
    import sys

    from chipbench.spec import ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chipbench" / "run.py"),
                        "--workload", "ds15.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2 and p.stdout == ""
    assert "no TPU found" in p.stderr and "platform=cpu" in p.stderr
