"""Helpers for the chip benchmark's CPU tests: a tiny benchmark tree (the
real metric readers and families, a two-layer configuration at toy
widths, a short mix) that the harness runs on the CPU without looking for
a chip.  With ``family="moe_probe"`` the tree also holds the test-only
family ``moe_probe_family.py`` as ``families/moe_probe.py``, and its
configuration serves the program's MoE arch at toy widths.

Test modules import this first: it puts the repository root on
``sys.path`` for ``chipbench``.  (A ``conftest.py`` here would shadow
the suite's own ``conftest`` module, which other tests import by name.)"""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_LIMIT = 0.05       # widest logit gap allowed at toy widths
TINY_WIDTHS = dict(hidden_size=64, intermediate_size=128,
                   num_attention_heads=4, num_key_value_heads=4,
                   vocab_size=512)
MOE_ARCH = "qwen3-moe-235b-a22b"
PROBE_WIDTHS = dict(hidden_size=64, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, num_experts=4,
                    num_experts_per_tok=2, moe_intermediate_size=32,
                    norm_topk_prob=True, vocab_size=512)


def family(name: str):
    """The committed ``chipbench/families/<name>.py``, loaded as the
    harness loads it."""
    from chipbench.spec import Benchmark
    return Benchmark().family(name)


def tiny_tree(d: Path, n_models: int = 1, arrivals=None,
              family: str = "dense") -> Path:
    src = ROOT / "chipbench"
    for sub in ("configs", "traffic", "cells"):
        (d / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "families"):
        shutil.copytree(src / sub, d / sub, dirs_exist_ok=True)
    cfg = json.loads((src / "configs" / "deepseek-7b.l15.json").read_text())
    cfg.update(TINY_WIDTHS, num_hidden_layers=2,
               models=[f"m{i}" for i in range(n_models)])
    if family == "moe_probe":
        shutil.copy(Path(__file__).with_name("moe_probe_family.py"),
                    d / "families" / "moe_probe.py")
        for k in ("intermediate_size", "hbm", "published"):
            cfg.pop(k)
        cfg.update(PROBE_WIDTHS, family="moe_probe", arch=MOE_ARCH,
                   rope_theta=1e6)
    cfg["serving"].update(kv_budget_bytes=10 ** 9, max_batch=4)
    (d / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = {"arrivals": arrivals or {"kind": "poisson", "rate_per_s": 3.0},
           "models": [1.0 / n_models] * n_models,
           "prompt": {"median": 200, "sigma": 0.6, "min": 20, "max": 600},
           "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 32},
           "check": {"min_tokens": 64, "max_requests": 6}}
    (d / "traffic" / "mix.json").write_text(json.dumps(mix))
    (d / "cells" / "tiny.mix.json").write_text(
        json.dumps({"logit_gap_max": TINY_LIMIT}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "toy widths",
                         "file": "configs/tiny.json", "reduced": [],
                         "why": "CPU test"}]
    bench["workloads"] = [{"name": "tiny.mix", "config": "tiny",
                           "traffic": "mix", "chips": 1, "why": "CPU test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    return d


@pytest.fixture
def tiny_bench(tmp_path, monkeypatch):
    """A tiny benchmark; the program's ``deepseek-7b`` and MoE archs are
    given the tiny widths for the test, so that the family's check that a
    configuration runs at the program's published widths holds."""
    import repro.configs as configs
    from chipbench.spec import Benchmark
    real = configs.get_arch
    tiny = {"deepseek-7b": dict(d_model=64, num_heads=4, num_kv_heads=4,
                                head_dim=16, d_ff=128, vocab_size=512),
            MOE_ARCH: dict(d_model=64, num_heads=4, num_kv_heads=2,
                           head_dim=16, vocab_size=512,
                           moe=configs.MoEConfig(num_experts=4, top_k=2,
                                                 d_ff_expert=32))}

    def tiny_arch(name):
        return configs.override(real(name), **tiny[name])
    monkeypatch.setattr(configs, "get_arch", tiny_arch)

    def make(n_models=1, arrivals=None, family="dense"):
        d = tiny_tree(tmp_path / f"bench{n_models}{family}", n_models,
                      arrivals, family)
        return Benchmark(d / "BENCHMARK.json", d)
    return make
