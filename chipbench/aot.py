#!/usr/bin/env python3
"""Compile each cell's serving programs for a described TPU v5e, without
the chip, and print the compiler's memory analysis.

    JAX_PLATFORMS=cpu python3 chipbench/aot.py [cell ...]

The programs are the ones the step engine runs at the cell's widths,
batch and page bank: a streaming prefill chunk, the final chunk (logits
and the first token), one decode step, and the fused 4-step decode, all
with the bank donated as the engine donates it.  Nothing runs; a compile
that passes is not a chip run.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _steer_to_tpu():
    """The kernels' backend probe sees this process's CPU: point it at
    the TPU branch, as the compile tests do."""
    import repro.kernels as kernels
    from repro.kernels.paged_attention import ops
    kernels.use_kernels = lambda: True
    kernels.get_mode = lambda: "auto"
    ops._on_tpu = lambda: True


def programs(model, B: int, max_len: int, page: int, chunk: int, steps: int):
    import jax
    import jax.numpy as jnp
    P = max_len // page

    def chunk_fn(params, caches, tokens, pos, tables):
        return model.prefill_chunk_pages(params, caches, tokens, pos, tables,
                                         need_logits=False)[1]

    def final_fn(params, caches, tokens, pos, tables, nvalid):
        wmask = jnp.arange(tokens.shape[1])[None, :] < nvalid[:, None]
        lg, caches = model.prefill_chunk_pages(params, caches, tokens, pos,
                                               tables, wmask=wmask)
        last = jnp.take_along_axis(lg, (nvalid - 1)[:, None, None], 1)[:, 0]
        return jnp.argmax(last, -1), caches

    def step_fn(params, caches, tok, pos, tables, live):
        lg, caches = model.decode_step_pages(params, caches, tok, pos,
                                             tables, live=live)
        return jnp.argmax(lg[:, -1], -1), caches

    def mstep_fn(params, caches, tok, pos, tables, live, rem):
        def sample(last, pos, carry):
            return jnp.argmax(last, -1).astype(jnp.int32), carry

        def stop(nxt, posr, i):
            return (live & (rem <= i + 1)).any()
        out = model.decode_multi_step_pages(
            params, caches, tok, pos, tables, steps, sample, stop,
            jnp.zeros((), jnp.int32), live=live, pos_cap=max_len - 1)
        return out[0], out[1], out[2]

    i32 = jnp.int32
    one = (lambda *s, dt=i32: jax.ShapeDtypeStruct(s, dt))
    return {
        "prefill_chunk": (chunk_fn, (one(1, chunk), one(1), one(1, P))),
        "prefill_final": (final_fn, (one(1, chunk), one(1), one(1, P),
                                     one(1))),
        "decode_step": (step_fn, (one(B, 1), one(B), one(B, P),
                                  one(B, dt=jnp.bool_))),
        "decode_multi_step": (mstep_fn, (one(B, 1), one(B), one(B, P),
                                         one(B, dt=jnp.bool_), one(B))),
    }


def analyse(cell: str) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import traffic as traffic_mod
    from chipbench.harness import SCHED, load_config
    from chipbench.spec import Benchmark
    from repro.models.model import build_model
    bench = Benchmark()
    c = bench.cell(cell)
    cfg = load_config(bench, c.config)
    tspec = bench.traffic(c.traffic)
    page, chunk = SCHED["page_size"], SCHED["prefill_chunk"]
    max_len = traffic_mod.max_len(tspec, page)
    B = cfg.batch(max_len)
    NP = B * (max_len // page) + 1
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    place = (lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), t))
    model = build_model(cfg.arch, cache_dtype=jnp.bfloat16)
    params = place(model.abstract())
    bank = place(model.init_page_pool(NP, page, abstract=True))
    out = {"batch": B, "max_len": max_len, "num_pages": NP,
           "bank_bytes": sum(x.size * x.dtype.itemsize
                             for x in jax.tree.leaves(bank)),
           "programs": {}}
    for name, (fn, extra) in programs(model, B, max_len, page, chunk,
                                      SCHED["multi_step"]).items():
        args = (params, bank) + tuple(place(e) for e in extra)
        comp = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        ma = comp.memory_analysis()
        out["programs"][name] = {
            "kernel": "tpu_custom_call" in comp.as_text(),
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
        }
        print(cell, name, out["programs"][name], file=sys.stderr,
              flush=True)
    return out


def main(argv=None) -> int:
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    _steer_to_tpu()
    from chipbench.spec import Benchmark
    cells = (argv if argv is not None else sys.argv[1:]) or \
        list(Benchmark().cells)
    print(json.dumps({c: analyse(c) for c in cells}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
