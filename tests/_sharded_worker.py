"""Sharded-page-bank multi-device checks, run in a subprocess with 4
fake host devices (the CI ``multi-device`` job exports the same flag).

Prints one JSON line: RESULTS_JSON:{check: {"ok": bool, ...}}.
Invoked by tests/test_sharded_devices.py; runnable standalone:
    python tests/_sharded_worker.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import json  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from conftest import reduced_arch, tokens_for  # noqa: E402
from repro.distributed.mesh import make_mesh  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serve.engine import StepEngine  # noqa: E402

RESULTS = {}


def record(name, ok, **extra):
    RESULTS[name] = {"ok": bool(ok), **extra}


def _run_stream(eng, p, prompts, steps, seeds):
    gens = [eng.admit(p, prompts[0], max_new=steps, seeds=[seeds[0]])[0]]
    for _ in range(2):
        eng.step(p)
    gens.append(eng.admit(p, prompts[1], max_new=steps,
                          seeds=[seeds[1]])[0])
    while eng.live_slots():
        eng.step(p)
    return [g.tokens for g in gens]


def main():
    assert jax.device_count() == 4, jax.device_count()
    cfg = reduced_arch("tinyllama-1.1b", dtype="float32",
                       param_dtype="float32")
    m = build_model(cfg, cache_dtype=jnp.float32)
    p = m.init(jax.random.key(0))
    mesh = make_mesh((4,), ("model",))
    prompts = [np.asarray(tokens_for(cfg, 1, 12, seed=3)),
               np.asarray(tokens_for(cfg, 1, 40, seed=4))]

    # the single-device reference streams the signature invariant pins
    refs = {}
    for temp, seeds in ((0.0, [None, None]), (0.8, [7, 9])):
        for chunk in (None, 8):
            one = StepEngine(m, batch_size=2, max_len=256,
                             temperature=temp, paged=True, page_size=64,
                             prefill_chunk=chunk)
            refs[(temp, chunk)] = _run_stream(one, p, prompts, 5, seeds)

    # 1. mesh placement: bank leaves actually live sharded over the mesh
    eng = StepEngine(m, batch_size=2, max_len=256, paged=True,
                     page_size=64, mesh=mesh)
    leaf = eng.state.caches["b0"].k
    sh = leaf.sharding
    placed = (getattr(sh, "mesh", None) is not None
              and "model" in str(sh.spec)
              and len(leaf.devices()) == 4)
    record("bank_placed_over_mesh", placed, spec=str(sh))

    # 2. signature invariant: sharded streams bitwise-identical to the
    # single-device paged engine (greedy + seeded temperature, one-shot
    # + chunked), under forced host device count 4
    ok = True
    for temp, seeds in ((0.0, [None, None]), (0.8, [7, 9])):
        for chunk in (None, 8):
            eng = StepEngine(m, batch_size=2, max_len=256,
                             temperature=temp, paged=True, page_size=64,
                             prefill_chunk=chunk, mesh=mesh)
            got = _run_stream(eng, p, prompts, 5, seeds)
            if got != refs[(temp, chunk)]:
                ok = False
                record(f"mesh_bitwise_t{temp}_c{chunk}", False,
                       got=got, want=refs[(temp, chunk)])
    record("mesh_streams_bitwise", ok)

    # 3. prefix hits stay bitwise under the mesh too
    def hit_run(eng):
        out = [eng.admit(p, prompts[0], max_new=4)[0]]
        while eng.live_slots():
            eng.step(p)
        out.append(eng.admit(p, prompts[0], max_new=4)[0])
        while eng.live_slots():
            eng.step(p)
        return [g.tokens for g in out], eng.stats["prefix_hits"]

    ref_hit, _ = hit_run(StepEngine(m, batch_size=2, max_len=256,
                                    paged=True, page_size=8,
                                    prefix_cache=True))
    got_hit, hits = hit_run(StepEngine(m, batch_size=2, max_len=256,
                                       paged=True, page_size=8,
                                       prefix_cache=True, mesh=mesh))
    record("mesh_prefix_bitwise", got_hit == ref_hit and hits == 1,
           hits=int(hits))

    # 4. local_read: every shard's kernel instance reads only its local
    # bank slice inside shard_map; the cross-shard flash combine changes
    # reduction order, so this tier is greedy-identical in practice and
    # gated allclose on logits-equivalent streams
    eng = StepEngine(m, batch_size=2, max_len=256, paged=True,
                     page_size=64, mesh=mesh, local_read=True)
    got = _run_stream(eng, p, prompts, 5, [None, None])
    record("local_read_greedy_streams", got == refs[(0.0, None)],
           got=got, want=refs[(0.0, None)])
    eng = StepEngine(m, batch_size=2, max_len=256, paged=True,
                     page_size=64, prefill_chunk=8, mesh=mesh,
                     local_read=True)
    got = _run_stream(eng, p, prompts, 5, [None, None])
    record("local_read_chunked_streams", got == refs[(0.0, 8)])

    # 5. Pallas kernels over a split bank (interpret mode here): a Mosaic
    # kernel cannot be auto-partitioned, so the global-gather path runs it
    # on the whole (all-gathered) bank and the local-read path runs the
    # per-shard partial kernel — both stay greedy-identical to the
    # one-device kernel engine, admitting in chunks or one shot (whose
    # prefill runs the flash kernel replicated over the mesh)
    import repro.kernels as kernels
    kernels.set_mode("interpret")
    try:
        ok, runs = True, {}
        for chunk in (8, None):
            kw = dict(batch_size=2, max_len=256, paged=True, page_size=64,
                      prefill_chunk=chunk)
            ref_k = _run_stream(StepEngine(m, **kw), p, prompts, 5,
                                [None, None])
            got_g = _run_stream(StepEngine(m, mesh=mesh, **kw), p, prompts,
                                5, [None, None])
            got_l = _run_stream(StepEngine(m, mesh=mesh, local_read=True,
                                           **kw), p, prompts, 5, [None, None])
            ok &= got_g == ref_k and got_l == ref_k
            runs[str(chunk)] = dict(gather=got_g, local=got_l, want=ref_k)
    finally:
        kernels.set_mode("auto")
    record("mesh_kernel_streams", ok, **runs)

    print("RESULTS_JSON:" + json.dumps(RESULTS))


if __name__ == "__main__":
    main()
