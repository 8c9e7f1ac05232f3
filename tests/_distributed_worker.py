"""Multi-device checks, run in a subprocess with 8 fake host devices.

Prints one JSON line: {check_name: {"ok": bool, "err": float}}.
Invoked by tests/test_distributed.py; runnable standalone:
    python tests/_distributed_worker.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import get_arch, override, reduced  # noqa: E402
from repro.configs.base import OptimizerConfig, ParallelConfig, RunConfig  # noqa: E402
from jax import shard_map  # noqa: E402
from repro.distributed.mesh import make_mesh  # noqa: E402
from repro.distributed.sharding import DEFAULT_RULES, shard_params_tree  # noqa: E402
from repro.models.model import build_model  # noqa: E402

RESULTS = {}


def record(name, ok, err=0.0):
    RESULTS[name] = {"ok": bool(ok), "err": float(err)}


# ---------------------------------------------------------------------------
# 1. EP MoE (shard_map all_to_all) == dense reference
# ---------------------------------------------------------------------------

def check_moe_ep():
    from repro.models.common import init_params
    from repro.models.moe import moe_dense_ref, moe_ep, moe_specs
    cfg = reduced(get_arch("qwen3-moe-235b-a22b"))
    cfg = override(cfg, moe=override(cfg.moe, num_experts=4, top_k=2,
                                     capacity_factor=4.0))  # no drops
    mesh = make_mesh((2, 4), ("data", "model"))
    specs = moe_specs(cfg)
    p = init_params(jax.random.key(0), specs)
    x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model))
    y_ref, aux_ref = moe_dense_ref(p, x, cfg)
    with mesh:
        y_ep, aux_ep = jax.jit(
            lambda p, x: moe_ep(p, x, cfg, mesh))(p, x)
    err = float(jnp.abs(y_ref - y_ep).max())
    record("moe_ep_vs_ref", err < 5e-4, err)


# ---------------------------------------------------------------------------
# 2. sharded train step == single-device step
# ---------------------------------------------------------------------------

def check_sharded_training():
    from repro.train.trainer import init_state, make_train_step
    cfg = override(reduced(get_arch("tinyllama-1.1b")), dtype="float32")
    rc = RunConfig(optimizer=OptimizerConfig(lr=1e-3),
                   parallel=ParallelConfig())
    batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 32), 0,
                                          cfg.vocab_size)}
    m0 = build_model(cfg)
    s0 = init_state(m0, jax.random.key(0), rc)
    out0, met0 = jax.jit(make_train_step(m0, rc))(s0, batch)

    mesh = make_mesh((4, 2), ("data", "model"))
    m1 = build_model(cfg, mesh=mesh)
    with mesh:
        s1 = init_state(m1, jax.random.key(0), rc)
        sh = shard_params_tree(mesh, s1["params"], m1.logical())
        s1["params"] = jax.device_put(s1["params"], sh)
        out1, met1 = jax.jit(make_train_step(m1, rc, mesh))(s1, batch)
    err = abs(float(met0["loss"]) - float(met1["loss"]))
    perr = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree.leaves(out0["params"]), jax.tree.leaves(out1["params"])))
    record("sharded_train_step", err < 1e-4 and perr < 1e-3,
           max(err, perr))


# ---------------------------------------------------------------------------
# 3. int8 error-feedback gradient compression across the pod axis
# ---------------------------------------------------------------------------

def check_compression():
    from repro.distributed.compression import compressed_psum_mean
    mesh = make_mesh((2, 4), ("pod", "data"))
    g = jax.random.normal(jax.random.key(0), (2, 64))  # per-pod grads
    ef = jnp.zeros((2, 64))

    def body(g, ef):
        red, ef = compressed_psum_mean({"g": g[0]}, "pod", {"g": ef[0]})
        return red["g"], ef["g"]

    f = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("pod"), P("pod")),
        out_specs=(P(), P("pod")), check_vma=False))
    red, ef_out = f(g, ef)
    true_mean = g.mean(0)
    err = float(jnp.abs(red - true_mean).max())
    # int8 with shared scale: |err| <= scale = amax/127 (+ mean div)
    bound = float(jnp.abs(g).max()) / 127.0
    resid_ok = float(jnp.abs(ef_out).max()) <= bound + 1e-6
    record("int8_ef_compression", err <= bound + 1e-6 and resid_ok, err)


# ---------------------------------------------------------------------------
# 4. pipeline parallelism == direct apply
# ---------------------------------------------------------------------------

def check_pipeline():
    from repro.distributed.pipeline import pipeline_apply
    mesh = make_mesh((4,), ("pipe",))
    S, B, D = 4, 8, 16
    ws = jax.random.normal(jax.random.key(0), (S, D, D)) / np.sqrt(D)
    x = jax.random.normal(jax.random.key(1), (B, D))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    with mesh:
        y_pipe = pipeline_apply(stage_fn, mesh, ws, x, num_microbatches=4)
    y_ref = x
    for s in range(S):
        y_ref = stage_fn(ws[s], y_ref)
    err = float(jnp.abs(y_pipe - y_ref).max())
    record("pipeline_1f1b", err < 1e-5, err)


# ---------------------------------------------------------------------------
# 5. elastic restart: checkpoint on mesh A, restore on smaller mesh B
# ---------------------------------------------------------------------------

def check_elastic(tmp="/tmp/repro_elastic_test"):
    import shutil
    from repro.train.checkpoint import CheckpointManager
    from repro.train.elastic import restore_elastic
    from repro.train.trainer import init_state
    shutil.rmtree(tmp, ignore_errors=True)
    cfg = override(reduced(get_arch("tinyllama-1.1b")), dtype="float32")
    rc = RunConfig()
    mesh_a = make_mesh((4, 2), ("data", "model"))
    m = build_model(cfg, mesh=mesh_a)
    with mesh_a:
        state = init_state(m, jax.random.key(0), rc)
        sh = shard_params_tree(mesh_a, state["params"], m.logical())
        state["params"] = jax.device_put(state["params"], sh)
    mgr = CheckpointManager(tmp, keep=2, async_save=False)
    mgr.save(1, state, extra={"step": 1})
    mgr.wait()

    mesh_b = make_mesh((2, 1), ("data", "model"))   # "lost" 6 of 8 devices
    m_b = build_model(cfg, mesh=mesh_b)
    with mesh_b:
        restored, extra = restore_elastic(tmp, m_b, rc, mesh_b,
                                          jax.random.key(0))
    err = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree.leaves(jax.device_get(state["params"])),
        jax.tree.leaves(jax.device_get(restored["params"]))))
    ok = err == 0.0 and extra.get("step") == 1
    shards = jax.tree.leaves(restored["params"])[0].sharding
    record("elastic_restore", ok and shards.mesh.shape == mesh_b.shape, err)


# ---------------------------------------------------------------------------
# 6. kv-seq-sharded decode (SP) == replicated decode
# ---------------------------------------------------------------------------

def check_sp_decode():
    cfg = override(reduced(get_arch("deepseek-7b")), dtype="float32")
    m0 = build_model(cfg)
    m0.cache_dtype = jnp.float32
    p = m0.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    logits0, caches0 = m0.prefill(p, toks, max_len=32)
    step0, c0 = m0.decode_step(p, caches0, toks[:, :1], jnp.int32(16))

    mesh = make_mesh((2, 4), ("data", "model"))
    rules = DEFAULT_RULES.with_(kv_heads=None, kv_seq="model")
    m1 = build_model(cfg, mesh=mesh, rules=rules)
    m1.cache_dtype = jnp.float32
    with mesh:
        logits1, caches1 = jax.jit(
            lambda p, t: m1.prefill(p, t, 32))(p, toks)
        step1, _ = jax.jit(m1.decode_step)(p, caches1, toks[:, :1],
                                           jnp.int32(16))
    err = float(jnp.abs(step0 - step1).max())
    record("sp_decode_seq_sharded_kv", err < 5e-3, err)


if __name__ == "__main__":
    for fn in (check_moe_ep, check_sharded_training, check_compression,
               check_pipeline, check_elastic, check_sp_decode):
        try:
            fn()
        except Exception as e:  # pragma: no cover
            record(fn.__name__, False, -1.0)
            RESULTS[fn.__name__]["exc"] = repr(e)
    print("RESULTS_JSON:" + json.dumps(RESULTS))
