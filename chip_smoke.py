#!/usr/bin/env python3
"""Smoke test of the serving main path on one TPU chip.

    python3 chip_smoke.py [--seed N]         # one chip
    python3 chip_smoke.py --four-chips       # sharded page bank, 4 chips

Everything runs in this one process (a chip belongs to one process at a
time).  Weights and tokens come from ``--seed``; nothing is read from
disk or the network.  Phases, in order — any failure exits non-zero
before the last line is printed:

  1. environment  platform, device kind and count, jax and libtpu
                  versions.  No TPU, or a kernel mode other than
                  ``auto`` (e.g. ``REPRO_PALLAS=interpret``), is an error:
                  there is no CPU or interpreter fallback.
  2. kernels      every main-path Pallas kernel at tinyllama-1.1b widths
                  against its ``ref.py`` oracle (flash prefill, paged
                  decode bf16 + int8, paged verify flat + tree); the
                  compiled program must contain the kernel
                  (``tpu_custom_call``).
  3. parity       tinyllama-1.1b at its published widths: prefill logits
                  and 8 cached paged decode steps with kernels on, against
                  a float32 jnp reference of the same weights (kernels
                  off, highest matmul precision).
  4. main path    ``repro.launch.serve``'s server and ContinuousScheduler
                  (continuous, paged, page 256, prefill chunk 256,
                  multi-step 4) serving tinyllama-1.1b and supersub-super
                  at their own widths on 2 context slots; traffic in
                  bursts makes supersub-super stream into the shadow slot
                  while tinyllama decodes (a prefetch load that overlaps
                  execution), then switches back.

``--four-chips`` runs only the sharded page bank over a 4-device mesh
(mesh placement, and mesh + ``local_read``) against the one-device paged
engine on the same greedy requests.

The last stdout line is ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.kernels as kernels  # noqa: E402
from repro.configs import get_arch, override  # noqa: E402
from repro.core import env  # noqa: E402

MODEL = "tinyllama-1.1b"
PAGE = 256

# Bounds (justified in CHANGES.md).  Kernel outputs are bf16 and the
# TPU runs f32 matmuls as bf16 passes: max |kernel - oracle| relative to
# max |oracle| stays within a few bf16 ulps (2^-8 = 3.9e-3 each).
KERNEL_TOL = 2e-2
# bf16 activations vs a float32 reference: relative L2 error of the
# logits.  The same comparison at full width on the CPU backend gives
# 1.6e-2 at 6 layers and 1.7e-2 at 12 (the error grows slowly with
# depth), so ~2e-2 is expected at 22; 5e-2 leaves 2.5x headroom while
# still catching a wrong kernel or layout (those land near 1).
PARITY_BOUND = 5e-2
# local_read only reorders the softmax reduction across shards (f32):
# logits may move by bf16 re-roundings downstream, not more.
LOCAL_READ_BOUND = 1e-2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def phase(name: str):
    print(f"== {name}", flush=True)


# ---------------------------------------------------------------- phase 1
def environment() -> dict:
    phase("environment")
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    devs = jax.devices()
    dev = devs[0]
    print(f"platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)} jax={jax.__version__} libtpu={libtpu} "
          f"kernel_mode={kernels.get_mode()}", flush=True)
    check(jax.default_backend() == "tpu",
          f"no TPU found (jax backend is {jax.default_backend()!r}); "
          "this smoke test does not fall back to another device")
    check(kernels.get_mode() == "auto",
          f"kernel mode is {kernels.get_mode()!r}: compiled Pallas needs "
          "'auto' (unset REPRO_PALLAS)")
    check(kernels.use_kernels(), "compiled Pallas kernels are off")
    print(f"compile cache: {env.enable_compile_cache()}", flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------- phase 2
def compiled_kernel(fn, *args):
    """AOT-compile ``fn`` and require the Pallas kernel inside it."""
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in the "
          "compiled program (the kernel did not lower)")
    return compiled


def _max_err(name: str, out, ref, tol: float) -> float:
    out = np.asarray(jnp.asarray(out, jnp.float32), np.float64)
    ref = np.asarray(jnp.asarray(ref, jnp.float32), np.float64)
    check(out.shape == ref.shape, f"{name}: shape {out.shape} != "
          f"{ref.shape}")
    check(bool(np.isfinite(out).all()), f"{name}: non-finite output")
    err = float(np.max(np.abs(out - ref)))
    rel = err / max(float(np.max(np.abs(ref))), 1e-30)
    print(f"kernel {name}: max_abs_err={err:.6g} rel_to_max={rel:.6g} "
          f"(bound {tol})", flush=True)
    check(rel <= tol, f"{name}: error {rel:.3g} above {tol}")
    return rel


def _kernel_case(name, fn, ref, args, tol=KERNEL_TOL) -> float:
    out = compiled_kernel(fn, *args)(*args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref)(*args)
    return _max_err(name, out, want, tol)


def _tree_masks(K: int) -> np.ndarray:
    """Ancestor bitmasks of a binary token tree of K nodes (node i's
    parent is (i-1)//2): bit j of mask i == node j visible to node i."""
    anc = [1]
    for i in range(1, K):
        anc.append(anc[(i - 1) // 2] | (1 << i))
    return np.asarray(anc, np.int32)


def kernel_phase(cfg, seed: int, B: int = 8, P: int = 5) -> dict:
    phase(f"kernels at {cfg.name} widths (B={B}, page={PAGE})")
    from repro.kernels.flash_attention.ops import (
        attention_reference, flash_attention)
    from repro.kernels.paged_attention.ops import (
        paged_decode_attention, paged_decode_reference,
        paged_verify_attention, paged_verify_reference)
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    NP = B * P + 1
    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16

    def normal(*shape, dtype=bf):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    table = jnp.asarray(rng.permutation(np.arange(1, NP))[:B * P]
                        .reshape(B, P), jnp.int32)
    pos = jnp.asarray(rng.integers(0, P * PAGE - 8, B), jnp.int32)
    errs = {}

    S = 512
    q, k, v = normal(2, H, S, hd), normal(2, Hkv, S, hd), normal(2, Hkv, S,
                                                                hd)
    errs["flash_prefill"] = _kernel_case(
        "flash_prefill", lambda q, k, v: flash_attention(q, k, v),
        lambda q, k, v: attention_reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32)), (q, k, v))

    kp, vp = normal(NP, Hkv, PAGE, hd), normal(NP, Hkv, PAGE, hd)
    qd = normal(B, H, hd)
    up = lambda x: x.astype(jnp.float32)  # noqa: E731
    errs["paged_decode_bf16"] = _kernel_case(
        "paged_decode_bf16",
        lambda q, kp, vp, t, p: paged_decode_attention(q, kp, vp, t, p),
        lambda q, kp, vp, t, p: paged_decode_reference(up(q), up(kp),
                                                       up(vp), t, p),
        (qd, kp, vp, table, pos))

    codes = lambda: jnp.asarray(  # noqa: E731
        rng.integers(-127, 128, (NP, Hkv, PAGE, hd)), jnp.int8)
    scales = lambda: jnp.asarray(  # noqa: E731
        rng.uniform(0.005, 0.03, (NP, Hkv, 1, PAGE)), jnp.float32)
    kq, vq, ks, vs = codes(), codes(), scales(), scales()
    errs["paged_decode_int8"] = _kernel_case(
        "paged_decode_int8",
        lambda q, kp, vp, t, p, ks, vs: paged_decode_attention(
            q, kp, vp, t, p, k_scale=ks, v_scale=vs),
        lambda q, kp, vp, t, p, ks, vs: paged_decode_reference(
            up(q), kp, vp, t, p, k_scale=ks, v_scale=vs),
        (qd, kq, vq, table, pos, ks, vs))

    K = 5
    qv = normal(B, K, H, hd)
    bk, bv = normal(B, K, Hkv, hd), normal(B, K, Hkv, hd)
    tree = jnp.asarray(np.broadcast_to(_tree_masks(K), (B, K)))
    for name, anc in (("paged_verify_flat", None), ("paged_verify_tree",
                                                     tree)):
        errs[name] = _kernel_case(
            name,
            lambda q, kp, vp, bk, bv, t, p, a=anc: paged_verify_attention(
                q, kp, vp, bk, bv, t, p, tree=a),
            lambda q, kp, vp, bk, bv, t, p, a=anc: paged_verify_reference(
                up(q), up(kp), up(vp), up(bk), up(bv), t, p, tree=a),
            (qv, kp, vp, bk, bv, table, pos))
    return errs


# ---------------------------------------------------------------- phase 3
def _rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _paged_run(m, params, prompt, dec, page: int):
    """Prefill ``prompt`` (B, S), scatter its cache into a page bank,
    then teacher-force the (B, T) ``dec`` tokens through T paged decode
    steps.  Fresh jitted functions: ``use_kernels()`` is read at trace
    time, so each mode must trace its own programs."""
    B, S = prompt.shape
    T = dec.shape[1]
    P = -(-(S + T) // page)
    max_len = P * page
    tables = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)

    def prefill(p, t):
        return m.prefill(p, t, max_len)

    def insert(pool, rows, tables):
        return m.insert_cache_pages(pool, rows, tables)

    def decode(p, pool, tok, pos, tables):
        return m.decode_step_pages(p, pool, tok, pos, tables)

    logits, rows = jax.jit(prefill)(params, prompt)
    pool = jax.jit(insert)(m.init_page_pool(1 + B * P, page), rows, tables)
    del rows
    out = [np.asarray(logits[:, -1], np.float32)]
    step = jax.jit(decode, donate_argnums=(1,))
    for i in range(T):
        pos = jnp.full((B,), S + i, jnp.int32)
        logits, pool = step(params, pool, dec[:, i:i + 1], pos, tables)
        out.append(np.asarray(logits[:, -1], np.float32))
    return out


def parity_phase(cfg, seed: int, B: int = 2, S: int = 256,
                 T: int = 8) -> list[float]:
    phase(f"model parity: {cfg.name} ({cfg.num_layers} layers, d_model "
          f"{cfg.d_model}), prefill {S} + {T} paged decode steps")
    from repro.models.model import build_model
    m = build_model(cfg)
    params = m.init(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    dec = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    got = _paged_run(m, params, prompt, dec, PAGE)

    mode = kernels.get_mode()
    kernels.set_mode("off")
    try:
        ref_m = build_model(override(cfg, dtype="float32"),
                            cache_dtype=jnp.float32)
        params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        with jax.default_matmul_precision("highest"):
            want = _paged_run(ref_m, params32, prompt, dec, PAGE)
    finally:
        kernels.set_mode(mode)
    del params, params32
    errs = [_rel_l2(g, w) for g, w in zip(got, want)]
    for i, e in enumerate(errs):
        label = "prefill" if i == 0 else f"decode{i}"
        print(f"parity {label}: logits rel_l2={e:.6g}", flush=True)
    for g in got:
        check(bool(np.isfinite(g).all()), "non-finite logits")
    worst = max(errs)
    print(f"parity worst rel_l2={worst:.6g} (bound {PARITY_BOUND})",
          flush=True)
    check(worst <= PARITY_BOUND,
          f"logit rel L2 {worst:.3g} above {PARITY_BOUND}")
    return errs


# ---------------------------------------------------------------- phase 4
def _wait_for(pred, what: str, timeout: float = 600.0) -> None:
    deadline = time.monotonic() + timeout
    while not pred():
        check(time.monotonic() < deadline, f"timed out waiting for {what}")
        time.sleep(0.005)


def main_path_phase(seed: int, reduced: bool = False) -> dict:
    from repro.launch.serve import bank_placement, build_server, serve_report
    from repro.serve.scheduler import ContinuousScheduler
    names = [MODEL, "supersub-super"]
    new_tokens = 32
    rng = np.random.default_rng(seed)
    lens = [int(x) for x in rng.integers(128, 1025, 16)]
    max_len = -(-(max(lens) + new_tokens) // PAGE) * PAGE
    phase(f"main path: {names} at "
          f"{'reduced' if reduced else 'published'} widths, "
          f"{len(lens)} requests, prompts {min(lens)}-{max(lens)}, "
          f"{new_tokens} new tokens, max_len {max_len}")
    server, cfgs = build_server(names, slots=2, max_len=max_len,
                                reduced=reduced)
    st = server.engine.stats
    try:
        for n in names:
            c = cfgs[n]
            print(f"context {n}: layers={c.num_layers} d_model={c.d_model} "
                  f"heads={c.num_heads}/{c.num_kv_heads} d_ff={c.d_ff} "
                  f"vocab={c.vocab_size}", flush=True)
        t0 = time.perf_counter()
        with ContinuousScheduler(server, batch_size=8, prefill_chunk=PAGE,
                                 paged=True, page_size=PAGE,
                                 multi_step=4) as sched:
            # three bursts: 4 tinyllama requests; 8 supersub-super ones
            # once tinyllama is stepping (their queue outweighs its 4 live
            # rows past the drain margin, so supersub-super streams into
            # the shadow slot behind tinyllama's steps); 4 more tinyllama
            # ones once both are resident (the way back is a select flip)
            stages = ((MODEL, 4, lambda: True),
                      (names[1], 8, lambda: sched.stats["steps"] >= 1),
                      (MODEL, 4, lambda: st["loads"] >= 2))
            futs, it = [], iter(lens)
            for name, count, ready in stages:
                _wait_for(ready, f"the traffic stage before {count} "
                          f"{name} requests")
                for _ in range(count):
                    toks = rng.integers(0, cfgs[name].vocab_size,
                                        (1, next(it)))
                    futs.append((name, sched.submit(name, toks,
                                                    steps=new_tokens)))
            outs = [(name, np.asarray(f.result(timeout=900)))
                    for name, f in futs]
        wall = time.perf_counter() - t0
        extra = {**sched.snapshot(),
                 "bank_placement": bank_placement(None, None)}
        report = serve_report(server, "continuous", wall, len(lens), extra)
        print("launcher report: " + json.dumps(report, default=str),
              flush=True)
        for name, out in outs:
            vocab = cfgs[name].vocab_size
            check(out.shape == (1, new_tokens),
                  f"{name}: output shape {out.shape}")
            check(bool(((out >= 0) & (out < vocab)).all()),
                  f"{name}: token outside vocabulary [0, {vocab})")
        print(f"main path: loads={st['loads']} "
              f"prefetch_loads={st['prefetch_loads']} "
              f"bytes_loaded={st['bytes_loaded']} "
              f"hidden_load_seconds={st['hidden_load_seconds']:.6g} "
              f"context_changes={st['context_changes']} "
              f"prefetch_failures={st['prefetch_failures']}", flush=True)
        check(st["bytes_loaded"] > 0, "no weight bytes crossed host->HBM")
        check(st["prefetch_loads"] >= 1, "no shadow-slot load: every load "
              "was a demand load")
        check(st["hidden_load_seconds"] > 0, "no load overlapped execution")
        check(st["context_changes"] >= 1, "no context change happened")
        check(st["prefetch_failures"] == 0,
              f"{st['prefetch_failures']} prefetch(es) failed")
        return report
    finally:
        server.shutdown()


# ------------------------------------------------------------ --four-chips
def _greedy_streams(eng, params, prompts, new_tokens: int):
    gens = [eng.admit(params, p[None], max_new=new_tokens)[0]
            for p in prompts]
    while eng.live_slots() or eng.pending_slots():
        eng.step(params)
    return [list(g.tokens) for g in gens]


def _on_mesh_replicated(tree, mesh) -> bool:
    from jax.sharding import NamedSharding
    return all(isinstance(x.sharding, NamedSharding)
               and x.sharding.mesh == mesh
               and x.sharding.is_fully_replicated
               for x in jax.tree.leaves(tree))


def four_chip_phase(cfg_name: str, seed: int, reduced: bool = False,
                    n: int = 4) -> dict:
    from repro.launch.serve import bank_placement, build_server, shard_mesh
    from repro.serve.engine import StepEngine
    phase(f"sharded page bank over {n} devices: {cfg_name}")
    check(jax.device_count() >= n,
          f"--four-chips needs {n} devices, found {jax.device_count()}")
    mesh = shard_mesh(n)
    placement = bank_placement(n, mesh)
    check(placement == "mesh", f"bank placement is {placement!r}, not "
          "'mesh'")
    new_tokens = 16
    rng = np.random.default_rng(seed)
    lens = [int(x) for x in rng.integers(128, 1025, 4)]
    max_len = -(-(max(lens) + new_tokens) // PAGE) * PAGE
    server, cfgs = build_server([cfg_name], slots=2, max_len=max_len,
                                reduced=reduced, mesh=mesh)
    try:
        # the context engine's load is where weights are placed: once,
        # replicated over the mesh — never re-sent per step
        server.engine.preload(cfg_name, block=True)
        server.engine.switch(cfg_name)
        params = server.engine.active.buffers
        check(_on_mesh_replicated(params, mesh),
              "loaded weights are not replicated over the mesh")
        host = server._served[cfg_name].weights_fn()
        one_dev = jax.device_put(host, jax.devices()[0])
        del host
        m = server._served[cfg_name].model
        prompts = [rng.integers(0, cfgs[cfg_name].vocab_size, L)
                   for L in lens]
        kw = dict(batch_size=8, max_len=max_len, paged=True,
                  page_size=PAGE, prefill_chunk=PAGE, multi_step=4)
        ref = _greedy_streams(StepEngine(m, **kw), one_dev, prompts,
                              new_tokens)
        del one_dev
        results = {}
        for label, extra in (("mesh", {}), ("local_read",
                                            {"local_read": True})):
            eng = StepEngine(m, shards=n, mesh=mesh, **kw, **extra)
            leaf = eng.state.caches["b0"].k
            check(len(leaf.devices()) == n and not
                  leaf.sharding.is_fully_replicated,
                  f"{label}: page bank is not split over the mesh")
            got = _greedy_streams(eng, params, prompts, new_tokens)
            same = sum(a == b for a, b in zip(got, ref))
            print(f"{label}: {same}/{len(ref)} greedy streams identical "
                  f"to the one-device engine", flush=True)
            results[label] = same == len(ref)
        check(results["mesh"], "mesh placement changed greedy streams")
        # local_read reorders the softmax reduction: identical streams,
        # or teacher-forced logits within LOCAL_READ_BOUND
        err, step_s = _local_read_logit_err(m, params, mesh, lens[0], rng)
        print(f"local_read teacher-forced logits rel_l2={err:.6g} "
              f"(bound {LOCAL_READ_BOUND})", flush=True)
        print("decode step, median wall s after the first: " + ", ".join(
            f"{k}={v:.6g}" for k, v in step_s.items()), flush=True)
        check(results["local_read"] or err <= LOCAL_READ_BOUND,
              f"local_read: streams differ and logit error {err:.3g} is "
              f"above {LOCAL_READ_BOUND}")
        return {"placement": placement, "local_read_rel_l2": err,
                "decode_step_s": step_s,
                **{f"{k}_identical": v for k, v in results.items()}}
    finally:
        server.shutdown()


def _local_read_logit_err(m, params, mesh, S: int, rng, T: int = 8):
    """Teacher-forced decode logits over the mesh-placed bank: per-shard
    local reads vs the global gather, same inputs.  Returns the worst
    logits rel L2 and each path's median step wall time after its
    first (compiling) step."""
    from repro.models.layers import BankShard
    axis = mesh.axis_names[0]
    B = 2
    P = -(-(S + T) // PAGE)
    NP = mesh.size * (-(-(B * P) // mesh.size) + 1)
    L = NP // mesh.size
    # local page 0 of every shard is that shard's park page
    ids = [p for p in range(NP) if p % L][:B * P]
    tables = jnp.asarray(np.reshape(ids, (B, P)), jnp.int32)
    prompt = jnp.asarray(rng.integers(0, m.cfg.vocab_size, (B, S)),
                         jnp.int32)
    dec = jnp.asarray(rng.integers(0, m.cfg.vocab_size, (B, T)), jnp.int32)
    gather, local = BankShard(mesh, axis, False), BankShard(mesh, axis)
    _, rows = jax.jit(lambda p, t: m.prefill(p, t, P * PAGE, shard=gather))(
        params, prompt)
    pool = m.init_page_pool(NP, PAGE)
    pool = jax.tree.map(jax.device_put, pool,
                        m.page_pool_shardings(pool, mesh, axis))
    pool = jax.jit(m.insert_cache_pages)(pool, rows, tables)
    del rows
    errs, step_s = [], {}
    for label, shard in (("gather", gather), ("local_read", local)):
        step = jax.jit(lambda p, c, t, pos, tb, s=shard:
                       m.decode_step_pages(p, c, t, pos, tb, shard=s))
        c, outs, times = pool, [], []
        for i in range(T):
            pos = jnp.full((B,), S + i, jnp.int32)
            t0 = time.perf_counter()
            logits, c = step(params, c, dec[:, i:i + 1], pos, tables)
            outs.append(np.asarray(logits, np.float32))
            times.append(time.perf_counter() - t0)
        errs.append(outs)
        step_s[label] = float(np.median(times[1:]))
    return max(_rel_l2(a, b) for a, b in zip(errs[1], errs[0])), step_s


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for weights, tokens and kernel inputs")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded page bank over 4 chips "
                         "and the one-device engine it is compared with")
    args = ap.parse_args(argv)
    try:
        device = environment()
        cfg = get_arch(MODEL)
        if args.four_chips:
            print(json.dumps({"four_chips": four_chip_phase(
                MODEL, args.seed)}), flush=True)
        else:
            kernel_phase(cfg, args.seed)
            parity_phase(cfg, args.seed)
            main_path_phase(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
