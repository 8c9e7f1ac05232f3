"""int8 page bank: the shared KV page pool stored as int8 codes with
per-token-per-head f32 scales in parallel leaves.

Quantized serving is tolerance-close, NOT bitwise — so the suite is a
parity ladder: exact bounds where exactness exists (roundtrip error,
kernel vs dequantized-row oracle), bounded logit divergence for greedy
teacher-forcing, and distribution-level statistics for sampling
(softmax total-variation distance + same-noise sampled-token agreement).
What stays bitwise: int8 multi-step == int8 single-step — the fused
loop and the tick loop run the same quantized programs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_arch, tokens_for
from repro.models.layers import dequantize_kv, quantize_kv
from repro.models.model import build_model
from repro.serve.engine import StepEngine


@pytest.fixture(scope="module")
def f32_lm():
    cfg = reduced_arch("tinyllama-1.1b", dtype="float32",
                       param_dtype="float32")
    m = build_model(cfg, cache_dtype=jnp.float32)
    return cfg, m, m.init(jax.random.key(0))


def _drain(eng, p):
    while eng.live_slots():
        eng.step(p)


# ---------------------------------------------------------------------------
# quantizer + pool layout
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_bounded():
    """Symmetric absmax int8: per-(token, head) error is at most half a
    quantization step, i.e. absmax/254 (+ rounding slack)."""
    x = jax.random.normal(jax.random.key(1), (3, 4, 20, 32)) * 5.0
    q, scale = quantize_kv(x)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert scale.shape == x.shape[:-1]
    err = jnp.abs(x - dequantize_kv(q, scale))
    assert float(jnp.max(err / scale[..., None])) <= 0.5 + 1e-4


def test_quantized_pool_layout(f32_lm):
    cfg, m, p = f32_lm
    pools = m.init_page_pool(8, 16, quantized=True)
    for c in pools.values():
        R, NP, Hkv, page, hd = c.k.shape
        assert (NP, Hkv, page, hd) == (8, cfg.num_kv_heads, 16,
                                       cfg.head_dim)
        assert c.k.dtype == c.v.dtype == jnp.int8
        assert c.ks.shape == c.vs.shape == (R, NP, Hkv, 1, page)
        assert c.ks.dtype == c.vs.dtype == jnp.float32
        # the headline ratio: codes+scales vs a bf16 pool, per token-head
        bf16 = 2 * hd
        assert (hd + 4) / bf16 < 0.6      # hd=32 reduced: 1.78x fewer


# ---------------------------------------------------------------------------
# kernel parity: int8 pool vs the dequantized-row oracle
# ---------------------------------------------------------------------------

def _quantized_pool_from_rows(k, v, page, seed, spare_pages=3):
    """Quantize a contiguous (B, Hkv, S, hd) row cache per token-head and
    scatter codes + scales into a SHUFFLED shared pool (garbage codes in
    unreferenced pages).  Returns the pool leaves, the tables, and the
    dequantized rows — the exact values the kernel must reproduce."""
    B, Hkv, S, hd = k.shape
    P = S // page
    NP = B * P + 1 + spare_pages
    rng = np.random.default_rng(seed)
    table = rng.permutation(np.arange(1, NP))[:B * P].reshape(B, P)
    kq, ksc = quantize_kv(k)
    vq, vsc = quantize_kv(v)
    kp = rng.integers(-127, 128, (NP, Hkv, page, hd)).astype(np.int8)
    vp = rng.integers(-127, 128, (NP, Hkv, page, hd)).astype(np.int8)
    ks = rng.random((NP, Hkv, 1, page)).astype(np.float32)
    vs = rng.random((NP, Hkv, 1, page)).astype(np.float32)
    for b in range(B):
        for j in range(P):
            sl = slice(j * page, (j + 1) * page)
            kp[table[b, j]] = np.asarray(kq[b, :, sl])
            vp[table[b, j]] = np.asarray(vq[b, :, sl])
            ks[table[b, j], :, 0] = np.asarray(ksc[b, :, sl])
            vs[table[b, j], :, 0] = np.asarray(vsc[b, :, sl])
    deq = (dequantize_kv(kq, ksc), dequantize_kv(vq, vsc))
    return (jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(ks),
            jnp.asarray(vs), jnp.asarray(table, jnp.int32), deq)


@pytest.mark.parametrize("B,H,Hkv,S,hd,page,pos", [
    (2, 4, 2, 64, 32, 16, (30, 63)),
    (1, 4, 4, 128, 32, 32, 0),             # first token
])
def test_int8_paged_decode_matches_dequant_oracle(B, H, Hkv, S, hd, page,
                                                  pos):
    from repro.kernels.decode_attention.ref import decode_reference
    from repro.kernels.paged_attention.ops import (
        paged_decode_attention, paged_decode_reference)
    ks = jax.random.split(jax.random.key(S + page), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, Hkv, S, hd))
    v = jax.random.normal(ks[2], (B, Hkv, S, hd))
    kp, vp, kscale, vscale, table, (kd, vd) = _quantized_pool_from_rows(
        k, v, page, seed=S)
    pos = jnp.asarray(pos, jnp.int32)
    ref = decode_reference(q, kd, vd, pos, ring=False)
    pref = paged_decode_reference(q, kp, vp, table, pos,
                                  k_scale=kscale, v_scale=vscale)
    np.testing.assert_allclose(np.asarray(pref), np.asarray(ref),
                               atol=1e-6)
    out = paged_decode_attention(q, kp, vp, table, pos,
                                 k_scale=kscale, v_scale=vscale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-3)


@pytest.mark.parametrize("B,H,Hkv,S,hd,page,K,pos", [
    (2, 4, 2, 64, 32, 16, 4, (40, 3)),
    (1, 4, 2, 64, 32, 32, 3, 0),
])
def test_int8_paged_verify_matches_dequant_oracle(B, H, Hkv, S, hd, page,
                                                  K, pos):
    """Mixed precision by design: int8 pool history, full-precision
    in-flight verify block."""
    from repro.kernels.paged_attention.ops import (
        paged_verify_attention, paged_verify_reference)
    from repro.kernels.verify_attention.ref import verify_reference
    ks = jax.random.split(jax.random.key(S + K), 5)
    q = jax.random.normal(ks[0], (B, K, H, hd))
    k = jax.random.normal(ks[1], (B, Hkv, S, hd))
    v = jax.random.normal(ks[2], (B, Hkv, S, hd))
    bk = jax.random.normal(ks[3], (B, K, Hkv, hd))
    bv = jax.random.normal(ks[4], (B, K, Hkv, hd))
    kp, vp, kscale, vscale, table, (kd, vd) = _quantized_pool_from_rows(
        k, v, page, seed=S + 1)
    pos = jnp.asarray(pos, jnp.int32)
    ref = verify_reference(q, kd, vd, bk, bv, pos, ring=False)
    pref = paged_verify_reference(q, kp, vp, bk, bv, table, pos,
                                  k_scale=kscale, v_scale=vscale)
    np.testing.assert_allclose(np.asarray(pref), np.asarray(ref),
                               atol=1e-6)
    out = paged_verify_attention(q, kp, vp, bk, bv, table, pos,
                                 k_scale=kscale, v_scale=vscale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-3)


# ---------------------------------------------------------------------------
# model-level divergence: int8 pool vs f32 pool, teacher-forced
# ---------------------------------------------------------------------------

def test_int8_logit_divergence_bounded(f32_lm):
    """Admit the same prompt into an f32 page pool and an int8 page pool,
    teacher-force the f32 greedy continuation through BOTH, and bound the
    damage per step: small worst-case logit error relative to the logit
    spread, small softmax total-variation distance at serving
    temperature, and high same-noise sampled-token agreement (the
    statistical sampling test: identical gumbel noise, the two logit
    sets must pick the same token nearly always)."""
    cfg, m, p = f32_lm
    page, P, steps, temp = 16, 4, 8, 0.8
    L = 12
    toks = jnp.asarray(tokens_for(cfg, 2, L, seed=3))
    B = toks.shape[0]
    max_len = P * page
    logits, rows = m.prefill(p, toks, max_len)
    tables = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)

    pools = {}
    for mode in ("f32", "int8"):
        pool = m.init_page_pool(1 + B * P + 2, page,
                                quantized=mode == "int8")
        pools[mode] = m.insert_cache_pages(pool, rows, tables)

    tok = jnp.argmax(logits[:, -1], -1)
    pos = jnp.full((B,), L, jnp.int32)
    worst_rel, worst_tv, worst_agree, greedy_same = 0.0, 0.0, 1.0, 0
    for i in range(steps):
        lf, pools["f32"] = m.decode_step_pages(
            p, pools["f32"], tok[:, None], pos, tables)
        lq, pools["int8"] = m.decode_step_pages(
            p, pools["int8"], tok[:, None], pos, tables)
        lf, lq = lf[:, -1], lq[:, -1]
        spread = jnp.max(lf, -1) - jnp.min(lf, -1)
        rel = jnp.max(jnp.abs(lf - lq), -1) / spread
        tv = 0.5 * jnp.sum(jnp.abs(jax.nn.softmax(lf / temp)
                                   - jax.nn.softmax(lq / temp)), -1)
        g = jax.random.gumbel(jax.random.key(i), (64,) + lf.shape)
        agree = jnp.mean(jnp.argmax(lf / temp + g, -1)
                         == jnp.argmax(lq / temp + g, -1))
        worst_rel = max(worst_rel, float(jnp.max(rel)))
        worst_tv = max(worst_tv, float(jnp.max(tv)))
        worst_agree = min(worst_agree, float(agree))
        greedy_same += int(jnp.all(jnp.argmax(lf, -1)
                                   == jnp.argmax(lq, -1)))
        tok = jnp.argmax(lf, -1)           # teacher-force the f32 stream
        pos = pos + 1
    # Random-init weights are the worst case for quantization (no learned
    # redundancy); measured worst rel ~0.11, tv ~0.023, agree ~0.98.
    assert worst_rel < 0.2, worst_rel      # <20% of the logit spread
    assert worst_tv < 0.05, worst_tv
    assert worst_agree > 0.9, worst_agree
    assert greedy_same >= steps - 2        # greedy picks survive quant


# ---------------------------------------------------------------------------
# engine: int8 multi-step is bitwise int8 single-step; no page leaks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_int8_multistep_bitwise_matches_int8_single(f32_lm, temperature):
    cfg, m, p = f32_lm

    def run(T):
        eng = StepEngine(m, batch_size=3, max_len=64,
                         temperature=temperature, seed=5, paged=True,
                         page_size=16, multi_step=T, quantize_kv="int8")
        seeds = [7, 9] if temperature > 0 else [None, None]
        gens = eng.admit(p, np.asarray(tokens_for(cfg, 1, 8, seed=1)),
                         max_new=6, seeds=seeds[:1])
        gens += eng.admit(p, np.asarray(tokens_for(cfg, 1, 20, seed=2)),
                          max_new=9, seeds=seeds[1:])
        _drain(eng, p)
        assert eng.free_pages() == eng._pages.allocatable   # no leaks
        return [g.tokens for g in gens]

    assert run(4) == run(1)


def test_quantize_guards(f32_lm):
    cfg, m, p = f32_lm
    with pytest.raises(ValueError, match="paged"):
        StepEngine(m, batch_size=2, max_len=64, quantize_kv="int8")
    with pytest.raises(ValueError, match="quantize_kv"):
        StepEngine(m, batch_size=2, max_len=64, paged=True, page_size=16,
                   quantize_kv="int4")
