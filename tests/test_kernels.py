"""Per-kernel correctness sweeps: every Pallas kernel (interpret mode on CPU)
against its pure-jnp oracle over shapes x dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import (
    decode_attention, decode_reference)
from repro.kernels.verify_attention.ops import (
    verify_attention, verify_reference)
from repro.kernels.flash_attention.ops import (
    attention_reference, flash_attention)
from repro.kernels.gmm.ops import (
    expert_mlp, expert_mlp_reference, gmm, gmm_reference)
from repro.kernels.mlstm_chunk.ops import (
    mlstm_chunk, mlstm_chunk_reference, mlstm_recurrent_reference)
from repro.kernels.ssm_scan.ops import (
    selective_scan, selective_scan_reference)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,Hkv,S,hd", [
    (2, 4, 2, 128, 64),       # GQA
    (1, 8, 8, 256, 32),       # MHA
    (2, 4, 1, 96, 64),        # MQA + padding (96 % 64 != 0)
    (1, 2, 2, 64, 128),       # head_dim 128
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(B, H, Hkv, S, hd, dtype):
    ks = jax.random.split(jax.random.key(S + hd), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, hd), dtype)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=1e-2)


@pytest.mark.parametrize("window", [16, 64, 128])
def test_flash_attention_sliding_window(window):
    B, H, Hkv, S, hd = 1, 4, 2, 256, 32
    ks = jax.random.split(jax.random.key(window), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, Hkv, S, hd))
    v = jax.random.normal(ks[2], (B, Hkv, S, hd))
    out = flash_attention(q, k, v, window=window, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=1e-3)


def test_flash_attention_block_shape_independence():
    """Numerical result must not depend on the BlockSpec tiling."""
    B, H, S, hd = 1, 2, 256, 64
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd))
    v = jax.random.normal(ks[2], (B, H, S, hd))
    outs = [flash_attention(q, k, v, block_q=bq, block_k=bk)
            for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,Hkv,S,hd,pos,ring", [
    (2, 8, 2, 256, 64, 100, False),
    (1, 4, 4, 512, 32, 511, False),
    (2, 8, 2, 128, 64, 300, True),      # wrapped ring (SWA)
    (2, 8, 2, 128, 64, 60, True),       # unwrapped ring
    (1, 16, 1, 256, 64, 0, False),      # first token
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_oracle(B, H, Hkv, S, hd, pos, ring, dtype):
    ks = jax.random.split(jax.random.key(S + pos), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, hd), dtype)
    out = decode_attention(q, k, v, pos, ring=ring, block_k=64)
    ref = decode_reference(q, k, v, pos, ring=ring)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=1e-2)


def test_decode_matches_flash_last_row():
    """Decoding the final position == last row of full flash attention."""
    B, H, S, hd = 1, 4, 128, 32
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd))
    v = jax.random.normal(ks[2], (B, H, S, hd))
    full = flash_attention(q, k, v, block_q=32, block_k=32)
    dec = decode_attention(q[:, :, -1], k, v, S - 1, block_k=32)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, :, -1]),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# verify attention (multi-token speculative verify)
# ---------------------------------------------------------------------------

def _verify_inputs(B, H, Hkv, S, hd, K, dtype, seed):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (B, K, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, hd), dtype)
    bk = jax.random.normal(ks[3], (B, K, Hkv, hd), dtype)
    bv = jax.random.normal(ks[4], (B, K, Hkv, hd), dtype)
    return q, k, v, bk, bv


@pytest.mark.parametrize("B,H,Hkv,S,hd,K,ring,pos", [
    (2, 8, 2, 256, 64, 4, False, (100, 3)),    # per-row positions (GQA)
    (1, 4, 4, 128, 32, 5, False, (120,)),      # MHA, near the cache end
    (2, 8, 2, 64, 64, 4, True, (200, 30)),     # wrapped + unwrapped rows
    (2, 4, 2, 64, 32, 3, True, (62, 64)),      # ring wraps mid-block
    (1, 16, 1, 128, 64, 2, False, (1,)),       # single-token prompt
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_verify_attention_matches_oracle(B, H, Hkv, S, hd, K, ring, pos,
                                         dtype):
    q, k, v, bk, bv = _verify_inputs(B, H, Hkv, S, hd, K, dtype, S + K)
    pos = jnp.asarray(pos, jnp.int32)
    out = verify_attention(q, k, v, bk, bv, pos, ring=ring, block_k=32)
    ref = verify_reference(q, k, v, bk, bv, pos, ring=ring)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=1e-2)


@pytest.mark.parametrize("S,ring,pos", [
    (128, False, (40, 3)),
    (64, True, (90, 30)),       # wrapped ring: the case write-then-mask
    (64, True, (63, 66)),       # formulations get wrong
])
def test_verify_reference_is_sequentially_exact(S, ring, pos):
    """The verify oracle == K iterations of the one-token decode oracle
    with the block's k/v written progressively — query i sees exactly the
    cache state the i-th sequential step would, including ring slots that
    later block tokens overwrite."""
    B, K, H, Hkv, hd = 2, 4, 4, 2, 32
    q, k, v, bk, bv = _verify_inputs(B, H, Hkv, S, hd, K, jnp.float32, 11)
    posv = np.asarray(pos, np.int32)
    ref = np.asarray(verify_reference(q, k, v, bk, bv,
                                      jnp.asarray(posv), ring=ring))
    kk, vv = np.array(k), np.array(v)
    for i in range(K):
        p = posv + i
        slot = p % S if ring else np.minimum(p, S - 1)
        for b in range(B):
            kk[b, :, slot[b]] = np.asarray(bk)[b, i]
            vv[b, :, slot[b]] = np.asarray(bv)[b, i]
        step = decode_reference(q[:, i], jnp.asarray(kk), jnp.asarray(vv),
                                jnp.asarray(p), ring=ring)
        np.testing.assert_allclose(ref[:, i], np.asarray(step), atol=2e-5)


# ---------------------------------------------------------------------------
# paged attention (per-row page tables over one shared page pool)
# ---------------------------------------------------------------------------

def _paged_from_rows(k, v, page, seed, spare_pages=3):
    """Scatter a contiguous (B, Hkv, S, hd) row cache into a SHUFFLED
    shared page pool: non-contiguous, interleaved-across-rows tables are
    the case a paged kernel must get right.  Page 0 stays the park page;
    ``spare_pages`` extra pages hold garbage (never referenced)."""
    B, Hkv, S, hd = k.shape
    P = S // page
    NP = B * P + 1 + spare_pages
    rng = np.random.default_rng(seed)
    perm = rng.permutation(np.arange(1, NP))[:B * P]
    table = perm.reshape(B, P)
    kp = rng.normal(size=(NP, Hkv, page, hd)).astype(np.asarray(k).dtype)
    vp = rng.normal(size=(NP, Hkv, page, hd)).astype(np.asarray(v).dtype)
    for b in range(B):
        for j in range(P):
            kp[table[b, j]] = np.asarray(k[b, :, j * page:(j + 1) * page])
            vp[table[b, j]] = np.asarray(v[b, :, j * page:(j + 1) * page])
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table, jnp.int32)


@pytest.mark.parametrize("B,H,Hkv,S,hd,page,pos", [
    (2, 8, 2, 256, 64, 64, (100, 255)),    # GQA, per-row positions
    (1, 4, 4, 512, 32, 128, 511),          # MHA, last position
    (3, 16, 1, 128, 64, 32, (0, 60, 127)),  # MQA, first token in the mix
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_matches_row_oracle(B, H, Hkv, S, hd, page,
                                                   pos, dtype):
    """Kernel AND paged ref against the contiguous-row oracle, through a
    shuffled non-contiguous page table."""
    from repro.kernels.paged_attention.ops import (
        paged_decode_attention, paged_decode_reference)
    ks = jax.random.split(jax.random.key(S + page), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, hd), dtype)
    kp, vp, table = _paged_from_rows(k, v, page, seed=S)
    pos = jnp.asarray(pos, jnp.int32)
    ref = decode_reference(q, k, v, pos, ring=False)
    pref = paged_decode_reference(q, kp, vp, table, pos)
    np.testing.assert_allclose(np.asarray(pref, np.float32),
                               np.asarray(ref, np.float32), atol=1e-6)
    out = paged_decode_attention(q, kp, vp, table, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=1e-2)


@pytest.mark.parametrize("B,H,Hkv,S,hd,page,K,pos", [
    (2, 8, 2, 256, 64, 64, 4, (100, 3)),
    (1, 4, 2, 128, 32, 32, 5, 0),          # admission chunk at pos 0
    (2, 4, 4, 128, 64, 64, 3, (126, 40)),  # block reaches the row's end
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_verify_attention_matches_row_oracle(B, H, Hkv, S, hd, page,
                                                   K, pos, dtype):
    from repro.kernels.paged_attention.ops import (
        paged_verify_attention, paged_verify_reference)
    q, k, v, bk, bv = _verify_inputs(B, H, Hkv, S, hd, K, dtype, S + K)
    kp, vp, table = _paged_from_rows(k, v, page, seed=S + 1)
    pos = jnp.asarray(pos, jnp.int32)
    ref = verify_reference(q, k, v, bk, bv, pos, ring=False)
    pref = paged_verify_reference(q, kp, vp, bk, bv, table, pos)
    np.testing.assert_allclose(np.asarray(pref, np.float32),
                               np.asarray(ref, np.float32), atol=1e-6)
    out = paged_verify_attention(q, kp, vp, bk, bv, table, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=1e-2)


def _stacked_bank(R, NP, Hkv, page, hd, quantized, seed):
    """Random stacked pools (R, NP, Hkv, page, hd): bf16 values, or int8
    codes with (R, NP, Hkv, 1, page) f32 scales."""
    ks = jax.random.split(jax.random.key(seed), 4)
    shape = (R, NP, Hkv, page, hd)
    if not quantized:
        return (jax.random.normal(ks[0], shape, jnp.bfloat16),
                jax.random.normal(ks[1], shape, jnp.bfloat16), None, None)
    code = lambda k: jax.random.randint(k, shape, -127, 128,  # noqa: E731
                                        jnp.int32).astype(jnp.int8)
    scale = lambda k: jax.random.uniform(  # noqa: E731
        k, (R, NP, Hkv, 1, page), jnp.float32, 1e-3, 2e-2)
    return code(ks[0]), code(ks[1]), scale(ks[2]), scale(ks[3])


@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kind", ["decode", "verify", "tree", "partial"])
def test_stacked_bank_kernel_matches_one_pool(kind, quantized, layer):
    """A paged kernel handed the whole stacked bank and a layer index
    gives bitwise what it gives on that layer's pool alone."""
    from repro.kernels.paged_attention.ops import (
        paged_decode_attention, paged_decode_partial, paged_verify_attention)
    R, NP, B, H, Hkv, page, hd, P, K = 3, 9, 2, 4, 2, 16, 32, 4, 3
    r = 0 if layer == "first" else R - 1
    kp, vp, kss, vss = _stacked_bank(R, NP, Hkv, page, hd, quantized, R + K)
    keys = jax.random.split(jax.random.key(7), 3)
    table = jax.random.randint(keys[0], (B, P), 1, NP, jnp.int32)
    pos = jnp.asarray([page * P - 1, page + 3], jnp.int32)
    one = lambda x: None if x is None else x[r]  # noqa: E731

    if kind in ("decode", "partial"):
        q = jax.random.normal(keys[1], (B, H, hd), jnp.bfloat16)
        if kind == "decode":
            f = lambda k, v, ks, vs, **kw: paged_decode_attention(  # noqa
                q, k, v, table, pos, k_scale=ks, v_scale=vs, **kw)
        else:                    # a shard whose slice starts at page 4
            f = lambda k, v, ks, vs, **kw: paged_decode_partial(  # noqa
                q, k, v, table, pos, 4, k_scale=ks, v_scale=vs, **kw)
    else:
        q = jax.random.normal(keys[1], (B, K, H, hd), jnp.bfloat16)
        blk = jax.random.normal(keys[2], (2, B, K, Hkv, hd), jnp.bfloat16)
        tree = (jnp.asarray([[1, 3, 5], [1, 3, 7]], jnp.int32)
                if kind == "tree" else None)
        f = lambda k, v, ks, vs, **kw: paged_verify_attention(  # noqa
            q, k, v, blk[0], blk[1], table, pos, k_scale=ks, v_scale=vs,
            tree=tree, **kw)
    got = f(kp, vp, kss, vss, layer=jnp.int32(r), interpret=True)
    want = f(one(kp), one(vp), one(kss), one(vss), interpret=True)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,L,d_in,N", [
    (2, 64, 128, 16), (1, 128, 64, 8), (2, 96, 192, 16), (1, 256, 32, 4),
])
@pytest.mark.parametrize("with_init", [False, True])
def test_ssm_scan_matches_oracle(B, L, d_in, N, with_init):
    ks = jax.random.split(jax.random.key(L + d_in), 7)
    u = jax.random.normal(ks[0], (B, L, d_in))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, d_in)))
    Bm = jax.random.normal(ks[2], (B, L, N))
    Cm = jax.random.normal(ks[3], (B, L, N))
    A = -jnp.exp(jax.random.normal(ks[4], (d_in, N)) * 0.5)
    D = jax.random.normal(ks[5], (d_in,))
    s0 = jax.random.normal(ks[6], (B, d_in, N)) if with_init else None
    y, s = selective_scan(u, dt, Bm, Cm, A, D, s0, block_d=64, block_l=32)
    yr, sr = selective_scan_reference(u, dt, Bm, Cm, A, D, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=1e-4)


def test_ssm_scan_chunk_handoff():
    """Scanning [0:L] == scanning [0:L/2] then [L/2:L] with carried state."""
    B, L, d_in, N = 1, 64, 32, 8
    ks = jax.random.split(jax.random.key(11), 6)
    u = jax.random.normal(ks[0], (B, L, d_in))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, d_in)))
    Bm = jax.random.normal(ks[2], (B, L, N))
    Cm = jax.random.normal(ks[3], (B, L, N))
    A = -jnp.exp(jax.random.normal(ks[4], (d_in, N)) * 0.5)
    D = jax.random.normal(ks[5], (d_in,))
    y_full, s_full = selective_scan(u, dt, Bm, Cm, A, D, block_l=16)
    h = L // 2
    y1, s1 = selective_scan(u[:, :h], dt[:, :h], Bm[:, :h], Cm[:, :h], A, D,
                            block_l=16)
    y2, s2 = selective_scan(u[:, h:], dt[:, h:], Bm[:, h:], Cm[:, h:], A, D,
                            s1, block_l=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=1e-4)


# ---------------------------------------------------------------------------
# chunkwise mLSTM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,L,dh,c", [
    (2, 2, 64, 32, 16), (1, 4, 128, 64, 32), (2, 1, 96, 48, 32),
])
def test_mlstm_chunk_matches_recurrent_oracle(B, H, L, dh, c):
    ks = jax.random.split(jax.random.key(L + dh), 5)
    q = jax.random.normal(ks[0], (B, H, L, dh))
    k = jax.random.normal(ks[1], (B, H, L, dh))
    v = jax.random.normal(ks[2], (B, H, L, dh))
    li = jax.random.normal(ks[3], (B, H, L)) * 0.5
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, H, L)) + 1.0)
    h, (C, n, m) = mlstm_chunk(q, k, v, li, lf, chunk=c)
    hr, (Cr, nr, mr) = mlstm_recurrent_reference(q, k, v, li, lf)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=5e-4)
    np.testing.assert_allclose(np.asarray(C), np.asarray(Cr), atol=5e-4)
    np.testing.assert_allclose(np.asarray(n), np.asarray(nr), atol=5e-4)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr), atol=1e-5)


def test_mlstm_chunk_matches_chunkwise_oracle():
    B, H, L, dh = 1, 2, 128, 32
    ks = jax.random.split(jax.random.key(3), 5)
    q = jax.random.normal(ks[0], (B, H, L, dh))
    k = jax.random.normal(ks[1], (B, H, L, dh))
    v = jax.random.normal(ks[2], (B, H, L, dh))
    li = jax.random.normal(ks[3], (B, H, L)) * 0.5
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, H, L)))
    h, _ = mlstm_chunk(q, k, v, li, lf, chunk=32)
    hr, _ = mlstm_chunk_reference(q, k, v, li, lf, 32)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=2e-4)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,C,D,F", [
    (4, 64, 128, 256), (2, 128, 64, 96), (8, 32, 32, 64), (1, 16, 16, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_matches_oracle(E, C, D, F, dtype):
    ks = jax.random.split(jax.random.key(E + C), 2)
    x = jax.random.normal(ks[0], (E, C, D), dtype)
    w = jax.random.normal(ks[1], (E, D, F), dtype)
    out = gmm(x, w, block_c=32, block_f=32, block_d=32)
    ref = gmm_reference(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=(0.5 if dtype == jnp.bfloat16 else 1e-4))


def test_expert_mlp_matches_oracle():
    E, C, D, F = 4, 32, 64, 128
    ks = jax.random.split(jax.random.key(9), 4)
    x = jax.random.normal(ks[0], (E, C, D))
    wg = jax.random.normal(ks[1], (E, D, F)) / 8
    wu = jax.random.normal(ks[2], (E, D, F)) / 8
    wd = jax.random.normal(ks[3], (E, F, D)) / 8
    out = expert_mlp(x, wg, wu, wd, block_c=16, block_f=32, block_d=32)
    ref = expert_mlp_reference(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                               rtol=1e-3)


# ---------------------------------------------------------------------------
# end-to-end: whole models with kernels in interpret mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["tinyllama-1.1b", "mixtral-8x7b",
                                  "xlstm-125m", "jamba-v0.1-52b"])
def test_model_forward_kernel_vs_reference(name):
    import repro.kernels as kernels
    from repro.configs import get_arch, override, reduced
    from repro.models.model import build_model
    cfg = override(reduced(get_arch(name)), dtype="float32")
    m = build_model(cfg)
    p = m.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    try:
        kernels.set_mode("off")
        l0, _ = m.forward(p, toks)
        kernels.set_mode("interpret")
        l1, _ = m.forward(p, toks)
    finally:
        kernels.set_mode("off")
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), atol=5e-4,
                               rtol=1e-3)
