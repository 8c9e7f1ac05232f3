"""Multi-token verify attention, TPU Pallas: the speculative-decode verify
step's K query tokens per row against a long KV cache in one kernel.

Extends ``decode_attention``'s design from one query token to a (B, K)
query block:

  * the ``(B,)`` per-request position vector still arrives via scalar
    prefetch (SMEM); each row's K queries sit at ``pos[b] .. pos[b]+K-1``
    with *per-row causal offsets* computed inside the kernel (query index
    i = score-row // G), so one program serves rows at wildly different
    positions — the continuous-batching invariant, now a block wide.
  * the K*G query rows of one kv head are batched into a single
    (K*G, hd) x (hd, bk) matmul per KV tile — the same MXU-occupancy trick
    as decode's G-row batching, K times taller.
  * the cache is read PRE-block (positions <= pos-1); the block's own K
    keys/values arrive as a separate (K, hd) operand folded into the
    running softmax after the last cache tile with an intra-block causal
    mask.  This split is what makes the result sequentially exact — for
    ring caches a later token's write lands on a slot an earlier query
    must still read, so write-then-mask cannot reproduce the one-token
    decode loop; cache-plus-block can, and does (tested).
  * grid = (B, Hkv, S/bk), cache axis innermost/"arbitrary"; (m, l, acc)
    running-softmax state in VMEM scratch; tiles past a row's valid
    length are skipped before their DMA is issued.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BK = 512


def _verify_kernel(pos_ref, anc_ref, q_ref, k_ref, v_ref, kb_ref, vb_ref,
                   o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                   ring: bool, tree: bool, bk: int, nk: int, S: int,
                   K: int, G: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _fold(s, v):
        """Fold one masked score tile into the running softmax state."""
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    k_start = j * bk
    # pre-block cache: valid slots hold positions <= pos-1, so a tile is
    # dead when it starts at/after pos (non-ring) — one query-block tighter
    # than decode's k_start <= pos.  A wrapped ring keeps every tile live.
    live = jnp.logical_or(k_start < pos, jnp.bool_(ring) & (pos >= S))

    @pl.when(live)
    def _cache_tile():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (K*G, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if ring:
            p = (pos - 1) - jnp.mod(pos - 1 - cols, S)
            valid = (p >= 0) & (p > pos + qi - S)
        else:
            valid = cols < pos
        _fold(jnp.where(valid, s, NEG_INF), v_ref[0, 0].astype(jnp.float32))

    @pl.when(j == nk - 1)
    def _block_and_finalize():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (K*G, hd)
        kb = kb_ref[0, 0].astype(jnp.float32)             # (K, hd)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
        jj = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if tree:
            # per-row ancestor bitmask: block column j is visible to block
            # query qi iff bit j of anc[b, qi] is set.  The bitmask rides
            # scalar prefetch (SMEM) like pos; the unroll over the K block
            # queries turns it into a per-score-row int32 whose bits the
            # iota extracts — no extra VMEM operand, no layout change.
            anc_q = jnp.zeros_like(jj)
            for i in range(K):
                anc_q = jnp.where(qi == i, anc_ref[b, i], anc_q)
            keep = jax.lax.shift_right_logical(anc_q, jj) & 1
            _fold(jnp.where(keep == 1, s, NEG_INF),
                  vb_ref[0, 0].astype(jnp.float32))
        else:
            _fold(jnp.where(jj <= qi, s, NEG_INF),
                  vb_ref[0, 0].astype(jnp.float32))
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def verify_attention_kernel(q, k, v, kb, vb, pos, *, ring: bool = False,
                            scale: float | None = None,
                            block_k: int = DEFAULT_BK,
                            tree=None,
                            interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, K*G, hd) — row r is query r//G of kv head h; k/v:
    (B, Hkv, S, hd) cache BEFORE the block's writes; kb/vb:
    (B, Hkv, K, hd) block keys/values; pos: (B,) int32 base positions.
    ``tree`` ((B, K) int32 ancestor bitmasks, bit j of row i = block
    token j visible to block query i) replaces the intra-block causal
    mask so several candidate branches verify in one pass; the cache
    side is unchanged (every tree node descends from position pos-1)."""
    B, Hkv, KG, hd = q.shape
    S = k.shape[2]
    K = kb.shape[2]
    assert KG % K == 0, (KG, K)
    G = KG // K
    bk = min(block_k, S)
    assert S % bk == 0, (S, bk)
    nk = S // bk
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    if tree is None:
        anc = jnp.zeros((B, 1), jnp.int32)
        is_tree = False
    else:
        assert not ring, "tree verify is full-attention only"
        assert K <= 31, K  # bitmask lives in a non-negative int32
        anc = jnp.asarray(tree, jnp.int32)
        assert anc.shape == (B, K), (anc.shape, B, K)
        is_tree = True

    kernel = functools.partial(_verify_kernel, scale=scale, ring=ring,
                               tree=is_tree, bk=bk, nk=nk, S=S, K=K, G=G)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, KG, hd),
                         lambda b, h, j, pos, anc: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, j, pos, anc: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, j, pos, anc: (b, h, j, 0)),
            pl.BlockSpec((1, 1, K, hd),
                         lambda b, h, j, pos, anc: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, K, hd),
                         lambda b, h, j, pos, anc: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, KG, hd),
                               lambda b, h, j, pos, anc: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KG, 1), jnp.float32),
            pltpu.VMEM((KG, 1), jnp.float32),
            pltpu.VMEM((KG, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="verify_attention",
    )(jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)), anc,
      q, k, v, kb, vb)
