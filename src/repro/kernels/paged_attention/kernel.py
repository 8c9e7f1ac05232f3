"""Paged flash-decode / flash-verify, TPU Pallas: attention over a KV
cache whose rows live as PAGES of one shared pool.

Extends ``decode_attention``'s design along the axis the paged slot pool
needs: the per-request ``(B,)`` position vector in SMEM grows a per-row
``(B, P)`` *page table*, also scalar-prefetched.  The cache operand is no
longer a ``(B, Hkv, S, hd)`` row bank but the model's stacked page pools
``(R, NP, Hkv, page, hd)``, one per layer, and the kernel's BlockSpec
index map reads the page table and the scalar-prefetched layer index to
decide which pool page each grid step DMAs:

    lambda b, h, j, pos, pt, lay: (lay[0], pt[b, j], h, 0, 0)

so row b's j-th cache tile is *its own* j-th page of layer ``lay[0]``,
wherever the host allocator placed it — pages of one request need not be
contiguous, and pages of different requests interleave freely in the
pool.  Reading the layer through the index map (a squeezed leading block
dim) lets the layer scan hand the kernel the whole donated bank: no
layer's pool is ever sliced out of it.

Everything else is the proven flash-decode structure:

  * grid = (B, Hkv, P) with the page-scan axis innermost/"arbitrary";
    (m, l, acc) running-softmax state persists in VMEM scratch.
  * GQA: the G = H/Hkv query heads of one kv head are batched into a
    single (G, hd) x (hd, page) matmul per page (K*G rows for verify).
  * tiles past a row's valid length are skipped before their DMA is
    issued (``pos`` gates the page index map too: dead entries point at
    the pool's park page, a always-valid index that is never read).
  * the verify variant reads the cache PRE-block and folds the block's
    own K keys/values in after the last page under an intra-block causal
    mask — the same cache-plus-block split that makes ``verify_attention``
    sequentially exact.  Paged pools are full-attention only (the paged
    engine gates rings out), so there is no ring path here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _layer_operand(layer):
    """The layer index as the (1,) int32 scalar-prefetch operand the
    page index maps read (``lay[0]``)."""
    return jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (1,))


def _paged_decode_kernel(pos_ref, pt_ref, lay_ref, q_ref, k_ref, v_ref,
                         o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                         page: int, np_row: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = j * page

    @pl.when(k_start <= pos)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (page, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)                  # (page, hd)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    @pl.when(j == np_row - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_decode_kernel_q(pos_ref, pt_ref, lay_ref, q_ref, k_ref, v_ref,
                           ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                           scale: float, page: int, np_row: int):
    """int8-bank variant: k/v tiles are int8 codes and two extra
    (1, 1, 1, page) scale tiles ride the SAME page-table index map, so the
    per-position scale arrives with its page as one lane-major row.  The
    dequantize folds into the matmuls: ``q.(k*ks)^T == (q.k^T)*ks`` and
    ``p.(v*vs) == (p*vs).v``, so the scales broadcast along lanes and no
    (page, hd) dequantized tile is ever built."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = j * page

    @pl.when(k_start <= pos)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (page, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * ks_ref[0, 0]                              # (1, page)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)                  # (page, hd)
        pv = jax.lax.dot_general(p * vs_ref[0, 0], v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    @pl.when(j == np_row - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_decode_attention_kernel(q, k_pages, v_pages, page_table, pos,
                                  layer, *, scale: float | None = None,
                                  k_scale=None, v_scale=None,
                                  interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G, hd); k_pages/v_pages: (R, NP, Hkv, page, hd)
    stacked pools, one per layer; page_table: (B, P) int32 pool-page ids
    (dead entries must hold a valid index — the park page); pos: (B,)
    int32 valid length per row; layer: () int32, the pool read.
    ``k_scale``/``v_scale`` ((R, NP, Hkv, 1, page) f32) select the int8
    bank path: codes dequantize inside the kernel."""
    B, Hkv, G, hd = q.shape
    page = k_pages.shape[-2]
    P = page_table.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    quantized = k_scale is not None

    page_spec = pl.BlockSpec(
        (None, 1, 1, page, hd),
        lambda b, h, j, pos, pt, lay: (lay[0], pt[b, j], h, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, G, hd),
                     lambda b, h, j, pos, pt, lay: (b, h, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        kernel = functools.partial(_paged_decode_kernel_q, scale=scale,
                                   page=page, np_row=P)
        # (1, page) trailing block: legal for the TPU tiling (a unit
        # second-minor dim equal to the array's), unlike (1, 1, page)
        # blocks over an (R, NP, Hkv, page) leaf
        scale_spec = pl.BlockSpec(
            (None, 1, 1, 1, page),
            lambda b, h, j, pos, pt, lay: (lay[0], pt[b, j], h, 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    else:
        kernel = functools.partial(_paged_decode_kernel, scale=scale,
                                   page=page, np_row=P)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, j, pos, pt, lay: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)),
      jnp.asarray(page_table, jnp.int32), _layer_operand(layer), *operands)


def _paged_verify_kernel(pos_ref, pt_ref, anc_ref, lay_ref, q_ref, k_ref,
                         v_ref, kb_ref, vb_ref, o_ref, m_scr, l_scr, acc_scr,
                         *, scale: float, tree: bool, page: int, np_row: int,
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
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    k_start = j * page
    # pre-block cache: valid positions are <= pos-1, so a page is dead
    # once it starts at/after pos — one query-block tighter than decode.

    @pl.when(k_start < pos)
    def _cache_page():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (K*G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (page, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _fold(jnp.where(cols < pos, s, NEG_INF),
              v_ref[0, 0].astype(jnp.float32))

    @pl.when(j == np_row - 1)
    def _block_and_finalize():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (K*G, hd)
        kb = kb_ref[0, 0].astype(jnp.float32)             # (K, hd)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
        jj = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if tree:
            # tree verify: per-row ancestor bitmask from SMEM replaces
            # the intra-block causal mask (see verify_attention/kernel.py)
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


def _paged_verify_kernel_q(pos_ref, pt_ref, anc_ref, lay_ref, q_ref, k_ref,
                           v_ref, ks_ref, vs_ref, kb_ref, vb_ref, o_ref,
                           m_scr, l_scr, acc_scr, *, scale: float,
                           tree: bool, page: int, np_row: int, K: int,
                           G: int):
    """int8-bank verify: cache pages dequantize in VMEM via the
    co-travelling (1, 1, 1, page) scale tiles (folded into the matmuls as
    in ``_paged_decode_kernel_q``); the block's own K keys/values
    stay full precision (they have not been written to the pool yet)."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _fold(s, v, v_scale=None):
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        if v_scale is not None:          # int8 page: (1, page) row scales
            p = p * v_scale
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    k_start = j * page

    @pl.when(k_start < pos)
    def _cache_page():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (K*G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (page, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * ks_ref[0, 0]                              # (1, page)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _fold(jnp.where(cols < pos, s, NEG_INF),
              v_ref[0, 0].astype(jnp.float32), vs_ref[0, 0])

    @pl.when(j == np_row - 1)
    def _block_and_finalize():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (K*G, hd)
        kb = kb_ref[0, 0].astype(jnp.float32)             # (K, hd)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
        jj = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if tree:
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


def paged_verify_attention_kernel(q, k_pages, v_pages, kb, vb, page_table,
                                  pos, layer, *, scale: float | None = None,
                                  k_scale=None, v_scale=None, tree=None,
                                  interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, K*G, hd) — row i is query i//G of kv head h;
    k_pages/v_pages: (R, NP, Hkv, page, hd) stacked pools BEFORE the
    block's writes; kb/vb: (B, Hkv, K, hd) block keys/values; page_table:
    (B, P) int32; pos: (B,) int32 base positions; layer: () int32, the
    pool read.  ``k_scale``/``v_scale`` ((R, NP, Hkv, 1, page) f32)
    select the int8 bank path.  ``tree``
    ((B, K) int32 ancestor bitmasks) replaces the intra-block causal
    mask with per-row tree visibility (bit j of ``tree[b, i]`` = block
    token j visible to block query i)."""
    B, Hkv, KG, hd = q.shape
    K = kb.shape[2]
    assert KG % K == 0, (KG, K)
    G = KG // K
    page = k_pages.shape[-2]
    P = page_table.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    quantized = k_scale is not None
    if tree is None:
        anc = jnp.zeros((B, 1), jnp.int32)
        is_tree = False
    else:
        assert K <= 31, K  # bitmask lives in a non-negative int32
        anc = jnp.asarray(tree, jnp.int32)
        assert anc.shape == (B, K), (anc.shape, B, K)
        is_tree = True

    page_spec = pl.BlockSpec(
        (None, 1, 1, page, hd),
        lambda b, h, j, pos, pt, anc, lay: (lay[0], pt[b, j], h, 0, 0))
    blk_spec = pl.BlockSpec((1, 1, K, hd),
                            lambda b, h, j, pos, pt, anc, lay: (b, h, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, KG, hd),
                     lambda b, h, j, pos, pt, anc, lay: (b, h, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        kernel = functools.partial(_paged_verify_kernel_q, scale=scale,
                                   tree=is_tree, page=page, np_row=P,
                                   K=K, G=G)
        scale_spec = pl.BlockSpec(
            (None, 1, 1, 1, page),
            lambda b, h, j, pos, pt, anc, lay: (lay[0], pt[b, j], h, 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    else:
        kernel = functools.partial(_paged_verify_kernel, scale=scale,
                                   tree=is_tree, page=page, np_row=P,
                                   K=K, G=G)
    in_specs += [blk_spec, blk_spec]
    operands += [kb, vb]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Hkv, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, KG, hd),
            lambda b, h, j, pos, pt, anc, lay: (b, h, 0, 0)),
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
        name="paged_verify_attention",
    )(jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)),
      jnp.asarray(page_table, jnp.int32), anc, _layer_operand(layer),
      *operands)


def _paged_decode_partial_kernel(pos_ref, pt_ref, base_ref, lay_ref,
                                 q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                                 m_scr, l_scr, acc_scr, *, scale: float,
                                 page: int, np_row: int, num_local: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    pos = pos_ref[b]
    pid = pt_ref[b, j]
    base = base_ref[0]
    owned = (pid >= base) & (pid < base + num_local)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = j * page

    @pl.when(owned & (k_start <= pos))
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (page, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)                  # (page, hd)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    @pl.when(j == np_row - 1)
    def _finalize():
        acc_ref[0, 0] = acc_scr[...]
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


def _paged_decode_partial_kernel_q(pos_ref, pt_ref, base_ref, lay_ref,
                                   q_ref, k_ref, v_ref, ks_ref, vs_ref,
                                   acc_ref, m_ref, l_ref, m_scr, l_scr,
                                   acc_scr, *, scale: float, page: int,
                                   np_row: int, num_local: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    pos = pos_ref[b]
    pid = pt_ref[b, j]
    base = base_ref[0]
    owned = (pid >= base) & (pid < base + num_local)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = j * page

    @pl.when(owned & (k_start <= pos))
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (page, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * ks_ref[0, 0]                              # (1, page)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)                  # (page, hd)
        pv = jax.lax.dot_general(p * vs_ref[0, 0], v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    @pl.when(j == np_row - 1)
    def _finalize():
        acc_ref[0, 0] = acc_scr[...]
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


def paged_decode_partial_kernel(q, k_pages, v_pages, page_table, pos,
                                base, layer, *, scale: float | None = None,
                                k_scale=None, v_scale=None,
                                interpret: bool = False):
    """Per-shard HALF of flash decode over a sharded page bank.

    ``k_pages``/``v_pages`` here are one shard's (R, L, Hkv, page, hd)
    LOCAL slice of the stacked pools, of which pool ``layer`` (() int32)
    is read; ``page_table`` still holds GLOBAL page ids and ``base``
    ((1,) int32, scalar-prefetched) is the shard's first global id, so
    the index map clamps ``pt[b, j] - base`` into [0, L) and the body
    additionally gates each fold on ownership — a foreign page's tile
    may be DMA'd (clamped to local park page 0) but never folded.

    Returns the UNNORMALIZED running-softmax state instead of an
    output: (acc (B, Hkv, G, hd) f32, m (B, Hkv, G, 1) f32,
    l (B, Hkv, G, 1) f32).  A row with no owned valid page yields
    (0, NEG_INF, 0), which a cross-shard ``exp(m - pmax(m))`` rescale +
    psum combine weighs to exactly zero."""
    B, Hkv, G, hd = q.shape
    _, L, _, page, _ = k_pages.shape
    P = page_table.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    quantized = k_scale is not None

    def _page_idx(b, h, j, pos, pt, base, lay):
        return (lay[0], jnp.clip(pt[b, j] - base[0], 0, L - 1), h, 0, 0)

    page_spec = pl.BlockSpec((None, 1, 1, page, hd), _page_idx)
    in_specs = [
        pl.BlockSpec((1, 1, G, hd),
                     lambda b, h, j, pos, pt, base, lay: (b, h, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        kernel = functools.partial(_paged_decode_partial_kernel_q,
                                   scale=scale, page=page, np_row=P,
                                   num_local=L)
        scale_spec = pl.BlockSpec((None, 1, 1, 1, page), _page_idx)
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    else:
        kernel = functools.partial(_paged_decode_partial_kernel,
                                   scale=scale, page=page, np_row=P,
                                   num_local=L)
    out_idx = lambda b, h, j, pos, pt, base, lay: (b, h, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Hkv, P),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, G, hd), out_idx),
            pl.BlockSpec((1, 1, G, 1), out_idx),
            pl.BlockSpec((1, 1, G, 1), out_idx),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_partial",
    )(jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)),
      jnp.asarray(page_table, jnp.int32),
      jnp.broadcast_to(jnp.asarray(base, jnp.int32), (1,)),
      _layer_operand(layer), *operands)
