"""Pure-jnp oracle for paged attention: the KV cache rows live as pages
of one shared pool, addressed through a per-row page table.

Layout:
  * ``k_pages``/``v_pages`` — (NP, Hkv, page, hd): the shared pool.
    Page 0 is conventionally the PARK page (never read; dead page-table
    entries point at it so every table entry is a valid pool index).
  * ``page_table`` — (B, P) int32: row b's virtual positions
    ``[j*page, (j+1)*page)`` live in pool page ``page_table[b, j]``.
  * ``pos`` — (B,) int32 (or scalar, broadcast).

The oracle simply *gathers* each row's pages back into a contiguous
(B, Hkv, P*page, hd) row bank and defers to the proven row oracles —
``decode_reference`` for the one-token case and ``verify_reference``
(ring=False; paged pools are full-attention only) for the K-token
verify/chunk case.  Gathering makes the equivalence the tests assert
literal: a paged cache read through its table IS the row cache.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.decode_attention.ref import decode_reference
from repro.kernels.verify_attention.ref import verify_reference


def gather_pages(pages, page_table):
    """(NP, Hkv, page, hd) pool + (B, P) table -> (B, Hkv, P*page, hd)
    contiguous per-row cache (virtual position j*page+s = page slot s of
    table entry j)."""
    g = pages[jnp.asarray(page_table, jnp.int32)]   # (B, P, Hkv, page, hd)
    B, P, Hkv, page, hd = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, P * page, hd)


def gather_scales(scales, page_table):
    """(NP, Hkv, 1, page) int8-bank scale leaf + (B, P) table ->
    (B, Hkv, P*page) per-position scales — ``gather_pages`` minus the
    head-dim axis, so a gathered int8 row dequantizes elementwise as
    ``codes * scales[..., None]``.  The unit axis is the kernel's tiling:
    each page's scales are one lane-major (1, page) row."""
    g = scales[jnp.asarray(page_table, jnp.int32)]  # (B, P, Hkv, 1, page)
    B, P, Hkv, _, page = g.shape
    return g[:, :, :, 0].transpose(0, 2, 1, 3).reshape(B, Hkv, P * page)


def _dequant(pages, scales, page_table):
    codes = gather_pages(pages, page_table)
    s = gather_scales(scales, page_table)
    return codes.astype(jnp.float32) * s[..., None]


def paged_decode_reference(q, k_pages, v_pages, page_table, pos, *,
                           scale: float | None = None,
                           k_scale=None, v_scale=None):
    """q: (B, H, hd) -> (B, H, hd); see module docstring for layouts.
    ``k_scale``/``v_scale`` ((NP, Hkv, 1, page) f32) mark an int8 bank:
    codes are dequantized after the gather, then the row oracle runs
    unchanged."""
    if k_scale is not None:
        k = _dequant(k_pages, k_scale, page_table)
        v = _dequant(v_pages, v_scale, page_table)
    else:
        k = gather_pages(k_pages, page_table)
        v = gather_pages(v_pages, page_table)
    return decode_reference(q, k, v, pos, ring=False, scale=scale)


def paged_verify_reference(q, k_pages, v_pages, blk_k, blk_v, page_table,
                           pos, *, scale: float | None = None,
                           k_scale=None, v_scale=None, tree=None):
    """q: (B, K, H, hd); blk_k/blk_v: (B, K, Hkv, hd) block keys/values;
    the pool holds the cache BEFORE the block's writes -> (B, K, H, hd).
    ``k_scale``/``v_scale`` dequantize an int8 bank (the block k/v stay
    full precision — they have not been written yet).  ``tree``
    ((B, K) int32 ancestor bitmasks) selects per-row tree visibility in
    place of the intra-block causal mask."""
    if k_scale is not None:
        k = _dequant(k_pages, k_scale, page_table)
        v = _dequant(v_pages, v_scale, page_table)
    else:
        k = gather_pages(k_pages, page_table)
        v = gather_pages(v_pages, page_table)
    return verify_reference(q, k, v, blk_k, blk_v, pos, ring=False,
                            scale=scale, tree=tree)


def paged_decode_partial_reference(q, k_pages, v_pages, page_table, pos,
                                   base, *, scale: float | None = None,
                                   k_scale=None, v_scale=None):
    """Oracle for ``paged_decode_partial``: one shard's unnormalized
    flash state.  ``k_pages``/``v_pages`` are the shard's LOCAL
    (L, Hkv, page, hd) slice, ``page_table`` holds GLOBAL ids and
    ``base`` is the shard's first global id.  q: (B, H, hd) ->
    (acc (B, Hkv, G, hd) f32, m (B, Hkv, G) f32, l (B, Hkv, G) f32),
    with rows that own no valid page at exactly (0, -1e30, 0)."""
    NEG_INF = -1e30
    B, H, hd = q.shape
    L, Hkv, page, _ = k_pages.shape
    G = H // Hkv
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    table = jnp.asarray(page_table, jnp.int32)
    lt = table - jnp.asarray(base, jnp.int32)
    owned = (lt >= 0) & (lt < L)
    lt = jnp.where(owned, lt, 0)
    if k_scale is not None:
        k = _dequant(k_pages, k_scale, lt)
        v = _dequant(v_pages, v_scale, lt)
    else:
        k = gather_pages(k_pages, lt)
        v = gather_pages(v_pages, lt)
    S = k.shape[2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    own_pos = jnp.repeat(owned, page, axis=1)            # (B, S)
    valid = ((jnp.arange(S)[None, :] <= pos[:, None])
             & own_pos)[:, None, None, :]                # (B, 1, 1, S)
    qh = q.reshape(B, Hkv, G, hd).astype(jnp.float32)
    s = jnp.einsum("bngd,bnsd->bngs", qh,
                   k.astype(jnp.float32)) * scale
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(valid, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bngs,bnsd->bngd", p, v.astype(jnp.float32))
    return acc, m, l
