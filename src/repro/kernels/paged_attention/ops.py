"""jit'd public wrappers for paged decode / paged verify attention."""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.kernel import (
    paged_decode_attention_kernel, paged_decode_partial_kernel,
    paged_verify_attention_kernel)
from repro.kernels.paged_attention.ref import (
    gather_pages, gather_scales, paged_decode_partial_reference,
    paged_decode_reference, paged_verify_reference)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _stacked(layer, *leaves):
    """The kernels read a stack of pools through a layer index: a single
    pool (``layer is None``) is a stack of one, read at layer 0 (the
    leading unit axis is a free reshape)."""
    if layer is not None:
        return (layer,) + leaves
    return (0,) + tuple(None if x is None else x[None] for x in leaves)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, page_table, pos, *,
                           scale: float | None = None,
                           k_scale=None, v_scale=None, layer=None,
                           interpret: bool | None = None) -> jax.Array:
    """q: (B, H, hd); k_pages/v_pages: (NP, Hkv, page, hd) shared pool,
    or with ``layer`` (() int32) the (R, NP, Hkv, page, hd) stacked pools
    of which pool ``layer`` is read; page_table: (B, P) int32; pos: () or
    (B,) int32 -> (B, H, hd).

    The paged analogue of ``decode_attention``: the same per-request
    position masking and tile skipping, with the cache tile for grid
    step j of row b resolved through the scalar-prefetched page table
    instead of a contiguous row.  Dead table entries (past a row's
    allocation) must hold a valid pool index — the engine points them at
    the park page; they are masked by ``pos`` regardless.  An int8 pool
    passes its (NP, Hkv, 1, page) f32 ``k_scale``/``v_scale`` leaves and
    the kernel dequantizes in VMEM (stacked like the codes)."""
    if interpret is None:
        interpret = not _on_tpu()
    B, H, hd = q.shape
    Hkv = k_pages.shape[-3]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    layer, k_pages, v_pages, k_scale, v_scale = _stacked(
        layer, k_pages, v_pages, k_scale, v_scale)
    out = paged_decode_attention_kernel(qg, k_pages, v_pages, page_table,
                                        pos, layer, scale=scale,
                                        k_scale=k_scale, v_scale=v_scale,
                                        interpret=interpret)
    return out.reshape(B, H, hd)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_verify_attention(q, k_pages, v_pages, blk_k, blk_v, page_table,
                           pos, *, scale: float | None = None,
                           k_scale=None, v_scale=None, tree=None,
                           layer=None,
                           interpret: bool | None = None) -> jax.Array:
    """q: (B, K, H, hd); pool holds the cache BEFORE the block's writes
    (a single pool, or stacked pools read at ``layer`` as in
    ``paged_decode_attention``); blk_k/blk_v: (B, K, Hkv, hd);
    page_table: (B, P); pos: () or (B,) int32 base positions ->
    (B, K, H, hd).

    Query i of row b sits at position ``pos[b] + i``; it attends to the
    paged cache (positions <= pos[b]-1, resolved through the page table)
    plus block tokens j <= i — the same cache-plus-block split as
    ``verify_attention``, which keeps the pass loop-exact.  Full
    attention only (the paged engine gates ring caches out).  ``tree``
    ((B, K) int32 ancestor bitmasks) swaps the intra-block causal mask
    for per-row tree visibility so several candidate branches verify in
    one pass."""
    if interpret is None:
        interpret = not _on_tpu()
    B, K, H, hd = q.shape
    Hkv = k_pages.shape[-3]
    G = H // Hkv
    qg = (q.reshape(B, K, Hkv, G, hd).transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, K * G, hd))
    kb = blk_k.swapaxes(1, 2)                       # (B, Hkv, K, hd)
    vb = blk_v.swapaxes(1, 2)
    layer, k_pages, v_pages, k_scale, v_scale = _stacked(
        layer, k_pages, v_pages, k_scale, v_scale)
    out = paged_verify_attention_kernel(qg, k_pages, v_pages, kb, vb,
                                        page_table, pos, layer, scale=scale,
                                        k_scale=k_scale, v_scale=v_scale,
                                        tree=tree, interpret=interpret)
    return (out.reshape(B, Hkv, K, G, hd).transpose(0, 2, 1, 3, 4)
            .reshape(B, K, H, hd))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_partial(q, k_pages, v_pages, page_table, pos, base, *,
                         scale: float | None = None,
                         k_scale=None, v_scale=None, layer=None,
                         interpret: bool | None = None):
    """One shard's unnormalized flash-decode state over its LOCAL bank
    slice.  q: (B, H, hd); k_pages/v_pages: (L, Hkv, page, hd) local
    slice (or (R, L, Hkv, page, hd) read at ``layer``); page_table:
    (B, P) GLOBAL page ids; base: scalar int32 first
    global id of this shard -> (acc (B, Hkv, G, hd) f32, m (B, Hkv, G)
    f32, l (B, Hkv, G) f32).  Pages outside [base, base+L) are skipped;
    a row owning no valid page comes back as (0, -1e30, 0), which the
    caller's pmax/psum combine weighs to zero.  Runs inside shard_map —
    every shard's kernel instance reads only its own slice."""
    if interpret is None:
        interpret = not _on_tpu()
    B, H, hd = q.shape
    Hkv = k_pages.shape[-3]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    layer, k_pages, v_pages, k_scale, v_scale = _stacked(
        layer, k_pages, v_pages, k_scale, v_scale)
    acc, m, l = paged_decode_partial_kernel(
        qg, k_pages, v_pages, page_table, pos, base, layer, scale=scale,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret)
    return acc, m[..., 0], l[..., 0]


__all__ = ["gather_pages", "gather_scales", "paged_decode_attention",
           "paged_decode_partial", "paged_decode_partial_reference",
           "paged_decode_reference", "paged_verify_attention",
           "paged_verify_reference"]
