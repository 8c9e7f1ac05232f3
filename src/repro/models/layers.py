"""Core transformer layers: RMSNorm, RoPE, GQA attention (full + sliding
window), gated MLP.  Pure-jnp reference path; on TPU the attention ops
dispatch to the Pallas kernels via ``repro.kernels``.

All functions are functional: ``params`` in, arrays out.  Attention exposes
three entry points matching the framework's execution modes:
  * ``attention``          — training forward (no cache)
  * ``attention_prefill``  — returns the populated KV cache
  * ``attention_decode``   — one token against the cache
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import PSpec

NEG_INF = -1e30  # bf16-safe large negative


# ---------------------------------------------------------------------------
# norms / mlp
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w).astype(dt)


def mlp_specs(d: int, f: int, gated: bool = True) -> dict:
    out = {
        "w_up": PSpec((d, f), ("embed", "ffn")),
        "w_down": PSpec((f, d), ("ffn", "embed")),
    }
    if gated:
        out["w_gate"] = PSpec((d, f), ("embed", "ffn"))
    return out


def mlp(params, x):
    if "w_gate" in params:
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    else:
        h = jax.nn.gelu(x @ params["w_up"])
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    freqs = rope_freqs(x.shape[-1], theta)                    # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs    # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    """Contiguous KV cache; for sliding-window archs S == window (ring)."""
    k: jax.Array          # (B, Hkv, S, hd)
    v: jax.Array          # (B, Hkv, S, hd)


def attn_specs(cfg: ArchConfig) -> dict:
    # explicit 1/sqrt(fan_in): the default reads fan-in off the second-
    # to-last axis, which for these head-split weights is heads/head_dim
    # (~8x too large at d_model 2048 — near-argmax attention whose bf16
    # rounding flips decorrelate the logits from an f32 reference)
    d = cfg.d_model
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(cfg.num_heads * cfg.head_dim)
    return {
        "wq": PSpec((d, cfg.num_heads, cfg.head_dim),
                    ("embed", "heads", "head_dim"), scale=s_in),
        "wk": PSpec((d, cfg.num_kv_heads, cfg.head_dim),
                    ("embed", "kv_heads", "head_dim"), scale=s_in),
        "wv": PSpec((d, cfg.num_kv_heads, cfg.head_dim),
                    ("embed", "kv_heads", "head_dim"), scale=s_in),
        "wo": PSpec((cfg.num_heads, cfg.head_dim, d),
                    ("heads", "head_dim", "embed"), scale=s_out),
    }


def _qkv(params, x, positions, cfg: ArchConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _constrain(x, mesh, rules, logical):
    if mesh is None:
        return x
    from repro.distributed.sharding import constrain as _c
    from repro.distributed.sharding import DEFAULT_RULES
    return _c(x, mesh, logical, rules if rules is not None else DEFAULT_RULES)


def _sdpa(q, k, v, mask, cfg: ArchConfig, mesh=None, rules=None):
    """q: (B,S,H,hd), k/v: (B,T,Hkv,hd), mask: (S,T) or (B,S,T) bool.

    KV heads are expanded to H so the (B,H,S,T) scores shard cleanly over
    the full `model` axis even when Hkv < axis size (GQA kv=4 archs on a
    16-wide axis).  On TPU the flash kernel does GQA natively; this is the
    XLA-visible formulation whose sharding GSPMD propagates.
    """
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    scores = jnp.einsum("bshk,bthk->bhst", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    scores = _constrain(scores, mesh, rules,
                        ("batch", "act_heads", "act_attn_q", None))
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bthk->bshk", probs, v)
    return out


ATTN_CHUNK_THRESHOLD = 2_048   # at/above this, use query-chunked attention
ATTN_CHUNK = 1_024


def _sdpa_chunked(q, k, v, cfg: ArchConfig, chunk: int = ATTN_CHUNK,
                  unroll: bool = False, mesh=None, rules=None):
    """Query-chunked SDPA: O(chunk * S) live scores instead of O(S^2).

    Baseline keeps full-K per chunk with masking (the causal/window FLOP
    waste is visible in the roofline utilization ratio; the Pallas flash
    kernel removes it on TPU).  ``unroll`` is the dry-run metrics mode.
    """
    B, S, H, hd = q.shape
    if S % chunk != 0:
        return _sdpa(q, k, v, causal_mask(S, cfg.sliding_window), cfg,
                     mesh, rules)
    n = S // chunk
    w = cfg.sliding_window

    def body(_, qc_i):
        qc, i = qc_i
        rows = i * chunk + jnp.arange(chunk)[:, None]
        cols = jnp.arange(S)[None, :]
        mask = cols <= rows
        if w > 0:
            mask &= (rows - cols) < w
        return 0, _sdpa(qc, k, v, mask, cfg, mesh, rules)

    qs = q.reshape(B, n, chunk, H, hd).swapaxes(0, 1)
    _, outs = jax.lax.scan(body, 0, (qs, jnp.arange(n)),
                           unroll=n if unroll else 1)
    return outs.swapaxes(0, 1).reshape(B, S, H, hd)


def causal_mask(S: int, window: int = 0) -> jax.Array:
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    m = j <= i
    if window > 0:
        m &= (i - j) < window
    return m


def _sdpa_auto(q, k, v, cfg: ArchConfig, unroll: bool = False,
               mesh=None, rules=None, shard=None):
    """``shard`` (a ``BankShard``): the program runs beside a page bank
    split over ``shard.mesh``, so the flash kernel runs replicated."""
    import repro.kernels as kernels
    S = q.shape[1]
    if kernels.use_kernels() and S == k.shape[1]:
        from repro.kernels.flash_attention.ops import flash_attention
        interp = None if kernels.get_mode() == "auto" else True

        def kernel(q, k, v):
            return flash_attention(q, k, v, causal=True,
                                   window=cfg.sliding_window,
                                   interpret=interp)
        out = _replicated(kernel, shard)(q.swapaxes(1, 2), k.swapaxes(1, 2),
                                         v.swapaxes(1, 2))
        return out.swapaxes(1, 2)
    if S >= ATTN_CHUNK_THRESHOLD:
        return _sdpa_chunked(q, k, v, cfg, unroll=unroll, mesh=mesh,
                             rules=rules)
    return _sdpa(q, k, v, causal_mask(S, cfg.sliding_window), cfg, mesh,
                 rules)


def attention(params, x, positions, cfg: ArchConfig, unroll: bool = False,
              mesh=None, rules=None):
    """Training forward (no cache)."""
    q, k, v = _qkv(params, x, positions, cfg)
    out = _sdpa_auto(q, k, v, cfg, unroll, mesh, rules)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))


def attention_prefill(params, x, positions, cfg: ArchConfig, max_len: int,
                      cache_dtype=jnp.bfloat16, unroll: bool = False,
                      mesh=None, rules=None, shard=None):
    """Prefill from position 0: returns output and a fixed-size cache.

    Full attention: cache length == max_len.  Sliding window: cache length ==
    window, laid out as a ring (slot = position % window).  ``shard``: see
    ``_sdpa_auto``.
    """
    q, k, v = _qkv(params, x, positions, cfg)
    out = _sdpa_auto(q, k, v, cfg, unroll, mesh, rules, shard)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))

    S, W = x.shape[1], cfg.sliding_window
    kT, vT = k.swapaxes(1, 2), v.swapaxes(1, 2)         # (B, Hkv, S, hd)
    if W > 0 and S > W:
        # keep the last `window` tokens, ring-aligned: token t -> slot t % W
        kT = jnp.roll(kT[:, :, -W:], S % W, axis=2)
        vT = jnp.roll(vT[:, :, -W:], S % W, axis=2)
    cache = init_kv_cache(cfg, x.shape[0], max_len, cache_dtype)
    ck = jax.lax.dynamic_update_slice(cache.k, kT.astype(cache_dtype),
                                      (0, 0, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache.v, vT.astype(cache_dtype),
                                      (0, 0, 0, 0))
    return out, KVCache(k=ck, v=cv)


def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> KVCache:
    S = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    shape = (batch, cfg.num_kv_heads, S, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def kv_cache_abstract(cfg: ArchConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16) -> KVCache:
    S = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    shape = (batch, cfg.num_kv_heads, S, cfg.head_dim)
    return KVCache(k=jax.ShapeDtypeStruct(shape, dtype),
                   v=jax.ShapeDtypeStruct(shape, dtype))


KV_LOGICAL = KVCache(k=("kv_batch", "kv_heads", "kv_seq", "head_dim"),
                     v=("kv_batch", "kv_heads", "kv_seq", "head_dim"))


# ---------------------------------------------------------------------------
# paged decode cache (vLLM-style, XLA-native)
#
# The contiguous decode cache costs ~2 full-cache copies per step on top of
# the read (the per-layer dynamic-update-slice chain double-buffers through
# the scan).  Paged layout removes the write path entirely:
#   big: (B, Hkv, NP, page, hd)  — read-only pages; never an output
#   act: (B, Hkv, page, hd)      — the one page being written (donated)
# The step writes one token into `act`; every `page` steps the serving
# engine commits `act` into `big` with one amortized DUS.
# ---------------------------------------------------------------------------

class BigKV(NamedTuple):
    k: jax.Array          # (B, Hkv, NP, page, hd)
    v: jax.Array


class ActKV(NamedTuple):
    k: jax.Array          # (B, Hkv, page, hd)
    v: jax.Array


DEFAULT_PAGE = 512

BIG_LOGICAL = BigKV(k=("kv_batch", "kv_heads", "kv_pages", None, "head_dim"),
                    v=("kv_batch", "kv_heads", "kv_pages", None, "head_dim"))
ACT_LOGICAL = ActKV(k=("kv_batch", "kv_heads", None, "head_dim"),
                    v=("kv_batch", "kv_heads", None, "head_dim"))


def paged_cache_shapes(cfg: ArchConfig, batch: int, max_len: int,
                       page: int = DEFAULT_PAGE):
    S = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    page = min(page, S)
    npages = -(-S // page)
    big = (batch, cfg.num_kv_heads, npages, page, cfg.head_dim)
    act = (batch, cfg.num_kv_heads, page, cfg.head_dim)
    return big, act


def init_paged_cache(cfg: ArchConfig, batch: int, max_len: int,
                     page: int = DEFAULT_PAGE, dtype=jnp.bfloat16,
                     abstract: bool = False):
    big, act = paged_cache_shapes(cfg, batch, max_len, page)
    mk = (lambda s: jax.ShapeDtypeStruct(s, dtype)) if abstract else \
        (lambda s: jnp.zeros(s, dtype))
    return (BigKV(k=mk(big), v=mk(big)), ActKV(k=mk(act), v=mk(act)))


def attention_decode_paged(params, x, pos, big: BigKV, act: ActKV,
                           cfg: ArchConfig):
    """One-step decode against a paged cache.  Returns (out, new act).

    `big` is read-only (pages < pos//page are valid); the new token's k/v
    land in `act` at slot pos % page.
    """
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _qkv(params, x, positions, cfg)     # q: (B,1,H,hd)
    page = act.k.shape[2]
    slot = pos % page
    a_k = jax.lax.dynamic_update_slice(
        act.k, k.swapaxes(1, 2).astype(act.k.dtype), (0, 0, slot, 0))
    a_v = jax.lax.dynamic_update_slice(
        act.v, v.swapaxes(1, 2).astype(act.v.dtype), (0, 0, slot, 0))

    Bq, Hkv, NP, pg, hd = big.k.shape
    page_start = (pos // page) * page

    H = q.shape[2]
    G = H // Hkv
    qh = q.reshape(B, Hkv, G, hd)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    # pages stay an explicit einsum dim: the big cache may be sharded on
    # its page axis (seq-sharded decode) and a (NP, pg) -> S reshape would
    # force GSPMD to re-layout the whole cache every step.
    s_big = jnp.einsum("bngk,bnpsk->bngps", qh,
                       big.k.astype(qh.dtype)).astype(jnp.float32) * scale
    s_act = jnp.einsum("bngk,bnsk->bngs", qh,
                       a_k.astype(qh.dtype)).astype(jnp.float32) * scale
    pos_big = (jnp.arange(NP)[:, None] * pg + jnp.arange(pg)[None, :])
    s_big = jnp.where(pos_big[None, None, None] < page_start, s_big,
                      NEG_INF)
    s_act = jnp.where(jnp.arange(pg)[None, None, None] <=
                      (pos - page_start), s_act, NEG_INF)
    # joint softmax across pages + active page (flash-decode combine)
    m_big = jnp.max(s_big, axis=(-2, -1))
    m = jnp.maximum(jnp.max(s_act, axis=-1), m_big)           # (B,N,G)
    e_big = jnp.exp(s_big - m[..., None, None])
    e_act = jnp.exp(s_act - m[..., None])
    denom = (jnp.sum(e_big, axis=(-2, -1)) + jnp.sum(e_act, axis=-1))
    num = (jnp.einsum("bngps,bnpsk->bngk", e_big.astype(q.dtype),
                      big.v.astype(q.dtype)) +
           jnp.einsum("bngs,bnsk->bngk", e_act.astype(q.dtype),
                      a_v.astype(q.dtype)))
    out = num / denom[..., None].astype(q.dtype)
    out = out.reshape(B, 1, H, hd)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return out, ActKV(k=a_k, v=a_v)


def commit_page(big: BigKV, act: ActKV, pos) -> BigKV:
    """Write the filled active page into the big cache (amortized: called
    once every `page` steps by the serving engine; donate both)."""
    page = act.k.shape[2]
    pidx = pos // page
    return BigKV(
        k=jax.lax.dynamic_update_slice(
            big.k, act.k[:, :, None].astype(big.k.dtype), (0, 0, pidx, 0, 0)),
        v=jax.lax.dynamic_update_slice(
            big.v, act.v[:, :, None].astype(big.v.dtype), (0, 0, pidx, 0, 0)))


# ---------------------------------------------------------------------------
# paged slot pool (vLLM-style): per-row page tables over ONE shared pool
#
# The slot-pooled decode cache above still reserves a full max_len row per
# slot, so pool capacity is provisioned for the worst-case sequence.  The
# paged pool drops that: the cache is one shared bank of fixed-size pages
# (PagedKV), each request owns only the pages its own length needs, and a
# host-side page table maps a row's virtual positions onto pool pages.
# Page 0 is the PARK page: never allocated to a request and never read —
# dead table entries point at it (every table entry must be a valid pool
# index), and non-live rows' per-step writes are routed into it, which is
# what keeps a retired slot's stale writes from disturbing pages already
# recycled to a neighbor (the dual-port disturb-free invariant at page
# granularity).
#
# The model stacks one pool per layer into (R, NP, ...) leaves, and every
# paged program keeps them stacked: the layer scan carries the whole bank
# and hands each layer a ``BankLayer`` (the bank plus the layer index).
# Kernels read the layer through their index maps and every write is
# ``_bank_write``'s scatter, so the donated bank is updated where it lies
# and no layer's pool is ever sliced out or copied back.
# ---------------------------------------------------------------------------

class PagedKV(NamedTuple):
    """Shared page pool: virtual row position j*page+s of a request lives
    at ``pool[table[j], :, s]`` for that request's page table.  The
    model's bank stacks one pool per layer repeat: leaves (R, NP, ...).

    ``ks``/``vs`` are the int8 bank's scale leaves ((NP, Hkv, 1, page)
    f32, ``None`` for full-precision pools): when present, ``k``/``v`` hold
    symmetric-absmax int8 codes and the real value of pool entry
    ``[p, h, s, :]`` is ``k[p, h, s, :] * ks[p, h, 0, s]`` — one scale per
    token per kv head, riding the same page table as the codes, so a
    single decoded token quantizes independently without rescaling its
    page.  The unit axis is the paged kernels' tiling: a page's scales
    are one (1, page) lane-major row, a block the TPU lowering accepts
    (a (1, page) block of an (NP, Hkv, page) leaf is not)."""
    k: jax.Array          # (NP, Hkv, page, hd) — cache dtype, or int8
    v: jax.Array
    ks: Any = None        # (NP, Hkv, 1, page) f32 scales (int8 pools only)
    vs: Any = None


PARK_PAGE = 0

KV_QMAX = 127.0           # symmetric int8: codes in [-127, 127]

PAGED_LOGICAL = PagedKV(k=("kv_pages", "kv_heads", None, "head_dim"),
                        v=("kv_pages", "kv_heads", None, "head_dim"))


class BankLayer(NamedTuple):
    """Layer ``layer`` (() int32) of a stacked bank (``PagedKV`` with
    leaves (R, NP, ...)): what one paged attention block reads and
    writes.  The kernels read the layer through their index maps and
    writes scatter into it (``_bank_write``), so the bank is not sliced
    (the jnp reference reads and the global-gather mesh path slice one
    layer's pool out: ``_layer_pool``)."""
    bank: PagedKV
    layer: Any


def init_page_pool(cfg: ArchConfig, num_pages: int, page: int,
                   dtype=jnp.bfloat16, abstract: bool = False,
                   quantized: bool = False) -> PagedKV:
    shape = (num_pages, cfg.num_kv_heads, page, cfg.head_dim)
    if quantized:
        sshape = (num_pages, cfg.num_kv_heads, 1, page)
        if abstract:
            return PagedKV(k=jax.ShapeDtypeStruct(shape, jnp.int8),
                           v=jax.ShapeDtypeStruct(shape, jnp.int8),
                           ks=jax.ShapeDtypeStruct(sshape, jnp.float32),
                           vs=jax.ShapeDtypeStruct(sshape, jnp.float32))
        return PagedKV(k=jnp.zeros(shape, jnp.int8),
                       v=jnp.zeros(shape, jnp.int8),
                       ks=jnp.zeros(sshape, jnp.float32),
                       vs=jnp.zeros(sshape, jnp.float32))
    if abstract:
        return PagedKV(k=jax.ShapeDtypeStruct(shape, dtype),
                       v=jax.ShapeDtypeStruct(shape, dtype))
    return PagedKV(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def quantize_kv(x):
    """Symmetric absmax int8 over the last axis: ``x (..., hd)`` ->
    ``(codes int8 (..., hd), scale f32 (...,))`` with
    ``x ~= codes * scale``.  One scale per token per head — the grain a
    token-at-a-time decode write can produce without touching the rest
    of its page."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / KV_QMAX
    q = jnp.clip(jnp.round(xf / scale[..., None]), -KV_QMAX, KV_QMAX)
    return q.astype(jnp.int8), scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# The contiguous per-row view of a paged bank: (NP, Hkv, page, hd) pool +
# (B, P) tables -> (B, Hkv, P*page, hd).  ONE definition, shared with the
# kernel package's oracle — the gathered values are elementwise what the
# row-cache layout holds at every written position, so the row attention
# math downstream is bitwise the row engine's (unwritten positions differ
# only in masked garbage).
from repro.kernels.paged_attention.ref import (  # noqa: E402
    gather_pages as _gather_pages, gather_scales as _gather_scales)


def _bank_write(bank: PagedKV, r, pids, new: PagedKV,
                slots=None) -> PagedKV:
    """The one write into a stacked bank: ``new``'s leaves go to layer(s)
    ``r`` and pages ``pids`` (index arrays that broadcast together to an
    index shape I; a scalar ``r`` is one layer).

    ``slots`` given: token rows — ``new`` holds (I..., Hkv, hd) codes and
    (I..., Hkv) scales, written at slot ``slots`` of each head.  Page,
    head and slot are all scatter indices, so the window is ``hd`` alone.
    A window of (Hkv, hd), with the page axis between them skipped, makes
    XLA lay the bank out head-major and copy it in and out of that layout
    around every write.  ``slots`` None: whole pages — (I..., Hkv, page,
    hd) and a page's Hkv*page scales, a window that is the page itself.

    Either way XLA keeps the bank's own layout and updates the donated
    buffer in place.  Duplicate indices (parked writes) are allowed."""
    if slots is None:
        idx = (r, pids)
    else:
        h = jnp.arange(bank.k.shape[2], dtype=jnp.int32)
        idx = (r, pids[..., None], h, slots[..., None])

    def put(leaf, x):
        return leaf.at[idx].set(x.astype(leaf.dtype))

    return PagedKV(k=put(bank.k, new.k), v=put(bank.v, new.v),
                   ks=_write_scales(bank.ks, r, pids, new.ks, slots),
                   vs=_write_scales(bank.vs, r, pids, new.vs, slots))


def _write_scales(leaf, r, pids, x, slots):
    """``_bank_write`` for a scale leaf (R, NP, Hkv, 1, page), through a
    (R, NP, Hkv*page/lanes, lanes) view of it.  On the TPU the leaf's
    (1, page) rows are tiled (1, 128): byte for byte the order of that
    view tiled (8, 128), so the view is free.  A scatter into the 5-D
    leaf makes XLA move it to an (8, 128) tiling of (Hkv, page) and back
    around every write."""
    if leaf is None:
        return None
    Hkv, page = leaf.shape[2], leaf.shape[-1]
    view = _scale_view(leaf)
    x = x.astype(leaf.dtype)
    if slots is None:                       # whole pages
        lead = jnp.broadcast_shapes(jnp.shape(r), jnp.shape(pids))
        view = view.at[r, pids].set(x.reshape(lead + view.shape[2:]))
    else:                                   # one scale per head at a slot
        lanes = view.shape[-1]
        off = jnp.arange(Hkv, dtype=jnp.int32) * page + slots[..., None]
        view = view.at[r, pids[..., None], off // lanes, off % lanes].set(x)
    return view.reshape(leaf.shape)


def _scale_view(leaf):
    """A scale leaf (R, NP, Hkv, 1, page) as (R, NP, Hkv*page/lanes,
    lanes): the same bytes on the TPU (see ``_write_scales``)."""
    R, NP, Hkv, _, page = leaf.shape
    lanes = math.gcd(page, 128)
    return leaf.reshape(R, NP, Hkv * page // lanes, lanes)


def _stored(bank: PagedKV, k, v) -> PagedKV:
    """k/v rows (..., hd) as the bank stores them: int8 banks quantize
    (codes plus one scale per row), full-precision banks keep them."""
    if bank.ks is None:
        return PagedKV(k=k, v=v)
    kq, ksc = quantize_kv(k)
    vq, vsc = quantize_kv(v)
    return PagedKV(k=kq, v=vq, ks=ksc, vs=vsc)


def _page_write(cache: BankLayer, k, v, tables, positions, wmask=None):
    """Scatter (B, K) token k/v into layer ``cache.layer`` of the bank.

    k/v: (B, K, Hkv, hd); tables: (B, P) int32; positions: (B, K) int32
    virtual positions; ``wmask`` ((B, K) bool, optional) routes False
    tokens' writes to the PARK page instead — pad tokens in a chunk, and
    non-live rows' per-step decode writes, land in garbage space without
    touching any request's pages.

    int8 pools (``ks is not None``) quantize on write: each token's
    (Hkv, hd) k/v rows become int8 codes plus a per-head scale scattered
    into the parallel scale leaf at the same (page, head, slot)."""
    bank, r = cache
    P = tables.shape[1]
    page = bank.k.shape[-2]
    positions = jnp.asarray(positions, jnp.int32)
    pidx = jnp.minimum(positions // page, P - 1)    # clamp: parked rows
    pids = jnp.take_along_axis(tables, pidx, axis=1)
    if wmask is not None:
        pids = jnp.where(wmask, pids, PARK_PAGE)
    return BankLayer(_bank_write(bank, r, pids, _stored(bank, k, v),
                                 slots=positions % page), r)


def _layer_pool(cache: BankLayer) -> PagedKV:
    """One layer's pool sliced out of the bank: the jnp reference reads
    and the global-gather mesh path, which the kernels bypass."""
    return jax.tree.map(lambda leaf: leaf[cache.layer], cache.bank)


def _gather(pool: PagedKV, tables, dtype):
    """The jnp reference read of one pool: (kg, vg) (B, Hkv, P*page, hd)
    through the page tables.  An int8 pool's codes and scales gather
    alike and dequantize to ``dtype``; unwritten positions hold code 0
    (exact 0.0 — the same masked garbage as the full-precision pool)."""
    if pool.ks is None:
        return _gather_pages(pool.k, tables), _gather_pages(pool.v, tables)
    kg = dequantize_kv(_gather_pages(pool.k, tables),
                       _gather_scales(pool.ks, tables), dtype)
    vg = dequantize_kv(_gather_pages(pool.v, tables),
                       _gather_scales(pool.vs, tables), dtype)
    return kg, vg


class BankShard(NamedTuple):
    """A page bank split over mesh axis ``axis`` (page axis).  With
    ``local_read`` attention is shard_mapped so each shard reads only its
    own slice (``attention_*_pages_sharded``); otherwise every device
    reads the whole bank (the global-gather path)."""
    mesh: Any
    axis: str
    local_read: bool = True


def _replicated(kernel, shard: Optional[BankShard]):
    """Run a Pallas call in a program laid out over ``shard.mesh`` (the
    global-gather paged path, and prefill beside a split bank).  Mosaic
    kernels cannot be auto-partitioned, so the call is shard_mapped with
    every operand replicated: each device gathers whole operands (the
    whole bank) and runs the one-device kernel, bitwise its result."""
    if shard is None:
        return kernel
    from jax import shard_map
    from jax.sharding import PartitionSpec as Ps
    return shard_map(kernel, mesh=shard.mesh, in_specs=Ps(),
                     out_specs=Ps(), check_vma=False)


def attention_decode_pages(params, x, pos, cache: BankLayer, tables,
                           cfg: ArchConfig, wmask=None, shard=None):
    """One-step decode against layer ``cache.layer`` of the shared page
    bank.  x: (B, 1, D); pos: (B,) int32 (or scalar, broadcast); tables:
    (B, P) int32; ``wmask`` ((B,) bool, optional): False rows write to
    the park page (non-live slots must not disturb recycled pages).
    Returns (out, the written ``BankLayer``).

    Write-then-read in the same order as ``attention_decode`` — the new
    token's k/v land in its page first, then attention reads the gathered
    pages under the same ``idx <= pos`` mask, so live rows' outputs are
    bitwise the row engine's.

    ``shard`` (a ``BankShard``, optional) says the bank is split over a
    mesh; with ``local_read`` it switches to the shard_mapped local-read
    path: see ``attention_decode_pages_sharded``."""
    if shard is not None and shard.local_read:
        return attention_decode_pages_sharded(params, x, pos, cache,
                                              tables, cfg, shard,
                                              wmask=wmask)
    B = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None]
    q, k, v = _qkv(params, x, positions, cfg)     # q: (B,1,H,hd)
    cache = _page_write(cache, k, v, tables, positions,
                        wmask=None if wmask is None else wmask[:, None])

    import repro.kernels as kernels
    if kernels.use_kernels():
        from repro.kernels.paged_attention.ops import paged_decode_attention
        interp = None if kernels.get_mode() == "auto" else True
        bank, layer = _kernel_bank(cache, shard)

        def kernel(q, k, v, tables, pos, ks, vs, layer):
            return paged_decode_attention(q, k, v, tables, pos, k_scale=ks,
                                          v_scale=vs, layer=layer,
                                          interpret=interp)
        out = _replicated(kernel, shard)(q[:, 0], bank.k, bank.v, tables,
                                         pos, bank.ks, bank.vs,
                                         layer)[:, None]
    else:
        kg, vg = _gather(_layer_pool(cache), tables, x.dtype)
        valid = jnp.arange(kg.shape[2])[None, :] <= pos[:, None]
        out = decode_sdpa(q, kg, vg, valid, cfg)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return out, cache


def _kernel_bank(cache: BankLayer, shard):
    """(bank, layer) for a paged kernel: the whole stacked bank and its
    layer index; on the global-gather mesh path, one layer's pool (layer
    None), since every device gathers whole what the kernel reads."""
    if shard is None:
        return cache
    return _layer_pool(cache), None


def attention_verify_pages(params, x, pos, cache: BankLayer, tables,
                           cfg: ArchConfig, wmask=None, offsets=None,
                           tree=None, shard=None):
    """Multi-token verify/chunk decode against layer ``cache.layer`` of
    the shared page bank.

    x: (B, K, D) block tokens at positions ``pos[b] .. pos[b]+K-1``;
    attention reads the pool as it stood BEFORE the block (through the
    page table) plus the block's own k/v under an intra-block causal
    mask — the same cache-plus-block split as ``attention_verify`` — then
    all K tokens' k/v are scattered into the row's pages (``wmask`` pads
    route to the park page).  No fresh-row zeroing is needed: a page is
    written by its owner before any of its positions become readable
    (reads mask ``cols < pos``), so a recycled page's stale content can
    never leak into a new request.

    Tree verification: ``offsets`` ((K,) int32, optional) replaces the
    default ``arange(K)`` position offsets with per-node tree depths
    (RoPE and write slots), and ``tree`` ((B, K) int32 ancestor
    bitmasks) replaces the intra-block causal mask — bit j of
    ``tree[b, i]`` makes block token j visible to block query i.
    Sibling branches share a depth, so the caller MUST park all but one
    writer per depth through ``wmask`` (the scatter has one slot per
    position).

    ``shard`` (a ``BankShard``, optional) says the bank is split over a
    mesh; with ``local_read`` it switches to the shard_mapped local-read
    path: see ``attention_verify_pages_sharded``."""
    if shard is not None and shard.local_read:
        return attention_verify_pages_sharded(params, x, pos, cache,
                                              tables, cfg, shard,
                                              wmask=wmask, offsets=offsets,
                                              tree=tree)
    B, K, _ = x.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    if offsets is None:
        offsets = jnp.arange(K, dtype=jnp.int32)
    positions = pos[:, None] + jnp.asarray(offsets, jnp.int32)[None]
    q, k, v = _qkv(params, x, positions, cfg)     # q: (B,K,H,hd)

    import repro.kernels as kernels
    if kernels.use_kernels():
        from repro.kernels.paged_attention.ops import paged_verify_attention
        interp = None if kernels.get_mode() == "auto" else True

        bank, layer = _kernel_bank(cache, shard)

        def kernel(q, kp, vp, k, v, tables, pos, ks, vs, tree, layer):
            return paged_verify_attention(q, kp, vp, k, v, tables, pos,
                                          k_scale=ks, v_scale=vs, tree=tree,
                                          layer=layer, interpret=interp)
        out = _replicated(kernel, shard)(q, bank.k, bank.v, k, v, tables,
                                         pos, bank.ks, bank.vs, tree, layer)
    else:
        from repro.kernels.verify_attention.ref import verify_reference
        kg, vg = _gather(_layer_pool(cache), tables, x.dtype)
        out = verify_reference(q, kg, vg, k, v, pos, ring=False, tree=tree)

    cache = _page_write(cache, k, v, tables, positions, wmask=wmask)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return out, cache


# ---------------------------------------------------------------------------
# sharded page bank: per-shard LOCAL reads under shard_map
#
# The functions above gather the WHOLE bank through the page table — under
# a mesh that is an all-gather of every shard's slice per step.  The
# sharded paths below shard_map attention instead: each mesh shard holds
# local pages [s*L, (s+1)*L) of the bank (L = NP/num_shards), recovers its
# local index as ``table - s*L``, reads/writes ONLY entries it owns, and
# the per-shard unnormalized flash partials (acc, m, l) merge with one
# pmax/psum.  The merged softmax is mathematically the global one, but the
# reduction ORDER differs from the single-gather path, so local-read
# outputs are allclose-, not bitwise-, equivalent (the engine keeps the
# global-gather path as its bitwise default).  Out-of-slice writes land in
# the shard's own reserved local page 0 (``ShardedPagePool`` never
# allocates any shard's local page 0), so no write crosses shards either —
# the paper's dual-port disturb-free argument at rack scale.
# ---------------------------------------------------------------------------

def _bank_leaves(cache: BankLayer):
    """(leaves, layer) of a bank for a shard_map: the stacked leaves,
    scale leaves only where the bank is int8, each split on its page
    axis by ``Ps(None, axis)``."""
    bank, r = cache
    leaves = tuple(bank) if bank.ks is not None else (bank.k, bank.v)
    return leaves, r


def _local_pages(tables, num_local: int, axis: str):
    """This shard's view of the (B, P) page table, inside shard_map:
    -> (local_table, owned) where ``owned`` marks entries whose page
    lives on this shard and ``local_table`` holds their local indices
    (everything else points at the shard's local park page 0)."""
    base = jax.lax.axis_index(axis) * num_local
    lt = tables - base
    owned = (lt >= 0) & (lt < num_local)
    return jnp.where(owned, lt, PARK_PAGE), owned


def _paged_partial(q, kg, vg, valid, scale):
    """Unnormalized flash partial over ONE gathered bank slice.

    q: (B, K, H, hd); kg/vg: (B, Hkv, S, hd); valid: (B, K, S) bool (a
    broadcastable (B, 1, S) is fine).  -> (acc (B, Hkv, K, G, hd) f32,
    m, l (B, Hkv, K, G) f32).  ``NEG_INF`` is finite, so a fully-masked
    row has ``m == NEG_INF`` and ``exp(s - m) == 1`` there — the
    explicit re-mask of ``p`` (not just ``s``) is what keeps that row's
    l/acc at exact 0.0 so the cross-shard combine ignores it."""
    B, K, H, hd = q.shape
    Hkv = kg.shape[1]
    G = H // Hkv
    qh = (q.reshape(B, K, Hkv, G, hd).transpose(0, 2, 1, 3, 4)
          .astype(jnp.float32))
    s = jnp.einsum("bnigd,bnsd->bnigs", qh, kg.astype(jnp.float32)) * scale
    vmask = valid[:, None, :, None, :]
    s = jnp.where(vmask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(vmask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bnigs,bnsd->bnigd", p, vg.astype(jnp.float32))
    return acc, m, l


def _psum_partials(acc, m, l, axis: str):
    """Merge per-shard flash partials across mesh axis ``axis`` —
    rescale every shard's (acc, l) to the global running max, then sum.
    Returns the still-unnormalized (acc, m, l), replicated."""
    mg = jax.lax.pmax(m, axis)
    w = jnp.exp(m - mg)
    return (jax.lax.psum(acc * w[..., None], axis), mg,
            jax.lax.psum(l * w, axis))


def _fold_block(acc, m, l, qh, kb, vb, scale, tree):
    """Fold the verify block's own K keys/values — replicated, identical
    on every shard — into a combined cache partial, then normalize.
    qh: (B, Hkv, K, G, hd) f32; kb/vb: (B, K, Hkv, hd); ``tree``
    ((B, K) int32 ancestor bitmasks) replaces the intra-block causal
    mask.  Exact flash fold: together with ``_psum_partials`` this is
    ``verify_reference``'s joint softmax in a different reduction
    order."""
    kbh = kb.astype(jnp.float32).transpose(0, 2, 1, 3)   # (B, Hkv, K, hd)
    vbh = vb.astype(jnp.float32).transpose(0, 2, 1, 3)
    K = kbh.shape[2]
    s = jnp.einsum("bnigd,bnjd->bnigj", qh, kbh) * scale
    if tree is None:
        ii = jnp.arange(K, dtype=jnp.int32)
        keep = (ii[None, :] <= ii[:, None])[None, None, :, None, :]
    else:
        t = jnp.asarray(tree, jnp.int32)
        keep = (((t[:, :, None] >> jnp.arange(K, dtype=jnp.int32)) & 1)
                == 1)[:, None, :, None, :]
    s = jnp.where(keep, s, NEG_INF)
    m2 = jnp.maximum(m, jnp.max(s, axis=-1))
    pb = jnp.where(keep, jnp.exp(s - m2[..., None]), 0.0)
    l2 = l * jnp.exp(m - m2) + jnp.sum(pb, axis=-1)
    acc2 = (acc * jnp.exp(m - m2)[..., None]
            + jnp.einsum("bnigj,bnjd->bnigd", pb, vbh))
    return acc2 / jnp.maximum(l2, 1e-30)[..., None]


def _heads_out(out, dt):
    """(B, Hkv, K, G, hd) f32 merged partial -> (B, K, H, hd) in the
    activation dtype."""
    out = out.transpose(0, 2, 1, 3, 4)
    return out.reshape(out.shape[0], out.shape[1], -1,
                       out.shape[-1]).astype(dt)


def attention_decode_pages_sharded(params, x, pos, cache: BankLayer,
                                   tables, cfg: ArchConfig, shard,
                                   wmask=None):
    """``attention_decode_pages`` with the bank sharded over mesh axis
    ``shard.axis`` of ``shard.mesh``: each shard writes/reads only its local
    slice (local Pallas partial kernel when kernels are on, jnp partial
    otherwise) and the per-shard flash partials merge with one
    pmax/psum.  Allclose — not bitwise — to the global-gather path (the
    merge changes the softmax reduction order)."""
    mesh, axis = shard.mesh, shard.axis
    from jax.sharding import PartitionSpec as Ps
    from jax import shard_map

    B = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None]
    q, k, v = _qkv(params, x, positions, cfg)     # q: (B,1,H,hd)
    bank, r = _bank_leaves(cache)
    tables = jnp.asarray(tables, jnp.int32)
    P = tables.shape[1]
    page = cache.bank.k.shape[-2]
    scale = 1.0 / (cfg.head_dim ** 0.5)
    dt = x.dtype
    wm = (jnp.ones((B, 1), bool) if wmask is None
          else jnp.asarray(wmask, bool)[:, None])

    def local(bank, r, q, k, v, tables, pos, wm):
        lc = BankLayer(PagedKV(*bank), r)
        L = lc.bank.k.shape[1]
        lt, owned = _local_pages(tables, L, axis)
        positions = pos[:, None]
        pidx = jnp.minimum(positions // page, P - 1)
        own_tok = jnp.take_along_axis(owned, pidx, axis=1)   # (B, 1)
        # write first (same order as the unsharded path); out-of-slice
        # tokens park into THIS shard's reserved local page 0
        lc = _page_write(lc, k, v, lt, positions, wmask=own_tok & wm)

        import repro.kernels as kernels
        if kernels.use_kernels():
            from repro.kernels.paged_attention.ops import (
                paged_decode_partial)
            interp = None if kernels.get_mode() == "auto" else True
            base = jax.lax.axis_index(axis) * L
            lb = lc.bank
            acc, m, l = paged_decode_partial(
                q[:, 0], lb.k, lb.v, tables, pos, base, k_scale=lb.ks,
                v_scale=lb.vs, layer=r, interpret=interp)
            acc, m, l = acc[:, :, None], m[:, :, None], l[:, :, None]
        else:
            kg, vg = _gather(_layer_pool(lc), lt, dt)
            own_pos = jnp.repeat(owned, page, axis=1)        # (B, S)
            valid = ((jnp.arange(kg.shape[2])[None, :] <= pos[:, None])
                     & own_pos)[:, None, :]                  # (B, 1, S)
            acc, m, l = _paged_partial(q, kg, vg, valid, scale)
        accg, mg, lg = _psum_partials(acc, m, l, axis)
        out = accg / jnp.maximum(lg, 1e-30)[..., None]
        return out, tuple(lc.bank)[:len(bank)]

    bank_specs = tuple(Ps(None, axis) for _ in bank)
    f = shard_map(local, mesh=mesh,
                  in_specs=(bank_specs, Ps(), Ps(), Ps(), Ps(), Ps(), Ps(),
                            Ps()),
                  out_specs=(Ps(), bank_specs), check_vma=False)
    out, bank = f(bank, r, q, k, v, tables, pos, wm)
    cache = BankLayer(PagedKV(*bank), r)
    out = _heads_out(out, dt)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return out, cache


def attention_verify_pages_sharded(params, x, pos, cache: BankLayer,
                                   tables, cfg: ArchConfig, shard,
                                   wmask=None, offsets=None, tree=None):
    """``attention_verify_pages`` with per-shard local bank reads (see
    ``attention_decode_pages_sharded``).  The cache side of the
    cache-plus-block split runs as per-shard partials merged with
    pmax/psum; the block's own K keys/values are replicated, so their
    fold — and the intra-block causal/tree mask — happens once outside
    the shard_map.  Allclose, not bitwise, to the global-gather path."""
    mesh, axis = shard.mesh, shard.axis
    from jax.sharding import PartitionSpec as Ps
    from jax import shard_map

    B, K, _ = x.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    if offsets is None:
        offsets = jnp.arange(K, dtype=jnp.int32)
    positions = pos[:, None] + jnp.asarray(offsets, jnp.int32)[None]
    q, k, v = _qkv(params, x, positions, cfg)     # q: (B,K,H,hd)
    bank, r = _bank_leaves(cache)
    tables = jnp.asarray(tables, jnp.int32)
    P = tables.shape[1]
    page = cache.bank.k.shape[-2]
    scale = 1.0 / (cfg.head_dim ** 0.5)
    dt = x.dtype
    wm = (jnp.ones((B, K), bool) if wmask is None
          else jnp.asarray(wmask, bool))

    def local(bank, r, q, k, v, tables, positions, pos, wm):
        lc = BankLayer(PagedKV(*bank), r)
        lt, owned = _local_pages(tables, lc.bank.k.shape[1], axis)
        # cache side reads the pool as it stood BEFORE the block
        kg, vg = _gather(_layer_pool(lc), lt, dt)
        own_pos = jnp.repeat(owned, page, axis=1)
        valid = ((jnp.arange(kg.shape[2])[None, :] < pos[:, None])
                 & own_pos)[:, None, :]                      # (B, 1, S)
        acc, m, l = _paged_partial(q, kg, vg, valid, scale)
        parts = _psum_partials(acc, m, l, axis)
        pidx = jnp.minimum(positions // page, P - 1)
        own_tok = jnp.take_along_axis(owned, pidx, axis=1)   # (B, K)
        lc = _page_write(lc, k, v, lt, positions, wmask=own_tok & wm)
        return parts, tuple(lc.bank)[:len(bank)]

    bank_specs = tuple(Ps(None, axis) for _ in bank)
    f = shard_map(local, mesh=mesh,
                  in_specs=(bank_specs, Ps(), Ps(), Ps(), Ps(), Ps(), Ps(),
                            Ps(), Ps()),
                  out_specs=((Ps(), Ps(), Ps()), bank_specs),
                  check_vma=False)
    (accg, mg, lg), bank = f(bank, r, q, k, v, tables, positions, pos, wm)
    cache = BankLayer(PagedKV(*bank), r)
    Hkv = cfg.num_kv_heads
    hd = cfg.head_dim
    qh = (q.reshape(B, K, Hkv, -1, hd).transpose(0, 2, 1, 3, 4)
          .astype(jnp.float32)) * scale
    out = _fold_block(accg, mg, lg, qh, k, v, 1.0, tree)
    out = _heads_out(out, dt)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return out, cache


def insert_pages(cache: PagedKV, rows: KVCache, tables) -> PagedKV:
    """Admission: scatter freshly prefilled cache rows (R, B, Hkv, S, hd)
    into the stacked bank (R, NP, ...) through (B, P) page tables
    (S == P*page), every layer at once.  Dead table entries (past a
    row's allocation) point at the park page, so the unconditional all-P
    scatter parks the rows' zero tails instead of touching anyone's
    pages.  Only the named pages change — the same disturb-free contract
    as ``LM.insert_cache_rows``."""
    R, B, Hkv, S, hd = rows.k.shape
    P = tables.shape[1]
    page = cache.k.shape[-2]
    assert S == P * page, (S, P, page)

    def paged_view(x):                      # (R, B, P, Hkv, page, hd)
        return (x.reshape(R, B, Hkv, P, page, hd)
                .transpose(0, 1, 3, 2, 4, 5))

    new = _stored(cache, paged_view(rows.k), paged_view(rows.v))
    layer = jnp.arange(R, dtype=jnp.int32)[:, None, None]
    return _bank_write(cache, layer, tables[None], new)


def copy_pages(cache: PagedKV, src, dst) -> PagedKV:
    """Device-side page copy: ``bank[:, dst[i]] = bank[:, src[i]]`` in
    every layer and leaf of the stacked bank (codes AND scales for an
    int8 pool — the copy is a byte copy, never a re-quantization).
    src/dst: (n,) int32 page ids.

    This is the copy-on-write primitive of prefix sharing: a request
    that diverges mid-page gets a private copy of the shared boundary
    page BEFORE its first write, so shared pages are never mutated and
    every reader keeps seeing bitwise the values its cold admission
    would have produced."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    new = PagedKV(k=cache.k[:, src], v=cache.v[:, src])
    if cache.ks is not None:                # read through the write's view
        new = new._replace(ks=_scale_view(cache.ks)[:, src],
                           vs=_scale_view(cache.vs)[:, src])
    layer = jnp.arange(cache.k.shape[0], dtype=jnp.int32)[:, None]
    return _bank_write(cache, layer, dst[None], new)


def attention_decode(params, x, pos, cache: KVCache, cfg: ArchConfig):
    """One-step decode.  x: (B, 1, D); pos: scalar int32 (whole batch at
    one position — the run-to-completion loop) or (B,) int32 (continuous
    batching: every row is at its own position).

    Full-attention: cache length == max_len, slot = pos.
    Sliding-window: cache length == window (ring), slot = pos % window.
    """
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    positions = pos[:, None] if per_row else jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _qkv(params, x, positions, cfg)     # q: (B,1,H,hd)
    S = cache.k.shape[2]
    slot = pos % S if cfg.sliding_window > 0 else pos
    kT = k.swapaxes(1, 2).astype(cache.k.dtype)   # (B, Hkv, 1, hd)
    vT = v.swapaxes(1, 2).astype(cache.v.dtype)
    if per_row:
        # per-row write slot: scatter one token into each row's cache line
        rows = jnp.arange(B)
        slot = jnp.minimum(slot, S - 1)           # freed slots park at S-1
        k_new = cache.k.at[rows, :, slot, :].set(kT[:, :, 0, :])
        v_new = cache.v.at[rows, :, slot, :].set(vT[:, :, 0, :])
    else:
        k_new = jax.lax.dynamic_update_slice(cache.k, kT, (0, 0, slot, 0))
        v_new = jax.lax.dynamic_update_slice(cache.v, vT, (0, 0, slot, 0))

    import repro.kernels as kernels
    if kernels.use_kernels():
        from repro.kernels.decode_attention.ops import decode_attention
        interp = None if kernels.get_mode() == "auto" else True
        ring = cfg.sliding_window > 0
        out = decode_attention(q[:, 0], k_new, v_new, pos, ring=ring,
                               interpret=interp)[:, None]
    else:
        idx = jnp.arange(S)
        pv = pos[:, None] if per_row else pos     # broadcast -> (B,S) / (S,)
        if cfg.sliding_window > 0:
            valid = (idx <= pv % S) | (pv >= S)   # ring not yet full -> mask
        else:
            valid = idx <= pv
        out = decode_sdpa(q, k_new, v_new, valid, cfg)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return out, KVCache(k=k_new, v=v_new)


def attention_verify(params, x, pos, cache: KVCache, cfg: ArchConfig,
                     wmask=None):
    """Multi-token verify decode (speculative decode's target pass).

    x: (B, K, D) — the K block tokens per row, at positions
    ``pos[b] .. pos[b]+K-1`` (``pos``: scalar or (B,) int32).  Attention
    reads the cache as it stood BEFORE this block plus the block's own
    keys/values under an intra-block causal mask, so token i sees exactly
    the state the i-th sequential ``attention_decode`` step would have
    seen — loop-exact even across a ring wraparound (where write-then-mask
    is not: a later token's write lands on a slot an earlier query must
    still read).  All K tokens' k/v are then written.  Returns
    (out (B, K, D), new cache).

    ``wmask`` ((B, K) bool, optional) gates the cache WRITES only: a
    False token computes normally but leaves its cache slot untouched.
    Chunked prefill pads its last chunk to a fixed width with trailing
    tokens — pads sit at the block's end, so no real token attends to
    them, and the write mask keeps their k/v out of the cache.
    """
    B, K, _ = x.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None] + jnp.arange(K, dtype=jnp.int32)[None]
    q, k, v = _qkv(params, x, positions, cfg)     # q: (B,K,H,hd)
    S = cache.k.shape[2]
    ring = cfg.sliding_window > 0

    import repro.kernels as kernels
    if kernels.use_kernels():
        from repro.kernels.verify_attention.ops import verify_attention
        interp = None if kernels.get_mode() == "auto" else True
        out = verify_attention(q, cache.k, cache.v, k, v, pos, ring=ring,
                               interpret=interp)
    else:
        from repro.kernels.verify_attention.ref import verify_reference
        out = verify_reference(q, cache.k, cache.v, k, v, pos, ring=ring)

    # write the block: slot = position (% S for rings); parked/retired rows
    # clamp at S-1 — their rows are dead and fully rewritten at the next
    # admission, so the duplicate clamped writes are harmless
    slots = positions % S if ring else jnp.minimum(positions, S - 1)
    rows = jnp.arange(B)[:, None]
    kw, vw = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
    if wmask is not None:
        # masked tokens write back what the slot already holds
        m = wmask[:, :, None, None]
        kw = jnp.where(m, kw, cache.k[rows, :, slots])
        vw = jnp.where(m, vw, cache.v[rows, :, slots])
    k_new = cache.k.at[rows, :, slots].set(kw)
    v_new = cache.v.at[rows, :, slots].set(vw)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return out, KVCache(k=k_new, v=v_new)


def decode_sdpa(q, k_cache, v_cache, valid, cfg: ArchConfig):
    """q: (B,1,H,hd); caches: (B,Hkv,S,hd); valid: (S,) or (B,S) bool."""
    B, _, H, hd = q.shape
    Hkv = k_cache.shape[1]
    G = H // Hkv
    qh = q.reshape(B, Hkv, G, hd)
    scores = jnp.einsum("bngk,bnsk->bngs", qh,
                        k_cache.astype(qh.dtype)).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    if valid.ndim == 1:
        valid = valid[None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bngs,bnsk->bngk", probs, v_cache.astype(q.dtype))
    return out.reshape(B, 1, H, hd)
