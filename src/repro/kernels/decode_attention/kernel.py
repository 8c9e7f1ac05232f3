"""Flash-decode, TPU Pallas: one token's attention over a long KV cache.

TPU-native design:
  * GQA is exploited for MXU occupancy: the G = H/Hkv query heads of one kv
    head are batched into a single (G, hd) x (hd, bk) matmul per KV tile —
    the decode analogue of grouping queries, instead of CUDA's
    one-warp-per-head pattern.
  * grid = (B, Hkv, S/bk): the cache-scan axis is innermost/"arbitrary";
    the running-softmax state (m, l, acc) persists in VMEM scratch, so HBM
    traffic is exactly one read of the K/V cache + one vector write.
  * ``pos`` arrives via scalar prefetch (SMEM) as a per-request ``(B,)``
    vector: tiles beyond a request's valid length are skipped *before*
    their DMA is issued — the bandwidth saving that makes early-decode
    steps cheap, now per batch row (continuous batching mixes requests at
    very different positions in one step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BK = 512


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, ring: bool,
                   bk: int, nk: int, S: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = j * bk
    live = jnp.logical_or(k_start <= pos, jnp.bool_(ring) & (pos >= S))

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if ring:
            valid = (cols <= pos % S) | (pos >= S)
        else:
            valid = cols <= pos
        s = jnp.where(valid, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_kernel(q, k, v, pos, *, ring: bool = False,
                            scale: float | None = None,
                            block_k: int = DEFAULT_BK,
                            interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G, hd); k/v: (B, Hkv, S, hd); pos: (B,) int32 — the
    valid length per batch row (scalars are broadcast by the wrapper)."""
    B, Hkv, G, hd = q.shape
    S = k.shape[2]
    bk = min(block_k, S)
    assert S % bk == 0, (S, bk)
    nk = S // bk
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_decode_kernel, scale=scale, ring=ring,
                               bk=bk, nk=nk, S=S)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j, pos: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, pos: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, pos: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, j, pos: (b, h, 0, 0)),
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
        name="decode_attention",
    )(jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)), q, k, v)
