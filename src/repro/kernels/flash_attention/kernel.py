"""Flash attention, TPU Pallas.

TPU-native design (not a CUDA port):
  * grid = (B, H, S/bq, S/bk); the kv dimension is the innermost
    ("arbitrary") axis so the f32 running-softmax state (m, l, acc) lives in
    VMEM scratch across kv steps — the HBM->VMEM pipeline streams K/V tiles
    while the MXU consumes the previous tile.
  * bq x bk tiles are MXU-aligned (128 x 128 default); scores never leave
    VMEM — HBM traffic is Q + K + V + O only (the memory-roofline win over
    the XLA-visible reference path).
  * GQA is native: the k/v BlockSpec index-maps q-head h to kv-head
    h // (H/Hkv); no materialized head repeat.
  * causal/window block skipping: fully-masked tiles are skipped via
    pl.when, halving compute for causal and bounding it for sliding window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BQ = 128
DEFAULT_BK = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, bq: int, bk: int,
                  nk: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * bq
    k_start = j * bk
    # tile-level skip: tile is live unless entirely above the diagonal
    # (causal) or entirely behind the window
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window > 0:
        live &= k_start + bk - 1 > q_start - window

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= (rows - cols) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                               # (bq, 1)
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                            # (bq, bk)
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


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           scale: float | None = None,
                           block_q: int = DEFAULT_BQ,
                           block_k: int = DEFAULT_BK,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, S, hd); k/v: (B, Hkv, S, hd).  S % block == 0."""
    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    assert H % Hkv == 0
    bq, bk = min(block_q, S), min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    group = H // Hkv

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk)
    grid = (B, H, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
