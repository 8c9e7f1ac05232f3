"""Chunkwise-parallel mLSTM (xLSTM), TPU Pallas.

TPU-native design:
  * grid = (B, H, L/c): chunks are the innermost "arbitrary" axis; the
    matrix memory (C: dh x dh), normalizer (n: dh) and stabilizer (m: scalar)
    persist in VMEM scratch across chunks — the O(L) recurrence never leaves
    VMEM, while the O(c^2) intra-chunk part runs on the MXU as dense
    (c x dh)(dh x c) matmuls.
  * c = 128/256 keeps the decay matrix (c x c f32) and the q/k/v tiles
    inside VMEM with dh up to 384 (xlstm-125m: dh = 1536/4 = 384).
  * All gate algebra is log-space with a running max (numerical parity with
    the reference recurrent form is asserted in tests, not just the
    chunkwise oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_CHUNK = 128


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref,
                  h_ref, cfin_ref, nfin_ref, mfin_ref,
                  c_scr, n_scr, m_scr, *, c: int, nc: int, dh: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)

    qc = q_ref[0, 0].astype(jnp.float32)                  # (c, dh)
    kc = k_ref[0, 0].astype(jnp.float32)
    vc = v_ref[0, 0].astype(jnp.float32)
    lic = li_ref[0, 0].astype(jnp.float32)                # (c,)
    lfc = lf_ref[0, 0].astype(jnp.float32)
    C_p = c_scr[...]                                      # (dh, dh)
    n_p = n_scr[...]                                      # (dh, 1)
    m_p = m_scr[0, 0]                                     # scalar

    scale = 1.0 / (dh ** 0.5)
    g = jnp.cumsum(lfc)                                   # (c,)
    dmat = g[:, None] - g[None, :] + lic[None, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    dmat = jnp.where(cols <= rows, dmat, NEG_INF)
    m_intra = jnp.max(dmat, axis=-1)                      # (c,)
    m_inter = g + m_p
    m_t = jnp.maximum(m_intra, m_inter)
    D = jnp.exp(dmat - m_t[:, None])
    scores = jax.lax.dot_general(qc, kc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
    sD = scores * D
    intra_num = jax.lax.dot_general(sD, vc, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    intra_den = jnp.sum(sD, axis=-1)                      # (c,)
    w_inter = jnp.exp(m_inter - m_t)                      # (c,)
    qC = jax.lax.dot_general(qc, C_p, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    inter_num = qC * w_inter[:, None]
    inter_den = (qc @ n_p)[:, 0] * w_inter                # (c,)
    num = intra_num + inter_num
    den = jnp.maximum(jnp.abs(intra_den + inter_den), jnp.exp(-m_t))
    h_ref[0, 0] = (num / den[:, None]).astype(h_ref.dtype)

    # ---- chunk-final state handoff ------------------------------------
    gT = g[c - 1]
    m_new = jnp.maximum(gT + m_p, jnp.max(gT - g + lic))
    wk = jnp.exp(gT - g + lic - m_new)                    # (c,)
    ks = kc * scale
    decay = jnp.exp(gT + m_p - m_new)
    wkv = wk[:, None] * vc                                # (c, dh)
    c_scr[...] = decay * C_p + jax.lax.dot_general(
        ks, wkv, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_scr[...] = decay * n_p + jax.lax.dot_general(
        ks, wk[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[0, 0] = m_new

    @pl.when(t == nc - 1)
    def _fin():
        cfin_ref[0, 0] = c_scr[...]
        nfin_ref[0, 0] = n_scr[...][:, 0]
        mfin_ref[0, 0] = m_scr[0, 0]


def mlstm_chunk_kernel(q, k, v, li, lf, *, chunk: int = DEFAULT_CHUNK,
                       interpret: bool = False):
    """q/k/v: (B, H, L, dh) f32; li/lf: (B, H, L) f32.  L % chunk == 0.

    Returns h (B, H, L, dh) and the final state (C, n, m)."""
    B, H, L, dh = q.shape
    c = min(chunk, L)
    assert L % c == 0, (L, c)
    nc = L // c

    kernel = functools.partial(_mlstm_kernel, c=c, nc=nc, dh=dh)
    grid = (B, H, nc)
    h, C, n, m = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, c, dh), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, c, dh), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, c, dh), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, c), lambda b, h, t: (b, h, t)),
            pl.BlockSpec((1, 1, c), lambda b, h, t: (b, h, t)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, dh), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda b, h, t: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, dh), lambda b, h, t: (b, h, 0)),
            pl.BlockSpec((1, 1), lambda b, h, t: (b, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, dh, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((dh, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mlstm_chunk",
    )(q, k, v, li, lf)
    return h, (C, n, m)
