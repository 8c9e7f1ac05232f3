"""Selective scan (Mamba SSM), TPU Pallas.

TPU-native design:
  * The channel dim d_in (8192 for jamba) is the *parallel* grid axis — each
    program owns a (bd, N) state slab in VMEM; channels are independent, so
    no cross-program communication.
  * Time is tiled (bl-step chunks) as the innermost "arbitrary" axis; the
    recurrent state persists in VMEM scratch across time tiles, so HBM
    traffic is one read of u/dt/B/C + one write of y — the recurrence never
    round-trips HBM (the CUDA version's shared-memory trick, mapped to the
    VMEM hierarchy).
  * The inner fori_loop is a true sequential recurrence over the time tile
    but each step is a (bd, N) VPU-wide elementwise op — lane-parallel
    across channels, exactly how the VPU wants it (8x128 vregs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BD = 256     # channels per program
DEFAULT_BL = 128     # time steps per tile


def _ssm_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s0_ref,
                y_ref, sfin_ref, s_scr, *, bl: int, bd: int, nl: int):
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)        # (bd, N)

    A = a_ref[...].astype(jnp.float32)                    # (bd, N)
    u = u_ref[0].astype(jnp.float32)                      # (bl, bd)
    dt = dt_ref[0].astype(jnp.float32)                    # (bl, bd)
    Bm = b_ref[0].astype(jnp.float32)                     # (bl, N)
    Cm = c_ref[0].astype(jnp.float32)                     # (bl, N)
    Dg = d_ref[...].astype(jnp.float32)                   # (1, bd)

    def step(t, s):
        dt_t = jax.lax.dynamic_slice_in_dim(dt, t, 1, 0)  # (1, bd)
        u_t = jax.lax.dynamic_slice_in_dim(u, t, 1, 0)
        B_t = jax.lax.dynamic_slice_in_dim(Bm, t, 1, 0)   # (1, N)
        C_t = jax.lax.dynamic_slice_in_dim(Cm, t, 1, 0)
        dA = jnp.exp(dt_t.T * A)                          # (bd, N)
        dBu = (dt_t * u_t).T * B_t                        # (bd, N)
        s = dA * s + dBu
        y_t = jnp.sum(s * C_t, axis=-1)[None] + u_t * Dg  # (1, bd)
        y_ref[0, pl.ds(t, 1), :] = y_t
        return s

    s = jax.lax.fori_loop(0, bl, step, s_scr[...])
    s_scr[...] = s

    @pl.when(l == nl - 1)
    def _fin():
        sfin_ref[0] = s.astype(sfin_ref.dtype)


def ssm_scan_kernel(u, dt, Bm, Cm, A, D, init_state, *,
                    block_d: int = DEFAULT_BD, block_l: int = DEFAULT_BL,
                    interpret: bool = False):
    """u/dt: (B, L, d_in); Bm/Cm: (B, L, N); A: (d_in, N); D: (1, d_in);
    init_state: (B, d_in, N).  L % block_l == 0, d_in % block_d == 0."""
    B, L, d_in = u.shape
    N = A.shape[1]
    bd = min(block_d, d_in)
    bl = min(block_l, L)
    assert d_in % bd == 0 and L % bl == 0, (d_in, bd, L, bl)
    nd, nl = d_in // bd, L // bl

    kernel = functools.partial(_ssm_kernel, bl=bl, bd=bd, nl=nl)
    grid = (B, nd, nl)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bl, bd), lambda b, d, l: (b, l, d)),   # u
            pl.BlockSpec((1, bl, bd), lambda b, d, l: (b, l, d)),   # dt
            pl.BlockSpec((1, bl, N), lambda b, d, l: (b, l, 0)),    # B
            pl.BlockSpec((1, bl, N), lambda b, d, l: (b, l, 0)),    # C
            pl.BlockSpec((bd, N), lambda b, d, l: (d, 0)),          # A
            pl.BlockSpec((1, bd), lambda b, d, l: (0, d)),          # D
            pl.BlockSpec((1, bd, N), lambda b, d, l: (b, d, 0)),    # s0
        ],
        out_specs=[
            pl.BlockSpec((1, bl, bd), lambda b, d, l: (b, l, d)),   # y
            pl.BlockSpec((1, bd, N), lambda b, d, l: (b, d, 0)),    # s_fin
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, d_in), jnp.float32),
            jax.ShapeDtypeStruct((B, d_in, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(u, dt, Bm, Cm, A, D, init_state)
