"""Grouped (per-expert) matmul, TPU Pallas — the MoE expert-compute hot-spot.

TPU-native design:
  * grid = (E, C/bc, F/bf, D/bd): one expert per outer step; the contraction
    axis D is innermost/"arbitrary" with an f32 VMEM accumulator, so each
    (bc x bf) output tile is written to HBM exactly once.
  * 128-aligned (bc, bf, bd) tiles feed the MXU at its native shape; the
    per-expert weight tiles stream HBM->VMEM while the previous tile is in
    the MXU (double buffering comes from the sequential grid pipeline).
  * This is the dense-capacity formulation (tokens pre-gathered per expert
    by the dispatch scatter); ragged group sizes are handled one level up
    by capacity padding, keeping the kernel shape-static for the compiler.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BC = 128
DEFAULT_BF = 128
DEFAULT_BD = 256


def _gmm_kernel(x_ref, w_ref, o_ref, acc_scr, *, nd: int):
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0]                                          # (bc, bd)
    w = w_ref[0]                                          # (bd, bf)
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(d == nd - 1)
    def _fin():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def gmm_kernel(x, w, *, block_c: int = DEFAULT_BC, block_f: int = DEFAULT_BF,
               block_d: int = DEFAULT_BD, interpret: bool = False):
    """x: (E, C, D); w: (E, D, F) -> (E, C, F)."""
    E, C, D = x.shape
    F = w.shape[2]
    bc, bf, bd = min(block_c, C), min(block_f, F), min(block_d, D)
    assert C % bc == 0 and F % bf == 0 and D % bd == 0, (C, F, D, bc, bf, bd)
    nd = D // bd

    kernel = functools.partial(_gmm_kernel, nd=nd)
    grid = (E, C // bc, F // bf, nd)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, i, j, d: (e, i, d)),
            pl.BlockSpec((1, bd, bf), lambda e, i, j, d: (e, d, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, i, j, d: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="gmm",
    )(x, w)
