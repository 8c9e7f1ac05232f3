"""Pipeline parallelism: GPipe-style microbatch pipeline over a `pipe` mesh
axis using shard_map + collective_permute.

Not used by the assignment's production mesh (which is (pod, data, model)),
but required for 1000+-node scale where a model no longer fits a single
model-parallel group; tested on small CPU meshes.

The schedule is the classic "loop over (microbatches + stages - 1) ticks"
pipeline: at tick t, stage s processes microbatch t - s; activations hop
stage->stage+1 with ppermute.  Bubble fraction = (S-1)/(M+S-1).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def pipeline_apply(stage_fn: Callable, mesh: Mesh, params_stacked, x,
                   num_microbatches: int, axis: str = "pipe"):
    """Run ``y = stage_fn(params_s, x)`` through S pipeline stages.

    params_stacked: pytree with leading stage axis (sharded over `axis`).
    x: (B, ...) batch; B must divide by num_microbatches.
    """
    S = mesh.shape[axis]
    M = num_microbatches
    B = x.shape[0]
    assert B % M == 0

    def body(params_local, x_local):
        # params_local: stage params (leading axis 1 after sharding) on this
        # stage; x_local: full microbatch set (replicated batch).
        params_me = jax.tree.map(lambda p: p[0], params_local)
        idx = jax.lax.axis_index(axis)
        mbs = x_local.reshape((M, B // M) + x_local.shape[1:])
        buf = jnp.zeros_like(mbs[0])            # stage input register
        outs = jnp.zeros_like(mbs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t; others take the permuted value
            take = jnp.clip(t, 0, M - 1)
            buf = jnp.where(idx == 0, mbs[take], buf)
            y = stage_fn(params_me, buf)
            # last stage records its output for microbatch t - (S-1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            record = jnp.logical_and(idx == S - 1, t >= S - 1)
            outs = jax.lax.cond(
                record,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_idx, 0),
                lambda o: o, outs)
            # hop: stage s -> s+1 (ring permute; stage S-1 -> 0 discarded)
            perm = [(i, (i + 1) % S) for i in range(S)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(M + S - 1))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(idx == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs.reshape((B,) + x_local.shape[1:])

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(axis), P()),
                   out_specs=P(), axis_names={axis}, check_vma=False)
    return fn(params_stacked, x)
