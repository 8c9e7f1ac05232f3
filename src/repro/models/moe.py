"""Mixture-of-Experts FFN.

Three execution strategies (selected by ``MoELayer`` callers):

* ``moe_dense_ref``   — exact top-k reference: every token visits its top-k
                        experts via dense per-expert einsum over a mask.
                        O(E x tokens) compute; used as the test oracle and
                        for smoke-scale runs.
* ``moe_tp``          — tensor-parallel experts: expert FFN hidden dim is
                        sharded over `model`; tokens are not moved.  Used when
                        num_experts < model-axis size (mixtral: 8e vs 16-wide
                        axis).  XLA inserts the standard TP all-reduce.
* ``moe_ep``          — expert-parallel: experts sharded over `model`;
                        capacity-padded scatter dispatch + all_to_all inside
                        shard_map (production path for qwen3 128e / jamba 16e).

Capacity semantics match across ep/ref when capacity_factor is large enough
that nothing drops (tested); with drops, overflow tokens pass through with
their residual only (standard dropping MoE).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.configs.base import ArchConfig, MoEConfig
from repro.models.common import PSpec


def moe_specs(cfg: ArchConfig) -> dict:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_experts
    return {
        "w_router": PSpec((d, e), ("embed", None), init="scaled", scale=0.02),
        "w_gate": PSpec((e, d, f), ("experts", "embed", "expert_ffn")),
        "w_up": PSpec((e, d, f), ("experts", "embed", "expert_ffn")),
        "w_down": PSpec((e, f, d), ("experts", "expert_ffn", "embed")),
    }


def router(params, x, m: MoEConfig):
    """x: (T, D) -> top-k probs (T, k), indices (T, k), aux loss scalar."""
    logits = (x.astype(jnp.float32) @ params["w_router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)   # renormalize
    # Switch-style load-balancing aux loss
    density = jnp.mean(jax.nn.one_hot(top_i[:, 0], m.num_experts), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * m.num_experts
    return top_p.astype(x.dtype), top_i, aux


def _expert_mlp(w_gate, w_up, w_down, x):
    """x: (E, C, D) grouped tokens; weights (E, D, F)/(E, F, D)."""
    import repro.kernels as kernels
    if kernels.use_kernels():
        from repro.kernels.gmm.ops import expert_mlp
        interp = None if kernels.get_mode() == "auto" else True
        return expert_mlp(x, w_gate, w_up, w_down, interpret=interp)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", x, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


# ---------------------------------------------------------------------------
# reference: exact top-k via masked dense dispatch (oracle)
# ---------------------------------------------------------------------------

def moe_dense_ref(params, x, cfg: ArchConfig):
    """x: (B, S, D).  Every token through every expert, masked to top-k."""
    m = cfg.moe
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    top_p, top_i, aux = router(params, xt, m)
    out = jnp.zeros_like(xt)
    dt = x.dtype
    for e in range(m.num_experts):                    # unrolled: oracle only
        w = jnp.where(top_i == e, top_p, 0).sum(axis=-1)      # (T,)
        h = jax.nn.silu(xt @ params["w_gate"][e].astype(dt))
        h = h * (xt @ params["w_up"][e].astype(dt))
        y = h @ params["w_down"][e].astype(dt)
        out = out + w[:, None].astype(dt) * y
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# TP strategy: experts replicated across devices, FFN dim sharded (E < axis)
# ---------------------------------------------------------------------------

def moe_tp(params, x, cfg: ArchConfig):
    """Dense capacity-free top-k via one-hot combine; expert hidden dim is TP-
    sharded through the logical rules (expert_ffn -> model override).

    The router combine weights are folded into the FFN activations *before*
    the down-projection, so the contraction collapses (e, f) at once and the
    TP partial-sum all-reduce carries (T, D) — not (T, E, D).  (Measured on
    mixtral train_4k: 8x less all-reduce traffic; EXPERIMENTS.md §Perf.)"""
    m = cfg.moe
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    top_p, top_i, aux = router(params, xt, m)
    comb = jnp.zeros((xt.shape[0], m.num_experts), x.dtype)
    comb = jax.vmap(lambda c, i, p: c.at[i].add(p))(comb, top_i, top_p)
    # (T, E) x experts: compute all experts on all tokens, combine.
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, params["w_gate"].astype(x.dtype)))
    h = h * jnp.einsum("td,edf->tef", xt, params["w_up"].astype(x.dtype))
    h = h * comb[:, :, None]
    out = jnp.einsum("tef,efd->td", h, params["w_down"].astype(x.dtype))
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# EP strategy: capacity-padded scatter + all_to_all inside shard_map
# ---------------------------------------------------------------------------

def _dispatch_local(xt, top_p, top_i, num_experts: int, capacity: int):
    """Scatter local tokens into per-expert capacity buffers.

    Returns (buf (E, C, D), slot (T, k), kept (T, k)); slot is the position a
    (token, choice) landed at, kept=False means dropped by capacity.
    """
    T, D = xt.shape
    k = top_i.shape[1]
    flat_e = top_i.reshape(-1)                                  # (T*k,)
    # position of each (token, choice) within its expert's buffer
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)  # (T*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) - 1                   # (T*k, E)
    slot = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    kept = slot < capacity
    dst = jnp.where(kept, flat_e * capacity + slot, num_experts * capacity)
    buf = jnp.zeros((num_experts * capacity + 1, D), xt.dtype)
    src = jnp.repeat(xt, k, axis=0)                             # (T*k, D)
    buf = buf.at[dst].set(src, mode="drop")
    return (buf[:-1].reshape(num_experts, capacity, D),
            slot.reshape(T, k), kept.reshape(T, k))


def _combine_local(y_buf, top_p, top_i, slot, kept, capacity: int):
    """Gather expert outputs back to token order, weighted by router probs."""
    T, k = top_i.shape
    E = y_buf.shape[0]
    flat = y_buf.reshape(E * capacity, -1)
    idx = jnp.where(kept, top_i * capacity + slot, 0)           # (T, k)
    y = flat[idx.reshape(-1)].reshape(T, k, -1)
    w = jnp.where(kept, top_p, 0)
    return jnp.einsum("tkd,tk->td", y, w.astype(y.dtype))


def moe_ep(params, x, cfg: ArchConfig, mesh: Mesh,
           ep_axis: str = "model", fsdp_axis: str | None = "data",
           capacity_factor: float | None = None):
    """Expert-parallel MoE: shard_map over the whole mesh.

    In-specs: tokens are sharded batch->('pod','data') and seq->model
    (sequence parallelism for the MoE region); expert weights are sharded
    experts->model (+ FSDP over data on the embed dim, all-gathered here).
    """
    m = cfg.moe
    ep = mesh.shape[ep_axis]
    assert m.num_experts % ep == 0, (m.num_experts, ep)
    cf = capacity_factor or m.capacity_factor
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)

    tok_spec = P(data_axes, ep_axis, None)          # (B, S, D) local tokens
    wr_spec = P(None, None)
    we_spec = P(ep_axis, fsdp_axis if fsdp_axis in mesh.shape else None, None)
    wd_spec = P(ep_axis, None, fsdp_axis if fsdp_axis in mesh.shape else None)

    def body(x_loc, w_router, w_gate, w_up, w_down):
        if fsdp_axis and fsdp_axis in mesh.shape and mesh.shape[fsdp_axis] > 1:
            w_gate = jax.lax.all_gather(w_gate, fsdp_axis, axis=1, tiled=True)
            w_up = jax.lax.all_gather(w_up, fsdp_axis, axis=1, tiled=True)
            w_down = jax.lax.all_gather(w_down, fsdp_axis, axis=2, tiled=True)
        B, S, D = x_loc.shape
        xt = x_loc.reshape(-1, D)
        T = xt.shape[0]
        top_p, top_i, aux = router({"w_router": w_router}, xt, m)
        capacity = max(int(math.ceil(T * m.top_k / m.num_experts * cf)), 1)
        buf, slot, kept = _dispatch_local(xt, top_p, top_i,
                                          m.num_experts, capacity)
        # deliver: (E, C, D) -> every device keeps its E/ep experts, gathering
        # the C-slices contributed by all ep peers along axis 1.
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)        # (E/ep, C*ep, D)
        y = _expert_mlp(w_gate.astype(xt.dtype), w_up.astype(xt.dtype),
                        w_down.astype(xt.dtype), buf)
        y = jax.lax.all_to_all(y, ep_axis, split_axis=1, concat_axis=0,
                               tiled=True)          # (E, C, D) back home
        out = _combine_local(y, top_p, top_i, slot, kept, capacity)
        aux = jax.lax.pmean(aux, data_axes + (ep_axis,))
        return out.reshape(B, S, D), aux

    fn = shard_map(body, mesh=mesh,
                   in_specs=(tok_spec, wr_spec, we_spec, we_spec, wd_spec),
                   out_specs=(tok_spec, P()), check_vma=False)
    return fn(x, params["w_router"], params["w_gate"], params["w_up"],
              params["w_down"])


def moe_apply(params, x, cfg: ArchConfig, mesh: Mesh | None = None,
              strategy: str = "auto"):
    """Entry point used by the model zoo."""
    m = cfg.moe
    if strategy == "auto":
        tp = mesh.shape.get("model", 1) if mesh is not None else 1
        # EP's all_to_all dispatch shards the seq dim over `model`; decode
        # steps (S == 1) and ragged seqs fall back to expert-sharded dense
        # dispatch (XLA partitions the expert dim + all-reduces the combine).
        if tp > 1 and m.num_experts % tp == 0 and x.shape[1] % tp == 0:
            strategy = "ep"
        elif tp > 1:
            strategy = "tp"
        else:
            strategy = "ref"
    if strategy == "ep":
        return moe_ep(params, x, cfg, mesh)
    if strategy == "tp":
        return moe_tp(params, x, cfg)
    return moe_dense_ref(params, x, cfg)
