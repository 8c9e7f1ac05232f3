"""Training loop substrate: loss, train_step factory, Trainer orchestration.

Production features:
  * microbatch gradient accumulation (``lax.scan``; constant HLO size)
  * remat (activation checkpointing) through the model's scanned blocks
  * chunked cross-entropy — never materializes (B, S, V) f32 logits for the
    150k-vocab archs; the head matmul is recomputed per chunk on backward
  * optional int8 error-feedback gradient compression across the `pod`
    (DCN) axis via partial shard_map — see distributed/compression.py
  * mixed precision: f32 master params, bf16 activations (model casts at use)
  * fault tolerance: CheckpointManager auto-resume, data cursor in the
    checkpoint, deterministic RNG per step
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import RunConfig
from jax import shard_map
from repro.distributed.sharding import (
    DEFAULT_RULES, ShardingRules, shard_params_tree)
from repro.models.model import LM
from repro.train.optimizer import adamw_init, adamw_update, make_schedule
from repro.train.checkpoint import CheckpointManager


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def softmax_xent(logits, labels, mask=None):
    """logits (..., V) f32, labels (...) int32; mean over unmasked."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_lm_loss(hidden, head_w, labels, mask, chunk: int = 1024):
    """CE over the vocab without materializing full logits.

    hidden: (B, S, D); head_w: (D, V); labels/mask: (B, S).
    The per-chunk head matmul + logsumexp is rematerialized on backward.
    """
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    if S % chunk != 0:            # fall back: irregular lengths (tests)
        logits = (hidden @ head_w.astype(hidden.dtype)).astype(jnp.float32)
        return softmax_xent(logits, labels, mask)
    n = S // chunk

    @jax.checkpoint
    def one(h, y, m):
        logits = (h @ head_w.astype(h.dtype)).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        m = m.astype(jnp.float32)
        return jnp.sum((logz - gold) * m), jnp.sum(m)

    def body(carry, xs):
        tot, cnt = carry
        h, y, m = xs
        s, c = one(h, y, m)
        return (tot + s, cnt + c), None

    xs = (hidden.reshape(B, n, chunk, D).swapaxes(0, 1),
          labels.reshape(B, n, chunk).swapaxes(0, 1),
          mask.reshape(B, n, chunk).swapaxes(0, 1))
    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),
                                        jnp.zeros((), jnp.float32)), xs)
    return tot / jnp.maximum(cnt, 1.0)


def lm_loss_fn(model: LM, params, batch, run_cfg: RunConfig,
               chunked: bool | None = None):
    """Next-token loss for every family; handles the VLM patch prefix."""
    cfg = model.cfg
    tokens = batch["tokens"]
    patch = batch.get("patch_embeds")
    remat = run_cfg.parallel.remat != "none"
    labels = tokens[:, 1:]
    if chunked is None:
        chunked = cfg.vocab_size >= 32_000
    n_patch = (cfg.frontend.num_positions
               if cfg.frontend.kind == "vision_patches" else 0)
    hidden, aux = model.hidden(params, tokens, patch, remat=remat)
    # predict token t+1 from hidden at (n_patch + t)
    h = hidden[:, n_patch:-1]
    mask = jnp.ones_like(labels, jnp.float32)
    head_w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    if chunked:
        ce = chunked_lm_loss(h, head_w, labels, mask)
    else:
        logits = (h @ head_w.astype(h.dtype)).astype(jnp.float32)
        ce = softmax_xent(logits, labels, mask)
    moe_w = cfg.moe.aux_loss_weight if cfg.moe is not None else 0.0
    return ce + moe_w * aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# train step factory
# ---------------------------------------------------------------------------

def init_state(model: LM, key, run_cfg: RunConfig) -> dict:
    params = model.init(key)
    state = {"params": params, "opt": adamw_init(params),
             "step": jnp.zeros((), jnp.int32)}
    if run_cfg.parallel.grad_compression == "int8_ef":
        state["ef"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return state


def state_shardings(model: LM, state, mesh: Mesh,
                    rules: ShardingRules = DEFAULT_RULES):
    logical = model.logical()
    p_sh = shard_params_tree(mesh, state["params"], logical, rules)
    out = {"params": p_sh,
           "opt": {"m": shard_params_tree(mesh, state["opt"]["m"], logical,
                                          rules),
                   "v": shard_params_tree(mesh, state["opt"]["v"], logical,
                                          rules),
                   "count": NamedSharding(mesh, P())},
           "step": NamedSharding(mesh, P())}
    if "ef" in state:
        out["ef"] = shard_params_tree(mesh, state["ef"], logical, rules)
    return out


def make_train_step(model: LM, run_cfg: RunConfig,
                    mesh: Mesh | None = None) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics), jit-ready."""
    pcfg = run_cfg.parallel
    ocfg = run_cfg.optimizer
    sched = make_schedule(ocfg)
    compress = (pcfg.grad_compression == "int8_ef" and mesh is not None
                and "pod" in mesh.shape and mesh.shape["pod"] > 1)

    def loss_fn(params, mb):
        # Mixed precision: cast the f32 master params to bf16 on their
        # *shards*, before XLA's FSDP all-gather — halves param-gather
        # wire bytes vs gathering f32 and casting at use (the model's
        # per-use astype then becomes a no-op).
        if pcfg.cast_bf16:
            params = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if p.dtype == jnp.float32 else p, params)
        return lm_loss_fn(model, params, mb, run_cfg)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def accum_grads(params, batch):
        """Microbatched accumulation with a scan (constant HLO size)."""
        A = pcfg.microbatches
        if A <= 1:
            (loss, m), grads = grad_fn(params, batch)
            return loss, m, grads
        def split(x):
            return x.reshape((A, x.shape[0] // A) + x.shape[1:])
        mbs = jax.tree.map(split, batch)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                            params)

        def body(carry, mb):
            acc, ltot = carry
            (loss, m), grads = grad_fn(params, mb)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / A,
                               acc, grads)
            return (acc, ltot + loss / A), m
        (grads, loss), ms = jax.lax.scan(body, (zero, 0.0), mbs)
        m = jax.tree.map(lambda x: x[-1], ms)
        return loss, m, grads

    if not compress:
        def train_step(state, batch):
            loss, m, grads = accum_grads(state["params"], batch)
            new_p, new_opt, om = adamw_update(grads, state["opt"],
                                              state["params"], ocfg, sched)
            out = {"params": new_p, "opt": new_opt,
                   "step": state["step"] + 1}
            if "ef" in state:
                out["ef"] = state["ef"]
            return out, {"loss": loss, **m, **om}
        return train_step

    # ---- int8 error-feedback compression across the pod (DCN) axis -------
    from repro.distributed.compression import compressed_psum_mean

    def train_step(state, batch):
        def per_pod(params, batch, ef):
            loss, m, grads = accum_grads(params, batch)
            grads, ef = compressed_psum_mean(grads, "pod", ef)
            loss = jax.lax.pmean(loss, "pod")
            return loss, m, grads, ef

        wrapped = shard_map(
            per_pod, mesh=mesh,
            in_specs=(P(), P("pod"), P()),
            out_specs=(P(), P(), P(), P()),
            axis_names={"pod"}, check_vma=False)
        loss, m, grads, ef = wrapped(state["params"], batch, state["ef"])
        new_p, new_opt, om = adamw_update(grads, state["opt"],
                                          state["params"], ocfg, sched)
        return ({"params": new_p, "opt": new_opt, "ef": ef,
                 "step": state["step"] + 1},
                {"loss": loss, **jax.tree.map(lambda x: x, m), **om})
    return train_step


# ---------------------------------------------------------------------------
# Trainer orchestration (checkpoint/restart, logging, stragglers)
# ---------------------------------------------------------------------------

@dataclass
class TrainState:
    """Thin holder for the live state dict + bookkeeping."""
    state: dict
    step: int = 0


class Trainer:
    def __init__(self, model: LM, run_cfg: RunConfig, data,
                 mesh: Mesh | None = None, rules=DEFAULT_RULES):
        self.model = model
        self.run_cfg = run_cfg
        self.data = data
        self.mesh = mesh
        self.rules = rules
        self.ckpt = CheckpointManager(run_cfg.checkpoint_dir,
                                      keep=run_cfg.keep_checkpoints)
        self.metrics_log: list[dict] = []

        step_fn = make_train_step(model, run_cfg, mesh)
        if mesh is not None:
            self._jit_step = jax.jit(step_fn, donate_argnums=(0,))
        else:
            self._jit_step = jax.jit(step_fn, donate_argnums=(0,))

    def init_or_restore(self, key) -> dict:
        state = init_state(self.model, key, self.run_cfg)
        latest = self.ckpt.latest_step()
        if latest is not None:
            state, extra = self.ckpt.restore(like=state)
            self.start_step = int(extra.get("step", latest))
        else:
            self.start_step = 0
        return state

    def train(self, state: dict, steps: int, log_cb: Callable | None = None):
        rc = self.run_cfg
        t0 = time.perf_counter()
        step = self.start_step if hasattr(self, "start_step") else 0
        for i in range(step, step + steps):
            batch = self.data.batch_at(i)
            state, metrics = self._jit_step(state, batch)
            if (i + 1) % rc.log_every == 0:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = i + 1
                m["sec_per_step"] = (time.perf_counter() - t0) / (i + 1 - step)
                self.metrics_log.append(m)
                if log_cb:
                    log_cb(m)
            if (i + 1) % rc.checkpoint_every == 0:
                self.ckpt.save(i + 1, state, extra={"step": i + 1,
                                                    "cursor": i + 1})
        self.ckpt.save(step + steps, state,
                       extra={"step": step + steps, "cursor": step + steps})
        self.ckpt.wait()
        return state
