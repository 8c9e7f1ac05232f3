"""Unified LM assembly for all assigned architecture families.

Every architecture is a *period* of block types repeated ``num_layers /
period`` times (dense: period 1; jamba: period 8; xlstm: period 4).  The
repeat dimension is ``lax.scan``-ned with stacked params, which keeps the HLO
size independent of depth (critical for the 94-layer dry-runs).

Execution modes:
  * ``forward``      — training forward, logits over the full sequence
  * ``prefill``      — builds the decode cache, returns last-position logits
  * ``decode_step``  — one token against the cache (``serve_step`` lowers this)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ArchConfig
from repro.distributed.sharding import (ShardingRules, DEFAULT_RULES,
                                        constrain, spec_for)
from repro.models import layers, moe as moe_mod, ssm as ssm_mod, xlstm as xl
from repro.models.common import (
    PSpec, stacked, init_params, abstract_params, logical_tree, count_params,
)

Mixer = str   # attn | mamba | mlstm | slstm
Ffn = str     # mlp | moe | none


def block_pattern(cfg: ArchConfig) -> list[tuple[Mixer, Ffn]]:
    if cfg.family == "ssm" and cfg.xlstm is not None:
        p = cfg.xlstm.slstm_every
        return [("slstm", "none") if cfg.is_slstm_layer(i) else
                ("mlstm", "none") for i in range(p)]
    if cfg.family == "hybrid":
        period = cfg.attn_every
        if cfg.moe is not None:
            import math
            period = math.lcm(cfg.attn_every, cfg.moe.every)
        return [("attn" if cfg.is_attention_layer(i) else "mamba",
                 "moe" if cfg.is_moe_layer(i) else "mlp")
                for i in range(period)]
    ffn = "moe" if cfg.moe is not None else "mlp"
    return [("attn", ffn)]


def _block_specs(cfg: ArchConfig, typ: tuple[Mixer, Ffn]) -> dict:
    mixer, ffn = typ
    d = cfg.d_model
    out: dict[str, Any] = {"norm1": PSpec((d,), ("embed",), init="ones")}
    if mixer == "attn":
        out["attn"] = layers.attn_specs(cfg)
    elif mixer == "mamba":
        out["mamba"] = ssm_mod.ssm_specs(cfg)
    elif mixer == "mlstm":
        out["mlstm"] = xl.mlstm_specs(cfg)
    elif mixer == "slstm":
        out["slstm"] = xl.slstm_specs(cfg)
    if ffn != "none":
        out["norm2"] = PSpec((d,), ("embed",), init="ones")
        if ffn == "mlp":
            out["mlp"] = layers.mlp_specs(d, cfg.d_ff, cfg.mlp_gated)
        else:
            out["moe"] = moe_mod.moe_specs(cfg)
    return out


@dataclass
class LM:
    cfg: ArchConfig
    mesh: Mesh | None = None
    rules: ShardingRules = field(default_factory=lambda: DEFAULT_RULES)
    moe_strategy: str = "auto"
    mlstm_mode: str = "auto"          # auto | parallel | chunkwise
    cache_dtype: Any = jnp.bfloat16
    # One-hot matmul embedding lookup: with the table sharded vocab->model,
    # a gather forces GSPMD to rematerialize the full table per step (the
    # "involuntary full rematerialization" SPMD warning); the one-hot
    # contraction keeps the table sharded and reduces the partials with a
    # (B, S, D)-sized all-reduce instead.
    embed_onehot: bool = False
    # Metrics-isolation mode: attention mixers become identity.  The
    # dry-run's kernel-substituted roofline compiles the model twice
    # (normal / identity) — the difference isolates the attention region's
    # HLO cost exactly, which is then replaced by the Pallas flash kernel's
    # analytic HBM traffic (the XLA-visible jnp path materializes f32
    # score chains that the kernel keeps in VMEM).
    attn_identity: bool = False
    # Dry-run metrics mode: fully unroll the layer scan and query-chunk scans
    # so cost_analysis() counts every iteration (XLA visits a while body
    # once); see launch/dryrun.py's two-point depth extrapolation.
    scan_unroll: bool = False

    # ------------------------------------------------------------------ specs
    @property
    def pattern(self) -> list[tuple[Mixer, Ffn]]:
        return block_pattern(self.cfg)

    @property
    def repeats(self) -> int:
        period = len(self.pattern)
        assert self.cfg.num_layers % period == 0, (self.cfg.num_layers, period)
        return self.cfg.num_layers // period

    def param_specs(self) -> dict:
        cfg = self.cfg
        specs: dict[str, Any] = {
            "embed": PSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                           init="scaled", scale=0.02),
            "final_norm": PSpec((cfg.d_model,), ("embed",), init="ones"),
            "blocks": {f"b{p}": stacked(self.repeats, _block_specs(cfg, t))
                       for p, t in enumerate(self.pattern)},
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = PSpec((cfg.d_model, cfg.vocab_size),
                                     ("embed", "vocab"), init="scaled",
                                     scale=0.02)
        if cfg.frontend.kind == "vision_patches":
            specs["patch_proj"] = PSpec(
                (cfg.frontend.embed_dim, cfg.d_model), (None, "embed"))
        return specs

    def init(self, key, dtype=None):
        dtype = dtype or jnp.dtype(self.cfg.param_dtype)
        return init_params(key, self.param_specs(), dtype)

    def abstract(self, dtype=None):
        dtype = dtype or jnp.dtype(self.cfg.param_dtype)
        return abstract_params(self.param_specs(), dtype)

    def logical(self):
        return logical_tree(self.param_specs())

    def n_params(self, active_only: bool = False) -> int:
        if not active_only or self.cfg.moe is None:
            return count_params(self.param_specs())
        from repro.configs.base import override
        cfg_a = override(self.cfg,
                         moe=override(self.cfg.moe,
                                      num_experts=self.cfg.moe.top_k))
        return count_params(LM(cfg_a).param_specs())

    # ------------------------------------------------------------ embeddings
    def _embed_in(self, params, tokens, patch_embeds=None):
        cfg = self.cfg
        adt = jnp.dtype(cfg.dtype)
        if self.embed_onehot:
            oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=adt)
            x = oh @ params["embed"].astype(adt)
        else:
            x = jnp.take(params["embed"], tokens, axis=0).astype(adt)
        if cfg.frontend.kind == "vision_patches" and patch_embeds is not None:
            # decode steps after prefill are text-only: patches already cached
            p = (patch_embeds.astype(adt) @
                 params["patch_proj"].astype(adt))
            x = jnp.concatenate([p, x], axis=1)
        return x

    def _head(self, params, x):
        w = (params["embed"].T if self.cfg.tie_embeddings
             else params["lm_head"])
        return (x @ w.astype(x.dtype)).astype(jnp.float32)

    # --------------------------------------------------------------- blocks
    def _mlstm_train_mode(self, L: int) -> str:
        if self.mlstm_mode != "auto":
            return self.mlstm_mode
        c = self.cfg.xlstm.chunk_size
        return "chunkwise" if (L % c == 0 and L > c) else "parallel"

    def _apply_block(self, typ, p, x, positions, mode, pos, cache,
                     big=None, max_len=None, wmask=None, tables=None,
                     offsets=None, tree=None, shard=None):
        """One block.  Returns (x, new_cache, aux).

        ``max_len`` (prefill mode) and ``wmask`` (verify mode; see
        ``layers.attention_verify``) are threaded EXPLICITLY from the
        caller: they are trace-time inputs, and stashing them on ``self``
        (as an earlier revision did with ``_max_len``) lets one ``LM``
        shared by two pools with different cache sizes retrace against
        the other pool's value — silently building wrong-size caches.

        ``tables`` ((B, P) int32, decode/verify modes) switches the
        attention cache to the shared page pool: ``cache`` is then a
        ``layers.BankLayer`` (this layer of the stacked bank) addressed
        through the per-row page tables, and ``wmask`` gates writes for
        decode too (non-live rows park).  ``offsets``/``tree`` (paged
        verify only) select tree verification — per-node depth offsets
        and per-row ancestor bitmasks; see
        ``layers.attention_verify_pages``.  ``shard``
        (a ``layers.BankShard``, paged modes only) says the page bank is
        split over a mesh: with ``local_read`` the paged attention is
        shard_mapped so each mesh shard reads only its local slice (see
        ``layers.attention_decode_pages_sharded``).
        """
        cfg = self.cfg
        mixer, ffn = typ
        h = layers.rmsnorm(x, p["norm1"].astype(x.dtype), cfg.norm_eps)
        aux = jnp.zeros((), jnp.float32)
        nc = cache
        if mixer == "attn" and self.attn_identity:
            a = h                       # metrics isolation; see attn_identity
        elif mixer == "attn" and big is not None:
            assert mode == "decode"
            a, nc = layers.attention_decode_paged(p["attn"], h, pos, big,
                                                  cache, cfg)
        elif mixer == "attn" and tables is not None:
            if mode == "verify":
                a, nc = layers.attention_verify_pages(p["attn"], h, pos,
                                                      cache, tables, cfg,
                                                      wmask=wmask,
                                                      offsets=offsets,
                                                      tree=tree,
                                                      shard=shard)
            else:
                assert mode == "decode", mode
                a, nc = layers.attention_decode_pages(p["attn"], h, pos,
                                                      cache, tables, cfg,
                                                      wmask=wmask,
                                                      shard=shard)
        elif mixer == "attn":
            if mode == "train":
                a = layers.attention(p["attn"], h, positions, cfg,
                                     self.scan_unroll, self.mesh, self.rules)
            elif mode == "prefill":
                a, nc = layers.attention_prefill(
                    p["attn"], h, positions, cfg, max_len,
                    self.cache_dtype, self.scan_unroll, self.mesh,
                    self.rules, shard)
            elif mode == "verify":
                a, nc = layers.attention_verify(p["attn"], h, pos, cache,
                                                cfg, wmask=wmask)
            else:
                a, nc = layers.attention_decode(p["attn"], h, pos, cache, cfg)
        elif mixer == "mamba":
            # the recurrent decode path takes (B, L, D) with carried state,
            # so "verify" (L == K block tokens) is the same call as decode
            if mode in ("decode", "verify"):
                a, nc = ssm_mod.mamba_decode(p["mamba"], h, cache, cfg)
            else:
                a, st = ssm_mod.mamba_forward(p["mamba"], h, cfg, mode="scan")
                nc = st if mode == "prefill" else cache
        elif mixer == "mlstm":
            if mode in ("decode", "verify"):
                a, nc = xl.mlstm_block(p["mlstm"], h, cfg, mode="recurrent",
                                       state=cache)
            else:
                m = self._mlstm_train_mode(h.shape[1])
                a, st = xl.mlstm_block(p["mlstm"], h, cfg, mode=m)
                nc = st if mode == "prefill" else cache
        elif mixer == "slstm":
            a, st = xl.slstm_block(p["slstm"], h, cfg,
                                   state=cache if mode in ("decode", "verify")
                                   else None)
            nc = st if mode in ("prefill", "decode", "verify") else cache
        else:
            raise ValueError(mixer)
        x = x + a
        if ffn != "none":
            h2 = layers.rmsnorm(x, p["norm2"].astype(x.dtype), cfg.norm_eps)
            if ffn == "mlp":
                f = layers.mlp({k: v.astype(x.dtype)
                                for k, v in p["mlp"].items()}, h2)
            else:
                f, aux = moe_mod.moe_apply(p["moe"], h2, cfg, self.mesh,
                                           self.moe_strategy)
            x = x + f
        if self.mesh is not None:
            x = constrain(x, self.mesh, ("batch", "act_seq", "act_embed"),
                          self.rules)
        return x, nc, aux

    def _run_blocks(self, params, x, positions, mode, pos, caches,
                    remat: bool = False, max_len: int | None = None,
                    wmask=None, tables=None, offsets=None, tree=None,
                    shard=None):
        """Scan over repeats; python-unrolled period inside the body."""
        if tables is not None:
            return self._run_paged_blocks(params, x, mode, pos, caches,
                                          wmask, tables, offsets, tree,
                                          shard)
        pattern = self.pattern

        def body(carry, xs):
            x, aux = carry
            params_r, cache_r = xs
            new_caches = {}
            for i, typ in enumerate(pattern):
                key = f"b{i}"
                c = None if cache_r is None else cache_r[key]
                x, nc, a = self._apply_block(typ, params_r[key], x,
                                             positions, mode, pos, c,
                                             max_len=max_len, wmask=wmask,
                                             tables=tables, offsets=offsets,
                                             tree=tree, shard=shard)
                new_caches[key] = nc
                aux = aux + a
            if mode == "train":
                new_caches = 0.0  # nothing to collect
            return (x, aux), new_caches

        if remat:
            body = jax.checkpoint(body)
        unroll = self.repeats if self.scan_unroll else 1
        # When there is no input cache (train/prefill) we scan over params
        # only; prefill *produces* caches as the scan outputs.
        if caches is None:
            (x, aux), ys = jax.lax.scan(
                lambda c, p: body(c, (p, None)),
                (x, jnp.zeros((), jnp.float32)), params["blocks"],
                unroll=unroll)
        else:
            (x, aux), ys = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)),
                (params["blocks"], caches), unroll=unroll)
        return x, aux, ys

    def _run_paged_blocks(self, params, x, mode, pos, banks, wmask, tables,
                          offsets, tree, shard):
        """``_run_blocks`` over the stacked page banks (leaves (R, NP,
        ...)).  The banks ride in the scan carry with a layer counter and
        each block reads and writes its layer in place
        (``layers.BankLayer``); the scan's xs are the params alone.  As xs
        and ys (the row caches' path) every layer would slice its pool
        out of the bank and stack it into a fresh output bank, copied
        back into the donated buffer at the end; see
        ``decode_step_paged``."""
        pattern = self.pattern

        def body(carry, params_r):
            x, aux, r, banks = carry
            banks = dict(banks)
            for i, typ in enumerate(pattern):
                key = f"b{i}"
                x, view, a = self._apply_block(
                    typ, params_r[key], x, None, mode, pos,
                    layers.BankLayer(banks[key], r), wmask=wmask,
                    tables=tables, offsets=offsets, tree=tree, shard=shard)
                banks[key] = view.bank
                aux = aux + a
            return (x, aux, r + 1, banks), None

        unroll = self.repeats if self.scan_unroll else 1
        (x, aux, _, banks), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
                   banks),
            params["blocks"], unroll=unroll)
        return x, aux, banks

    # ---------------------------------------------------------------- modes
    def forward(self, params, tokens, patch_embeds=None, remat: bool = False):
        """Training forward: logits (B, S_total, V) f32, aux loss scalar."""
        cfg = self.cfg
        x = self._embed_in(params, tokens, patch_embeds)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, aux, _ = self._run_blocks(params, x, positions, "train", None,
                                     None, remat)
        x = layers.rmsnorm(x, params["final_norm"].astype(x.dtype),
                           cfg.norm_eps)
        return self._head(params, x), aux

    def hidden(self, params, tokens, patch_embeds=None, remat: bool = False):
        """Final hidden states (pre-head); used by the chunked-loss path."""
        cfg = self.cfg
        x = self._embed_in(params, tokens, patch_embeds)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, aux, _ = self._run_blocks(params, x, positions, "train", None,
                                     None, remat)
        x = layers.rmsnorm(x, params["final_norm"].astype(x.dtype),
                           cfg.norm_eps)
        return x, aux

    def prefill(self, params, tokens, max_len: int, patch_embeds=None,
                shard=None):
        """Populate the decode cache.  Returns (last-pos logits, caches).
        ``shard`` (a ``layers.BankShard``): the caller's program also holds
        a page bank split over a mesh (paged admission); the attention
        kernel then runs replicated over that mesh."""
        cfg = self.cfg
        x = self._embed_in(params, tokens, patch_embeds)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, aux, caches = self._run_blocks(params, x, positions, "prefill",
                                          None, None, max_len=max_len,
                                          shard=shard)
        x = layers.rmsnorm(x, params["final_norm"].astype(x.dtype),
                           cfg.norm_eps)
        logits = self._head(params, x[:, -1:])
        return logits, caches

    def decode_step(self, params, caches, tokens, pos):
        """One decode step.  tokens: (B, 1) int32; pos: scalar int32
        (whole batch at one position) or (B,) int32 (continuous batching:
        per-request positions).  Returns (logits (B,1,V), new caches)."""
        cfg = self.cfg
        x = self._embed_in(params, tokens)
        x, aux, caches = self._run_blocks(params, x, None, "decode", pos,
                                          caches)
        x = layers.rmsnorm(x, params["final_norm"].astype(x.dtype),
                           cfg.norm_eps)
        return self._head(params, x), caches

    def verify_step(self, params, caches, tokens, pos):
        """Multi-token verify: score K tokens per row in ONE pass
        (speculative decode's target pass).

        tokens: (B, K) int32 — the block tokens, at cache positions
        ``pos .. pos+K-1`` per row (pos: scalar or (B,) int32).  Returns
        (logits (B, K, V), new caches) where ``logits[:, i]`` is the
        distribution for position pos+i+1 — identical to K iterations of
        ``decode_step`` (tested), including ring-buffer caches: attention
        reads the pre-block cache plus an intra-block causal term, so
        token i sees exactly the window the i-th sequential step would
        have seen.  Recurrent mixers run their carried-state scan over the
        K tokens, which is the sequential computation itself.
        """
        cfg = self.cfg
        x = self._embed_in(params, tokens)
        x, aux, caches = self._run_blocks(params, x, None, "verify", pos,
                                          caches)
        x = layers.rmsnorm(x, params["final_norm"].astype(x.dtype),
                           cfg.norm_eps)
        return self._head(params, x), caches

    def prefill_chunk(self, params, caches, tokens, pos, slots,
                      wmask=None, need_logits: bool = True):
        """Chunked prefill: score a (b, C) prompt *chunk* at per-row cache
        offsets ``pos .. pos+C-1`` and write its k/v into batch rows
        ``slots`` of the pooled ``caches`` (leaves (R, B, ...)).

        This is the verify machinery pointed at admission: one fixed
        (b, C) program processes every chunk of every prompt (prompts pad
        to the chunk width; ``wmask`` keeps pad writes out of the cache),
        so admission stops compiling one prefill program per prompt
        length, and a long prompt streams into its slot across many calls
        interleaved with decode steps — the paper's hide-the-load
        principle applied to the prompt itself.  Rows at ``pos == 0``
        have their gathered cache/state zeroed first, so chunk 0 starts
        from the same blank state a fresh ``prefill`` does (a recycled
        slot's stale row must not leak into the new request).

        Only the named rows change — the same disturb-free invariant
        ``insert_cache_rows`` keeps.  Returns (logits (b, C, V) f32 or
        ``None`` when ``need_logits`` is False, new pooled caches).
        """
        cfg = self.cfg
        slots = jnp.asarray(slots, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        sub = jax.tree.map(lambda c: c[:, slots], caches)

        def _fresh(c):
            m = (pos == 0).reshape((1, -1) + (1,) * (c.ndim - 2))
            return jnp.where(m, jnp.zeros((), c.dtype), c)

        sub = jax.tree.map(_fresh, sub)
        x = self._embed_in(params, tokens)
        x, aux, sub = self._run_blocks(params, x, None, "verify", pos, sub,
                                       wmask=wmask)
        logits = None
        if need_logits:
            x = layers.rmsnorm(x, params["final_norm"].astype(x.dtype),
                               cfg.norm_eps)
            logits = self._head(params, x)
        caches = jax.tree.map(lambda c, r: c.at[:, slots].set(r), caches,
                              sub)
        return logits, caches

    # ------------------------------------------------------- paged slot pool
    def _require_paged_support(self):
        if any(mix != "attn" for mix, _ in self.pattern):
            raise ValueError(
                "the paged page pool needs an all-attention model "
                "(recurrent mixers keep per-row state, not pages)")
        if self.cfg.sliding_window:
            raise ValueError(
                "the paged page pool needs full (non-ring) attention: "
                "ring slots alias positions a page table cannot express")

    def init_page_pool(self, num_pages: int, page: int,
                       abstract: bool = False, quantized: bool = False):
        """Shared-page decode cache: one ``layers.PagedKV`` bank per
        block, leaves (R, NP, Hkv, page, hd).  Page 0 is the PARK page
        (see ``layers._page_write``); the page table is shared across
        layers — page id p is position range [j*page, (j+1)*page) of its
        owning row in EVERY layer's bank.  ``quantized`` stores the bank
        as int8 codes plus (R, NP, Hkv, 1, page) f32 scale leaves — roughly
        half the bytes per page, so ~2x pages per HBM budget."""
        self._require_paged_support()
        out = {}
        for i in range(len(self.pattern)):
            one = layers.init_page_pool(self.cfg, num_pages, page,
                                        self.cache_dtype, abstract,
                                        quantized=quantized)
            out[f"b{i}"] = _stack_tree(one, self.repeats, abstract)
        return out

    def page_pool_logical(self):
        return {f"b{i}": jax.tree.map(
            lambda l: ("layers",) + tuple(l), layers.PAGED_LOGICAL,
            is_leaf=lambda q: isinstance(q, tuple) and
            all(isinstance(e, str) or e is None for e in q))
            for i in range(len(self.pattern))}

    def page_pool_shardings(self, caches, mesh, axis: str):
        """``NamedSharding`` per page-pool leaf: the page (NP) axis of
        every bank leaf splits over mesh axis ``axis`` (so shard s
        physically holds the local slice its kernel instance reads under
        local-read sharding), everything else replicated.  The returned
        tree matches ``caches`` leaf-for-leaf — feed it to
        ``jax.device_put``/``jax.tree.map``."""
        rules = self.rules if self.rules is not None else DEFAULT_RULES
        rules = rules.with_(kv_pages=axis)
        kv = ("layers", "kv_pages", "kv_heads", None, "head_dim")
        sc = ("layers", "kv_pages", "kv_heads", None, None)

        def one(bank):
            return layers.PagedKV(
                k=spec_for(mesh, kv, bank.k.shape, rules),
                v=spec_for(mesh, kv, bank.v.shape, rules),
                ks=(None if bank.ks is None
                    else spec_for(mesh, sc, bank.ks.shape, rules)),
                vs=(None if bank.vs is None
                    else spec_for(mesh, sc, bank.vs.shape, rules)))

        return {key: one(bank) for key, bank in caches.items()}

    def insert_cache_pages(self, caches, rows, tables):
        """Admission into the page pool: scatter prefilled cache rows
        (a pytree with ``KVCache`` leaves (R, b, Hkv, S, hd)) into the
        pooled ``caches`` through the admitted rows' (b, P) page tables.
        Only the named pages (plus the park page) change — the paged
        analogue of ``insert_cache_rows``."""
        tables = jnp.asarray(tables, jnp.int32)
        return {key: layers.insert_pages(c, rows[key], tables)
                for key, c in caches.items()}

    def copy_cache_pages(self, caches, src, dst):
        """Copy-on-write support: duplicate pool pages ``src[i]`` into
        ``dst[i]`` across every block and repeat of the paged ``caches``
        (all leaves — int8 codes and their scales move together).  The
        page table is layer-shared, so one (src, dst) pair names the same
        position range in every bank; everything outside ``dst`` is
        untouched."""
        return {key: layers.copy_pages(c, src, dst)
                for key, c in caches.items()}

    def decode_step_pages(self, params, caches, tokens, pos, tables,
                          live=None, shard=None):
        """One decode step against the shared page pool.  tokens: (B, 1)
        int32; pos: (B,) int32; tables: (B, P) int32 page tables;
        ``live`` ((B,) bool, optional) routes non-live rows' cache writes
        to the park page — a retired slot's per-step garbage write must
        not land in pages already recycled to a neighbor.  ``shard``
        (a ``layers.BankShard``) marks a bank split over a mesh; see
        ``_apply_block``.  Returns (logits (B, 1, V), new
        caches)."""
        cfg = self.cfg
        tables = jnp.asarray(tables, jnp.int32)
        x = self._embed_in(params, tokens)
        x, aux, caches = self._run_blocks(params, x, None, "decode", pos,
                                          caches, wmask=live,
                                          tables=tables, shard=shard)
        x = layers.rmsnorm(x, params["final_norm"].astype(x.dtype),
                           cfg.norm_eps)
        return self._head(params, x), caches

    def verify_step_pages(self, params, caches, tokens, pos, tables,
                          wmask=None, need_logits: bool = True,
                          offsets=None, tree=None, shard=None):
        """Multi-token verify against the shared page pool — one (b, K)
        block scored at per-row offsets ``pos .. pos+K-1`` through the
        rows' page tables, k/v written into the rows' own pages.  Serves
        both chunked prefill (the verify machinery pointed at admission;
        ``wmask`` gates pad writes, ``need_logits=False`` for streaming
        chunks) and a paged ``SpecEngine`` verify column.  Unlike the
        row-granular ``prefill_chunk`` there is no gather/scatter of
        whole cache rows and no fresh-row zeroing: writes touch exactly
        the block's positions (O(K), not O(max_len)), and a recycled
        page is always rewritten before any of its positions become
        readable (reads mask ``cols < pos``).

        Tree verification (``SpecEngine(tree_width > 1)``): ``offsets``
        ((K,) int32 per-node depths) and ``tree`` ((B, K) int32 ancestor
        bitmasks) verify several candidate branches in one pass — the
        caller parks all but one writer per depth via ``wmask``."""
        cfg = self.cfg
        tables = jnp.asarray(tables, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        x = self._embed_in(params, tokens)
        x, aux, caches = self._run_blocks(params, x, None, "verify", pos,
                                          caches, wmask=wmask,
                                          tables=tables, offsets=offsets,
                                          tree=tree, shard=shard)
        logits = None
        if need_logits:
            x = layers.rmsnorm(x, params["final_norm"].astype(x.dtype),
                               cfg.norm_eps)
            logits = self._head(params, x)
        return logits, caches

    # chunked admission is the verify machinery pointed at the page pool
    prefill_chunk_pages = verify_step_pages

    # ------------------------------------------------------ multi-step decode
    def _decode_multi(self, params, caches, tokens, pos, steps, sample_fn,
                      stop_fn, carry, live=None, pos_cap=None, tables=None,
                      shard=None):
        """Up to ``steps`` decode steps in ONE device loop (the host tick
        amortizes over every iteration; see ``StepEngine(multi_step=T)``).

        Each iteration runs the SAME ``decode_step`` /
        ``decode_step_pages`` body a single-step engine would, then:

          * ``nxt, carry = sample_fn(last_logits, pos, carry)`` — the
            engine supplies its exact sampling rule (keys advance inside
            ``carry``), which is what keeps the fused stream bitwise
            equal to iterated single steps;
          * ``stop = stop_fn(nxt, advanced_pos, i)`` — a () bool that is
            True the moment ANY slot changes occupancy (EOS, token
            budget, page exhaustion).  The loop commits this step and
            exits, handing control back to the host while every slot's
            membership is still exactly what the host last saw.

        ``pos_cap`` clamps the advanced positions (the single-step
        engine's run-off guard); ``stop_fn`` sees them UNCLAMPED so a
        budget bitmap can fire on the true value.  Returns
        ``(out (B, steps) int32, n_steps () int32, caches, tok, pos,
        carry)`` — only ``out[:, :n_steps]`` is meaningful.
        """
        B = tokens.shape[0]

        def cond(st):
            return (st[0] < steps) & ~st[1]

        def body(st):
            i, stop, caches, tok, pos, carry, out = st
            if tables is None:
                logits, caches = self.decode_step(params, caches, tok, pos)
            else:
                logits, caches = self.decode_step_pages(
                    params, caches, tok, pos, tables, live=live,
                    shard=shard)
            nxt, carry = sample_fn(logits[:, -1], pos, carry)
            posr = pos + 1 if live is None else jnp.where(live, pos + 1, pos)
            stop = stop_fn(nxt, posr, i)
            if pos_cap is not None:
                posr = jnp.minimum(posr, pos_cap)
            out = jax.lax.dynamic_update_index_in_dim(out, nxt, i, 1)
            return (i + 1, stop, caches, nxt[:, None], posr, carry, out)

        init = (jnp.zeros((), jnp.int32), jnp.zeros((), bool), caches,
                jnp.asarray(tokens, jnp.int32), jnp.asarray(pos, jnp.int32),
                carry, jnp.zeros((B, steps), jnp.int32))
        n, _, caches, tok, pos, carry, out = jax.lax.while_loop(
            cond, body, init)
        return out, n, caches, tok, pos, carry

    def decode_multi_step(self, params, caches, tokens, pos, steps,
                          sample_fn, stop_fn, carry, live=None,
                          pos_cap=None):
        """Row-cache multi-step decode; see ``_decode_multi``.  ``steps``
        must be static (it sizes the output buffer)."""
        return self._decode_multi(params, caches, tokens, pos, steps,
                                  sample_fn, stop_fn, carry, live=live,
                                  pos_cap=pos_cap)

    def decode_multi_step_pages(self, params, caches, tokens, pos, tables,
                                steps, sample_fn, stop_fn, carry,
                                live=None, pos_cap=None, shard=None):
        """Paged multi-step decode; see ``_decode_multi``.  ``tables``
        is loop-invariant by construction: the loop exits before any
        occupancy change, so no page moves while it runs."""
        return self._decode_multi(params, caches, tokens, pos, steps,
                                  sample_fn, stop_fn, carry, live=live,
                                  pos_cap=pos_cap,
                                  tables=jnp.asarray(tables, jnp.int32),
                                  shard=shard)

    def decode_step_paged(self, params, bigs, acts, tokens, pos):
        """One decode step against a paged cache (see layers: BigKV/ActKV).

        ``bigs`` is read-only (per-block stacked BigKV; None for non-attn
        mixers); ``acts`` carries the active page + recurrent states and is
        the only cache state the step writes — donate it.
        """
        cfg = self.cfg
        x = self._embed_in(params, tokens)
        pattern = self.pattern

        # `bigs` is closed over and dynamic-indexed per layer rather than
        # threaded as scan xs: xs get copied into while-loop state by
        # buffer assignment (~2x the read-only cache in temps); an
        # invariant capture is read in place.
        def body(carry, xs):
            x, aux, r = carry
            params_r, act_r = xs
            big_r = jax.tree.map(
                lambda b: jax.lax.dynamic_index_in_dim(b, r, 0,
                                                       keepdims=False),
                bigs)
            new_acts = {}
            for i, typ in enumerate(pattern):
                key = f"b{i}"
                big = None if big_r is None else big_r.get(key)
                x, nc, a = self._apply_block(typ, params_r[key], x, None,
                                             "decode", pos, act_r[key], big)
                new_acts[key] = nc
                aux = aux + a
            return (x, aux, r + 1), new_acts

        unroll = self.repeats if self.scan_unroll else 1
        (x, aux, _), acts_new = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
            (params["blocks"], acts), unroll=unroll)
        x = layers.rmsnorm(x, params["final_norm"].astype(x.dtype),
                           cfg.norm_eps)
        return self._head(params, x), acts_new

    # ---------------------------------------------------------------- cache
    def insert_cache_rows(self, caches, rows, slots):
        """Per-slot cache reset/admission for the continuous-batching step
        engine: write ``rows`` (a decode-cache pytree for b requests,
        leaves (R, b, ...)) into batch rows ``slots`` ((b,) int32) of
        ``caches`` (leaves (R, B, ...)).  Only the named rows change — a
        freed slot is recycled by overwriting it with a fresh prefill, so
        admission never disturbs in-flight requests."""
        slots = jnp.asarray(slots, jnp.int32)
        return jax.tree.map(
            lambda c, r: c.at[:, slots].set(r.astype(c.dtype)),
            caches, rows)

    def init_paged_cache(self, batch: int, max_len: int,
                         page: int = layers.DEFAULT_PAGE,
                         abstract: bool = False):
        """(bigs, acts) pytrees for decode_step_paged.  Non-attention
        mixers keep their (small, per-step) state on the act side."""
        cfg = self.cfg
        bigs, acts = {}, {}
        for i, (mixer, _) in enumerate(self.pattern):
            key = f"b{i}"
            if mixer == "attn":
                big, act = layers.init_paged_cache(
                    cfg, batch, max_len, page, self.cache_dtype, abstract)
                bigs[key] = _stack_tree(big, self.repeats, abstract)
                acts[key] = _stack_tree(act, self.repeats, abstract)
                continue
            bigs[key] = None
            if mixer == "mamba":
                one = (ssm_mod.ssm_state_abstract(cfg, batch,
                                                  self.cache_dtype)
                       if abstract else
                       ssm_mod.init_ssm_state(cfg, batch, self.cache_dtype))
            elif mixer == "mlstm":
                one = (xl.mlstm_state_abstract(cfg, batch, self.cache_dtype)
                       if abstract else
                       xl.init_mlstm_state(cfg, batch, self.cache_dtype))
            else:
                one = (xl.slstm_state_abstract(cfg, batch) if abstract
                       else xl.init_slstm_state(cfg, batch))
            acts[key] = _stack_tree(one, self.repeats, abstract)
        return bigs, acts

    def paged_cache_logical(self):
        bigs, acts = {}, {}
        base = {"mamba": ssm_mod.SSM_LOGICAL, "mlstm": xl.MLSTM_LOGICAL,
                "slstm": xl.SLSTM_LOGICAL}

        def add_layers(tree):
            return jax.tree.map(
                lambda l: ("layers",) + tuple(l), tree,
                is_leaf=lambda q: isinstance(q, tuple) and
                all(isinstance(e, str) or e is None for e in q))

        for i, (mixer, _) in enumerate(self.pattern):
            key = f"b{i}"
            if mixer == "attn":
                bigs[key] = add_layers(layers.BIG_LOGICAL)
                acts[key] = add_layers(layers.ACT_LOGICAL)
            else:
                bigs[key] = None
                acts[key] = add_layers(base[mixer])
        return bigs, acts

    def init_cache(self, batch: int, max_len: int, abstract: bool = False):
        """Decode-cache pytree matching the scanned-block structure."""
        cfg = self.cfg
        out = {}
        for i, (mixer, _) in enumerate(self.pattern):
            if mixer == "attn":
                one = (layers.kv_cache_abstract(cfg, batch, max_len,
                                                self.cache_dtype) if abstract
                       else layers.init_kv_cache(cfg, batch, max_len,
                                                 self.cache_dtype))
            elif mixer == "mamba":
                one = (ssm_mod.ssm_state_abstract(cfg, batch, self.cache_dtype)
                       if abstract else
                       ssm_mod.init_ssm_state(cfg, batch, self.cache_dtype))
            elif mixer == "mlstm":
                one = (xl.mlstm_state_abstract(cfg, batch, self.cache_dtype)
                       if abstract else
                       xl.init_mlstm_state(cfg, batch, self.cache_dtype))
            else:
                one = (xl.slstm_state_abstract(cfg, batch) if abstract
                       else xl.init_slstm_state(cfg, batch))
            out[f"b{i}"] = _stack_tree(one, self.repeats, abstract)
        return out

    def cache_logical(self):
        out = {}
        for i, (mixer, _) in enumerate(self.pattern):
            base = {"attn": layers.KV_LOGICAL, "mamba": ssm_mod.SSM_LOGICAL,
                    "mlstm": xl.MLSTM_LOGICAL, "slstm": xl.SLSTM_LOGICAL}[mixer]
            out[f"b{i}"] = jax.tree.map(
                lambda l: ("layers",) + tuple(l), base,
                is_leaf=lambda q: isinstance(q, tuple) and
                all(isinstance(e, str) or e is None for e in q))
        return out


def _stack_tree(tree, n: int, abstract: bool):
    if abstract:
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), tree)


def build_model(cfg: ArchConfig, mesh: Mesh | None = None,
                rules: ShardingRules = DEFAULT_RULES, **kw) -> LM:
    return LM(cfg, mesh=mesh, rules=rules, **kw)
