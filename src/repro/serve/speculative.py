"""Speculative cascade decode on the paged slot pool.

The paper's Super-Sub cascade (Fig 6a, S1a) runs the small network while
the big network's context streams into the shadow slot — load hidden
behind execution.  ``SpecEngine`` is the LLM-serving analogue at token
granularity: a cheap *draft* context proposes tokens, the *target*
context scores them all in ONE multi-token verify pass
(``LM.verify_step_pages`` over the ``verify_attention`` kernel), and
exact speculative sampling accepts a prefix + draws one continuation —
so the committed stream is distributed exactly as target-only sampling,
and greedy output is token-identical to ``StepEngine.generate``
(tested).

The engine keeps TWO cache columns over paged pools (one per model),
not per-slot rows: each admitted request owns only the pages its own
lifetime needs in each column, addressed through per-slot page tables
(``SpecState.d_table``/``t_table``) that the paged attention kernels
scalar-prefetch.  Admission gates on free slots AND free pages in both
pools (``can_admit``), retirement releases pages instead of a whole
row, and the target column can share one ``SharedBank`` — allocator,
prefix index, and device pages — with the plain paged engines serving
the same context, so a prompt one engine indexed is a prefix hit for
the speculative target too.

Proposal shapes:

  * ``tree_width=1`` (default) — the classic flat strip: K draft tokens
    verified with the intra-block causal mask (``speculative_accept``).
  * ``tree_width=W>1`` — a *sausage tree*: every depth carries W
    sibling candidates (the chain = sibling 0), all ``1 + K*W`` nodes
    verified in ONE pass with per-node depth offsets and an ancestor
    bitmask folded into the kernel's intra-block mask
    (``tree_speculative_accept``).  When the chain token dies at depth
    i but a sibling survives, the round still commits i+1 tokens where
    the flat strip would stop at i — wider trees buy acceptance length
    for draft compute, not extra target passes.

``k`` is *adaptive*: ``set_k`` moves the current depth within
``[1, k_max]`` (one compiled roll/verify pair per depth, cached), and
the continuous scheduler drives it from a measured-acceptance EWMA —
an aligned draft climbs to ``k_max``, a mismatched one falls back to
short cheap blocks.

Rollback stays positional: a rejected proposal's stale page writes are
masked by the row's committed position and overwritten later.  That
works for full attention caches only, so both models must be
all-attention with no sliding window — the same paged-support gate the
paged ``StepEngine`` applies.
"""
from __future__ import annotations

import math
from collections import deque
from dataclasses import dataclass, field
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import LM
from repro.serve.engine import StepEngine
from repro.serve.pool import (Generation, PagePool, SharedBank, SlotPool,
                              PrefixIndex)
from repro.serve.telemetry import Telemetry, safe_ratio

# committed tokens per row per round lands in [1, K+1]; buckets cover
# the practical K range (the histogram is cumulative-bucket style)
SPEC_ACCEPT_BUCKETS = (1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0)


def speculative_accept(key, proposals, draft_logits, target_logits,
                       temperature: float):
    """Exact speculative sampling: accept/reject K proposals, draw the
    continuation.

    proposals: (B, K) int32 — draft tokens d_1..d_K; draft_logits:
    (B, K, V) — the distributions each d_i was sampled from;
    target_logits: (B, K+1, V) — target distributions for block-relative
    positions 1..K+1.  Returns (tokens (B, K+1), n_accepted (B,)):
    ``tokens[:, :n]`` are the accepted proposals, entry n is the residual
    draw (n < K) or the bonus token from the target's last distribution
    (n == K); entries past n are undefined.  The committed prefix is
    distributed exactly as target-only sampling for ANY draft
    distribution (tested statistically).

    Greedy (temperature == 0): accept while d_i equals the target argmax;
    the continuation is the target argmax — the committed stream is
    token-identical to plain greedy target decode.
    """
    B, K = proposals.shape
    cols = jnp.arange(K + 1, dtype=jnp.int32)[None, :]
    if temperature <= 0.0:
        tgt = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)
        acc = proposals == tgt[:, :K]
        n = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)
        nxt = jnp.take_along_axis(tgt, n[:, None], axis=1)[:, 0]
    else:
        p_all = jax.nn.softmax(target_logits.astype(jnp.float32)
                               / temperature, axis=-1)       # (B, K+1, V)
        q_all = jax.nn.softmax(draft_logits.astype(jnp.float32)
                               / temperature, axis=-1)       # (B, K, V)
        pd = jnp.take_along_axis(p_all[:, :K], proposals[..., None],
                                 axis=-1)[..., 0]            # (B, K)
        qd = jnp.take_along_axis(q_all, proposals[..., None],
                                 axis=-1)[..., 0]
        u = jax.random.uniform(key, (B, K), jnp.float32)
        acc = u * qd <= pd            # accept w.p. min(1, p/q); p==q -> 1
        n = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)
        # residual at the rejection point: r ∝ max(p - q, 0); all-accepted
        # rows pad q with zeros so the "residual" is the bonus draw from p
        q_pad = jnp.concatenate(
            [q_all, jnp.zeros_like(q_all[:, :1])], axis=1)
        pn = jnp.take_along_axis(p_all, n[:, None, None], axis=1)[:, 0]
        qn = jnp.take_along_axis(q_pad, n[:, None, None], axis=1)[:, 0]
        r = jnp.clip(pn - qn, 0.0, None)
        rs = jnp.sum(r, axis=-1, keepdims=True)
        r = jnp.where(rs > 0, r / jnp.maximum(rs, 1e-30), pn)
        g = jax.random.gumbel(jax.random.fold_in(key, 1),
                              r.shape, jnp.float32)
        nxt = jnp.argmax(jnp.log(r + 1e-30) + g, axis=-1).astype(jnp.int32)
    props_pad = jnp.concatenate([proposals, proposals[:, :1]], axis=1)
    tokens = jnp.where(cols < n[:, None], props_pad,
                       jnp.where(cols == n[:, None], nxt[:, None], 0))
    return tokens.astype(jnp.int32), n.astype(jnp.int32)


def tree_speculative_accept(key, cand, draft_logits, target_logits,
                            temperature: float):
    """Recursive-rejection acceptance over a sausage token tree.

    Node layout (depths i in 1..K, siblings w in 0..W-1): node 0 is the
    last committed token; node ``1 + (i-1)*W + w`` is candidate w at
    depth i; sibling 0 is the *chain* (the path the draft rolled its own
    cache along).  ``cand``: (B, K, W) int32 candidates — the W draws at
    each depth were sampled i.i.d. from the SAME chain draft
    distribution ``draft_logits[:, i-1]`` ((B, K, V)).
    ``target_logits``: (B, 1+K*W, V), one distribution per tree node
    from the tree-verify pass.

    Per depth the W siblings run SpecInfer-style recursive rejection
    against the parent-node target distribution: candidate w is accepted
    with probability ``min(1, r/q)`` where r starts at p and renormalizes
    to ``max(r - q, 0)`` after each rejection; the first accepted sibling
    wins.  Sibling 0 accepted -> descend the chain.  A later sibling
    accepted -> commit the chain prefix, the sibling, AND a bonus token
    from the sibling's own verified distribution (the round ends there —
    the tree has no grandchildren off-chain).  All W rejected -> commit
    the residual draw.  Marginally the committed stream is exactly
    target-distributed (tested statistically), and at temperature 0 it
    is token-identical to greedy target decode: the committed token at
    depth i is ALWAYS the parent node's target argmax.

    Returns ``(tokens (B, K+1), n (B,), alt_depth (B,), alt_tok (B,))``:
    ``tokens[:, :n+1]`` is the committed block (same contract as
    ``speculative_accept``); rows with ``alt_depth > 0`` committed a
    non-chain sibling ``alt_tok`` at that depth, whose k/v the caches
    hold for the *chain* candidate — the engine repairs that one
    position with a masked decode step.
    """
    B, K, W = cand.shape
    chain = lambda i: 1 + (i - 1) * W           # chain node at depth i

    alive = jnp.ones((B,), bool)
    n = jnp.zeros((B,), jnp.int32)
    alt_depth = jnp.zeros((B,), jnp.int32)
    alt_tok = jnp.zeros((B,), jnp.int32)
    toks = jnp.zeros((B, K + 1), jnp.int32)

    if temperature <= 0.0:
        tgt = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)
        for i in range(1, K + 1):
            parent = 0 if i == 1 else chain(i - 1)
            t_i = tgt[:, parent]
            # chain hit, alt hit (first matching sibling), or residual —
            # the committed token at depth i is t_i in every case
            toks = toks.at[:, i - 1].set(
                jnp.where(alive, t_i, toks[:, i - 1]))
            chain_hit = cand[:, i - 1, 0] == t_i
            alt_hit = jnp.zeros((B,), bool)
            alt_node = jnp.zeros((B,), jnp.int32)
            for w in range(1, W):
                hw = (~alt_hit) & (cand[:, i - 1, w] == t_i)
                alt_node = jnp.where(hw, chain(i) + w, alt_node)
                alt_hit = alt_hit | hw
            alt_hit = alt_hit & ~chain_hit
            n = jnp.where(alive & (chain_hit | alt_hit), i, n)
            bonus = jnp.take_along_axis(tgt, alt_node[:, None],
                                        axis=1)[:, 0]
            sel = alive & alt_hit
            toks = toks.at[:, i].set(jnp.where(sel, bonus, toks[:, i]))
            alt_depth = jnp.where(sel, i, alt_depth)
            alt_tok = jnp.where(sel, t_i, alt_tok)
            alive = alive & chain_hit
        toks = toks.at[:, K].set(
            jnp.where(alive, tgt[:, chain(K)], toks[:, K]))
        return toks, n, alt_depth, alt_tok

    p_all = jax.nn.softmax(target_logits.astype(jnp.float32)
                           / temperature, axis=-1)       # (B, Kt, V)
    q_all = jax.nn.softmax(draft_logits.astype(jnp.float32)
                           / temperature, axis=-1)       # (B, K, V)
    V = p_all.shape[-1]
    u = jax.random.uniform(key, (B, K, W), jnp.float32)
    # one residual + one bonus gumbel field: each row realizes each at
    # most once (the depth it dies rejecting / the node it bonuses from),
    # so sharing the field across depths keeps the draws independent
    gres = jax.random.gumbel(jax.random.fold_in(key, 1), (B, V),
                             jnp.float32)
    gbon = jax.random.gumbel(jax.random.fold_in(key, 2), (B, V),
                             jnp.float32)
    for i in range(1, K + 1):
        parent = 0 if i == 1 else chain(i - 1)
        p = p_all[:, parent]                             # (B, V)
        q = q_all[:, i - 1]
        r = p
        acc = jnp.zeros((B,), bool)
        acc_alt = jnp.zeros((B,), bool)
        acc_tok = jnp.zeros((B,), jnp.int32)
        acc_node = jnp.zeros((B,), jnp.int32)
        for w in range(W):
            tw = cand[:, i - 1, w]
            qt = jnp.take_along_axis(q, tw[:, None], axis=1)[:, 0]
            rt = jnp.take_along_axis(r, tw[:, None], axis=1)[:, 0]
            aw = (~acc) & (u[:, i - 1, w] * qt <= rt)
            acc_tok = jnp.where(aw, tw, acc_tok)
            acc_node = jnp.where(aw, chain(i) + w, acc_node)
            acc_alt = acc_alt | (aw & (w > 0))
            acc = acc | aw
            if w < W - 1:
                # rejected w: renormalized leftover target mass (fall
                # back to p when nothing is left, like the flat rule)
                rm = jnp.clip(r - q, 0.0, None)
                rs = jnp.sum(rm, axis=-1, keepdims=True)
                rn = jnp.where(rs > 0, rm / jnp.maximum(rs, 1e-30), p)
                r = jnp.where(acc[:, None], r, rn)
        # all W rejected: residual draw from the final leftover mass
        rm = jnp.clip(r - q, 0.0, None)
        rs = jnp.sum(rm, axis=-1, keepdims=True)
        r = jnp.where(rs > 0, rm / jnp.maximum(rs, 1e-30), p)
        residual = jnp.argmax(jnp.log(r + 1e-30) + gres,
                              axis=-1).astype(jnp.int32)
        tok_i = jnp.where(acc, acc_tok, residual)
        toks = toks.at[:, i - 1].set(
            jnp.where(alive, tok_i, toks[:, i - 1]))
        n = jnp.where(alive & acc, i, n)
        bl = jnp.take_along_axis(p_all, acc_node[:, None, None],
                                 axis=1)[:, 0]           # (B, V)
        bonus = jnp.argmax(jnp.log(bl + 1e-30) + gbon,
                           axis=-1).astype(jnp.int32)
        sel = alive & acc_alt
        toks = toks.at[:, i].set(jnp.where(sel, bonus, toks[:, i]))
        alt_depth = jnp.where(sel, i, alt_depth)
        alt_tok = jnp.where(sel, acc_tok, alt_tok)
        alive = alive & (acc & ~acc_alt)
    blK = p_all[:, chain(K)]
    bonusK = jnp.argmax(jnp.log(blK + 1e-30) + gbon,
                        axis=-1).astype(jnp.int32)
    toks = toks.at[:, K].set(jnp.where(alive, bonusK, toks[:, K]))
    return toks, n, alt_depth, alt_tok


class SpecKey(NamedTuple):
    """Frozen cache key for ONE speculative-engine configuration — the
    SpecEngine counterpart of ``EngineKey``: every knob that changes a
    compiled program or a cache layout is a named field, so two
    configurations can never silently alias one pool.  ``k`` is the
    engine's K_MAX — adaptive K moves ``eng.k`` underneath it without
    changing which engine serves the context."""
    name: Optional[str] = None          # target context
    draft: Optional[str] = None         # draft context
    batch_size: int = 1
    k: int = 4                          # constructor k == adaptive ceiling
    tree_width: int = 1
    page_size: Optional[int] = None     # resolved (never None in practice)
    quantize_kv: Optional[str] = None
    prefix_cache: bool = False
    prefill_chunk: Optional[int] = None
    shared_bank: bool = False           # target column on a SharedBank


class SpecState(NamedTuple):
    """Device half of the speculative pool (a pytree; donated each call).

    One slot pool, two PAGED cache columns: at every round boundary both
    columns hold exactly the committed prefix (positions <= pos-1,
    addressed through the per-slot page tables) and ``tok`` is the last
    committed token at position ``pos`` — the same invariant
    ``decode_step_pages`` keeps, so draft and target stay
    interchangeable views of one sequence."""
    d_caches: Any         # draft page-bank pytree, leaves (R, NP, ...)
    t_caches: Any         # target page-bank pytree (bank-shared when set)
    tok: jax.Array        # (B, 1) int32 — last committed token per slot
    pos: jax.Array        # (B,) int32  — its cache position
    key: jax.Array        # PRNG key, folded once per round
    t: jax.Array          # () int32    — round counter
    d_table: jax.Array    # (B, P) int32 — draft-column page tables
    t_table: jax.Array    # (B, P) int32 — target-column page tables


@dataclass
class _SpecPending:
    """One admitted-but-still-prefilling request (chunked admission):
    its slot and pages (both columns) are reserved, its prompt streams
    into both cache columns one chunk per engine tick."""
    tokens: np.ndarray                    # (b, S) full prompt, int32
    gens: list                            # Generation handles (slots set)
    t_tables: np.ndarray                  # (b, P) target page tables
    d_tables: np.ndarray                  # (b, P) draft page tables
    done: int = 0                         # prompt tokens already chunked
    started: bool = False                 # first chunk has executed


class SpecEngine(SlotPool):
    """Speculative continuous-batching engine for one draft/target pair,
    on paged KV columns.

    Host surface is the shared ``SlotPool`` base ``StepEngine`` also
    builds on (slots, free-list, ``admit``, ``step``, ``drain``) so the
    continuous scheduler drives either interchangeably; one ``step()`` is
    a full speculative ROUND — a K+1 draft rollout plus one multi-token
    verify — committing between 1 and K+1 tokens per live row.

    Each column is a paged pool (``PagePool`` + per-slot page table):
    admission takes ``pages_needed`` pages per column (gated by
    ``can_admit`` on slots AND both pools), retirement releases them.
    The target column accepts a ``SharedBank`` so its allocator, prefix
    index, and device pages are the SAME objects a plain paged
    ``StepEngine`` over the same context uses — a prompt either engine
    admitted is a prefix hit for both.  ``prefix_cache=True`` maps a new
    prompt's indexed pages read-only into the target table and prefills
    only the un-cached suffix (one-shot single-row admissions; the draft
    column always prefills cold — its pages are private).

    ``prefill_chunk=C`` streams admission: each engine tick runs one
    (b, C) chunk into BOTH columns before the round, so admission
    latency for live rows is bounded by one chunk regardless of prompt
    length (greedy streams are token-identical across chunk sizes —
    tested).

    ``tree_width=W>1`` widens each draft depth to W sibling candidates
    verified in one tree pass (see ``tree_speculative_accept``); the
    committed distribution is unchanged.  ``k`` is the CURRENT depth,
    adjustable per round via ``set_k`` within [1, k_max] (k_max = the
    constructor ``k``); admission always reserves ``k_max`` slack so a
    depth change never overruns a row's pages.

    ``params`` per call is ``(draft_params, target_params)``, or ``None``
    when ``runner`` is set: the scheduler's runner receives
    ``(which, fn, *args)`` with ``which`` in {"draft", "target"} and runs
    the program against the right context slot (switching + hidden-load
    accounting included) — the engine never captures weights.
    """

    def __init__(self, draft: LM, target: LM, batch_size: int, max_len: int,
                 k: int = 4, temperature: float = 0.0, seed: int = 0,
                 eos_id: Optional[int] = None,
                 tree_width: int = 1,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = False,
                 bank: Optional[SharedBank] = None,
                 quantize_kv: Optional[str] = None,
                 telemetry: Optional[Telemetry] = None):
        for m, role in ((draft, "draft"), (target, "target")):
            if any(mix != "attn" for mix, _ in m.pattern):
                raise ValueError(
                    f"speculative decode needs an all-attention {role} "
                    "(recurrent state cannot rewind a rejected proposal)")
            if m.cfg.sliding_window:
                raise ValueError(
                    f"speculative decode needs a full-cache {role} (ring "
                    "writes wrap onto slots a rollback must preserve)")
            m._require_paged_support()
        if draft.cfg.vocab_size != target.cfg.vocab_size:
            raise ValueError("draft and target must share a vocabulary")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if tree_width < 1:
            raise ValueError(f"tree_width must be >= 1, got {tree_width}")
        if tree_width > 1 and 1 + k * tree_width > 31:
            raise ValueError(
                f"tree of depth {k} x width {tree_width} has "
                f"{1 + k * tree_width} nodes; the ancestor bitmask holds "
                "at most 31 (int32)")
        if quantize_kv not in (None, "int8"):
            raise ValueError(f"quantize_kv must be None or 'int8', got "
                             f"{quantize_kv!r}")
        self.draft_model = draft
        self.target_model = target
        self.batch_size = batch_size
        self.max_len = max_len
        self.k = k                  # CURRENT depth (set_k moves it)
        self.k_max = k              # admission slack + program-cache cap
        self.tree_width = tree_width
        self.temperature = temperature
        self.seed = seed
        self.eos_id = eos_id
        self.quantize_kv = quantize_kv
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{prefill_chunk}")
        self.prefill_chunk = prefill_chunk

        telemetry = telemetry if telemetry is not None else Telemetry()

        # ---- paged columns: one pool per model (the target may share)
        if page_size is None:
            page_size = math.gcd(max_len, 256)
        page_size = min(page_size, max_len)
        if max_len % page_size:
            raise ValueError(
                f"page_size {page_size} must divide max_len {max_len}: a "
                "row's virtual space is a whole number of pages")
        self.page_size = page_size
        self.pages_per_row = max_len // page_size
        if num_pages is None:
            num_pages = batch_size * self.pages_per_row + 1
        if num_pages < self.pages_per_row + 1:
            raise ValueError(
                f"num_pages {num_pages} cannot hold one worst-case row "
                f"({self.pages_per_row} pages) plus the park page")
        self.num_pages = num_pages
        # scoped pool telemetry so the two free_pages gauges don't collide
        self._d_pages = PagePool(num_pages,
                                 telemetry=telemetry.scoped("draft."))
        self._bank = bank
        if bank is not None:
            if bank.pool.total_pages < self.pages_per_row + 1:
                raise ValueError(
                    f"shared bank of {bank.pool.total_pages} pages cannot "
                    f"hold one worst-case row ({self.pages_per_row} pages)")
            self._t_pages = bank.pool
        else:
            self._t_pages = PagePool(num_pages,
                                     telemetry=telemetry.scoped("target."))
        self.prefix_cache = prefix_cache
        if prefix_cache:
            if bank is not None:
                if bank.index is None:
                    bank.index = PrefixIndex(page_size,
                                             namespace=quantize_kv or "fp16")
                self._prefix = bank.index
            else:
                self._prefix = PrefixIndex(page_size,
                                           namespace=quantize_kv or "fp16")
        else:
            self._prefix = None
        # the prefix machinery reads/writes the TARGET column only
        self._pages = self._t_pages
        self.paged = True

        B, T = batch_size, temperature
        V = target.cfg.vocab_size
        max_len_ = max_len

        def _admit_draw(state: SpecState, last, slots):
            """First-token draw from prefill logits — the target's draw:
            the committed stream must be target-distributed from token
            one.  Past t=0 the draw key is salted (same hazard and same
            salt as ``StepEngine._admit``): the stored key equals round
            t-1's roll base, whose small-integer folds generated that
            round's draft fields — an unsalted admission at t <= K would
            reuse one of them."""
            if T > 0.0:
                salted = jax.random.fold_in(state.key,
                                            (1 << 30) ^ state.t)
                akey = jnp.where(state.t == 0, state.key, salted)
                g = jax.random.gumbel(akey, (B, V), jnp.float32)
                first = jnp.argmax(last / T + g[slots], axis=-1)
            else:
                first = jnp.argmax(last, axis=-1)
            return first.astype(jnp.int32)

        def _admit_target(tparams, state: SpecState, tokens, slots, tables):
            """Target prefill scattered into the rows' own pages + first
            token draw."""
            S = tokens.shape[1]
            logits, rows = target.prefill(tparams, tokens, max_len_)
            first = _admit_draw(state, logits[:, -1], slots)
            t_caches = target.insert_cache_pages(state.t_caches, rows,
                                                 tables)
            return first, state._replace(
                t_caches=t_caches,
                tok=state.tok.at[slots].set(first[:, None]),
                pos=state.pos.at[slots].set(jnp.int32(S)),
                t_table=state.t_table.at[slots].set(tables))

        def _admit_draft(dparams, state: SpecState, tokens, slots, tables):
            """Draft prefill into the draft column's pages (its last-token
            logits are unused — the draft only needs the prompt's k/v)."""
            _, rows = draft.prefill(dparams, tokens, max_len_)
            return state._replace(
                d_caches=draft.insert_cache_pages(state.d_caches, rows,
                                                  tables),
                d_table=state.d_table.at[slots].set(tables))

        def _admit_t_hit(tparams, state: SpecState, suffix, pos, slots,
                         tables, nvalid):
            """Prefix-hit target admission: only the prompt's un-cached
            suffix runs, as one verify-machinery chunk through the page
            tables (matched pages were mapped read-only by the host);
            the last real token's logits draw the first token under the
            same rules as a cold admit."""
            Wd = suffix.shape[1]
            wmask = (jnp.arange(Wd, dtype=jnp.int32)[None, :]
                     < nvalid[:, None])
            logits, t_caches = target.verify_step_pages(
                tparams, state.t_caches, suffix, pos, tables, wmask=wmask)
            last = jnp.take_along_axis(
                logits, (nvalid - 1)[:, None, None], axis=1)[:, 0]
            first = _admit_draw(state, last, slots)
            return first, state._replace(
                t_caches=t_caches,
                tok=state.tok.at[slots].set(first[:, None]),
                pos=state.pos.at[slots].set(pos + nvalid),
                t_table=state.t_table.at[slots].set(tables))

        def _chunk_d(dparams, state: SpecState, chunk, pos, tables, nvalid):
            """One streaming draft prefill chunk through the draft page
            tables (pad positions write-masked; no logits)."""
            Wd = chunk.shape[1]
            wmask = (jnp.arange(Wd, dtype=jnp.int32)[None, :]
                     < nvalid[:, None])
            _, d_caches = draft.prefill_chunk_pages(
                dparams, state.d_caches, chunk, pos, tables, wmask=wmask,
                need_logits=False)
            return state._replace(d_caches=d_caches)

        def _chunk_t(tparams, state: SpecState, chunk, pos, tables, nvalid):
            """One streaming target prefill chunk (non-final: no logits,
            no sampling)."""
            Wd = chunk.shape[1]
            wmask = (jnp.arange(Wd, dtype=jnp.int32)[None, :]
                     < nvalid[:, None])
            _, t_caches = target.prefill_chunk_pages(
                tparams, state.t_caches, chunk, pos, tables, wmask=wmask,
                need_logits=False)
            return state._replace(t_caches=t_caches)

        def _chunk_t_final(tparams, state: SpecState, chunk, pos, slots,
                           tables, nvalid):
            """Final target chunk: write the tail, sample the first token
            from the last real token's logits (same admission draw as
            one-shot), and arm the row's tok/pos."""
            Wd = chunk.shape[1]
            wmask = (jnp.arange(Wd, dtype=jnp.int32)[None, :]
                     < nvalid[:, None])
            logits, t_caches = target.prefill_chunk_pages(
                tparams, state.t_caches, chunk, pos, tables, wmask=wmask)
            last = jnp.take_along_axis(
                logits, (nvalid - 1)[:, None, None], axis=1)[:, 0]
            first = _admit_draw(state, last, slots)
            plen = pos + nvalid
            return first, state._replace(
                t_caches=t_caches,
                tok=state.tok.at[slots].set(first[:, None]),
                pos=state.pos.at[slots].set(plen))

        def _copy_t(params, state: SpecState, src, dst):
            """Copy-on-write a shared target page before the diverging
            row's first write.  ``params`` is unused but keeps the
            runner's uniform ``fn(params, *args)`` convention."""
            del params
            return state._replace(
                t_caches=target.copy_cache_pages(state.t_caches, src, dst))

        def _repair_d(dparams, state: SpecState, tok, rpos, alive):
            """Tree repair, draft column: the round committed a non-chain
            sibling, so the draft cache holds the CHAIN candidate's k/v
            at the sibling's position — one masked decode step feeding
            the committed sibling overwrites it with exactly what a
            sequential draft decode would have written (reads at rpos see
            only the committed prefix).  Logits are discarded."""
            _, d_caches = draft.decode_step_pages(
                dparams, state.d_caches, tok, rpos, state.d_table,
                live=alive)
            return state._replace(d_caches=d_caches)

        self._admit_target_fn = jax.jit(_admit_target, donate_argnums=(1,))
        self._admit_draft_fn = jax.jit(_admit_draft, donate_argnums=(1,))
        self._admit_t_hit_fn = jax.jit(_admit_t_hit, donate_argnums=(1,))
        self._chunk_d_fn = jax.jit(_chunk_d, donate_argnums=(1,))
        self._chunk_t_fn = jax.jit(_chunk_t, donate_argnums=(1,))
        self._chunk_t_final_fn = jax.jit(_chunk_t_final, donate_argnums=(1,))
        self._copy_t_fn = jax.jit(_copy_t, donate_argnums=(1,))
        self._repair_d_fn = jax.jit(_repair_d, donate_argnums=(1,))
        self._fns: dict = {}        # depth k -> {"roll", "verify"} jits

        # Execution hook: when set, every device program runs as
        # ``runner(which, fn, *args)`` with which in {"draft", "target"} —
        # the continuous scheduler activates the matching context slot and
        # prefetches the other into the shadow slot before each call.
        self.runner = None

        self.state: Optional[SpecState] = None
        self._pending: deque = deque()
        self._d_owned: dict = {}    # slot -> draft-column pages owned
        self._pool_init(B, telemetry=telemetry)
        # speculative accounting rides the shared pool counters; the tick
        # counters stay 0 — a round is not a decode round-trip and must
        # not skew the steps-per-tick aggregate.
        self.stats.update({"rounds": 0, "row_rounds": 0, "draft_steps": 0,
                           "committed_tokens": 0, "admitted_tokens": 0,
                           "prefix_hits": 0, "prefix_pages_mapped": 0,
                           "cow_copies": 0, "cache_evictions": 0})
        reg = self.telemetry.registry
        reg.gauge(self.telemetry.prefix + "k_current", self.k,
                  doc="current adaptive speculation depth")
        reg.gauge(self.telemetry.prefix + "tree_width", self.tree_width,
                  doc="draft candidates per speculation depth")
        self.reset()

    # -------------------------------------------------------- round programs
    def set_k(self, k: int):
        """Move the current speculation depth within [1, k_max] (adaptive
        K: the scheduler calls this from its acceptance EWMA).  Programs
        for each depth compile once and are cached; admission slack
        always reserves ``k_max`` so a later rise never overruns pages
        already granted."""
        k = max(1, min(int(k), self.k_max))
        if k != self.k:
            self.k = k
            self.telemetry.registry.gauge(
                self.telemetry.prefix + "k_current", k,
                doc="current adaptive speculation depth")

    def _programs(self, k: int):
        fns = self._fns.get(k)
        if fns is None:
            fns = self._build_round_programs(k)
            self._fns[k] = fns
        return fns

    def _build_round_programs(self, k: int):
        draft, target = self.draft_model, self.target_model
        B, T = self.batch_size, self.temperature
        V = target.cfg.vocab_size
        W = self.tree_width
        K = k
        max_len = self.max_len

        if W == 1:
            def _roll(dparams, state: SpecState, live):
                """K+1 draft decode steps from the committed token:
                iteration i feeds block token i at pos+i, sampling
                proposal d_{i+1}.  The extra iteration feeds d_K so its
                k/v lands in the draft pages (needed when the whole block
                is accepted); its sample is discarded.  Dead rows' writes
                park (their pages may already belong to a neighbor);
                sampling never sees the cache layout, so the stream is
                bitwise the dense-row engine's."""
                base = jax.random.fold_in(state.key, state.t)

                def body(carry, i):
                    caches, tok = carry
                    logits, caches = draft.decode_step_pages(
                        dparams, caches, tok, state.pos + i,
                        state.d_table, live=live)
                    last = logits[:, -1]
                    if T > 0.0:
                        g = jax.random.gumbel(jax.random.fold_in(base, i),
                                              (B, V), jnp.float32)
                        nxt = jnp.argmax(last / T + g, axis=-1)
                    else:
                        nxt = jnp.argmax(last, axis=-1)
                    nxt = nxt.astype(jnp.int32)
                    return (caches, nxt[:, None]), (nxt, last)

                (d_caches, _), (props, dlogits) = jax.lax.scan(
                    body, (state.d_caches, state.tok),
                    jnp.arange(K + 1, dtype=jnp.int32))
                return (props[:K].T, dlogits[:K].transpose(1, 0, 2),
                        state._replace(d_caches=d_caches))

            def _verify(tparams, state: SpecState, props, dlogits, live,
                        remaining):
                """One multi-token target pass over [t0, d_1..d_K] through
                the target page tables + exact accept/reject.  Commits
                m = min(n_accepted+1, remaining) tokens per live row;
                stale page writes past pos+m are masked by position and
                overwritten by later rounds.  Dead rows write-mask the
                whole block."""
                block = jnp.concatenate([state.tok, props], axis=1)
                wmask = jnp.broadcast_to(live[:, None], block.shape)
                logits, t_caches = target.verify_step_pages(
                    tparams, state.t_caches, block, state.pos,
                    state.t_table, wmask=wmask)
                vkey = jax.random.fold_in(
                    jax.random.fold_in(state.key, state.t), 1 << 20)
                toks, n = speculative_accept(vkey, props, dlogits, logits,
                                             T)
                m = jnp.where(live, jnp.minimum(n + 1, remaining), 0)
                tok_new = jnp.take_along_axis(
                    toks, jnp.clip(m - 1, 0, K)[:, None], axis=1)
                tok_new = jnp.where(m[:, None] > 0, tok_new, state.tok)
                pos_new = jnp.minimum(state.pos + m, max_len - 1)
                # advance the key once per round (like StepEngine._step):
                # a later admission must draw from a FRESH field, not the
                # one every earlier admission into that slot already used
                return toks, m, state._replace(
                    t_caches=t_caches, tok=tok_new, pos=pos_new,
                    key=jax.random.fold_in(state.key, state.t),
                    t=state.t + 1)

            return {"roll": jax.jit(_roll, donate_argnums=(1,)),
                    "verify": jax.jit(_verify, donate_argnums=(1,))}

        # ---- sausage tree: W candidates per depth, one verify pass
        Kt = 1 + K * W
        chain = lambda i: 1 + (i - 1) * W
        offsets_np = np.concatenate(
            [[0], np.repeat(np.arange(1, K + 1), W)]).astype(np.int32)
        mask_np = np.zeros((Kt,), np.int32)
        mask_np[0] = 1                               # node 0 sees itself
        for i in range(1, K + 1):
            anc = 1                                  # bit 0: committed tok
            for d in range(1, i):
                anc |= 1 << chain(d)
            for w in range(W):
                j = chain(i) + w
                mask_np[j] = anc | (1 << j)
        writer_np = np.zeros((Kt,), bool)
        writer_np[0] = True                          # committed tok at pos
        for i in range(1, K + 1):
            writer_np[chain(i)] = True               # chain k/v at pos+i

        def _roll_tree(dparams, state: SpecState, live):
            """K+1 draft steps along the CHAIN (sibling 0), sampling W
            i.i.d. candidates per depth from the chain distribution
            (greedy: top-W, so sibling 0 is the argmax chain).  Only the
            chain's k/v enters the draft pages — siblings are scored by
            the target's tree pass, never decoded by the draft."""
            base = jax.random.fold_in(state.key, state.t)

            def body(carry, i):
                caches, tok = carry
                logits, caches = draft.decode_step_pages(
                    dparams, caches, tok, state.pos + i, state.d_table,
                    live=live)
                last = logits[:, -1]                         # (B, V)
                if T > 0.0:
                    g = jax.random.gumbel(jax.random.fold_in(base, i),
                                          (B, W, V), jnp.float32)
                    cands = jnp.argmax(last[:, None, :] / T + g, axis=-1)
                else:
                    _, cands = jax.lax.top_k(last, W)
                cands = cands.astype(jnp.int32)              # (B, W)
                return (caches, cands[:, :1]), (cands, last)

            (d_caches, _), (cs, ls) = jax.lax.scan(
                body, (state.d_caches, state.tok),
                jnp.arange(K + 1, dtype=jnp.int32))
            return (cs[:K].transpose(1, 0, 2),
                    ls[:K].transpose(1, 0, 2),
                    state._replace(d_caches=d_caches))

        def _verify_tree(tparams, state: SpecState, cand, dlogits, live,
                         remaining):
            """ONE target pass over all 1+K*W tree nodes: per-node
            depth offsets place queries/writes at pos+depth, the
            scalar-prefetched ancestor bitmask replaces the
            intra-block causal mask, and only the chain nodes write
            k/v (siblings park — a dead branch must not dirty the
            pages).  Tree acceptance picks the committed block; when
            a non-chain sibling wins, the target cache's chain k/v at
            that depth is repaired in-place with one masked decode
            step before the state advances."""
            block = jnp.concatenate(
                [state.tok, cand.reshape(B, K * W)], axis=1)  # (B, Kt)
            wmask = live[:, None] & jnp.asarray(writer_np)[None, :]
            tree = jnp.broadcast_to(jnp.asarray(mask_np), (B, Kt))
            logits, t_caches = target.verify_step_pages(
                tparams, state.t_caches, block, state.pos,
                state.t_table, wmask=wmask,
                offsets=jnp.asarray(offsets_np), tree=tree)
            vkey = jax.random.fold_in(
                jax.random.fold_in(state.key, state.t), 1 << 20)
            toks, n, alt_depth, alt_tok = tree_speculative_accept(
                vkey, cand, dlogits, logits, T)
            m = jnp.where(live, jnp.minimum(n + 1, remaining), 0)
            tok_new = jnp.take_along_axis(
                toks, jnp.clip(m - 1, 0, K)[:, None], axis=1)
            tok_new = jnp.where(m[:, None] > 0, tok_new, state.tok)
            # repair: overwrite the chain k/v at the sibling's depth
            # with the committed sibling's.  Always ran (parked when
            # no row needs it); safe under the remaining clip — a
            # clipped-out sibling's repair lands past pos_new, in the
            # stale region later rounds overwrite anyway.
            alt_live = live & (alt_depth > 0)
            rpos = state.pos + alt_depth
            _, t_caches = target.decode_step_pages(
                tparams, t_caches, alt_tok[:, None], rpos,
                state.t_table, live=alt_live)
            pos_new = jnp.minimum(state.pos + m, max_len - 1)
            return toks, m, alt_depth, alt_tok, rpos, state._replace(
                t_caches=t_caches, tok=tok_new, pos=pos_new,
                key=jax.random.fold_in(state.key, state.t),
                t=state.t + 1)

        return {"roll": jax.jit(_roll_tree, donate_argnums=(1,)),
                "verify": jax.jit(_verify_tree, donate_argnums=(1,))}

    # the prefix-cache and page-allocation machinery is byte-for-byte
    # StepEngine's, pointed at the TARGET column (``self._pages`` aliases
    # the target pool; the draft column never shares pages)
    _reclaim = StepEngine._reclaim
    _prefix_plan = StepEngine._prefix_plan
    _route_prefix = StepEngine._route_prefix
    _take_prefix_pages = StepEngine._take_prefix_pages
    _drop_prefix_pages = StepEngine._drop_prefix_pages
    _index_prompt = StepEngine._index_prompt
    _take_pages = StepEngine._take_pages
    _note_chunk = StepEngine._note_chunk

    # ------------------------------------------------------------- lifecycle
    def reset(self, seed: Optional[int] = None):
        B = self.batch_size
        # give the target column's pages back before the host pools reset:
        # a private pool just resets; a shared bank keeps serving the
        # OTHER engines, so only this engine's own rows release
        if self._bank is not None:
            own = []
            for g in self.slots:
                if g is not None and g.pages:
                    own += g.pages
                    g.pages = None
            for ps in self._pending:
                for g in ps.gens:
                    if g.pages:
                        own += g.pages
                        g.pages = None
            if own:
                self._t_pages.release(own)
        else:
            self._t_pages.reset()
            if self._prefix is not None:
                self._prefix.clear()   # its pages just left the allocator
        self._d_pages.reset()
        self._d_owned = {}
        self._pending.clear()

        def _alive(c):
            return c is not None and not any(
                getattr(x, "is_deleted", lambda: False)()
                for x in jax.tree.leaves(c))

        d_caches = t_caches = None
        if self.state is not None:
            d_caches, t_caches = self.state.d_caches, self.state.t_caches
        if self._bank is not None and self._bank.caches is not None:
            t_caches = self._bank.caches   # the bank copy is authoritative
        if not _alive(d_caches):
            d_caches = self.draft_model.init_page_pool(
                self.num_pages, self.page_size,
                quantized=self.quantize_kv is not None)
        if not _alive(t_caches):
            t_caches = self.target_model.init_page_pool(
                self._t_pages.total_pages, self.page_size,
                quantized=self.quantize_kv is not None)
        if self._bank is not None:
            self._bank.caches = t_caches
        P = self.pages_per_row
        self.state = SpecState(
            d_caches=d_caches, t_caches=t_caches,
            tok=jnp.zeros((B, 1), jnp.int32),
            pos=jnp.zeros((B,), jnp.int32),
            key=jax.random.PRNGKey(self.seed if seed is None else seed),
            t=jnp.zeros((), jnp.int32),
            # every table entry must be a valid pool index; park (0) is
            # the safe default — empty slots read/write garbage space
            d_table=jnp.zeros((B, P), jnp.int32),
            t_table=jnp.zeros((B, P), jnp.int32))
        self._pool_reset()

    def _call(self, which: str, fn, params, *args):
        if self.runner is not None:
            return self.runner(which, fn, *args)
        dp, tp = params
        return fn(dp if which == "draft" else tp, *args)

    def _bank_pull(self):
        """Adopt the bank's current target pages: another engine's jitted
        call may have donated the buffers this state still references."""
        if (self._bank is not None and self._bank.caches is not None
                and self.state is not None
                and self._bank.caches is not self.state.t_caches):
            self.state = self.state._replace(t_caches=self._bank.caches)

    def _bank_push(self):
        """Publish the (possibly donated-and-replaced) target pages back
        to the bank for the next engine."""
        if self._bank is not None and self.state is not None:
            self._bank.caches = self.state.t_caches

    # -------------------------------------------------------------- queries
    @property
    def accepted_per_round(self) -> float:
        """Mean committed tokens per row per verify pass, in [1, K+1]
        (> 1 means speculation is paying: extra tokens rode each target
        pass)."""
        return safe_ratio(self.stats["committed_tokens"],
                          self.stats["row_rounds"])

    def pending_slots(self) -> int:
        return sum(len(ps.gens) for ps in self._pending)

    def free_pages(self) -> int:
        """Admission headroom is the TIGHTER column."""
        return min(self._d_pages.free_pages(), self._t_pages.free_pages())

    def pages_needed(self, prompt_len: int, max_new: int) -> int:
        """Pages one row needs per column: a round's block writes run up
        to ``k_max`` positions past the last committed token (position
        ``prompt_len + max_new - 2 + k_max`` at worst), and the admission
        bound ``prompt + max_new + k_max <= max_len`` guarantees that
        slack exists inside the row's virtual space."""
        return max(1, -(-(prompt_len + max_new + self.k_max - 1)
                        // self.page_size))

    def can_admit(self, tokens, max_new: int) -> bool:
        if not SlotPool.can_admit(self, tokens, max_new):
            return False
        tokens = np.asarray(tokens)
        b, S = (1, tokens.shape[0]) if tokens.ndim == 1 else tokens.shape
        needed = b * self.pages_needed(S, max_new)
        if needed > self._d_pages.free_pages():
            self.last_admit_block = "pages"
            return False               # the draft column has no cache to
        #                                reclaim from — pages or nothing
        t_needed = needed
        protect = []
        if self.prefix_cache and b == 1 and self.prefill_chunk is None:
            plan = self._prefix_plan(tokens.reshape(1, S), max_new,
                                     peek=True)
            if plan is not None:
                retained, cow_src, _, owned = plan
                t_needed = owned       # shared pages cost nothing
                protect = retained + ([cow_src] if cow_src is not None
                                      else [])
        if t_needed <= self._t_pages.free_pages():
            return True
        self._reclaim(t_needed - self._t_pages.free_pages(),
                      protect=protect)
        ok = t_needed <= self._t_pages.free_pages()
        if not ok:
            self.last_admit_block = "pages"
        return ok

    # ------------------------------------------------------ page allocation
    def _take_d_pages(self, b: int, npages: int):
        """Allocate the draft column's pages and build the (b, P) tables
        (unused tail entries point at the park page)."""
        pages = self._d_pages.take(b * npages)
        tables = np.full((b, self.pages_per_row), PagePool.PARK, np.int32)
        for i in range(b):
            tables[i, :npages] = pages[i * npages:(i + 1) * npages]
        return tables, pages

    # ------------------------------------------------------------- admission
    def admit(self, params, tokens, max_new: int,
              metas: Optional[list] = None,
              seeds: Optional[list] = None,
              submitted_at: Optional[float] = None) -> list[Generation]:
        """Admit (b, S) prompt rows into b free slots (both columns).

        Needs ``k_max`` extra cache slack beyond ``max_new``: a round's
        block writes run up to K positions past the last committed token
        (and adaptive K may rise back to ``k_max`` at any round)."""
        if seeds and any(s is not None for s in seeds):
            raise ValueError("SpecEngine does not honor per-request seeds; "
                             "route seeded requests to a plain context")
        tokens, _, _ = self._admit_args(tokens, metas, seeds)
        b, S = tokens.shape
        if S + max_new + self.k_max > self.max_len:
            raise ValueError(
                f"prompt {S} + {max_new} new + {self.k_max} speculative "
                f"slack exceeds max_len {self.max_len}")
        self._bank_pull()
        try:
            if self.prefill_chunk is not None:
                return self._admit_chunked(tokens, max_new, metas,
                                           submitted_at)
            plan = (self._prefix_plan(tokens, max_new)
                    if self.prefix_cache else None)
            if plan is not None:
                return self._admit_prefix_hit(params, tokens, max_new,
                                              metas, plan, submitted_at)
            return self._admit_cold(params, tokens, max_new, metas,
                                    submitted_at)
        finally:
            self._bank_push()

    def _admit_cold(self, params, tokens, max_new, metas, submitted_at):
        """One-shot cold admission: whole-prompt prefill into both
        columns' freshly-taken pages."""
        b, S = tokens.shape
        slots = self._take_slots(b)
        npages = self.pages_needed(S, max_new)
        t_pages = []
        try:
            t_tables, t_pages = self._take_pages(b, S, max_new)
            d_tables, d_pages = self._take_d_pages(b, npages)
        except BaseException:
            self._restore_slots(slots)
            if t_pages:
                self._t_pages.restore(t_pages)
            raise
        try:
            tk = jnp.asarray(tokens, jnp.int32)
            sl = jnp.asarray(slots, jnp.int32)
            first, self.state = self._call(
                "target", self._admit_target_fn, params, self.state, tk,
                sl, jnp.asarray(t_tables))
            self.state = self._call(
                "draft", self._admit_draft_fn, params, self.state, tk, sl,
                jnp.asarray(d_tables))
        except BaseException:
            self._restore_slots(slots)   # failed admit must not leak slots
            self._t_pages.restore(t_pages)   # nor either column's pages
            self._d_pages.restore(d_pages)
            raise
        gens = self._register(slots, S, max_new, metas,
                              first=np.asarray(first),
                              submitted_at=submitted_at)
        for i, g in enumerate(gens):
            g.pages = t_pages[i * npages:(i + 1) * npages]
            self._d_owned[g.slot] = d_pages[i * npages:(i + 1) * npages]
            self._index_prompt(tokens[i], g.pages)
        self.stats["admitted_tokens"] += b
        if self._retire_done(gens):
            # same-boundary re-admission of an instantly retired slot must
            # not reuse this draw field (salt disjoint from round folds)
            self._salt_admit_key()
        return gens

    def _admit_prefix_hit(self, params, tokens, max_new, metas, plan,
                          submitted_at):
        """One-shot admission on a target-column prefix hit: the matched
        pages map read-only into the new row's target table, the boundary
        page is copied-on-write when the divergence lands inside one, and
        only the prompt's un-cached suffix runs through the target.  The
        draft column has no sharing — it prefills the whole prompt cold
        into its own pages."""
        b, S = tokens.shape
        retained, cow_src, d, owned = plan
        slots = self._take_slots(b)
        npages = self.pages_needed(S, max_new)
        try:
            t_table, t_pages, fresh = self._take_prefix_pages(plan, S,
                                                              max_new)
        except BaseException:
            self._restore_slots(slots)
            raise
        try:
            d_tables, d_pages = self._take_d_pages(b, npages)
        except BaseException:
            self._restore_slots(slots)
            self._drop_prefix_pages(plan, fresh)
            raise
        jslots = jnp.asarray(slots, jnp.int32)
        jtable = jnp.asarray(t_table)
        try:
            if cow_src is not None:
                self.state = self._call(
                    "target", self._copy_t_fn, params, self.state,
                    jnp.asarray([cow_src], jnp.int32),
                    jnp.asarray([fresh[0]], jnp.int32))
            first, self.state = self._call(
                "target", self._admit_t_hit_fn, params, self.state,
                jnp.asarray(tokens[:, d:], jnp.int32),
                jnp.full((b,), d, jnp.int32), jslots, jtable,
                jnp.full((b,), S - d, jnp.int32))
            self.state = self._call(
                "draft", self._admit_draft_fn, params, self.state,
                jnp.asarray(tokens, jnp.int32), jslots,
                jnp.asarray(d_tables))
        except BaseException:
            self._restore_slots(slots)
            self._drop_prefix_pages(plan, fresh)
            self._d_pages.restore(d_pages)
            raise
        if cow_src is not None:
            self._t_pages.release([cow_src])     # copy done: pin drops
        gens = self._register(slots, S, max_new, metas,
                              first=np.asarray(first),
                              submitted_at=submitted_at)
        gens[0].pages = t_pages
        self._d_owned[gens[0].slot] = d_pages
        self._index_prompt(tokens[0], t_pages)
        self.stats["admitted_tokens"] += b
        self.stats["prefix_hits"] += 1
        self.stats["prefix_pages_mapped"] += len(retained)
        if cow_src is not None:
            self.stats["cow_copies"] += 1
        if self._trace.enabled:
            self._trace.instant(
                f"prefix-hit:{gens[0].req}", self._track,
                args={"mapped": len(retained), "cow": cow_src is not None})
        if self._retire_done(gens):
            self._salt_admit_key()
        return gens

    def _admit_chunked(self, tokens, max_new, metas, submitted_at):
        """Reserve slots + pages in both columns and queue the prompt;
        each engine tick streams one (b, C) chunk into BOTH columns.  No
        position parking is needed (unlike the row engine): pending rows
        are not live, so every round-program write they'd make is routed
        to the park page by the live/wmask plumbing."""
        b, S = tokens.shape
        slots = self._take_slots(b)
        npages = self.pages_needed(S, max_new)
        t_pages = []
        try:
            t_tables, t_pages = self._take_pages(b, S, max_new)
            d_tables, d_pages = self._take_d_pages(b, npages)
        except BaseException:
            self._restore_slots(slots)
            if t_pages:
                self._t_pages.restore(t_pages)
            raise
        jslots = jnp.asarray(slots, jnp.int32)
        # tables go live at reserve time: the rounds that run while the
        # prompt streams in don't read them (dead rows park), the chunk
        # programs write through an explicit arg, and the final chunk's
        # sampled row needs them next round
        self.state = self.state._replace(
            t_table=self.state.t_table.at[jslots].set(
                jnp.asarray(t_tables)),
            d_table=self.state.d_table.at[jslots].set(
                jnp.asarray(d_tables)))
        gens = self._register(slots, S, max_new, metas,
                              submitted_at=submitted_at)
        for i, g in enumerate(gens):
            g.pages = t_pages[i * npages:(i + 1) * npages]
            self._d_owned[g.slot] = d_pages[i * npages:(i + 1) * npages]
        self._pending.append(_SpecPending(
            tokens=np.asarray(tokens, np.int32), gens=gens,
            t_tables=t_tables, d_tables=d_tables))
        return gens

    def prefill_tick(self, params) -> list[Generation]:
        """Run at most ONE chunk tick — one (b, C) chunk into EACH
        column — the admission budget per round.  Returns generations
        that finished at this boundary (a final chunk can instant-retire:
        steps==1, or EOS as the first token)."""
        if not self._pending:
            return []
        C = self.prefill_chunk
        ps = self._pending[0]
        b, S = ps.tokens.shape
        start = ps.done
        end = min(start + C, S)
        nvalid = end - start
        chunk = np.zeros((b, C), np.int32)
        chunk[:, :nvalid] = ps.tokens[:, start:end]
        pos = jnp.full((b,), start, jnp.int32)
        nv = jnp.full((b,), nvalid, jnp.int32)
        jchunk = jnp.asarray(chunk)
        t0 = self.telemetry.clock()
        try:
            with self._trace.region("eng.prefill_chunk", self._track,
                                    req=ps.gens[0].req, start=start,
                                    end=end, final=end == S):
                self.state = self._call(
                    "draft", self._chunk_d_fn, params, self.state, jchunk,
                    pos, jnp.asarray(ps.d_tables), nv)
                if end < S:
                    self.state = self._call(
                        "target", self._chunk_t_fn, params, self.state,
                        jchunk, pos, jnp.asarray(ps.t_tables), nv)
                    ps.done = end
                    self._note_chunk(ps, t0)
                    return []
                slots = jnp.asarray([g.slot for g in ps.gens], jnp.int32)
                first, self.state = self._call(
                    "target", self._chunk_t_final_fn, params, self.state,
                    jchunk, pos, slots, jnp.asarray(ps.t_tables), nv)
        except BaseException:
            # a failed chunk abandons the whole request: release its rows
            # so the pool keeps serving (the caller fails the futures).
            # Each column's pages restore in ONE call, in their original
            # take order — per-gen restores would break FIFO determinism.
            self._pending.popleft()
            t_pg, d_pg = [], []
            for g in ps.gens:
                self.slots[g.slot] = None
                t_pg += g.pages or []
                g.pages = None
                d_pg += self._d_owned.pop(g.slot, [])
            if t_pg:
                self._t_pages.restore(t_pg)
            if d_pg:
                self._d_pages.restore(d_pg)
            self._restore_slots([g.slot for g in ps.gens])
            raise
        self._pending.popleft()
        self._note_chunk(ps, t0)
        first = np.asarray(first)
        tok_now = self.telemetry.clock()
        for i, g in enumerate(ps.gens):
            g.tokens.append(int(first[i]))
            self._live[g.slot] = True
            self.stats["tokens_out"] += 1
            self._note_first_token(g, tok_now)
        self.stats["admitted_tokens"] += b
        for i, g in enumerate(ps.gens):
            # the prompt is now fully written into the target column: its
            # whole pages become indexable (BEFORE retirement, so an
            # instant retire still populates the cache)
            self._index_prompt(ps.tokens[i], g.pages)
        finished = self._retire_done(ps.gens)
        if finished:
            self._salt_admit_key()
        return finished

    # ----------------------------------------------------------- retirement
    def _retire_done(self, gens: list[Generation]) -> list[Generation]:
        """Retire finished rows AND release both columns' pages (FIFO: to
        the back of each free-list).  No device-side table reset is
        needed: the retired slot stops being live, so its writes route to
        the park page from the next round on."""
        finished = SlotPool._retire_done(self, gens)
        for g in finished:
            if g.pages:
                self._t_pages.release(g.pages)
                g.pages = None
            d = self._d_owned.pop(g.slot, None)
            if d:
                self._d_pages.release(d)
        return finished

    # ----------------------------------------------------------------- round
    def step(self, params=None) -> list[Generation]:
        """One engine tick: at most one chunk tick (chunked admission),
        then one speculative round for every live slot — K+1 draft steps,
        one verify pass, 1..K+1 committed tokens per row.  Returns the
        generations that finished at this boundary."""
        self._bank_pull()
        try:
            finished = self.prefill_tick(params) if self._pending else []
            if not self._live.any():
                return finished
            remaining = np.zeros(self.batch_size, np.int32)
            for s, g in enumerate(self.slots):
                if g is not None and self._live[s]:
                    remaining[s] = g.remaining
            live = jnp.asarray(self._live)
            fns = self._programs(self.k)
            with self._trace.region("eng.decode", self._track) as rnd:
                t0 = self.telemetry.clock()
                props, dlogits, self.state = self._call(
                    "draft", fns["roll"], params, self.state, live)
                if self.tree_width == 1:
                    toks, m, self.state = self._call(
                        "target", fns["verify"], params, self.state, props,
                        dlogits, live, jnp.asarray(remaining))
                else:
                    (toks, m, alt_depth, alt_tok, rpos,
                     self.state) = self._call(
                        "target", fns["verify"], params, self.state, props,
                        dlogits, live, jnp.asarray(remaining))
                    # the target column repaired itself inside the verify
                    # program; the draft column repairs here, host-gated (the
                    # common all-chain rounds skip the extra draft step)
                    alt_live = self._live & (np.asarray(alt_depth) > 0)
                    if alt_live.any():
                        self.state = self._call(
                            "draft", self._repair_d_fn, params, self.state,
                            alt_tok[:, None], rpos, jnp.asarray(alt_live))
                        self.stats["draft_steps"] += 1
                toks, m = np.asarray(toks), np.asarray(m)
                now = self.telemetry.clock()
                stepped = []
                committed = 0
                reg = self.telemetry.registry
                for s in range(self.batch_size):
                    g = self.slots[s]
                    if g is None or not self._live[s]:
                        continue              # empty, or reserved mid-prefill
                    new = [int(x) for x in toks[s, :m[s]]]
                    if self.eos_id is not None and self.eos_id in new:
                        new = new[:new.index(self.eos_id) + 1]
                    g.tokens.extend(new)
                    committed += len(new)
                    reg.observe("spec_accept_len", float(len(new)),
                                buckets=SPEC_ACCEPT_BUCKETS,
                                doc="tokens committed per row per "
                                    "speculative round")
                    stepped.append(g)
                self.stats["rounds"] += 1
                self.stats["row_rounds"] += len(stepped)
                self.stats["draft_steps"] += self.k + 1
                self.stats["committed_tokens"] += committed
                self.stats["tokens_out"] += committed
                # per-token latency: the round amortizes over the tokens each
                # row committed (1..K+1); the round itself is not a decode
                # tick.
                self._note_tick(t0, now, safe_ratio(committed, len(stepped)),
                                len(stepped))
                if self._trace.enabled:
                    rnd.set(rows=len(stepped))
                    self._trace.instant(
                        "spec-round", self._track, ts=now,
                        args={"committed": committed, "rows": len(stepped),
                              "k": self.k, "tree_width": self.tree_width,
                              "accepted": [int(x) for x in m if x]})
                return finished + self._retire_done(stepped)
        finally:
            self._bank_push()
