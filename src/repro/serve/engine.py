"""Serving execution layer: continuous-batching step engine + batch loops.

The core abstraction is ``StepEngine`` — a persistent, fixed-shape decode
batch advanced one token at a time:

  * ``BatchState``   — slot-pooled KV cache (one cache row per slot, a
                       free-list over rows) + per-slot token/position, all
                       under ONE jitted ``step(params, state) -> (tokens,
                       state)`` with a fixed batch shape (no recompiles as
                       requests come and go)
  * ``admit``        — prefill a prompt into a free slot's cache row
                       (``LM.insert_cache_rows``: only that row changes)
  * ``step``         — one decode step for every live slot; per-request
                       positions go down to the attention kernel as a
                       ``(B,)`` vector
  * retirement       — EOS / step-limit frees the slot back to the pool

Requests join, leave, and (one level up, in ``serve/scheduler.py``) switch
model contexts at *step* boundaries — the paper's hide-the-load principle
at token granularity instead of batch granularity.

``ServingEngine`` keeps the classic run-to-completion API; ``generate`` is
now a thin wrapper that admits the whole batch into a ``StepEngine`` and
steps it to completion (token-for-token identical — tested).  Sampling:
greedy or temperature; draws match ``jax.random.categorical`` exactly,
including single-row admissions (the per-row gumbel trick below).
"""
from __future__ import annotations

from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers
from repro.models.model import LM
from repro.serve.pool import (Generation, PagePool, PrefixIndex, SharedBank,
                              ShardedPagePool, SlotPool)
from repro.serve.telemetry import Telemetry, safe_ratio

__all__ = ["DecodeState", "EngineKey", "Generation", "PagePool",
           "PrefixIndex", "ServeStats", "ServingEngine", "SharedBank",
           "ShardedPagePool", "SlotPool", "StepEngine"]


class EngineKey(NamedTuple):
    """Frozen cache key for ONE step-engine configuration.

    Every knob that changes a compiled program or the cache layout is a
    named field; the engine caches in ``ServingEngine``,
    ``SwitchableServer``, and ``ContinuousScheduler`` all key on this
    type, so adding the next knob means adding a field here (with a
    default) — it can no longer silently alias two configurations the
    way a growing positional tuple could.  ``page_size is None`` means
    the row cache layout (``paged=False``); a paged engine always
    records its page size."""
    name: Optional[str] = None          # model context (None: single-model)
    batch_size: int = 1
    prefill_chunk: Optional[int] = None
    page_size: Optional[int] = None     # None == row layout (paged off)
    multi_step: int = 1
    quantize_kv: Optional[str] = None
    prefix_cache: bool = False
    shared_bank: bool = False           # pages/prefixes from a SharedBank
    shards: int = 1                     # page-bank shards (1 == unsharded)


class ServeStats:
    """Run-to-completion loop accounting.  Same attribute API as the old
    dataclass (``stats.tokens += ...``), but the values live in the shared
    ``MetricRegistry`` (``serve.*`` under a server) so one snapshot sees
    the batch loops next to the step engines and the context engine."""

    __slots__ = ("_v",)
    _FLOATS = ("prefill_s", "decode_s")

    def __init__(self, view=None):
        if view is None:
            view = Telemetry().view()
        object.__setattr__(self, "_v", view)
        for k in self._FLOATS:
            view.setdefault(k, 0.0)
        view.setdefault("tokens", 0)

    def __getattr__(self, k):
        try:
            return self._v[k]
        except KeyError:
            raise AttributeError(k) from None

    def __setattr__(self, k, v):
        self._v[k] = v

    @property
    def tok_per_s(self) -> float:
        return safe_ratio(self._v["tokens"], self._v["decode_s"])


# ---------------------------------------------------------------------------
# continuous-batching step engine
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    """Device half of the batch state (a pytree; donated every step).

    ``key``/``t`` implement the same cumulative fold-in schedule the
    run-to-completion loop uses, so a batch admitted at t=0 samples
    token-for-token identically to ``generate``.

    ``rkey``/``seeded`` are the per-request seed column: a seeded slot
    draws from its own key folded with the *position of the token being
    produced* instead of the pool schedule, so a seeded resubmission
    reproduces its tokens exactly regardless of which slot it lands in or
    what else shares the pool.  Unseeded slots keep the pool schedule
    (bitwise ``generate`` equality).
    """
    caches: Any           # decode-cache pytree: leaves (R, B, ...) for the
    #                       row layout, (R, NP, ...) PagedKV banks when paged
    tok: jax.Array        # (B, 1) int32 — last sampled token per slot
    pos: jax.Array        # (B,) int32  — cache position `tok` is fed at
    key: jax.Array        # PRNG key, folded once per step
    t: jax.Array          # () int32    — global step counter
    rkey: jax.Array       # (B, 2) uint32 — per-slot request PRNG key
    seeded: jax.Array     # (B,) bool — slot draws from rkey, not the pool
    table: jax.Array      # (B, P) int32 — per-slot page table (paged mode;
    #                       (B, 0) placeholder for the row layout)


@dataclass
class _PendingPrefill:
    """One admitted-but-still-prefilling request (chunked admission):
    its slots are reserved, its prompt streams into their cache rows one
    chunk per engine tick."""
    tokens: np.ndarray                    # (b, S) full prompt, int32
    gens: list                            # Generation handles (slots set)
    rkeys: np.ndarray                     # (b, 2) uint32 per-row keys
    seeded: np.ndarray                    # (b,) bool
    done: int = 0                         # prompt tokens already chunked
    #                                       (starts at the first divergent
    #                                       token on a prefix hit)
    tables: Optional[np.ndarray] = None   # (b, P) page tables (paged mode)
    cow: Optional[tuple] = None           # (src, dst) page pair to copy
    #                                       before the first chunk write;
    #                                       src holds a pool reference
    #                                       (dropped when the copy runs)
    hit: bool = False                     # admitted through a prefix hit
    mapped: int = 0                       # shared pages mapped read-only
    had_cow: bool = False                 # plan included a boundary copy
    started: bool = False                 # first chunk has executed
    #                                       (admit-to-first-chunk latency)


class StepEngine(SlotPool):
    """Continuous-batching decode engine for one model context.

    Fixed batch shape ``batch_size``; requests occupy slots.  All device
    work happens in three jitted programs: ``_admit_<S>`` (per prompt
    length), ``_step``, and the cache-row insert fused into admit.  The
    engine is deliberately un-timed and thread-free: callers (the classic
    ``generate`` wrapper, the token-granular ``ContinuousScheduler``)
    decide when to step, when to switch contexts, and what to measure.

    ``params`` is passed per call: under the context-switching server the
    weights live in a ``ContextSwitchEngine`` slot that may be evicted and
    reloaded between steps; the engine never captures them.

    ``prefill_chunk=C`` switches admission to *chunked prefill*: instead
    of one whole-prompt program per prompt length, ``admit`` reserves the
    slots and queues the prompt, and each engine tick runs at most ONE
    fixed-shape (b, C) chunk program (``LM.prefill_chunk``, the verify
    machinery pointed at admission) before the decode step.  Admission
    latency for live rows is therefore bounded by one chunk regardless of
    prompt length, prompts pad to the chunk width (≤2 compiled chunk
    programs total: streaming + final), and the prompt streams into its
    slot behind decode the way context loads stream into the shadow slot.
    The final chunk samples the first token under the same admission
    gumbel rules as one-shot admit, so greedy and seeded-temperature
    streams are token-identical across chunk sizes (tested).  Chunked
    mode needs an all-attention model with a full (non-ring) cache: a
    mid-prefill row's parked decode writes go to the last cache slot,
    which a ring would wrap onto live window entries, and recurrent state
    cannot carry across host-side chunk boundaries.

    ``paged=True`` swaps the row-granular cache for a *paged slot pool*:
    instead of one ``max_len`` cache row per slot, the cache is ONE
    shared bank of ``num_pages`` fixed-size pages (``page_size`` tokens
    each), each admitted row owns only the ``ceil((S+max_new-1)/page)``
    pages its own lifetime needs, and a per-slot page table
    (``DecodeState.table``, scalar-prefetched down to the
    ``paged_attention`` kernel) maps virtual positions onto pool pages.
    ``num_pages`` is the HBM budget knob: the default
    ``batch_size * max_len/page_size + 1`` matches the row layout's
    capacity, while a smaller bank serves MORE concurrent short requests
    in the same memory (admission gates on ``can_admit``: free slots AND
    free pages).  Retirement returns pages, not a whole row (FIFO
    recycling, see ``PagePool``); non-live rows' per-step writes route to
    the park page so a freed page can be recycled instantly without
    disturbing its new owner.  Sampling never sees the cache layout, so
    paged and row streams are bitwise-identical (greedy + seeded
    temperature, one-shot + chunked admission — tested).  Paged mode
    needs an all-attention, non-ring model, same as chunked prefill.

    ``multi_step=T`` fuses up to T decode steps into ONE device program
    per tick (``LM.decode_multi_step[_pages]``): the host's
    rank/drain/admit bookkeeping amortizes over every committed step
    instead of being paid per token.  On-device EOS / token-budget /
    page-exhaustion bitmaps early-exit the loop the moment any slot
    would change occupancy, so retirement timing — and, because the
    sampling rule and key-fold chain are shared with the single-step
    program, every sampled token — is bitwise-identical to T single
    steps (tested).  While a chunked prefill is mid-stream the engine
    drops to single steps so the prompt keeps its one-chunk-per-tick
    admission latency.

    ``quantize_kv="int8"`` (paged mode only) stores the shared page bank
    as int8 codes with per-token-per-head f32 scales in parallel leaves
    — about half the bytes per page, so roughly 2x the pages fit in the
    same HBM budget and admitted concurrency rises with them.  Writes
    quantize on insert/decode/verify; the paged attention kernel
    dequantizes in VMEM (the scales ride the same scalar-prefetched page
    table).  Outputs are no longer bitwise-equal to fp16 — the parity
    suite bounds greedy logit divergence and distribution-level sampling
    drift instead (tested).

    ``prefix_cache=True`` (paged mode only) shares already-written
    prompt pages across admissions: every completed prompt's whole pages
    are indexed by their token runs (``PrefixIndex``), and a new
    admission whose prompt starts with an indexed run maps those page
    ids straight into its table — refcounted, read-only — and prefills
    only from the first divergent token.  A full-prefix hit recomputes
    just the last prompt token, and because that write would land in a
    *shared* page, the engine copy-on-writes that one boundary page
    (``LM.copy_cache_pages``) before it: shared pages are never mutated,
    so a prefix-hit stream is bitwise-identical to the same request
    admitted cold (greedy + seeded temperature — tested).  Retired
    prompts' pages live on in the cache at refcount 1; when admission
    would fail on pages, ``can_admit`` evicts those cached pages
    LRU-first (leaf pages before their parents) until the request fits
    or nothing evictable remains.  Lookup is per-request (single-row
    admissions; multi-row admits stay cold but still populate the
    index).  int8 banks index under their own namespace — codes are a
    lossy function of the same tokens, so fp16 and int8 entries never
    cross-match.

    ``shards=N`` / ``mesh=...`` (paged mode only) partition the page
    bank into N equal slices with one host-side free-list each
    (``ShardedPagePool``): a page id encodes (shard, local page) as
    ``(id // pages_per_shard, id % pages_per_shard)``, admission routes
    whole small requests to one shard (prefix hits to the shard holding
    their cached pages, cold admissions to the least-loaded shard) and
    spans big requests across shards.  ``shards`` alone is *logical*
    sharding — allocator routing plus per-shard telemetry on a single
    device.  ``mesh`` additionally lays the bank leaves out over the
    mesh's ``shard_axis`` (``NamedSharding`` on the page axis) so shard
    s's pages live on device s.  Allocation order is the only thing
    that changes and the gathered attention math is permutation-
    invariant in page ids, so sharded streams stay bitwise-identical to
    the single-device paged engine (tested under forced host device
    count).  ``local_read=True`` (needs ``mesh``) additionally
    shard_maps decode/verify so each shard's kernel instance reads ONLY
    its local bank slice and partial softmaxes merge with one
    pmax/psum; the merge changes the reduction order, so that path is
    allclose-, not bitwise-, equivalent.
    """

    def __init__(self, model: LM, batch_size: int, max_len: int,
                 temperature: float = 0.0, seed: int = 0,
                 eos_id: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 paged: bool = False, page_size: int = 256,
                 num_pages: Optional[int] = None,
                 admit_jump_limit: int = 4,
                 multi_step: int = 1,
                 quantize_kv: Optional[str] = None,
                 prefix_cache: bool = False,
                 bank: Optional[SharedBank] = None,
                 shards: Optional[int] = None,
                 mesh=None, shard_axis: Optional[str] = None,
                 local_read: bool = False,
                 telemetry: Optional[Telemetry] = None):
        self.model = model
        telemetry = telemetry if telemetry is not None else Telemetry()
        self.max_len = max_len
        self.temperature = temperature
        self.seed = seed
        self.eos_id = eos_id
        if multi_step < 1:
            raise ValueError(f"multi_step must be >= 1, got {multi_step}")
        self.multi_step = multi_step
        if quantize_kv not in (None, "int8"):
            raise ValueError(f"quantize_kv must be None or 'int8', got "
                             f"{quantize_kv!r}")
        if quantize_kv is not None and not paged:
            raise ValueError(
                "quantize_kv targets the shared page bank: it needs "
                "paged=True (the row cache stays full precision)")
        self.quantize_kv = quantize_kv
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got "
                                 f"{prefill_chunk}")
            if any(mix != "attn" for mix, _ in model.pattern):
                raise ValueError(
                    "chunked prefill needs an all-attention model "
                    "(recurrent state cannot carry across chunk "
                    "boundaries)")
            if model.cfg.sliding_window:
                raise ValueError(
                    "chunked prefill needs a full (non-ring) cache: a "
                    "pending row's parked decode writes would wrap onto "
                    "window entries the chunks just filled")
        self.prefill_chunk = prefill_chunk
        self.admit_jump_limit = admit_jump_limit
        self._jumps = 0              # consecutive short-prompt jump-aheads
        self._pending: deque[_PendingPrefill] = deque()

        # ---- sharded page bank: resolve the mesh/shard knobs up front
        # (the pool they configure is built in the paged branch below)
        if mesh is not None:
            if shard_axis is None:
                shard_axis = mesh.axis_names[0]
            if shard_axis not in mesh.axis_names:
                raise ValueError(f"shard_axis {shard_axis!r} is not a mesh "
                                 f"axis {tuple(mesh.axis_names)}")
            mesh_n = mesh.shape[shard_axis]
            if shards is None:
                shards = mesh_n
            elif shards != mesh_n:
                raise ValueError(
                    f"shards={shards} disagrees with mesh axis "
                    f"{shard_axis!r} of size {mesh_n}")
        if shards is not None and shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if (mesh is not None or (shards or 1) > 1) and not paged:
            raise ValueError(
                "sharding partitions the page bank: shards/mesh need "
                "paged=True (the row cache has per-slot affinity)")
        if local_read and mesh is None:
            raise ValueError(
                "local_read shard_maps the bank reads over mesh devices: "
                "it needs mesh=")
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.local_read = bool(local_read)
        self.num_shards = 1

        # ---- paged slot pool: per-slot page tables over one shared bank
        self.paged = paged
        if bank is not None and not paged:
            raise ValueError(
                "a shared bank IS a page pool: it needs paged=True")
        self._bank = bank
        if paged:
            model._require_paged_support()   # all-attention, non-ring
            page_size = min(page_size, max_len)
            if max_len % page_size:
                raise ValueError(
                    f"page_size {page_size} must divide max_len "
                    f"{max_len}: a row's virtual space is a whole number "
                    "of pages (and the gathered view must equal the row "
                    "cache elementwise for the identity guarantees)")
            self.page_size = page_size
            self.pages_per_row = max_len // page_size
            if bank is not None:
                # the bank's creator sized AND sharded the pool; this
                # engine just allocates from it alongside its siblings
                bank_shards = getattr(bank.pool, "num_shards", 1)
                if shards is not None and shards != bank_shards:
                    raise ValueError(
                        f"shards={shards} but the shared bank's pool has "
                        f"{bank_shards} shard(s) — the bank's creator "
                        "fixes the sharding")
                self.num_shards = bank_shards
                if bank.pool.total_pages - bank_shards < self.pages_per_row:
                    raise ValueError(
                        f"shared bank of {bank.pool.total_pages} pages "
                        f"cannot hold one worst-case row "
                        f"({self.pages_per_row} pages) plus the reserved "
                        "park page(s)")
                self.num_pages = bank.pool.total_pages
                self._pages = bank.pool
            else:
                self.num_shards = shards or 1
                if num_pages is None:
                    # capacity parity with the row layout: every slot can
                    # always hold a worst-case row, split evenly across
                    # shards (+1 reserved local park page per shard)
                    need = batch_size * self.pages_per_row
                    num_pages = self.num_shards * (
                        -(-need // self.num_shards) + 1)
                if self.num_shards > 1 and num_pages % self.num_shards:
                    raise ValueError(
                        f"num_pages {num_pages} must divide by shards "
                        f"{self.num_shards}: the bank splits into equal "
                        "per-shard slices")
                if num_pages - self.num_shards < self.pages_per_row:
                    raise ValueError(
                        f"num_pages {num_pages} cannot hold one worst-case "
                        f"row ({self.pages_per_row} pages) plus the "
                        "reserved park page(s)")
                self.num_pages = num_pages
                self._pages = (
                    ShardedPagePool(num_pages, self.num_shards,
                                    telemetry=telemetry)
                    if self.num_shards > 1
                    else PagePool(num_pages, telemetry=telemetry))
        else:
            self.page_size = None
            self.pages_per_row = 0
            self.num_pages = 0
            self._pages = None
        if prefix_cache and not paged:
            raise ValueError(
                "prefix_cache shares pages of the pooled bank: it needs "
                "paged=True (the row cache has nothing to share)")
        self.prefix_cache = prefix_cache
        # int8 codes are a lossy function of the same source tokens:
        # namespacing keeps fp16/int8 entries from ever cross-matching
        if not prefix_cache:
            self._prefix = None
        elif bank is not None:
            # one index per bank: prefixes another engine of this bank
            # indexed are hits here — the pages are the same pool
            if bank.index is None:
                bank.index = PrefixIndex(self.page_size,
                                         namespace=quantize_kv or "fp16")
            self._prefix = bank.index
        else:
            self._prefix = PrefixIndex(self.page_size,
                                       namespace=quantize_kv or "fp16")

        B, T, V = batch_size, temperature, model.cfg.vocab_size
        # a bank split over the mesh: local_read shard_maps attention so
        # each shard reads only its local slice; otherwise every device
        # reads the whole bank (global gather)
        shard_arg = (layers.BankShard(mesh, shard_axis, self.local_read)
                     if mesh is not None else None)

        def _row_gumbel(rkeys, produced_at):
            """Per-slot gumbel fields for seeded rows: each slot's key is
            folded with the position of the token being produced — unique
            per draw, and independent of slot index, admission boundary,
            or pool traffic (that's what makes seeds reproducible)."""
            folded = jax.vmap(jax.random.fold_in)(rkeys, produced_at)
            return jax.vmap(
                lambda k: jax.random.gumbel(k, (V,), jnp.float32))(folded)

        def _sample_tok(last, key, pos, live, seeded, rkey):
            """The engine's ONE sampling rule, shared verbatim by the
            single-step and fused multi-step programs — that sharing is
            what makes ``multi_step=T`` bitwise-identical to T single
            steps.  Pool schedule: argmax(l/T + gumbel) IS categorical's
            own computation, bitwise (same key, same (B, V) field).  The
            per-row seeded field only exists while a LIVE seeded row
            does (lax.cond) — unseeded pools pay nothing extra."""
            if T > 0.0:
                g = jax.random.gumbel(key, (B, V), jnp.float32)
                sl = seeded & live
                g = jax.lax.cond(
                    sl.any(),
                    lambda g: jnp.where(
                        sl[:, None], _row_gumbel(rkey, pos + 1), g),
                    lambda g: g, g)
                return jnp.argmax(last / T + g, axis=-1).astype(jnp.int32)
            return jnp.argmax(last, axis=-1).astype(jnp.int32)

        def _step(params, state: DecodeState, live):
            key = jax.random.fold_in(state.key, state.t)
            if paged:
                # non-live rows' per-step writes route to the park page
                # (their pages may already be recycled to a neighbor)
                logits, caches = model.decode_step_pages(
                    params, state.caches, state.tok, state.pos,
                    state.table, live=live, shard=shard_arg)
            else:
                logits, caches = model.decode_step(params, state.caches,
                                                   state.tok, state.pos)
            nxt = _sample_tok(logits[:, -1], key, state.pos, live,
                              state.seeded, state.rkey)
            pos = jnp.where(live, state.pos + 1, state.pos)
            pos = jnp.minimum(pos, max_len - 1)               # parked slots
            return nxt, state._replace(caches=caches, tok=nxt[:, None],
                                       pos=pos, key=key, t=state.t + 1)

        MS = multi_step
        eos = eos_id

        def _mstep(params, state: DecodeState, live, rem, budget):
            """Up to ``multi_step`` decode steps in ONE device program
            (``LM.decode_multi_step[_pages]``): the host tick amortizes
            over every committed step.  ``rem`` ((B,) int32) is each live
            row's remaining token budget and ``budget`` its position cap
            (page allocation / cache end); together with EOS they form
            the on-device occupancy bitmap — the loop exits the moment
            any live slot would change occupancy, so the host's view of
            the pool is never stale.  The (key, t) fold chain threads
            through the loop carry exactly as the single-step program
            advances it."""

            def sample_fn(last, pos, carry):
                key, t = carry
                k2 = jax.random.fold_in(key, t)
                nxt = _sample_tok(last, k2, pos, live, state.seeded,
                                  state.rkey)
                return nxt, (k2, t + 1)

            def stop_fn(nxt, posr, i):
                done = live & (rem <= i + 1)          # token budget spent
                if eos is not None:
                    done = done | (live & (nxt == eos))
                done = done | (live & (posr >= budget))   # pages exhausted
                return done.any()

            carry = (state.key, state.t)
            if paged:
                out, n, caches, tok, pos, carry = (
                    model.decode_multi_step_pages(
                        params, state.caches, state.tok, state.pos,
                        state.table, MS, sample_fn, stop_fn, carry,
                        live=live, pos_cap=max_len - 1, shard=shard_arg))
            else:
                out, n, caches, tok, pos, carry = model.decode_multi_step(
                    params, state.caches, state.tok, state.pos, MS,
                    sample_fn, stop_fn, carry, live=live,
                    pos_cap=max_len - 1)
            key, t = carry
            return out, n, state._replace(caches=caches, tok=tok, pos=pos,
                                          key=key, t=t)

        def _admit(params, state: DecodeState, tokens, slots, tables,
                   rkeys, seeded):
            """Prefill (b, S) prompts into cache rows `slots`; sample their
            first tokens at t=0 with the *current* (unfolded) key — the
            same draw ``generate`` makes from its prefill logits.  Row r
            of a (B, V) gumbel field reproduces ``categorical``'s row r
            exactly, so a single-row admission in a half-full batch
            samples the same token it would in a full batched prefill.
            Past t=0 the admission key is salted: ``state.key`` is the key
            step t-1 DREW from, and a slot retired by that step and
            recycled here must not hand the newcomer the old occupant's
            last gumbel row (the salt lives above 2^30, disjoint from
            step folds).  Seeded rows draw from their own key instead
            (folded with S: the first token is produced at position S).

            ``tables`` is the admitted rows' (b, P) page tables in paged
            mode ((b, 0) placeholder otherwise): the prefilled rows
            scatter into the rows' own pages instead of a slot row, and
            the draw logic above is UNTOUCHED — sampling never sees the
            cache layout, which is what makes paged and row streams
            token-identical."""
            S = tokens.shape[1]
            logits, rows = model.prefill(params, tokens, max_len,
                                         shard=shard_arg)
            last = logits[:, -1]                               # (b, V) f32
            if T > 0.0:
                salted = jax.random.fold_in(state.key,
                                            (1 << 30) ^ state.t)
                akey = jnp.where(state.t == 0, state.key, salted)
                g = jax.random.gumbel(akey, (B, V), jnp.float32)[slots]
                g = jax.lax.cond(
                    seeded.any(),
                    lambda g: jnp.where(
                        seeded[:, None],
                        _row_gumbel(rkeys, jnp.full(slots.shape, S,
                                                    jnp.int32)), g),
                    lambda g: g, g)
                first = jnp.argmax(last / T + g, axis=-1)
            else:
                first = jnp.argmax(last, axis=-1)
            first = first.astype(jnp.int32)
            if paged:
                caches = model.insert_cache_pages(state.caches, rows,
                                                  tables)
            else:
                caches = model.insert_cache_rows(state.caches, rows, slots)
            tok = state.tok.at[slots].set(first[:, None])
            pos = state.pos.at[slots].set(jnp.int32(S))
            return first, state._replace(
                caches=caches, tok=tok, pos=pos,
                table=state.table.at[slots].set(tables),
                rkey=state.rkey.at[slots].set(rkeys),
                seeded=state.seeded.at[slots].set(seeded))

        C = prefill_chunk

        def _chunk(params, state: DecodeState, tokens, pos, slots, tables):
            """One streaming (non-final) prefill chunk: write the (b, C)
            block's k/v into cache rows `slots` at per-row offsets `pos`.
            No logits, no sampling — ONE compiled program serves every
            non-final chunk of every prompt length.  Paged mode writes
            through the rows' page tables instead: exactly the chunk's
            (pos, pos+C) positions move, O(C) per chunk instead of the
            row path's O(max_len) gather/scatter."""
            if paged:
                _, caches = model.prefill_chunk_pages(
                    params, state.caches, tokens, pos, tables,
                    need_logits=False, shard=shard_arg)
            else:
                _, caches = model.prefill_chunk(params, state.caches,
                                                tokens, pos, slots,
                                                need_logits=False)
            return state._replace(caches=caches)

        def _chunk_final(params, state: DecodeState, tokens, pos, slots,
                         tables, nvalid, rkeys, seeded):
            """Final prefill chunk: the block is padded to C (`nvalid`
            real tokens per row; the write mask keeps pad k/v out of the
            cache) and the last real token's logits sample the first
            token under the SAME admission gumbel rules as one-shot
            ``_admit`` — shared (B, V) field indexed by slot for pool
            rows, per-row key folded with the prompt length for seeded
            rows — so chunked and one-shot admission are token-identical
            for greedy and seeded-temperature streams.  The chunk width
            is read off ``tokens`` (not the closure) so the same program
            also serves one-shot prefix-hit admission, which runs the
            prompt's un-cached suffix — whatever its width — as one
            final chunk."""
            W = tokens.shape[1]
            wmask = jnp.arange(W, dtype=jnp.int32)[None, :] < nvalid[:, None]
            if paged:
                logits, caches = model.prefill_chunk_pages(
                    params, state.caches, tokens, pos, tables, wmask=wmask,
                    shard=shard_arg)
            else:
                logits, caches = model.prefill_chunk(params, state.caches,
                                                     tokens, pos, slots,
                                                     wmask=wmask)
            last = jnp.take_along_axis(
                logits, (nvalid - 1)[:, None, None], axis=1)[:, 0]  # (b, V)
            plen = pos + nvalid                    # (b,) prompt length S
            if T > 0.0:
                salted = jax.random.fold_in(state.key,
                                            (1 << 30) ^ state.t)
                akey = jnp.where(state.t == 0, state.key, salted)
                g = jax.random.gumbel(akey, (B, V), jnp.float32)[slots]
                g = jax.lax.cond(
                    seeded.any(),
                    lambda g: jnp.where(seeded[:, None],
                                        _row_gumbel(rkeys, plen), g),
                    lambda g: g, g)
                first = jnp.argmax(last / T + g, axis=-1)
            else:
                first = jnp.argmax(last, axis=-1)
            first = first.astype(jnp.int32)
            return first, state._replace(
                caches=caches, tok=state.tok.at[slots].set(first[:, None]),
                pos=state.pos.at[slots].set(plen),
                rkey=state.rkey.at[slots].set(rkeys),
                seeded=state.seeded.at[slots].set(seeded))

        def _copy(params, state: DecodeState, src, dst):
            """Copy-on-write: duplicate pool pages src -> dst across all
            banks BEFORE the diverging row's first write.  ``params`` is
            unused but keeps the runner's uniform ``fn(params, *args)``
            calling convention."""
            del params
            return state._replace(
                caches=model.copy_cache_pages(state.caches, src, dst))

        self._step_fn = jax.jit(_step, donate_argnums=(1,))
        self._mstep_fn = jax.jit(_mstep, donate_argnums=(1,))
        self._admit_fn = jax.jit(_admit, donate_argnums=(1,))
        self._chunk_fn = jax.jit(_chunk, donate_argnums=(1,))
        self._chunk_final_fn = jax.jit(_chunk_final, donate_argnums=(1,))
        self._copy_fn = jax.jit(_copy, donate_argnums=(1,))

        # Execution hook: when set, every device program runs as
        # ``runner(fn, params, *args)`` — the continuous scheduler points
        # this at ``ContextSwitchEngine.run_step`` so steps execute
        # against the ACTIVE slot's buffers with hidden-load accounting.
        self.runner = None

        self.state: Optional[DecodeState] = None
        self._pool_init(B, telemetry=telemetry)
        if paged:
            # prefix-cache counters (stay 0 with the cache off): benches
            # and the scheduler snapshot surface them engine-lifetime
            self.stats.update(prefix_hits=0, prefix_pages_mapped=0,
                              cow_copies=0, cache_evictions=0)
        self.reset()

    # ------------------------------------------------------------- lifecycle
    def reset(self, seed: Optional[int] = None, keep_prefix: bool = False):
        """Empty pool + restarted key schedule.  Cache buffers are reused
        when they exist: a freed slot's stale row is dead weight that the
        next admission overwrites in full, so only the first reset pays
        the allocation (generate() resets per call — keep it cheap).

        ``keep_prefix=True`` carries the prefix cache across the reset:
        the index is snapshotted before the allocator clears, and — if
        the bank's buffers survived (no rebuild) — its pages are
        re-adopted from the fresh free-list afterwards, so the first
        post-reset admission of a cached prompt still hits.  A rebuilt
        (zeroed) bank drops the snapshot instead: the pages' bytes are
        gone and a restored index would serve zero k/v."""
        B = self.batch_size
        snap = None
        if keep_prefix and self._bank is None and self._prefix is not None:
            snap = self._prefix.snapshot()
        # a private page pool just resets; a shared bank keeps serving
        # the OTHER engines, so only this engine's own rows release
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
                self._pages.release(own)
        elif self._pages is not None:
            self._pages.reset()
        if self._bank is None and self._prefix is not None:
            self._prefix.clear()     # its pages just left the allocator
        caches = None
        if self.state is not None and not any(
                getattr(x, "is_deleted", lambda: False)()
                for x in jax.tree.leaves(self.state.caches)):
            caches = self.state.caches   # reuse, unless a failed step
        if self._bank is not None and self._bank.caches is not None:
            caches = self._bank.caches   # the bank copy is authoritative
        rebuilt = caches is None
        if rebuilt:                      # donated them out from under us
            caches = (self.model.init_page_pool(
                          self.num_pages, self.page_size,
                          quantized=self.quantize_kv is not None)
                      if self.paged else
                      self.model.init_cache(B, self.max_len))
            if self.paged and self.mesh is not None:
                # lay the bank over the mesh: the page axis of every
                # leaf splits across shard_axis so shard s physically
                # holds local pages [s*per, (s+1)*per)
                caches = self._place_bank(caches)
        if self._bank is not None:
            self._bank.caches = caches
        self.state = DecodeState(
            caches=caches,
            tok=jnp.zeros((B, 1), jnp.int32),
            pos=jnp.zeros((B,), jnp.int32),
            key=jax.random.PRNGKey(self.seed if seed is None else seed),
            t=jnp.zeros((), jnp.int32),
            rkey=jnp.zeros((B, 2), jnp.uint32),
            seeded=jnp.zeros((B,), bool),
            # every table entry must be a valid pool index; park (0) is
            # the safe default — empty slots read/write garbage space
            table=jnp.zeros((B, self.pages_per_row), jnp.int32))
        self._pool_reset()
        self._pending.clear()
        self._jumps = 0
        if snap is not None and not rebuilt:
            # the bank's buffers survived the reset: the snapshot's pages
            # still hold their token runs, so re-adopt them from the
            # fresh free-list (refcount 1 each, LRU recency preserved)
            self._prefix.restore(snap, self._pages.adopt)

    def _place_bank(self, caches):
        """``jax.device_put`` every page-bank leaf with its mesh layout
        (page axis split over ``shard_axis``, everything else
        replicated) — see ``LM.page_pool_shardings``."""
        shardings = self.model.page_pool_shardings(caches, self.mesh,
                                                   self.shard_axis)
        return jax.tree.map(jax.device_put, caches, shardings)

    def export_prefix_index(self) -> Optional[dict]:
        """Host-side snapshot of the prefix index.  The page bank keeps
        the k/v bytes; this captures which pool pages hold which token
        runs so a later engine over the SAME bank content can re-adopt
        them (``restore_prefix_index``).  ``None`` with the cache off."""
        return None if self._prefix is None else self._prefix.snapshot()

    def restore_prefix_index(self, snap: dict) -> list[int]:
        """Re-adopt a snapshot's cached pages into this engine's index:
        every page still on the free-list is claimed back at refcount 1
        with its LRU recency; entries whose page was reallocated in the
        meantime drop out along with their subtrees (their bytes are
        someone else's now).  Returns the page ids adopted."""
        if self._prefix is None:
            raise ValueError("prefix_cache is off: nothing to restore "
                             "into")
        return self._prefix.restore(snap, self._pages.adopt)

    def _call(self, fn, params, *args):
        if self.runner is None:
            return fn(params, *args)
        return self.runner(fn, params, *args)

    def _bank_pull(self):
        """Adopt the bank's current pages: another engine's jitted call
        may have donated the buffers this state still references."""
        if (self._bank is not None and self._bank.caches is not None
                and self.state is not None
                and self._bank.caches is not self.state.caches):
            self.state = self.state._replace(caches=self._bank.caches)

    def _bank_push(self):
        """Publish the (possibly donated-and-replaced) pages back to the
        bank for the next engine."""
        if self._bank is not None and self.state is not None:
            self._bank.caches = self.state.caches

    # -------------------------------------------------------------- queries
    def pending_slots(self) -> int:
        return sum(len(ps.gens) for ps in self._pending)

    def free_pages(self) -> int:
        return self._pages.free_pages() if self.paged else 0

    def pages_needed(self, prompt_len: int, max_new: int) -> int:
        """Pages one row needs for its whole lifetime: positions
        ``0 .. prompt_len + max_new - 2`` are written/read (the final
        sampled token is never fed back), so the last page is the one
        holding position ``prompt_len + max_new - 2``."""
        return max(1, -(-(prompt_len + max_new - 1) // self.page_size))

    def can_admit(self, tokens, max_new: int) -> bool:
        if not super().can_admit(tokens, max_new):
            return False                 # super set last_admit_block
        if not self.paged:
            return True
        tokens = np.asarray(tokens)
        b, S = (1, tokens.shape[0]) if tokens.ndim == 1 else tokens.shape
        npages = self.pages_needed(S, max_new)
        plan = None
        protect = []
        if self.prefix_cache and b == 1:
            plan = self._prefix_plan(tokens.reshape(1, S), max_new,
                                     peek=True)
            if plan is not None:
                retained, cow_src, _, _ = plan
                protect = retained + ([cow_src] if cow_src is not None
                                      else [])
        block = self._admit_block(b, npages, plan)
        if block is not None:
            # under pressure the cache gives memory back before admission
            # is rejected: refcount-1 cached pages (no live table maps
            # them) leave LRU-first until the request fits or nothing
            # evictable remains — never the pages this very request is
            # about to map.  A shard-local shortage ("shard_pages")
            # scopes eviction to the routed shard: freeing elsewhere
            # cannot help the shard the request must land on.
            need = plan[3] if plan is not None else b * npages
            if block == "shard_pages":
                shard = (self._route_prefix(plan) if plan is not None
                         else self._pages.route(npages))
                if shard is not None:
                    self._reclaim(need - self._pages.shard_free(shard),
                                  protect=protect, shard=shard)
            else:
                self._reclaim(need - self.free_pages(), protect=protect)
            block = self._admit_block(b, npages, plan)
        self.last_admit_block = block
        return block is None

    def _admit_block(self, b: int, npages: int, plan) -> Optional[str]:
        """Why the next admission would fail on pages: ``None`` (it
        fits), ``"pages"`` (pool-wide shortage) or ``"shard_pages"``
        (the routed shard is short even though the pool is not — sharded
        pools only)."""
        if plan is not None:
            return self._pages.blocked(plan[3],
                                       shard=self._route_prefix(plan))
        if b == 1:
            return self._pages.blocked(npages)
        return self._pages.blocked_rows(b, npages)

    def _route_prefix(self, plan) -> Optional[int]:
        """Locality routing for a prefix hit: the row's fresh pages land
        on the shard already holding the matched pages (the CoW boundary
        page when there is one — its copy destination must be
        co-resident with the source under local reads).  ``None`` (route
        free / spanning) when nothing anchors the hit or the pool is
        unsharded."""
        if self._pages.num_shards == 1:
            return None
        retained, cow_src, _, _ = plan
        anchor = cow_src if cow_src is not None else (
            retained[-1] if retained else None)
        return None if anchor is None else self._pages.shard_of(anchor)

    # -------------------------------------------------------- prefix cache
    def _reclaim(self, deficit: int, protect=(),
                 shard: Optional[int] = None) -> int:
        """Evict up to ``deficit`` cached prefix pages (LRU leaves first;
        only refcount-1 pages, i.e. held by nothing but the index) back
        into the free-list.  ``shard`` scopes eviction to pages owned by
        that shard — relieving a shard-local shortage without spending
        cache entries whose pages could not help.  -> pages reclaimed."""
        if self._prefix is None or deficit <= 0:
            return 0
        keep = set(protect)

        def _evictable(p):
            if p in keep or self._pages.refcount(p) != 1:
                return False
            return shard is None or self._pages.shard_of(p) == shard

        evicted = self._prefix.evict_lru(deficit, _evictable)
        if evicted:
            self._pages.release(evicted)
            self._pages.note_reclaimed(evicted)
            self.stats["cache_evictions"] += len(evicted)
            if self._trace.enabled:
                self._trace.instant(
                    "page-reclaim", self._track,
                    args={"evicted": len(evicted)})
        return len(evicted)

    def _prefix_plan(self, tokens, max_new: int, peek: bool = False):
        """Look up the longest indexed whole-page prefix of a single-row
        prompt.  -> ``(retained, cow_src, d, owned)`` or ``None`` (miss /
        cache off / multi-row): ``retained`` are the page ids mapped
        read-only, ``d`` the position prefill resumes at (the first
        divergent token, floored at S-1 — the last prompt token is always
        recomputed so there are logits to sample from), ``cow_src`` the
        shared boundary page to copy-on-write when ``d`` lands mid-page
        inside it, and ``owned`` the fresh pages still to allocate
        (including the CoW destination).  ``peek`` keeps the index's LRU
        recency untouched — ``can_admit`` is a pure capacity probe and
        the ``admit`` that may follow does the one real (bumping)
        lookup."""
        if self._prefix is None or tokens.shape[0] != 1:
            return None
        b, S = tokens.shape
        hit = self._prefix.lookup(tokens[0], peek=peek)
        if not hit:
            return None
        ps = self.page_size
        d = min(len(hit) * ps, S - 1)
        retained = hit[:d // ps]
        cow_src = hit[d // ps] if d < len(hit) * ps else None
        owned = self.pages_needed(S, max_new) - len(retained)
        return retained, cow_src, d, owned

    def _take_prefix_pages(self, plan, S: int, max_new: int):
        """Build a prefix-hit row's table: matched pages mapped read-only
        (one pool reference each), fresh pages for the rest — the first
        fresh page is the CoW destination when the plan has one.  The CoW
        *source* also takes a pool reference even though it never enters
        the table: the copy may run later (chunked admission defers it to
        the first chunk tick), and without the pin an interleaved
        admission's ``_reclaim`` could see it at refcount 1 once its
        original owner retired, evict it, and recycle the storage before
        the copy reads it.  The pin drops when the copy executes (or on
        the failure paths).  Returns ``(table (1, P), pages in table
        order, fresh)``."""
        retained, cow_src, d, owned = plan
        shard = self._route_prefix(plan)
        protect = retained + ([cow_src] if cow_src is not None else [])
        block = self._pages.blocked(owned, shard=shard)
        if block == "shard_pages" and shard is not None:
            self._reclaim(owned - self._pages.shard_free(shard),
                          protect=protect, shard=shard)
        elif block is not None:
            self._reclaim(owned - self._pages.free_pages(),
                          protect=protect)
        fresh = self._pages.take(owned, shard=shard)   # raises if short
        self._pages.acquire(retained)
        if cow_src is not None:
            self._pages.acquire([cow_src])       # pinned until the copy
        npages = len(retained) + owned
        table = np.full((1, self.pages_per_row), PagePool.PARK, np.int32)
        table[0, :len(retained)] = retained
        table[0, len(retained):npages] = fresh
        return table, retained + fresh, fresh

    def _drop_prefix_pages(self, plan, fresh):
        """Failed prefix-hit admission: fresh pages back to the FRONT in
        original order (the retry re-draws them), the mapped references
        dropped (the index still pins those pages, so they never free),
        and the CoW-source pin released."""
        retained, cow_src, _, _ = plan
        self._pages.restore(fresh)
        self._pages.release(retained)
        if cow_src is not None:
            self._pages.release([cow_src])

    def _index_prompt(self, tokens_row, pages):
        """Index one row's *fully written* prompt pages — called only
        once its prefill completed, so every indexed page holds its
        complete token run and is never written again (the owner's
        remaining writes are decode tokens at positions >= S).  The
        partially-filled last prompt page never enters.  The index takes
        one pool reference per page it newly adopted; runs already
        indexed keep their first writer's page."""
        if self._prefix is None or pages is None:
            return
        n = len(tokens_row) // self.page_size
        if n:
            self._pages.acquire(self._prefix.insert(tokens_row, pages[:n]))

    # ------------------------------------------------------ page allocation
    def _take_pages(self, b: int, S: int, max_new: int):
        """Allocate each admitted row its pages and build the (b, P)
        tables (unused tail entries point at the park page).  Returns
        (tables, flat page list for failure restore)."""
        npages = self.pages_needed(S, max_new)
        if self._pages.num_shards > 1:
            return self._take_pages_sharded(b, npages)
        if self.prefix_cache and b * npages > self._pages.free_pages():
            self._reclaim(b * npages - self._pages.free_pages())
        pages = self._pages.take(b * npages)
        tables = np.full((b, self.pages_per_row), PagePool.PARK, np.int32)
        for i in range(b):
            tables[i, :npages] = pages[i * npages:(i + 1) * npages]
        return tables, pages

    def _take_pages_sharded(self, b: int, npages: int):
        """Cold admission on a sharded pool: each row routes to the
        least-loaded shard at its turn (spanning when a row outgrows one
        shard), so a multi-row admit spreads across shards exactly as
        ``b`` sequential single-row admits would — the simulation
        ``ShardedPagePool.blocked_rows`` prices.  Rows allocate
        sequentially; a mid-batch shortage rolls the earlier rows' takes
        back so the caller sees one atomic failure."""
        if self.prefix_cache:
            blk = self._pages.blocked_rows(b, npages)
            if blk == "pages":
                self._reclaim(b * npages - self._pages.free_pages())
            elif blk == "shard_pages":
                # the pool has room but the routed shard does not; evict
                # up to one row's worth scoped to the shard the next row
                # would land on
                shard = self._pages.route(npages)
                if shard is not None:
                    self._reclaim(npages - self._pages.shard_free(shard),
                                  shard=shard)
        taken: list[list[int]] = []
        tables = np.full((b, self.pages_per_row), PagePool.PARK, np.int32)
        try:
            for i in range(b):
                rows = self._pages.take(npages)   # routed internally
                tables[i, :npages] = rows
                taken.append(rows)
        except BaseException:
            for rows in reversed(taken):
                self._pages.restore(rows)
            raise
        return tables, [p for rows in taken for p in rows]

    # ------------------------------------------------------------- admission
    def admit(self, params, tokens, max_new: int,
              metas: Optional[list] = None,
              seeds: Optional[list] = None,
              submitted_at: Optional[float] = None) -> list[Generation]:
        """Admit (b, S) prompt rows into b free slots.  Raises if the pool
        lacks room or the request would run past the cache; callers gate
        on ``free_slots()``.

        One-shot mode (``prefill_chunk is None``): prefill + first token
        happen here, in one whole-prompt program.  Chunked mode: the
        slots are reserved and the prompt queued; chunks stream in one
        per subsequent ``step``/``prefill_tick``, and the returned
        ``Generation``s stay token-less until their final chunk samples
        the first token.

        ``seeds``: optional per-row sampling seeds — ``None`` entries keep
        the pool's shared key schedule; an int (or raw (2,) uint32 key)
        pins that row to its own key column, making its draws reproducible
        independent of slot, admission boundary, and surrounding traffic.
        """
        self._bank_pull()
        try:
            return self._admit_dispatch(params, tokens, max_new, metas,
                                        seeds, submitted_at)
        finally:
            self._bank_push()

    def _admit_dispatch(self, params, tokens, max_new, metas, seeds,
                        submitted_at) -> list[Generation]:
        tokens, rkeys, seeded = self._admit_args(tokens, metas, seeds)
        b, S = tokens.shape
        if S + max_new > self.max_len:
            raise ValueError(f"prompt {S} + {max_new} new tokens exceeds "
                             f"max_len {self.max_len}")
        plan = (self._prefix_plan(tokens, max_new) if self.paged
                and self.prefix_cache else None)
        if self.prefill_chunk is not None:
            return self._admit_chunked(tokens, max_new, metas, rkeys,
                                       seeded, plan=plan,
                                       submitted_at=submitted_at)
        if plan is not None:
            return self._admit_prefix_hit(params, tokens, max_new, metas,
                                          rkeys, seeded, plan,
                                          submitted_at=submitted_at)
        slots = self._take_slots(b)
        tables = np.zeros((b, self.pages_per_row), np.int32)
        pages = []
        if self.paged:
            try:
                tables, pages = self._take_pages(b, S, max_new)
            except BaseException:
                self._restore_slots(slots)
                raise
        try:
            first, self.state = self._call(
                self._admit_fn, params, self.state,
                jnp.asarray(tokens, jnp.int32), jnp.asarray(slots, jnp.int32),
                jnp.asarray(tables), jnp.asarray(rkeys), jnp.asarray(seeded))
        except BaseException:
            self._restore_slots(slots)   # failed admit must not leak slots
            if pages:                    # nor pages (front, original order)
                self._pages.restore(pages)
            raise
        gens = self._register(slots, S, max_new, metas,
                              first=np.asarray(first),
                              submitted_at=submitted_at)
        if self.paged:
            npages = self.pages_needed(S, max_new)
            for i, g in enumerate(gens):
                g.pages = pages[i * npages:(i + 1) * npages]
                self._index_prompt(tokens[i], g.pages)
        if self._retire_done(gens):
            # a slot freed with no step in between (steps==1 / EOS at
            # admission): advance the key so a same-boundary re-admission
            # of that slot cannot reuse this draw field.
            self._salt_admit_key()
        return gens

    def _admit_prefix_hit(self, params, tokens, max_new: int, metas,
                          rkeys, seeded, plan,
                          submitted_at=None) -> list[Generation]:
        """One-shot admission on a prefix hit: the matched pages map
        read-only into the new row's table, the boundary page is
        copied-on-write when the divergence lands inside one (BEFORE any
        write — shared pages are never mutated), and only the prompt's
        un-cached suffix runs, as ONE final-chunk program.  The final
        chunk samples under the same admission gumbel rules as
        ``_admit`` and the shared pages hold bitwise the k/v this
        prompt's own prefill would have written (same tokens, same
        positions, same math), so the stream is bitwise a cold
        admission's."""
        b, S = tokens.shape
        retained, cow_src, d, owned = plan
        slots = self._take_slots(b)
        try:
            table, pages, fresh = self._take_prefix_pages(plan, S, max_new)
        except BaseException:
            self._restore_slots(slots)
            raise
        jslots = jnp.asarray(slots, jnp.int32)
        jtable = jnp.asarray(table)
        try:
            if cow_src is not None:
                self.state = self._call(
                    self._copy_fn, params, self.state,
                    jnp.asarray([cow_src], jnp.int32),
                    jnp.asarray([fresh[0]], jnp.int32))
            self.state = self.state._replace(
                table=self.state.table.at[jslots].set(jtable))
            first, self.state = self._call(
                self._chunk_final_fn, params, self.state,
                jnp.asarray(tokens[:, d:], jnp.int32),
                jnp.full((b,), d, jnp.int32), jslots, jtable,
                jnp.full((b,), S - d, jnp.int32),
                jnp.asarray(rkeys), jnp.asarray(seeded))
        except BaseException:
            self._restore_slots(slots)
            self._drop_prefix_pages(plan, fresh)
            raise
        if cow_src is not None:
            self._pages.release([cow_src])       # copy done: pin drops
        gens = self._register(slots, S, max_new, metas,
                              first=np.asarray(first),
                              submitted_at=submitted_at)
        gens[0].pages = pages
        self._index_prompt(tokens[0], pages)
        # counters only once the admission committed — a failed program
        # rolls pages and slots back and must leave the stats (and the
        # BENCH gates reading them) untouched
        self.stats["prefix_hits"] += 1
        self.stats["prefix_pages_mapped"] += len(retained)
        if self._trace.enabled:
            self._trace.instant(
                f"prefix-hit:{gens[0].req}", self._track,
                args={"mapped": len(retained), "cow": cow_src is not None})
        if cow_src is not None:
            self.stats["cow_copies"] += 1
        if self._retire_done(gens):
            self._salt_admit_key()
        return gens

    def _admit_chunked(self, tokens, max_new, metas, rkeys, seeded,
                       plan=None, submitted_at=None):
        """Reserve slots and queue the prompt for chunked prefill.  The
        reserved rows' parked position moves to the LAST cache slot:
        every decode step still writes a (garbage) k/v for every row, and
        a pending row's default parked slot could sit inside the prompt
        region a later chunk just filled.  Slot max_len-1 is the one safe
        parking spot because it is never READABLE: with the admit check
        ``prompt + max_new <= max_len``, a row's decode feeds stop at
        position S+max_new-2 <= max_len-2, and the attention mask only
        reads slots <= the query position — nothing ever overwrites the
        parked garbage, nothing ever attends to it.  (Relaxing the admit
        bound, adding speculative K-slack, or a ring cache would break
        this — hence the all-attention/non-ring constructor gate.)"""
        b, S = tokens.shape
        slots = self._take_slots(b)
        tables, pages, done, cow = None, [], 0, None
        if self.paged:
            try:
                if plan is not None:
                    # prefix hit: matched pages map read-only, chunking
                    # resumes at the first divergent token; the boundary
                    # page (if any) copies right before the first chunk
                    tables, pages, fresh = self._take_prefix_pages(
                        plan, S, max_new)
                    done = plan[2]
                    if plan[1] is not None:
                        cow = (plan[1], fresh[0])
                else:
                    tables, pages = self._take_pages(b, S, max_new)
            except BaseException:
                self._restore_slots(slots)
                raise
        jslots = jnp.asarray(slots, jnp.int32)
        st = self.state._replace(
            pos=self.state.pos.at[jslots].set(self.max_len - 1))
        if self.paged:
            # tables go live at reserve time: the decode steps that run
            # while the prompt streams in don't read them (non-live rows
            # park), the chunk programs write through an explicit arg,
            # and the final chunk's sampled row needs them next step
            st = st._replace(table=st.table.at[jslots].set(
                jnp.asarray(tables)))
        self.state = st
        gens = self._register(slots, S, max_new, metas,
                              submitted_at=submitted_at)
        if self.paged:
            npages = self.pages_needed(S, max_new)
            for i, g in enumerate(gens):
                g.pages = pages[i * npages:(i + 1) * npages]
        self._pending.append(_PendingPrefill(
            tokens=np.asarray(tokens, np.int32), gens=gens, rkeys=rkeys,
            seeded=seeded, done=done, tables=tables, cow=cow,
            hit=plan is not None,
            mapped=len(plan[0]) if plan is not None else 0,
            had_cow=cow is not None))
        return gens

    def _promote_pending(self):
        """Admission priority: a short prompt (whole prompt in ONE chunk)
        may jump ahead of a long prompt's queued chunk work — its single
        final chunk costs the long prompt one tick of streaming but gets
        the short request its first token immediately.  Bounded by a
        fairness counter: after ``admit_jump_limit`` consecutive jumps
        the head MUST run a chunk, so a stream of shorts can delay a
        long prompt by at most ``limit`` ticks per chunk, never starve
        it.  Rotates the chosen entry to the queue front."""
        C = self.prefill_chunk
        head = self._pending[0]
        head_remaining = head.tokens.shape[1] - head.done
        if (len(self._pending) > 1 and head_remaining > C
                and self._jumps < self.admit_jump_limit):
            for i in range(1, len(self._pending)):
                if self._pending[i].tokens.shape[1] <= C:
                    ps = self._pending[i]
                    del self._pending[i]
                    self._pending.appendleft(ps)
                    self._jumps += 1
                    return
        if self._pending[0] is head:
            self._jumps = 0              # the head made progress

    def _note_chunk(self, ps: _PendingPrefill, t0: float):
        """Chunk-program telemetry: the admit-to-first-chunk latency
        sample (admission until its first chunk starts).  The chunk's
        span is the ``eng.prefill_chunk`` region around it."""
        if not ps.started:
            ps.started = True
            self.telemetry.observe("admit_to_first_chunk_s",
                                   t0 - ps.gens[0].admitted_at)

    def prefill_tick(self, params) -> list[Generation]:
        """Run at most ONE chunk program — the admission budget.  A live
        decode row therefore waits for one (b, C) chunk per step, never a
        whole prompt.  Returns generations that finished at this boundary
        (a final chunk can instant-retire: steps==1, or EOS as the first
        token)."""
        if not self._pending:
            return []
        self._bank_pull()
        try:
            return self._prefill_tick_impl(params)
        finally:
            self._bank_push()

    def _prefill_tick_impl(self, params) -> list[Generation]:
        C = self.prefill_chunk
        if self.admit_jump_limit:
            self._promote_pending()
        ps = self._pending[0]
        b, S = ps.tokens.shape
        start = ps.done
        end = min(start + C, S)
        nvalid = end - start
        chunk = np.zeros((b, C), np.int32)
        chunk[:, :nvalid] = ps.tokens[:, start:end]
        slots = np.asarray([g.slot for g in ps.gens], np.int32)
        tables = (ps.tables if ps.tables is not None
                  else np.zeros((b, self.pages_per_row), np.int32))
        pos = np.full((b,), start, np.int32)
        t0 = self.telemetry.clock()
        try:
            with self._trace.region("eng.prefill_chunk", self._track,
                                    req=ps.gens[0].req, start=start,
                                    end=end, final=end == S):
                if ps.cow is not None:
                    # copy-on-write the shared boundary page BEFORE this
                    # request's first write lands in it
                    src, dst = ps.cow
                    self.state = self._call(
                        self._copy_fn, params, self.state,
                        jnp.asarray([src], jnp.int32),
                        jnp.asarray([dst], jnp.int32))
                    ps.cow = None
                    self._pages.release([src])   # copy done: the admission-
                    #                              time pin on the source
                    #                              drops (the index still
                    #                              holds its own reference)
                if end < S:
                    self.state = self._call(
                        self._chunk_fn, params, self.state,
                        jnp.asarray(chunk), jnp.asarray(pos),
                        jnp.asarray(slots), jnp.asarray(tables))
                    ps.done = end
                    self._note_chunk(ps, t0)
                    return []
                first, self.state = self._call(
                    self._chunk_final_fn, params, self.state,
                    jnp.asarray(chunk), jnp.asarray(pos), jnp.asarray(slots),
                    jnp.asarray(tables), jnp.full((b,), nvalid, jnp.int32),
                    jnp.asarray(ps.rkeys), jnp.asarray(ps.seeded))
        except BaseException:
            # a failed chunk abandons the whole request: release its rows
            # so the pool keeps serving (the caller fails the futures).
            # Pages restore in ONE call, in their original take order —
            # per-gen restore calls would reverse the group order and
            # break the free-list's documented FIFO determinism.
            self._pending.popleft()
            if ps.cow is not None:
                # the deferred copy never ran: drop the source pin so the
                # page goes back to being plain index-cached (evictable)
                self._pages.release([ps.cow[0]])
            pages = []
            for g in ps.gens:
                self.slots[g.slot] = None
                pages += g.pages or []
                g.pages = None
            if pages:
                self._pages.restore(pages)
            self._restore_slots([g.slot for g in ps.gens])
            raise
        self._pending.popleft()
        self._note_chunk(ps, t0)
        if ps.hit:
            # counters only once the prefix-hit admission committed (its
            # final chunk sampled): an abandoned pending rolled its pages
            # back and must not inflate the stats
            self.stats["prefix_hits"] += 1
            self.stats["prefix_pages_mapped"] += ps.mapped
            if self._trace.enabled:
                self._trace.instant(
                    f"prefix-hit:{ps.gens[0].req}",
                    self._track,
                    args={"mapped": ps.mapped, "cow": ps.had_cow})
            if ps.had_cow:
                self.stats["cow_copies"] += 1
        first = np.asarray(first)
        tok_now = self.telemetry.clock()
        for i, g in enumerate(ps.gens):
            g.tokens.append(int(first[i]))
            self._live[g.slot] = True
            self.stats["tokens_out"] += 1
            self._note_first_token(g, tok_now)
        if self.paged:
            # the prompt is now fully written: its whole pages become
            # indexable (BEFORE retirement, so an instant retire still
            # populates the cache — the index reference outlives the row)
            for i, g in enumerate(ps.gens):
                self._index_prompt(ps.tokens[i], g.pages)
        finished = self._retire_done(ps.gens)
        if finished:
            self._salt_admit_key()
        return finished

    # ----------------------------------------------------------- retirement
    def _retire_done(self, gens: list[Generation]) -> list[Generation]:
        """Retire finished rows AND release their pages (FIFO: to the
        back of the page free-list).  No device-side table reset is
        needed: the retired slot stops being ``live``, so its per-step
        writes route to the park page from the next step on, and its
        stale reads only feed a discarded output — freed pages can be
        recycled to a neighbor immediately without a disturb hazard."""
        finished = super()._retire_done(gens)
        if self.paged:
            for g in finished:
                if g.pages:
                    self._pages.release(g.pages)
                    g.pages = None
        return finished

    # ---------------------------------------------------------------- step
    def step(self, params) -> list[Generation]:
        """One engine tick: at most one prefill chunk (chunked admission),
        then one decode step for every live slot — or, with
        ``multi_step=T`` and no prompt mid-stream, up to T fused decode
        steps in one device program (the loop early-exits the moment any
        slot would change occupancy, so the returned retirements are
        exactly what T single ticks would have produced).  While chunked
        prefill work is pending the engine stays single-step: a fused
        loop would stall the streaming prompt for T tokens instead of
        one.  Returns the generations that finished (EOS or step limit)
        at this boundary; their slots are already back on the
        free-list."""
        finished = self.prefill_tick(params) if self._pending else []
        if not self._live.any():
            return finished
        self._bank_pull()
        try:
            return finished + self._step_live(params)
        finally:
            self._bank_push()

    def _step_live(self, params) -> list[Generation]:
        if self.multi_step > 1 and not self._pending:
            return self._step_multi(params)
        tr = self._trace
        with tr.region("eng.decode", self._track) as reg:
            with tr.region("eng.dispatch", self._track):
                t0 = self.telemetry.clock()
                nxt, self.state = self._call(self._step_fn, params,
                                             self.state,
                                             jnp.asarray(self._live))
            with tr.region("eng.sync", self._track):
                nxt = np.asarray(nxt)
            now = self.telemetry.clock()
            self.stats["host_ticks"] += 1
            self.stats["device_steps"] += 1
            stepped = []
            for s in range(self.batch_size):
                g = self.slots[s]
                if g is None or not self._live[s]:
                    continue              # empty, or reserved mid-prefill
                g.tokens.append(int(nxt[s]))
                stepped.append(g)
            self.stats["tokens_out"] += len(stepped)
            self._note_tick(t0, now, 1, len(stepped))
            if tr.enabled:
                reg.set(steps=1, rows=len(stepped))
            return self._retire_done(stepped)

    def _step_multi(self, params) -> list[Generation]:
        """The fused tick: ship every live row's remaining-token budget
        and position cap to the device, run up to ``multi_step`` decode
        steps, read back ONE (tokens, n_steps) pair.  Exactly one host
        sync per call regardless of how many steps committed."""
        B = self.batch_size
        tr = self._trace
        with tr.region("eng.decode", self._track) as reg:
            with tr.region("eng.dispatch", self._track):
                rem = np.zeros((B,), np.int32)
                budget = np.zeros((B,), np.int32)
                for s in range(B):
                    g = self.slots[s]
                    if g is None or not self._live[s]:
                        continue
                    rem[s] = g.remaining
                    budget[s] = (len(g.pages) * self.page_size
                                 if self.paged and g.pages else self.max_len)
                t0 = self.telemetry.clock()
                toks, n, self.state = self._call(
                    self._mstep_fn, params, self.state,
                    jnp.asarray(self._live), jnp.asarray(rem),
                    jnp.asarray(budget))
            with tr.region("eng.sync", self._track):
                toks = np.asarray(toks)
                n = int(n)
            now = self.telemetry.clock()
            self.stats["host_ticks"] += 1
            self.stats["device_steps"] += n
            stepped = []
            for s in range(B):
                g = self.slots[s]
                if g is None or not self._live[s]:
                    continue
                g.tokens.extend(int(t) for t in toks[s, :n])
                stepped.append(g)
            self.stats["tokens_out"] += n * len(stepped)
            self._note_tick(t0, now, n, len(stepped))
            if tr.enabled:
                reg.set(steps=n, rows=len(stepped))
            return self._retire_done(stepped)


# ---------------------------------------------------------------------------
# classic run-to-completion engine (wrappers over StepEngine)
# ---------------------------------------------------------------------------

class ServingEngine:
    def __init__(self, model: LM, params, max_len: int,
                 temperature: float = 0.0, seed: int = 0,
                 telemetry: Optional[Telemetry] = None):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.temperature = temperature
        self.seed = seed
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self.stats = ServeStats(self.telemetry.view())
        self._eng_seq = 0            # per-engine metric namespace counter
        # Per-batch-size engine cache, LRU-bounded: each entry pins a full
        # (layers, B, max_len) KV pool, so traffic with many distinct
        # batch shapes must not accumulate pools without limit — evicting
        # an entry frees its pool (a returning shape re-compiles, which
        # is what it paid before the step-engine refactor anyway).
        self.max_cached_pools = 4
        # keyed ``EngineKey``: row and paged pools are different engines
        # over different cache layouts, and every future knob is a named
        # field instead of a silently-aliasing positional slot
        self._step_engines: "OrderedDict[EngineKey, StepEngine]" = (
            OrderedDict())

        def _prefill(params, tokens, patch_embeds=None):
            return model.prefill(params, tokens, max_len,
                                 patch_embeds=patch_embeds)

        def _step(params, caches, tok, pos, key):
            logits, caches = model.decode_step(params, caches, tok, pos)
            nxt = _sample(logits[:, -1], key, temperature)
            return nxt[:, None], caches

        self._prefill = jax.jit(_prefill)
        self._step = jax.jit(_step, donate_argnums=(1,))

    # ------------------------------------------------------------------
    def _key(self, seed: Optional[int]):
        """Per-request sampling key: `seed` overrides the engine default
        (the switching server threads a fresh per-request seed through
        here so temperature>0 requests are independent draws)."""
        return jax.random.PRNGKey(self.seed if seed is None else seed)

    def step_engine(self, batch_size: int, paged: bool = False,
                    page_size: int = 256) -> StepEngine:
        """The continuous-batching engine behind ``generate`` /
        ``generate_paged`` (cached per (batch shape, page layout); jitted
        programs compile once per key; least recently used keys beyond
        ``max_cached_pools`` are dropped to free their KV pools)."""
        key = EngineKey(batch_size=batch_size,
                        page_size=page_size if paged else None)
        eng = self._step_engines.get(key)
        if eng is None:
            eng = StepEngine(self.model, batch_size, self.max_len,
                             temperature=self.temperature, seed=self.seed,
                             paged=paged, page_size=page_size,
                             telemetry=self.telemetry.scoped(
                                 f"eng.{self._eng_seq}."))
            self._eng_seq += 1
            self._step_engines[key] = eng
        self._step_engines.move_to_end(key)
        if len(self._step_engines) > self.max_cached_pools:
            # evict oldest IDLE shapes only: dropping an engine with live
            # rows would split state between the caller's handle and a
            # later recreation
            for b in [b for b, e in self._step_engines.items()
                      if e is not eng and not e.live_slots()]:
                if len(self._step_engines) <= self.max_cached_pools:
                    break
                del self._step_engines[b]
        return eng

    def generate(self, tokens, steps: int, patch_embeds=None,
                 seed: Optional[int] = None) -> np.ndarray:
        """tokens: (B, S) prompt; returns (B, steps) generated ids.

        Thin wrapper over ``StepEngine``: the whole batch is admitted at
        t=0 and stepped to completion — the degenerate (static-batch) case
        of continuous batching, with identical sampling draws."""
        if patch_embeds is not None:
            return self._generate_vision(tokens, steps, patch_embeds, seed)
        tokens = np.asarray(tokens)
        B, S = tokens.shape
        eng = self.step_engine(B)

        t0 = self.telemetry.clock()
        eng.reset(seed=self.seed if seed is None else seed)
        gens = eng.admit(self.params, tokens, max_new=steps)
        jax.block_until_ready(eng.state.tok)
        self.stats.prefill_s += self.telemetry.clock() - t0

        t0 = self.telemetry.clock()
        while eng.live_slots():
            eng.step(self.params)
        jax.block_until_ready(eng.state.tok)
        self.stats.decode_s += self.telemetry.clock() - t0
        self.stats.tokens += B * steps
        return np.stack([np.asarray(g.tokens, np.int32) for g in gens])

    def _generate_vision(self, tokens, steps: int, patch_embeds,
                         seed: Optional[int]) -> np.ndarray:
        """Vision-frontend path: patch embeds prefill with the prompt and
        shift every position by n_patch; decode runs the legacy loop."""
        B, S = tokens.shape
        t0 = self.telemetry.clock()
        logits, caches = self._prefill(self.params, tokens, patch_embeds)
        n_patch = patch_embeds.shape[1]
        key = self._key(seed)
        tok = _sample(logits[:, -1], key, self.temperature)[:, None]
        jax.block_until_ready(tok)
        self.stats.prefill_s += self.telemetry.clock() - t0

        out = [np.asarray(tok)]
        t0 = self.telemetry.clock()
        pos = S + n_patch
        for i in range(steps - 1):
            key = jax.random.fold_in(key, i)
            tok, caches = self._step(self.params, caches, tok,
                                     jnp.int32(pos), key)
            out.append(np.asarray(tok))
            pos += 1
        jax.block_until_ready(tok)
        self.stats.decode_s += self.telemetry.clock() - t0
        self.stats.tokens += B * steps
        return np.concatenate(out, axis=1)

    # ------------------------------------------------------------------
    def generate_paged(self, tokens, steps: int,
                       page: int = 256,
                       seed: Optional[int] = None) -> np.ndarray:
        """Paged-cache decode loop — a thin wrapper over
        ``StepEngine(paged=True)``, exactly as ``generate`` wraps the row
        engine: the whole batch is admitted at t=0 into per-slot page
        tables over one shared page pool and stepped to completion.
        Identical outputs to generate() — tested.  (The earlier
        BigKV/ActKV commit-cadence loop lives on in
        ``LM.decode_step_paged`` for the sharded/analysis paths; the
        serving tier now pools pages across requests instead of
        committing per-batch pages in lockstep.)

        Models the page pool cannot express (recurrent/hybrid mixers,
        sliding-window rings) fall back to the row engine: the output
        contract (== ``generate``) is unchanged, only the cache layout
        differs."""
        tokens = np.asarray(tokens)
        B, S = tokens.shape
        page = min(page, self.max_len)
        try:
            self.model._require_paged_support()
        except ValueError:
            return self.generate(tokens, steps, seed=seed)
        eng = self.step_engine(B, paged=True, page_size=page)

        t0 = self.telemetry.clock()
        eng.reset(seed=self.seed if seed is None else seed)
        gens = eng.admit(self.params, tokens, max_new=steps)
        jax.block_until_ready(eng.state.tok)
        self.stats.prefill_s += self.telemetry.clock() - t0

        t0 = self.telemetry.clock()
        while eng.live_slots():
            eng.step(self.params)
        jax.block_until_ready(eng.state.tok)
        self.stats.decode_s += self.telemetry.clock() - t0
        self.stats.tokens += B * steps
        return np.stack([np.asarray(g.tokens, np.int32) for g in gens])

    # ------------------------------------------------------------------
    def generate_fused(self, tokens, steps: int,
                       seed: Optional[int] = None) -> jax.Array:
        """Whole decode loop in one XLA program (benchmark path)."""
        model, T = self.model, self.temperature

        def run(params, tokens, key):
            B, S = tokens.shape
            logits, caches = model.prefill(params, tokens, self.max_len)
            tok = _sample(logits[:, -1], key, T)[:, None]

            def body(carry, i):
                tok, caches, key = carry
                key = jax.random.fold_in(key, i)
                logits, caches = model.decode_step(params, caches, tok, S + i)
                nxt = _sample(logits[:, -1], key, T)[:, None]
                return (nxt, caches, key), tok

            (_, _, _), toks = jax.lax.scan(
                body, (tok, caches, key), jnp.arange(steps))
            return toks[:, :, 0].T                       # (B, steps)

        return jax.jit(run)(self.params, tokens, self._key(seed))


def _sample(logits, key, temperature: float):
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)
