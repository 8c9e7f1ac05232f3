"""Paged slot pool: per-slot page tables over one shared KV page pool.

Covers the PagePool allocator contract (FIFO recycling, failed-admit
restore, leak freedom under randomized traffic), the token-identity
matrix against the row engine (greedy + seeded temperature, page sizes
{64, 256}, one-shot + chunked admission), page-granular chunk writes
(transferred-bytes check), the short-prompt admission priority with its
fairness bound, and the scheduler end to end with ``paged=True``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_arch, tokens_for
from repro.models.model import build_model
from repro.serve.engine import StepEngine
from repro.serve.pool import PagePool


@pytest.fixture(scope="module")
def f32_lm():
    """f32 end to end: the paged identity tests assert BITWISE equality
    of token streams between two cache layouts, which needs the gathered
    page view to reproduce the row math exactly (it does — same shapes,
    same masked reductions — but only in a dtype where the intermediate
    values are the same numbers)."""
    cfg = reduced_arch("tinyllama-1.1b", dtype="float32",
                       param_dtype="float32")
    m = build_model(cfg, cache_dtype=jnp.float32)
    return cfg, m, m.init(jax.random.key(0))


def _drain(eng, p):
    while eng.live_slots():
        eng.step(p)


# ---------------------------------------------------------------------------
# PagePool allocator contract
# ---------------------------------------------------------------------------

def test_page_pool_fifo_contract():
    pool = PagePool(8)                     # page 0 = park, 7 allocatable
    assert pool.allocatable == 7
    assert pool.free_pages() == 7
    a = pool.take(3)
    assert a == [1, 2, 3]                  # front of the free-list
    b = pool.take(2)
    assert b == [4, 5]
    pool.release(a)                        # retirement: to the BACK
    assert pool.take(2) == [6, 7]          # older frees go out first...
    assert pool.take(3) == [1, 2, 3]       # ...then the recycled pages
    with pytest.raises(RuntimeError):
        pool.take(3)                       # only b's 2 pages remain free
    pool.restore(b)                        # failed admit: FRONT, in order
    assert pool.take(2) == b
    assert pool.free_pages() == 0


def test_page_pool_guards():
    with pytest.raises(ValueError):
        PagePool(1)                        # park page alone is no pool
    pool = PagePool(4)
    pool.take(3)
    pool.reset()
    assert pool.free_pages() == 3


def test_paged_engine_guards(f32_lm):
    cfg, m, p = f32_lm
    hybrid = build_model(reduced_arch("jamba-v0.1-52b"))
    with pytest.raises(ValueError, match="all-attention"):
        StepEngine(hybrid, batch_size=2, max_len=64, paged=True)
    windowed = build_model(reduced_arch("tinyllama-1.1b",
                                        sliding_window=16))
    with pytest.raises(ValueError, match="non-ring"):
        StepEngine(windowed, batch_size=2, max_len=64, paged=True)
    with pytest.raises(ValueError, match="divide"):
        StepEngine(m, batch_size=2, max_len=96, paged=True, page_size=64)
    with pytest.raises(ValueError, match="worst-case"):
        StepEngine(m, batch_size=2, max_len=64, paged=True, page_size=16,
                   num_pages=3)            # one row needs 4 pages + park


# ---------------------------------------------------------------------------
# token-identity matrix: paged engine vs row engine
# ---------------------------------------------------------------------------

def _run_stream(eng, p, prompts, steps, seeds):
    """Admit request 0, step twice, admit request 1 (staggered admission:
    rows sit at different positions), drain.  Returns token lists."""
    gens = [eng.admit(p, prompts[0], max_new=steps, seeds=[seeds[0]])[0]]
    for _ in range(2):
        eng.step(p)
    gens.append(eng.admit(p, prompts[1], max_new=steps,
                          seeds=[seeds[1]])[0])
    _drain(eng, p)
    return [g.tokens for g in gens]


@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("page", [64, 256])
@pytest.mark.parametrize("chunk", [None, 8])
def test_paged_streams_bitwise_identical_to_row(f32_lm, temperature, page,
                                                chunk):
    """The full matrix: page sizes {64, 256} x {greedy, seeded
    temperature} x {one-shot, chunked} admission — every combination
    emits bitwise the row engine's token streams.  Sampling never sees
    the cache layout; the gathered page view reproduces the row
    attention math exactly (masked garbage contributes exact zeros)."""
    cfg, m, p = f32_lm
    max_len, steps = 256, 5
    prompts = [np.asarray(tokens_for(cfg, 1, 12, seed=3)),
               np.asarray(tokens_for(cfg, 1, 40, seed=4))]
    seeds = [7, 9] if temperature > 0 else [None, None]

    row = StepEngine(m, batch_size=2, max_len=max_len,
                     temperature=temperature)
    ref = _run_stream(row, p, prompts, steps, seeds)

    eng = StepEngine(m, batch_size=2, max_len=max_len,
                     temperature=temperature, paged=True, page_size=page,
                     prefill_chunk=chunk)
    got = _run_stream(eng, p, prompts, steps, seeds)
    assert got == ref
    assert eng.free_pages() == eng._pages.allocatable   # all returned
    assert eng.free_slots() == 2


def test_inserted_pages_match_row_prefill_leaf_for_leaf(f32_lm):
    """Admission writes the SAME cache values, page-scattered: gathering
    a row's pages back through its table equals the row engine's cache
    row leaf-for-leaf over the row's whole allocation (prompt + zero
    tail — whole pages are written)."""
    from repro.models.layers import _gather_pages
    cfg, m, p = f32_lm
    max_len, page, S, steps = 256, 64, 12, 5
    prompt = np.asarray(tokens_for(cfg, 1, S, seed=3))

    row = StepEngine(m, batch_size=2, max_len=max_len)
    gr = row.admit(p, prompt, max_new=steps)[0]
    eng = StepEngine(m, batch_size=2, max_len=max_len, paged=True,
                     page_size=page)
    gp = eng.admit(p, prompt, max_new=steps)[0]
    npages = eng.pages_needed(S, steps)
    assert gp.pages is not None and len(gp.pages) == npages

    table = np.asarray(eng.state.table)[gp.slot]
    assert list(table[:npages]) == gp.pages
    span = npages * page
    for key in eng.state.caches:
        paged, rowc = eng.state.caches[key], row.state.caches[key]
        for pa, ra in ((paged.k, rowc.k), (paged.v, rowc.v)):
            g = jax.vmap(_gather_pages, in_axes=(0, None))(
                pa, jnp.asarray(table)[None])      # (R, 1, Hkv, 256, hd)
            np.testing.assert_array_equal(
                np.asarray(g[:, 0, :, :span]),
                np.asarray(ra[:, gr.slot, :, :span]))


# ---------------------------------------------------------------------------
# leak / fragmentation under randomized traffic
# ---------------------------------------------------------------------------

def _random_traffic(eng, m, p, cfg, rounds, seed):
    """Randomized admit/step/fail/retire churn; returns the emitted
    streams (determinism probe).  Failed admissions (params=None) must
    restore slots AND pages."""
    rng = np.random.default_rng(seed)
    streams = []
    for r in range(rounds):
        action = rng.integers(0, 4)
        S = int(rng.integers(4, 30))
        steps = int(rng.integers(1, 10))
        toks = rng.integers(0, cfg.vocab_size, (1, S))
        if action == 0 and eng.can_admit(toks, steps):
            g = eng.admit(p, toks, max_new=steps)[0]
            streams.append(g.tokens)       # list reference: fills later
        elif action == 1:
            before = (list(eng._free), list(eng._pages._free))
            with pytest.raises(BaseException):
                eng.admit(None, toks, max_new=steps)
            assert (list(eng._free), list(eng._pages._free)) == before
        else:
            eng.step(p)
    _drain(eng, p)
    return streams


def test_failed_multirow_chunk_restores_pages_in_take_order(f32_lm):
    """A failed chunk abandons the whole multi-row request; its pages go
    back to the FRONT of the free-list in their original take order
    (one restore call, not one per row — the retry must draw exactly
    what the failed admission drew)."""
    cfg, m, p = f32_lm
    eng = StepEngine(m, batch_size=4, max_len=64, paged=True, page_size=16,
                     prefill_chunk=4)
    slot_order = list(eng._free)
    page_order = list(eng._pages._free)
    eng.admit(p, np.asarray(tokens_for(cfg, 2, 20, seed=3)), max_new=10)
    with pytest.raises(BaseException):
        eng.prefill_tick(None)             # params=None: chunk fails
    assert list(eng._free) == slot_order
    assert list(eng._pages._free) == page_order


def test_generate_paged_falls_back_for_unsupported_models():
    """Models the page pool cannot express (hybrid/recurrent mixers)
    keep working through generate_paged — row-engine fallback, same
    output contract as generate()."""
    from repro.serve.engine import ServingEngine
    cfg = reduced_arch("jamba-v0.1-52b")
    m = build_model(cfg)
    p = m.init(jax.random.key(0))
    eng = ServingEngine(m, p, max_len=48)
    prompt = np.asarray(tokens_for(cfg, 2, 8))
    np.testing.assert_array_equal(eng.generate_paged(prompt, steps=4),
                                  eng.generate(prompt, steps=4))


def test_page_pool_batched_release_under_multistep(f32_lm):
    """A fused multi-step tick can retire SEVERAL slots in one host call
    — one ``_retire_done`` batch, several page releases back to back.
    The batch must land on the BACK of the free-list row-by-row in slot
    order (exactly what that tick's single-step equivalent does), and
    the randomized churn invariants — ``free_pages == allocatable``,
    deterministic replay of streams and free-list order — hold under
    fused ticks too."""
    cfg, m, p = f32_lm
    # deterministic batch retire: 3 equal-budget rows finish on the SAME
    # fused tick
    eng = StepEngine(m, batch_size=4, max_len=64, paged=True,
                     page_size=16, num_pages=13, seed=5, multi_step=8)
    free0 = list(eng._pages._free)
    gens = [eng.admit(p, np.asarray(tokens_for(cfg, 1, 8, seed=s)),
                      max_new=4)[0] for s in (1, 2, 3)]
    owned = [g.pages[:] for g in gens]     # 1 page each (8+4-1 < 16)
    finished = eng.step(p)                 # the 3 remaining tokens ...
    assert sorted(g.rid for g in finished) == sorted(g.rid for g in gens)
    assert eng.stats["host_ticks"] == 1    # ... in ONE fused tick
    assert eng.stats["device_steps"] == 3
    assert eng.free_pages() == eng._pages.allocatable
    # FIFO after a batched release: survivors first, then the batch's
    # pages in slot order
    assert list(eng._pages._free) == \
        free0[3:] + owned[0] + owned[1] + owned[2]

    final = []
    for attempt in range(2):               # randomized churn, replayed
        e2 = StepEngine(m, batch_size=4, max_len=64, paged=True,
                        page_size=16, num_pages=10, seed=5, multi_step=4)
        streams = _random_traffic(e2, m, p, cfg, rounds=40, seed=123)
        assert e2.free_slots() == 4
        assert e2.free_pages() == e2._pages.allocatable == 9
        final.append((streams, list(e2._pages._free)))
    assert final[0] == final[1]


def test_page_pool_no_leak_no_fragmentation(f32_lm):
    """N rounds of randomized admit/retire/fail traffic end with every
    page back on the free-list (free_pages == allocatable) and every
    slot free — nothing leaks through failures, instant retires, or
    EOS-free drains.  The same traffic replayed is bit-identical
    (streams AND final free-list order): FIFO recycling makes the
    allocator deterministic."""
    cfg, m, p = f32_lm
    final = []
    for attempt in range(2):
        eng = StepEngine(m, batch_size=4, max_len=64, paged=True,
                         page_size=16, num_pages=10, seed=5)
        streams = _random_traffic(eng, m, p, cfg, rounds=40, seed=123)
        assert eng.free_slots() == 4
        assert eng.free_pages() == eng._pages.allocatable == 9
        final.append((streams, list(eng._pages._free)))
    assert final[0] == final[1]            # deterministic recycling


# ---------------------------------------------------------------------------
# page-granular chunk writes: O(C) moved bytes, not O(max_len)
# ---------------------------------------------------------------------------

def _scatter_update_bytes(jaxpr, scale=1):
    """Sum the bytes of every scatter / dynamic-update-slice UPDATE
    operand in a (closed) jaxpr, recursing into inner jaxprs and
    multiplying by scan trip counts — i.e. the bytes a program actually
    MOVES into its state buffers, which buffer-level cost analysis hides
    behind whole-buffer scatter accounting."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name.startswith("scatter"):
            upd = eqn.invars[2].aval       # (operand, indices, updates)
            total += scale * upd.size * upd.dtype.itemsize
        elif name == "dynamic_update_slice":
            upd = eqn.invars[1].aval
            total += scale * upd.size * upd.dtype.itemsize
        inner_scale = scale * eqn.params.get("length", 1) \
            if name == "scan" else scale
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(j, "eqns") or hasattr(j, "jaxpr"):
                    total += _scatter_update_bytes(j, inner_scale)
    return total


def _chunk_update_bytes(eng, p):
    C = eng.prefill_chunk
    b = 1
    args = (p, eng.state, jnp.zeros((b, C), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.zeros((b, eng.pages_per_row), jnp.int32))
    jaxpr = jax.make_jaxpr(lambda *a: eng._chunk_fn(*a))(*args)
    return _scatter_update_bytes(jaxpr)


def test_chunk_scatter_is_page_granular(f32_lm):
    """Transferred-bytes check for page-granular chunk writes: the
    row-layout chunk program re-scatters WHOLE (R, b, max_len) cache
    rows per chunk — O(max_len) moved bytes regardless of C — while the
    paged program scatters only the chunk's (pos, pos+C) positions into
    the row's pages: O(C), independent of max_len."""
    cfg, m, p = f32_lm
    C = 8
    got = {}
    for max_len in (256, 512):
        row = StepEngine(m, batch_size=2, max_len=max_len,
                         prefill_chunk=C)
        paged = StepEngine(m, batch_size=2, max_len=max_len, paged=True,
                           page_size=64, prefill_chunk=C)
        got[max_len] = (_chunk_update_bytes(row, p),
                        _chunk_update_bytes(paged, p))
        row_b, paged_b = got[max_len]
        assert paged_b * 4 < row_b, (max_len, paged_b, row_b)
    # O(max_len) vs O(C): doubling max_len ~doubles the row program's
    # moved bytes and leaves the paged program's unchanged
    assert got[512][0] > 1.8 * got[256][0]
    assert got[512][1] == got[256][1]


# ---------------------------------------------------------------------------
# disturb-free writes through the carried bank
# ---------------------------------------------------------------------------

def _random_bank(m, NP, page, quantized, seed=3):
    """A page bank full of random values, so that an untouched entry is
    told apart from a rewritten one."""
    bank = m.init_page_pool(NP, page, quantized=quantized)
    leaves, tree = jax.tree.flatten(bank)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    leaves = [jax.random.randint(k, x.shape, -127, 128).astype(x.dtype)
              if x.dtype == jnp.int8 else
              jax.random.uniform(k, x.shape, x.dtype, 0.5, 2.0)
              for k, x in zip(keys, leaves)]
    return jax.tree.unflatten(tree, leaves)


def _assert_only(old, new, written):
    """Every bank leaf changed exactly at the ``written`` (layer, page,
    head, slot) entries: bitwise the same everywhere else, rewritten
    there."""
    for o, n in zip(jax.tree.leaves(old), jax.tree.leaves(new)):
        o, n = np.asarray(o), np.asarray(n)
        if o.shape[3] == 1:                      # int8 scales: (.., 1, page)
            o, n = o[:, :, :, 0, :, None], n[:, :, :, 0, :, None]
        same = (o == n).all(axis=-1)             # (R, NP, Hkv, page)
        assert same[~written].all()
        assert not same[written].any()


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_carried_bank_writes_only_its_entries(f32_lm, program, quantized):
    """A decode step and a prefill chunk through the carried bank change
    only the (layer, page, head, slot) entries they write, in every
    layer; the park page changes only at parked writes (a non-live
    decode row, a chunk's pad tokens)."""
    cfg, m, p = f32_lm
    page, P = 8, 4
    NP = 3 * P + 1
    bank = _random_bank(m, NP, page, quantized)
    tables = np.arange(1, NP).reshape(3, P)[:, ::-1].copy()
    R = m.repeats
    written = np.zeros((R, NP, cfg.num_kv_heads, page), bool)
    if program == "decode":
        pos = np.array([5, 17, 30])
        live = np.array([True, False, True])
        _, new = m.decode_step_pages(
            p, bank, jnp.asarray([[3], [4], [5]], jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(tables, jnp.int32),
            live=jnp.asarray(live))
        for b in range(3):
            pid = tables[b, pos[b] // page] if live[b] else 0
            written[:, pid, :, pos[b] % page] = True
    else:
        C, pos, nvalid = 12, 6, 9
        _, new = m.prefill_chunk_pages(
            p, bank, tokens_for(cfg, batch=1, seq=C),
            jnp.asarray([pos], jnp.int32),
            jnp.asarray(tables[1:2], jnp.int32),
            wmask=jnp.arange(C)[None, :] < nvalid, need_logits=False)
        for i in range(C):
            pid = tables[1, (pos + i) // page] if i < nvalid else 0
            written[:, pid, :, (pos + i) % page] = True
    for key in bank:
        _assert_only(bank[key], new[key], written)


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_page_write_touches_one_layer(quantized):
    """The write of one block lands in its own layer of the stacked bank:
    every other layer's pages stay bitwise untouched."""
    from repro.models import layers
    cfg = reduced_arch("tinyllama-1.1b", num_layers=3)
    m = build_model(cfg, cache_dtype=jnp.float32)
    page, Hkv, hd = 8, cfg.num_kv_heads, cfg.head_dim
    bank = _random_bank(m, 9, page, quantized)["b0"]
    k, v = jax.random.normal(jax.random.key(1), (2, 2, 3, Hkv, hd))
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    positions = jnp.asarray([[9, 10, 11], [0, 1, 2]], jnp.int32)
    view = layers._page_write(layers.BankLayer(bank, jnp.int32(1)), k, v,
                              tables, positions)
    written = np.zeros((3, 9, Hkv, page), bool)
    written[1, 2, :, 1:4] = True                 # row 0: page 2, slots 1-3
    written[1, 5, :, 0:3] = True                 # row 1: page 5, slots 0-2
    _assert_only(bank, view.bank, written)


# ---------------------------------------------------------------------------
# admission priority: short prompts jump queued chunk work, fairly
# ---------------------------------------------------------------------------

def test_short_prompt_jumps_long_chunk_stream(f32_lm):
    """With a long prompt mid-stream, a later-admitted single-chunk
    prompt is prefilled first: its first token arrives while the long
    prompt is still streaming, and both streams stay correct (greedy:
    identical to their solo runs)."""
    cfg, m, p = f32_lm
    C = 4
    long_p = np.asarray(tokens_for(cfg, 1, 30, seed=5))
    short_p = np.asarray(tokens_for(cfg, 1, 3, seed=6))

    def solo(prompt, steps):
        e = StepEngine(m, batch_size=2, max_len=64)
        g = e.admit(p, prompt, max_new=steps)[0]
        _drain(e, p)
        return g.tokens

    ref_long, ref_short = solo(long_p, 5), solo(short_p, 5)
    eng = StepEngine(m, batch_size=2, max_len=64, prefill_chunk=C)
    gl = eng.admit(p, long_p, max_new=5)[0]
    eng.prefill_tick(p)                    # long starts streaming
    gs = eng.admit(p, short_p, max_new=5)[0]
    eng.prefill_tick(p)                    # priority: short's final chunk
    assert len(gs.tokens) == 1             # short sampled its first token
    assert len(gl.tokens) == 0             # long still mid-prefill
    _drain(eng, p)
    assert gl.tokens == ref_long and gs.tokens == ref_short


def test_admission_priority_fairness_bound(f32_lm):
    """A stream of shorts cannot starve the long prompt: after
    ``admit_jump_limit`` consecutive jumps the long head MUST run a
    chunk.  Feed a fresh short every tick and assert the long's
    streaming still progresses at >= 1/(limit+1) chunks per tick."""
    cfg, m, p = f32_lm
    C, limit = 4, 2
    eng = StepEngine(m, batch_size=8, max_len=64, prefill_chunk=C,
                     admit_jump_limit=limit)
    gl = eng.admit(p, np.asarray(tokens_for(cfg, 1, 24, seed=5)),
                   max_new=2)[0]           # 6 chunks of streaming
    ticks = 0
    while len(gl.tokens) == 0:             # until the long's final chunk
        if eng.free_slots():
            eng.admit(p, np.asarray(tokens_for(cfg, 1, 3, seed=ticks)),
                      max_new=1)           # short: retires instantly
        eng.prefill_tick(p)
        ticks += 1
        assert ticks <= 6 * (limit + 1) + 1, "long prompt starved"
    assert ticks > 6                       # some shorts did jump ahead

    strict = StepEngine(m, batch_size=8, max_len=64, prefill_chunk=C,
                        admit_jump_limit=0)
    gl = strict.admit(p, np.asarray(tokens_for(cfg, 1, 24, seed=5)),
                      max_new=2)[0]
    strict.admit(p, np.asarray(tokens_for(cfg, 1, 3, seed=7)), max_new=1)
    for _ in range(6):
        strict.prefill_tick(p)             # strict FIFO: long first
    assert len(gl.tokens) == 1


# ---------------------------------------------------------------------------
# density: the same memory admits more concurrent short requests
# ---------------------------------------------------------------------------

def test_paged_pool_outconcurrents_row_pool_at_equal_memory(f32_lm):
    """The tradeoff the refactor breaks: a row pool with B slots serves
    at most B requests no matter how short they are; a paged pool with
    the SAME token capacity (B * max_len) serves one request per
    ~pages_needed."""
    cfg, m, p = f32_lm
    B_row, max_len, page = 2, 64, 16
    toks = np.asarray(tokens_for(cfg, 1, 8, seed=1))

    row = StepEngine(m, batch_size=B_row, max_len=max_len)
    n_row = 0
    while row.can_admit(toks, 7):
        row.admit(p, toks, max_new=7)
        n_row += 1
    # equal memory: B_row * max_len tokens = 8 pages (+1 park)
    eng = StepEngine(m, batch_size=8, max_len=max_len, paged=True,
                     page_size=page, num_pages=B_row * max_len // page + 1)
    n_paged = 0
    while eng.can_admit(toks, 7):          # 8+7-1 = 14 -> 1 page each
        eng.admit(p, toks, max_new=7)
        n_paged += 1
    assert n_row == B_row
    assert n_paged >= 2 * n_row
    _drain(row, p)
    _drain(eng, p)
    assert eng.free_pages() == eng._pages.allocatable


# ---------------------------------------------------------------------------
# scheduler end to end
# ---------------------------------------------------------------------------

def test_continuous_scheduler_paged():
    """ContinuousScheduler(paged=True): mixed-context, mixed-length
    greedy traffic through paged pools produces the run-to-completion
    reference outputs, and every context's pages drain back."""
    from repro.launch.serve import build_server
    from repro.serve.scheduler import ContinuousScheduler

    names = ["supersub-super", "supersub-sub"]
    server, cfgs = build_server(names, 2, 64, load_delay_s=0.01,
                                arch_overrides={"dtype": "float32",
                                                "param_dtype": "float32"})
    rng = np.random.default_rng(0)
    reqs = [(names[r % 2],
             rng.integers(0, cfgs[names[r % 2]].vocab_size,
                          (2, [8, 40, 16][r % 3])))
            for r in range(6)]
    with ContinuousScheduler(server, batch_size=4, paged=True,
                             page_size=16) as sched:
        futs = [sched.submit(n, t, steps=4) for n, t in reqs]
        outs = [f.result(timeout=300) for f in futs]
    assert all(o.shape == (2, 4) for o in outs)
    for (name, toks), out in zip(reqs, outs):
        ref = server.serve_batch(name, toks, steps=4)
        np.testing.assert_array_equal(out, ref)
    for key, eng in server._step_engines.items():
        assert key.page_size == 16 and eng.paged
        assert eng.free_pages() == eng._pages.allocatable
    server.shutdown()
