"""Ahead-of-time compiles of the serving main path for a TPU v5e that is
described, not attached: the Pallas kernels and one whole paged decode
step at ``tinyllama-1.1b``'s published widths, and the paged serving
programs at reduced widths, checked for writing the bank in place.

Interpret-mode tests cannot see what the Mosaic lowering refuses (block
shapes that break the (8, 128) tiling, VMEM overruns); these compiles
can, without a chip.  Each test asserts the compiled program really
contains the kernel (``tpu_custom_call``), i.e. nothing fell back to the
jnp reference.

The topology is described inside a module fixture — never at import
time — so only the test worker that runs this file loads the TPU
compiler library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch, override

CFG = get_arch("tinyllama-1.1b")
B, PAGE, P = 8, 256, 5                  # 8 rows of up to 1280 tokens
NP = B * P + 1                          # + the park page
H, HKV, HD = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def mesh4(topo, one_chip):
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(topo.devices).reshape(4), ("model",))


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _bank(sh, quantized: bool, layers: tuple = ()):
    """One pool, or with ``layers`` (``(R,)``) the stacked pools."""
    dt = jnp.int8 if quantized else jnp.bfloat16
    kv = [_sds(sh, layers + (NP, HKV, PAGE, HD), dt) for _ in range(2)]
    scales = ([_sds(sh, layers + (NP, HKV, 1, PAGE), jnp.float32)
               for _ in range(2)] if quantized else [None, None])
    return kv, scales


def _steer_to_tpu(monkeypatch):
    """``use_kernels`` and the ops' backend probe see this process's CPU:
    point them at the TPU branch."""
    import repro.kernels as kernels
    from repro.kernels.flash_attention import ops as flash_ops
    from repro.kernels.paged_attention import ops as paged_ops
    monkeypatch.setattr(kernels, "use_kernels", lambda: True)
    monkeypatch.setattr(kernels, "get_mode", lambda: "auto")
    for mod in (flash_ops, paged_ops):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)


def _layer(sh, stacked):
    """(layer index, leading bank dims): ``stacked`` hands the kernel the
    whole bank of 3 layers and an index, as the layer scan does."""
    return (_sds(sh, (), jnp.int32), (3,)) if stacked else (None, ())


@pytest.mark.parametrize(
    "quantized,stacked", [(False, False), (True, False), (False, True),
                          (True, True)],
    ids=["bf16", "int8", "bf16-stacked", "int8-stacked"])
def test_paged_decode_compiles(one_chip, quantized, stacked):
    from repro.kernels.paged_attention.ops import paged_decode_attention
    layer, lead = _layer(one_chip, stacked)
    (kp, vp), (ks, vs) = _bank(one_chip, quantized, lead)
    q = _sds(one_chip, (B, H, HD), jnp.bfloat16)
    table = _sds(one_chip, (B, P), jnp.int32)
    pos = _sds(one_chip, (B,), jnp.int32)

    def f(q, kp, vp, table, pos, ks, vs, layer):
        return paged_decode_attention(q, kp, vp, table, pos, k_scale=ks,
                                      v_scale=vs, layer=layer,
                                      interpret=False)
    assert "tpu_custom_call" in _hlo(f, q, kp, vp, table, pos, ks, vs,
                                     layer)


@pytest.mark.parametrize(
    "tree,stacked,quantized", [(False, False, False), (True, False, False),
                               (False, True, False), (True, True, True)],
    ids=["flat", "tree", "flat-stacked", "tree-stacked-int8"])
def test_paged_verify_compiles(one_chip, tree, stacked, quantized):
    from repro.kernels.paged_attention.ops import paged_verify_attention
    K = 5
    layer, lead = _layer(one_chip, stacked)
    (kp, vp), (ks, vs) = _bank(one_chip, quantized, lead)
    q = _sds(one_chip, (B, K, H, HD), jnp.bfloat16)
    blk = _sds(one_chip, (B, K, HKV, HD), jnp.bfloat16)
    table = _sds(one_chip, (B, P), jnp.int32)
    pos = _sds(one_chip, (B,), jnp.int32)
    anc = _sds(one_chip, (B, K), jnp.int32) if tree else None

    def f(q, kp, vp, bk, bv, table, pos, anc, ks, vs, layer):
        return paged_verify_attention(q, kp, vp, bk, bv, table, pos,
                                      tree=anc, k_scale=ks, v_scale=vs,
                                      layer=layer, interpret=False)
    assert "tpu_custom_call" in _hlo(f, q, kp, vp, blk, blk, table, pos,
                                     anc, ks, vs, layer)


def test_paged_decode_partial_compiles(one_chip):
    from repro.kernels.paged_attention.ops import paged_decode_partial
    L = NP // 4 + 1                     # one shard's local slice
    kp = _sds(one_chip, (L, HKV, PAGE, HD), jnp.bfloat16)
    q = _sds(one_chip, (B, H, HD), jnp.bfloat16)
    table = _sds(one_chip, (B, P), jnp.int32)
    pos = _sds(one_chip, (B,), jnp.int32)
    base = _sds(one_chip, (), jnp.int32)

    def f(q, kp, vp, table, pos, base):
        return paged_decode_partial(q, kp, vp, table, pos, base,
                                    interpret=False)
    assert "tpu_custom_call" in _hlo(f, q, kp, kp, table, pos, base)


def test_flash_prefill_compiles(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention
    S = 512
    q = _sds(one_chip, (2, H, S, HD), jnp.bfloat16)
    kv = _sds(one_chip, (2, HKV, S, HD), jnp.bfloat16)

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)
    assert "tpu_custom_call" in _hlo(f, q, kv, kv)


def test_full_width_decode_step_pages_compiles(one_chip, monkeypatch):
    """One whole ``LM.decode_step_pages`` of the published model: float32
    params, bf16 page bank."""
    from repro.models.model import build_model
    _steer_to_tpu(monkeypatch)
    m = build_model(CFG)
    put = lambda t: jax.tree.map(  # noqa: E731
        lambda s: _sds(one_chip, s.shape, s.dtype), t)
    params = put(m.abstract())
    caches = put(m.init_page_pool(NP, PAGE, abstract=True))
    tok = _sds(one_chip, (B, 1), jnp.int32)
    pos = _sds(one_chip, (B,), jnp.int32)
    table = _sds(one_chip, (B, P), jnp.int32)
    compiled = jax.jit(m.decode_step_pages).lower(
        params, caches, tok, pos, table).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # weights + bank + temporaries fit one 16 GB v5e
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16e9


def test_prefill_beside_mesh_bank_compiles(mesh4, monkeypatch):
    """Paged admission over a bank split across 4 chips: the prefill runs
    in a program over the mesh, where the flash kernel compiles only
    shard_mapped (a Mosaic kernel cannot be auto-partitioned)."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.models.layers import BankShard
    from repro.models.model import build_model
    _steer_to_tpu(monkeypatch)
    m = build_model(CFG)
    rep = NamedSharding(mesh4, PartitionSpec())
    params = jax.tree.map(lambda s: _sds(rep, s.shape, s.dtype),
                          m.abstract())
    toks = _sds(rep, (1, 512), jnp.int32)
    shard = BankShard(mesh4, "model", False)
    assert "tpu_custom_call" in _hlo(
        lambda p, t: m.prefill(p, t, P * PAGE, shard=shard), params, toks)


@pytest.mark.parametrize("local_read", [False, True],
                         ids=["gather", "local_read"])
def test_decode_step_over_mesh_bank_compiles(mesh4, monkeypatch,
                                             local_read):
    """The one sharded combination the launcher allows (``--mode
    continuous --paged --shards 4`` on 4 chips, and ``StepEngine(mesh=,
    local_read=)``): a full-width decode step with replicated weights
    and the page bank split over the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.models.layers import BankShard
    from repro.models.model import build_model
    _steer_to_tpu(monkeypatch)
    m = build_model(CFG)
    rep = NamedSharding(mesh4, PartitionSpec())
    params = jax.tree.map(lambda s: _sds(rep, s.shape, s.dtype),
                          m.abstract())
    np4 = 4 * (NP // 4 + 1)             # whole pages per shard
    pool = m.init_page_pool(np4, PAGE, abstract=True)
    caches = jax.tree.map(
        lambda s, sh: _sds(sh, s.shape, s.dtype), pool,
        m.page_pool_shardings(pool, mesh4, "model"))
    tok = _sds(rep, (B, 1), jnp.int32)
    pos = _sds(rep, (B,), jnp.int32)
    table = _sds(rep, (B, P), jnp.int32)
    shard = BankShard(mesh4, "model", local_read)
    assert "tpu_custom_call" in _hlo(
        lambda p, c, t, ps, tb: m.decode_step_pages(p, c, t, ps, tb,
                                                    shard=shard),
        params, caches, tok, pos, table)


# ---------------------------------------------------------------------------
# the paged serving programs write the donated bank where it lies
# ---------------------------------------------------------------------------

GUARD = override(get_arch("deepseek-7b"), num_layers=2, d_model=512,
                 num_heads=4, num_kv_heads=4, head_dim=128, d_ff=1024,
                 vocab_size=1024, dtype="bfloat16", param_dtype="bfloat16")
GB, GPAGE, GP, GCHUNK, GSTEPS = 16, 256, 8, 256, 4  # rows of 2048 tokens
# an HLO value: %name = dtype[dims]{layout...} opcode(
_VALUE = re.compile(r"%([\w.\-]+) = \w+\[([\d,]*)\]\{([\d,]*)[^}]*\} "
                    r"([\w\-]+)\(")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice", "transpose")


def _serving_programs(m):
    """The engine's paged programs: a streaming prefill chunk, the final
    chunk (pad mask, logits), one decode step and the fused decode."""
    def chunk(params, banks, tok, pos, tables):
        return m.prefill_chunk_pages(params, banks, tok, pos, tables,
                                     need_logits=False)[1]

    def final(params, banks, tok, pos, tables):
        wmask = jnp.arange(GCHUNK)[None, :] < GCHUNK - 7
        lg, banks = m.prefill_chunk_pages(params, banks, tok, pos, tables,
                                          wmask=wmask)
        return jnp.argmax(lg[:, -8], -1), banks

    def step(params, banks, tok, pos, tables, live):
        lg, banks = m.decode_step_pages(params, banks, tok, pos, tables,
                                        live=live)
        return jnp.argmax(lg[:, -1], -1), banks

    def mstep(params, banks, tok, pos, tables, live):
        def sample(last, pos, carry):
            return jnp.argmax(last, -1).astype(jnp.int32), carry
        out = m.decode_multi_step_pages(
            params, banks, tok, pos, tables, GSTEPS, sample,
            lambda nxt, posr, i: (nxt == 0).any(), jnp.zeros((), jnp.int32),
            live=live, pos_cap=GP * GPAGE - 1)
        return out[0], out[1], out[2]

    # int32 inputs by shape; a trailing ``bool`` marks the live mask
    return {"prefill_chunk": (chunk, ((1, GCHUNK), (1,), (1, GP))),
            "prefill_final": (final, ((1, GCHUNK), (1,), (1, GP))),
            "decode_step": (step, ((GB, 1), (GB,), (GB, GP), (GB, bool))),
            "decode_multi_step": (mstep, ((GB, 1), (GB,), (GB, GP),
                                          (GB, bool)))}


def _bank_moves(hlo: str, shapes: set) -> list:
    """Values of a bank leaf's shape, or one layer's, that move the bank:
    copies, slices and their fusions, and any such value laid out other
    than row-major (a relayout).  Async memory-space moves (copy-start /
    copy-done, which keep the layout) are the compiler's prefetches and
    do not count."""
    bad = []
    for name, dims, layout, op in _VALUE.findall(hlo):
        shape = tuple(int(d) for d in dims.split(",") if d)
        if shape not in shapes:
            continue
        moved = op in _MOVES or (op == "fusion"
                                 and any(w in name for w in _MOVES))
        row_major = ",".join(map(str, reversed(range(len(shape)))))
        if moved or layout != row_major:
            bad.append(f"{op} %{name} {shape}{{{layout}}}")
    return bad


@pytest.mark.parametrize("program", ["prefill_chunk", "prefill_final",
                                     "decode_step", "decode_multi_step"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_program_writes_bank_in_place(one_chip, monkeypatch,
                                            quantized, program):
    """Each paged serving program, compiled with the bank donated as the
    engine donates it, writes its k/v into the bank where it lies: no
    copy, slice or relayout of the stacked bank or of one layer's pool,
    and temporaries a small share of the bank.  The fused decode may
    keep a relayout of the attention projections (at most their bytes)."""
    from repro.models.model import build_model
    _steer_to_tpu(monkeypatch)
    m = build_model(GUARD, cache_dtype=jnp.bfloat16)
    put = lambda t: jax.tree.map(  # noqa: E731
        lambda s: _sds(one_chip, s.shape, s.dtype), t)
    params = put(m.abstract())
    banks = put(m.init_page_pool(GB * GP + 1, GPAGE, abstract=True,
                                 quantized=quantized))
    fn, extra = _serving_programs(m)[program]
    args = [params, banks] + [
        _sds(one_chip, e[:-1], bool) if e[-1] is bool
        else _sds(one_chip, e, jnp.int32) for e in extra]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    leaves = jax.tree.leaves(banks)
    shapes = {tuple(x.shape) for x in leaves} | {tuple(x.shape[1:])
                                                for x in leaves}
    assert _bank_moves(hlo, shapes) == []
    bank_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    attn = params["blocks"]["b0"]["attn"]
    relayout = (sum(attn[w].size * attn[w].dtype.itemsize
                    for w in ("wq", "wk", "wv"))
                if program == "decode_multi_step" else 0)
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(program, "temp", temp, "bank", bank_bytes, "relayout", relayout)
    assert temp <= 0.05 * bank_bytes + relayout
