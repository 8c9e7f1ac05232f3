"""Ahead-of-time compiles of the serving main path for a TPU v5e that is
described, not attached: the Pallas kernels and one whole paged decode
step at ``tinyllama-1.1b``'s published widths.

Interpret-mode tests cannot see what the Mosaic lowering refuses (block
shapes that break the (8, 128) tiling, VMEM overruns); these compiles
can, without a chip.  Each test asserts the compiled program really
contains the kernel (``tpu_custom_call``), i.e. nothing fell back to the
jnp reference.

The topology is described inside a module fixture — never at import
time — so only the test worker that runs this file loads the TPU
compiler library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch

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


def _bank(sh, quantized: bool):
    dt = jnp.int8 if quantized else jnp.bfloat16
    kv = [_sds(sh, (NP, HKV, PAGE, HD), dt) for _ in range(2)]
    scales = ([_sds(sh, (NP, HKV, 1, PAGE), jnp.float32) for _ in range(2)]
              if quantized else [None, None])
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


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_decode_compiles(one_chip, quantized):
    from repro.kernels.paged_attention.ops import paged_decode_attention
    (kp, vp), (ks, vs) = _bank(one_chip, quantized)
    q = _sds(one_chip, (B, H, HD), jnp.bfloat16)
    table = _sds(one_chip, (B, P), jnp.int32)
    pos = _sds(one_chip, (B,), jnp.int32)

    def f(q, kp, vp, table, pos, ks, vs):
        return paged_decode_attention(q, kp, vp, table, pos, k_scale=ks,
                                      v_scale=vs, interpret=False)
    assert "tpu_custom_call" in _hlo(f, q, kp, vp, table, pos, ks, vs)


@pytest.mark.parametrize("tree", [False, True], ids=["flat", "tree"])
def test_paged_verify_compiles(one_chip, tree):
    from repro.kernels.paged_attention.ops import paged_verify_attention
    K = 5
    (kp, vp), _ = _bank(one_chip, False)
    q = _sds(one_chip, (B, K, H, HD), jnp.bfloat16)
    blk = _sds(one_chip, (B, K, HKV, HD), jnp.bfloat16)
    table = _sds(one_chip, (B, P), jnp.int32)
    pos = _sds(one_chip, (B,), jnp.int32)
    anc = _sds(one_chip, (B, K), jnp.int32) if tree else None

    def f(q, kp, vp, bk, bv, table, pos, anc):
        return paged_verify_attention(q, kp, vp, bk, bv, table, pos,
                                      tree=anc, interpret=False)
    assert "tpu_custom_call" in _hlo(f, q, kp, vp, blk, blk, table, pos,
                                     anc)


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
