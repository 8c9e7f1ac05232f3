"""Serving tier: generation loops and the context-switching server."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_arch, tokens_for
from repro.models.model import build_model
from repro.serve.engine import ServingEngine
from repro.serve.switching import ServedModel, SwitchableServer


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = reduced_arch("tinyllama-1.1b")
    m = build_model(cfg)
    p = m.init(jax.random.key(0))
    return cfg, m, p


def test_generate_shapes_and_determinism(tiny_lm):
    cfg, m, p = tiny_lm
    eng = ServingEngine(m, p, max_len=48, temperature=0.0)
    prompt = tokens_for(cfg, batch=2, seq=16)
    out1 = eng.generate(prompt, steps=8)
    out2 = eng.generate(prompt, steps=8)
    assert out1.shape == (2, 8)
    np.testing.assert_array_equal(out1, out2)       # greedy = deterministic
    assert eng.stats.tokens > 0


def test_generate_matches_fused(tiny_lm):
    cfg, m, p = tiny_lm
    eng = ServingEngine(m, p, max_len=48, temperature=0.0)
    prompt = tokens_for(cfg, batch=2, seq=16)
    host = eng.generate(prompt, steps=6)
    fused = np.asarray(eng.generate_fused(prompt, steps=6))
    np.testing.assert_array_equal(host, fused)


def test_switchable_server_round_robin():
    server = SwitchableServer(num_slots=2)
    cfgs = {}
    for i, name in enumerate(["supersub-super", "supersub-sub"]):
        cfg = reduced_arch(name)
        cfgs[name] = cfg
        m = build_model(cfg)
        p = m.init(jax.random.key(i))
        server.register(ServedModel(name=name, model=m,
                                    weights_fn=lambda p=p: p, max_len=40))
    outs = []
    for r in range(6):
        name = ["supersub-super", "supersub-sub"][r % 2]
        toks = np.asarray(tokens_for(cfgs[name], batch=2, seq=16, seed=r))
        outs.append(server.serve_batch(name, toks))
    assert len(outs) == 6
    stats = server.engine.stats
    assert stats["loads"] == 2                       # loaded once each
    assert stats["switches"] >= 6
    # O(1) switches: orders faster than loads
    assert (stats["switch_seconds"] / stats["switches"]) < \
        (stats["load_seconds"] / stats["loads"])
    server.shutdown()


def test_serve_stream_lookahead_equivalent():
    server = SwitchableServer(num_slots=2)
    name_cfg = {}
    for i, name in enumerate(["supersub-super", "supersub-sub"]):
        cfg = reduced_arch(name)
        name_cfg[name] = cfg
        m = build_model(cfg)
        p = m.init(jax.random.key(i))
        server.register(ServedModel(name=name, model=m,
                                    weights_fn=lambda p=p: p, max_len=40))
    reqs = [(n, np.asarray(tokens_for(name_cfg[n], 1, 16, seed=s)))
            for s, n in enumerate(["supersub-super", "supersub-sub",
                                   "supersub-super"])]
    with_la = server.serve_stream(reqs, lookahead=True)
    no_la = server.serve_stream(reqs, lookahead=False)
    for a, b in zip(with_la, no_la):
        np.testing.assert_array_equal(a, b)
    server.shutdown()


def test_run_schedule_live_conventional_slower():
    """Live engine: dynamic (overlapped) schedule beats conventional."""
    import time
    from repro.core.context import ContextDescriptor, ContextSwitchEngine
    from repro.core.scheduler import Run, run_schedule_live

    def desc(name, delay):
        def weights_fn():
            time.sleep(delay)
            return {"w": jnp.eye(512)}
        return ContextDescriptor(name=name,
                                 apply_fn=lambda p, x: jnp.tanh(x @ p["w"]),
                                 weights_fn=weights_fn)

    # execution long enough (repeat=40) for loads to hide behind it
    sched = [Run("a", 0.0, 40), Run("b", 0.0, 40),
             Run("a", 0.0, 40), Run("b", 0.0, 40)]
    inputs = {"a": (jnp.ones((2048, 512)),), "b": (jnp.ones((2048, 512)),)}
    # warm the backend so cold-start doesn't land in either branch's loads
    jnp.tanh(inputs["a"][0] @ jnp.eye(512)).block_until_ready()

    eng = ContextSwitchEngine(num_slots=2)
    eng.register(desc("a", 0.05))
    eng.register(desc("b", 0.05))
    dyn = run_schedule_live(eng, sched, inputs, dynamic=True)
    eng.shutdown()

    eng2 = ContextSwitchEngine(num_slots=2)
    eng2.register(desc("a", 0.05))
    eng2.register(desc("b", 0.05))
    conv = run_schedule_live(eng2, sched, inputs, dynamic=False)
    eng2.shutdown()
    # conventional pays a fresh 50 ms load on every net change (4 changes);
    # the dynamic engine pays at most the first two (cold) loads
    assert dyn["visible_stalls"] < conv["visible_stalls"]
    assert conv["visible_stalls"] > 0.15


def test_generate_paged_matches_dense():
    """Paged-cache serving loop == contiguous-cache loop, greedy.

    f32 end to end: in bf16 the two cache layouts reduce in different
    orders, and a random-weight model's near-flat logits let greedy
    argmax tie-break differently (the model-level paged test bounds the
    numeric gap at 5e-3)."""
    from repro.configs import override
    import jax.numpy as jnp
    cfg = override(reduced_arch("tinyllama-1.1b"), dtype="float32",
                   param_dtype="float32")
    m = build_model(cfg)
    m.cache_dtype = jnp.float32
    p = m.init(jax.random.key(0))
    eng = ServingEngine(m, p, max_len=64, temperature=0.0)
    prompt = tokens_for(cfg, batch=2, seq=16)
    dense = eng.generate(prompt, steps=20)
    paged = eng.generate_paged(prompt, steps=20, page=8)
    np.testing.assert_array_equal(dense, paged)


# ---------------------------------------------------------------------------
# launcher: published widths, host-side weights, bank placement, cache dir
# ---------------------------------------------------------------------------

def test_build_server_published_widths_loads_host_weights():
    """``reduced=False`` serves the arch's own widths, and the registered
    weights live on the host, so a load moves every byte host->device."""
    from repro.configs import get_arch
    from repro.launch.serve import build_server
    server, cfgs = build_server(["supersub-super"], slots=2, max_len=32,
                                reduced=False)
    got, want = cfgs["supersub-super"], get_arch("supersub-super")
    assert (got.d_model, got.num_layers) == (want.d_model, want.num_layers)
    host = server._served["supersub-super"].weights_fn()
    leaves = jax.tree.leaves(host)
    assert leaves and all(isinstance(x, np.ndarray) for x in leaves)
    server.preload("supersub-super", block=True)
    assert server.engine.stats["bytes_loaded"] == sum(x.nbytes
                                                      for x in leaves)
    server.shutdown()


@pytest.mark.parametrize("shards,mesh,want", [
    (None, None, "single"), (1, None, "single"), (4, None, "logical"),
    (4, object(), "mesh")])
def test_bank_placement_names_the_layout(shards, mesh, want):
    from repro.launch.serve import bank_placement
    assert bank_placement(shards, mesh) == want


def test_shard_mesh_needs_enough_devices():
    """``--shards N`` with fewer than N devices gets no mesh (the report
    then says ``logical``) instead of failing."""
    from repro.launch.serve import shard_mesh
    assert shard_mesh(None) is None
    assert shard_mesh(jax.device_count() + 1) is None


@pytest.mark.parametrize("mode", ["queue", "sync", "speculative"])
def test_shards_only_in_continuous_mode(mode):
    """Only the continuous step engine lays its bank out over a mesh;
    ``--shards`` elsewhere is refused instead of replicating weights for
    engines whose kernels run on one device."""
    from repro.launch.serve import main
    argv = ["--mode", mode, "--paged", "--shards", "2"]
    if mode == "speculative":
        argv += ["--draft", "supersub-sub"]
    with pytest.raises(SystemExit) as e:
        main(argv)
    assert e.value.code == 2


@pytest.mark.parametrize("env_dir", [None, "/some/cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """The environment's cache dir wins and is left to jax; otherwise a
    fixed path inside the checkout, set in code."""
    from repro.core import env
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    path, set_in_code = env.compile_cache_dir()
    if env_dir is None:
        from pathlib import Path
        assert set_in_code
        assert Path(path) == Path(__file__).resolve().parents[1] / ".jax_cache"
    else:
        assert (path, set_in_code) == (env_dir, False)
