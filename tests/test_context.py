"""The paper's contribution: ContextSwitchEngine slot semantics, overlap,
and the non-volatile context store."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.context import (
    ContextDescriptor, ContextStore, ContextSwitchEngine)


def _desc(name, scale, delay=0.0):
    def weights_fn():
        if delay:
            time.sleep(delay)
        return {"w": jnp.full((32, 32), scale, jnp.float32)}

    def apply_fn(params, x):
        return x @ params["w"]

    return ContextDescriptor(name=name, apply_fn=apply_fn,
                             weights_fn=weights_fn)


def test_switch_and_run():
    eng = ContextSwitchEngine(num_slots=2)
    eng.register(_desc("a", 1.0))
    eng.register(_desc("b", 2.0))
    eng.preload("a", block=True)
    eng.switch("a")
    x = jnp.ones((4, 32))
    ya = eng.run(x)
    eng.preload("b", block=True)
    eng.switch("b")
    yb = eng.run(x)
    np.testing.assert_allclose(np.asarray(yb), 2 * np.asarray(ya))
    eng.shutdown()


def test_switch_is_o1_vs_load():
    """The paper's headline: switching resident contexts is orders of
    magnitude cheaper than loading one."""
    eng = ContextSwitchEngine(num_slots=2)
    eng.register(_desc("a", 1.0, delay=0.05))
    eng.register(_desc("b", 2.0, delay=0.05))
    eng.preload("a", block=True)
    eng.preload("b", block=True)
    eng.switch("a")
    t_switch = min(eng.switch("b") or 1.0, eng.switch("a"))
    load_t = eng.stats["load_seconds"] / eng.stats["loads"]
    assert t_switch < load_t / 10, (t_switch, load_t)
    eng.shutdown()


def test_load_never_disturbs_active_execution():
    """The serial-enable-transistor invariant: run() output is unaffected
    by a concurrent load into the shadow slot."""
    eng = ContextSwitchEngine(num_slots=2)
    eng.register(_desc("a", 1.0))
    eng.register(_desc("b", 2.0, delay=0.02))
    eng.preload("a", block=True)
    eng.switch("a")
    x = jnp.ones((4, 32))
    want = np.asarray(eng.run(x))
    eng.preload("b")                      # loads while we keep running
    for _ in range(20):
        np.testing.assert_array_equal(np.asarray(eng.run(x)), want)
    eng.shutdown()


def test_active_slot_never_evicted():
    eng = ContextSwitchEngine(num_slots=2)
    for n, s in [("a", 1.0), ("b", 2.0), ("c", 3.0)]:
        eng.register(_desc(n, s))
    eng.preload("a", block=True)
    eng.switch("a")
    eng.preload("b", block=True)
    eng.preload("c", block=True)          # evicts b (READY), never a (ACTIVE)
    assert "a" in eng.resident()
    assert eng.active.name == "a"
    with pytest.raises(RuntimeError):
        eng.evict("a")
    eng.shutdown()


def test_switch_waits_for_loading_context():
    eng = ContextSwitchEngine(num_slots=2)
    eng.register(_desc("a", 1.0, delay=0.2))
    fut = eng.preload("a")
    dt = eng.switch("a", wait=True)       # visible stall = remaining load
    assert eng.active.name == "a"
    assert dt > 0.05                      # had to wait
    eng.shutdown()


def test_switch_unknown_context_raises():
    eng = ContextSwitchEngine(num_slots=2)
    eng.register(_desc("a", 1.0))
    with pytest.raises(KeyError):
        eng.switch("a")                   # never preloaded
    eng.shutdown()


def test_more_slots_time_multiplexed_mode():
    """num_slots > 2 == Trimberger'97 time-multiplexed FPGA: all resident."""
    eng = ContextSwitchEngine(num_slots=4)
    for n in "abcd":
        eng.register(_desc(n, 1.0))
        eng.preload(n, block=True)
    assert sorted(eng.resident()) == list("abcd")
    assert eng.stats["evictions"] == 0
    eng.shutdown()


def test_context_store_persistence(tmp_path):
    """FeFET non-volatility analogue: a context survives engine restart."""
    store = ContextStore(str(tmp_path))
    w = {"w": jnp.full((8, 8), 3.0)}
    store.save("ctx", w)
    eng = ContextSwitchEngine(num_slots=2, store=store)
    eng.register(ContextDescriptor(
        name="ctx", apply_fn=lambda p, x: x @ p["w"],
        weights_fn=store.weights_fn("ctx")))
    eng.preload("ctx", block=True)
    eng.switch("ctx")
    out = eng.run(jnp.ones((2, 8)))
    np.testing.assert_allclose(np.asarray(out), 24.0)
    eng.shutdown()


def test_overlap_accounting():
    eng = ContextSwitchEngine(num_slots=2)
    eng.register(_desc("a", 1.0))
    eng.register(_desc("b", 2.0, delay=0.05))
    eng.preload("a", block=True)
    eng.switch("a")
    x = jnp.ones((256, 32))
    eng.preload("b")
    for _ in range(10):
        eng.run(x)                        # execution overlaps the load
    eng.switch("b", wait=True)
    assert eng.stats["loads"] == 2
    assert eng.stats["switches"] >= 2
    eng.shutdown()


def test_partial_reconfiguration_delta_load():
    """Paper Fig 1(b) analogue: a specialist sharing the base's backbone
    loads only its head delta — wire bytes ~ delta, not full context."""
    backbone = {"backbone": jnp.ones((256, 256)), "head": jnp.ones((256, 8))}
    delta = {"head": jnp.full((256, 8), 2.0)}

    from repro.core.context import ContextDescriptor
    eng = ContextSwitchEngine(num_slots=3)
    eng.register(ContextDescriptor(
        name="base", apply_fn=lambda p, x: (x @ p["backbone"]) @ p["head"],
        weights_fn=lambda: backbone))
    eng.register(ContextDescriptor(
        name="spec", apply_fn=lambda p, x: (x @ p["backbone"]) @ p["head"],
        weights_fn=lambda: delta, base="base"))
    eng.preload("base", block=True)
    b0 = eng.stats["bytes_loaded"]
    eng.preload("spec", block=True)
    delta_bytes = eng.stats["bytes_loaded"] - b0
    assert delta_bytes == 256 * 8 * 4          # only the head crossed H2D
    eng.switch("spec")
    out = eng.run(jnp.ones((2, 256)))
    np.testing.assert_allclose(np.asarray(out), 256 * 256 * 2.0)
    # base context unchanged and still correct
    eng.switch("base")
    out_b = eng.run(jnp.ones((2, 256)))
    np.testing.assert_allclose(np.asarray(out_b), 256 * 256 * 1.0)
    eng.shutdown()


def test_delta_load_assembles_exactly_a_full_load():
    """Partial reconfiguration end state == full reconfiguration end
    state: the delta context's assembled slot must match, leaf for leaf,
    what a from-scratch full load of the same weights produces — while
    only the delta bytes cross the host->device link."""
    backbone = {"backbone": jnp.ones((64, 64)),
                "head": jnp.ones((64, 8)),
                "norm": {"w": jnp.full((64,), 0.5)}}
    delta = {"head": jnp.full((64, 8), 2.0),
             "norm": {"w": jnp.full((64,), 0.25)}}   # nested dicts merge
    full = {**backbone, **delta}

    eng = ContextSwitchEngine(num_slots=3)
    eng.register(ContextDescriptor(
        name="base", apply_fn=lambda p, x: x, weights_fn=lambda: backbone))
    eng.register(ContextDescriptor(
        name="spec", apply_fn=lambda p, x: x, weights_fn=lambda: delta,
        base="base"))
    eng.register(ContextDescriptor(
        name="spec-full", apply_fn=lambda p, x: x,
        weights_fn=lambda: full))
    eng.preload("base", block=True)
    b0 = eng.stats["bytes_loaded"]
    spec_slot = eng.preload("spec", block=True).result()
    delta_bytes = eng.stats["bytes_loaded"] - b0
    assert delta_bytes == sum(x.nbytes for x in jax.tree.leaves(delta))
    full_slot = eng.preload("spec-full", block=True).result()

    # identical structure and values; the untouched backbone tensor is the
    # base slot's device buffer (zero-copy on device)
    assert (jax.tree.structure(spec_slot.buffers)
            == jax.tree.structure(full_slot.buffers))
    for a, b in zip(jax.tree.leaves(spec_slot.buffers),
                    jax.tree.leaves(full_slot.buffers)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    base_slot = eng._find_slot("base")
    assert spec_slot.buffers["backbone"] is base_slot.buffers["backbone"]
    eng.shutdown()


def test_delta_load_requires_base_resident():
    from repro.core.context import ContextDescriptor
    eng = ContextSwitchEngine(num_slots=2)
    eng.register(ContextDescriptor(
        name="spec", apply_fn=lambda p, x: x,
        weights_fn=lambda: {"w": jnp.ones(2)}, base="missing"))
    fut = eng.preload("spec")
    with pytest.raises(Exception):
        fut.result(timeout=10)
    eng.shutdown()


def test_failed_prefetch_is_counted_not_swallowed():
    """A weights_fn that raises on the loader thread during a prefetch
    counts into ``ctx.prefetch_failures`` before its future resolves;
    demand loads that fail are the caller's error, not a prefetch
    failure."""
    def boom():
        raise MemoryError("device memory exhausted")

    eng = ContextSwitchEngine(num_slots=2)
    eng.register(_desc("a", 1.0))
    eng.register(ContextDescriptor(name="bad", apply_fn=lambda p, x: x,
                                   weights_fn=boom))
    eng.preload("a", block=True)
    eng.switch("a")
    futs = eng.prefetch(["bad"], limit=1)
    assert len(futs) == 1
    with pytest.raises(MemoryError):
        futs[0].result(timeout=10)
    assert eng.stats["prefetch_failures"] == 1
    assert eng.resident() == ["a"]            # the failed slot freed
    with pytest.raises(MemoryError):
        eng.preload("bad").result(timeout=10)
    assert eng.stats["prefetch_failures"] == 1
    eng.shutdown()


def test_scheduler_counts_prefetch_that_raises():
    """The schedulers' advisory prefetch keeps serving when the call
    itself raises, and counts it."""
    from repro.serve.scheduler import _advisory_prefetch
    eng = ContextSwitchEngine(num_slots=2)

    def raising_prefetch(names, limit=None):
        raise RuntimeError("policy refused")

    eng.prefetch = raising_prefetch
    _advisory_prefetch(eng, ["x"])
    assert eng.stats["prefetch_failures"] == 1
    eng.shutdown()


def test_prefetch_loads_count_shadow_loads_only():
    """``ctx.prefetch_loads`` counts completed loads a prefetch started;
    a demand preload is a load but not a prefetch load."""
    eng = ContextSwitchEngine(num_slots=2)
    eng.register(_desc("a", 1.0))
    eng.register(_desc("b", 2.0))
    eng.preload("a", block=True)
    eng.switch("a")
    assert (eng.stats["loads"], eng.stats["prefetch_loads"]) == (1, 0)
    futs = eng.prefetch(["b"], limit=1)
    assert len(futs) == 1
    futs[0].result(timeout=10)
    assert (eng.stats["loads"], eng.stats["prefetch_loads"]) == (2, 1)
    eng.shutdown()
