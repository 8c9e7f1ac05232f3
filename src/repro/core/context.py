"""Context-switching execution engine — the paper's contribution on TPU.

The paper's FPGA holds **two local copies** of every configuration primitive
(2T-2FeFET switches, dual LUT banks): the inactive copy is programmed while
the active one executes, and switching is a <1 ns select-signal flip.

Mapping here (see DESIGN.md §2):
  * a *context* = weight pytree + its jitted executables ("fabric programs")
  * a *slot*    = device-resident buffer set; ``num_slots=2`` is the paper's
    dual-configuration design (more slots = the time-multiplexed FPGA of
    Trimberger'97, supported but costing HBM exactly as the paper notes it
    costs area)
  * *preload*   = asynchronous host->device streaming into a non-active slot
    (the serial enable transistor == the slot state machine: an executing
    step can never read a LOADING slot)
  * *switch*    = O(1) pointer swap; no device data movement, no recompile

Executables are compiled at registration ("synthesis time"), never at switch
time.  A non-volatile context store (checkpoint dir) plays the role of the
FeFET's retention: contexts survive process restarts.
"""
from __future__ import annotations

import enum
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.policy import ReconfigPolicy
from repro.core.telemetry import Telemetry, safe_ratio


class ContextState(enum.Enum):
    EMPTY = "empty"
    LOADING = "loading"      # enable transistor OFF: invisible to execution
    READY = "ready"          # resident, selectable
    ACTIVE = "active"        # the select signal points here


@dataclass
class ContextDescriptor:
    """A registered configuration: how to compute and where weights come from.

    ``base`` enables *partial reconfiguration* (the paper's Fig 1(b)
    analogue at weight-tensor granularity): ``weights_fn`` then returns
    only the leaves that DIFFER from the base context; the loader streams
    just the delta and assembles the slot from the base's resident buffers
    + the delta.  Super-Sub cascades with a shared backbone load their
    specialists this way (head-only deltas)."""
    name: str
    apply_fn: Callable                    # (params, *inputs) -> outputs
    weights_fn: Callable[[], Any]         # -> host weight pytree (or delta)
    shardings: Any = None                 # optional NamedSharding pytree
    donate_params: bool = False
    base: Optional[str] = None            # delta-load on top of this context
    meta: dict = field(default_factory=dict)


@dataclass
class ContextSlot:
    idx: int
    state: ContextState = ContextState.EMPTY
    name: Optional[str] = None
    buffers: Any = None                   # device weight pytree
    bytes_resident: int = 0
    ready_event: threading.Event = field(default_factory=threading.Event)


def _nbytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree)
               if hasattr(x, "nbytes"))


def _overlay(base, delta):
    """Merge a (possibly partial) delta pytree over a base pytree: dict
    nodes merge key-wise, anything else in the delta replaces the base."""
    if isinstance(delta, dict) and isinstance(base, dict):
        out = dict(base)
        for k, v in delta.items():
            out[k] = _overlay(base[k], v) if k in base else v
        return out
    return delta


class ContextSwitchEngine:
    """Dual-slot (by default) context-switching executor.

    All slot-allocation / eviction / prefetch *decisions* are delegated to
    a ``ReconfigPolicy`` (``repro.core.policy``) — the same object the
    discrete-event simulator runs — so the engine only performs the
    physical work: device transfers, slot state flips, stats.
    """

    def __init__(self, num_slots: int = 2, mesh=None,
                 store: "ContextStore | None" = None,
                 policy: ReconfigPolicy | None = None,
                 telemetry: Telemetry | None = None):
        assert num_slots >= 2, "dynamic reconfiguration needs >= 2 slots"
        if policy is None:
            policy = ReconfigPolicy(num_slots=num_slots)
        assert policy.num_slots == num_slots, \
            (policy.num_slots, num_slots)
        self.policy = policy
        self.slots = [ContextSlot(i) for i in range(num_slots)]
        self.mesh = mesh
        self.store = store
        self._contexts: dict[str, ContextDescriptor] = {}
        self._executables: dict[tuple, Any] = {}
        self._pending: dict[str, Future] = {}
        self._deferred: dict[str, Future] = {}    # waiting for a free slot
        self._lock = threading.RLock()
        # one configuration port, like the FPGA's single config interface:
        self._loader = ThreadPoolExecutor(max_workers=1,
                                          thread_name_prefix="ctx-loader")
        # Shared measurement layer: stats live in the server-wide registry
        # under ``ctx.`` (dict call-sites unchanged — MetricView), spans go
        # to the shared tracer on one track per slot (``ctxslot<i>``), and
        # the clock is injected so simulated engines tick virtual time.
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._clock = self.telemetry.clock
        self._trace = self.telemetry.tracer
        self.stats = self.telemetry.view("ctx.")
        self.stats.update({
            "loads": 0, "load_seconds": 0.0, "bytes_loaded": 0,
            "switches": 0, "switch_seconds": 0.0, "evictions": 0,
            "hidden_load_seconds": 0.0, "context_changes": 0,
            "prefetch_loads": 0, "prefetch_failures": 0,
        })
        # overlap accounting (all guarded by self._lock).  One loader
        # thread => at most one load window open at a time.
        self._exec_busy_until = 0.0
        self._runs_in_flight = 0
        self._run_started_at: Optional[float] = None
        self._load_started_at: Optional[float] = None
        self._load_hidden_accum = 0.0     # exec∩load overlap, completed runs

    # ------------------------------------------------------------- registry
    def register(self, desc: ContextDescriptor,
                 example_inputs: tuple = (), compile_now: bool = True):
        """Register a context; AOT-compile its executable ("synthesis")."""
        with self._lock:
            self._contexts[desc.name] = desc
        if compile_now and example_inputs:
            self._get_executable(desc, example_inputs)

    def _sig(self, inputs: tuple) -> tuple:
        def one(x):
            if hasattr(x, "shape"):
                return (tuple(x.shape), str(getattr(x, "dtype", "?")))
            return type(x).__name__
        return tuple(one(x) for x in jax.tree.leaves(inputs))

    def _get_executable(self, desc: ContextDescriptor, inputs: tuple):
        key = (desc.name, self._sig(inputs))
        with self._lock:
            if key in self._executables:
                return self._executables[key]
        fn = jax.jit(desc.apply_fn,
                     donate_argnums=(0,) if desc.donate_params else ())
        with self._lock:
            self._executables[key] = fn
        return fn

    # --------------------------------------------------------------- slots
    def _find_slot(self, name: str) -> Optional[ContextSlot]:
        for s in self.slots:
            if s.name == name and s.state in (ContextState.READY,
                                              ContextState.ACTIVE):
                return s
        return None

    # ------------------------------------------------------------- loading
    def _active_name(self) -> Optional[str]:
        a = self.active
        return a.name if a is not None else None

    def _evict_name_unlocked(self, name: str, demote_ok: bool = False):
        """Free the slot holding `name` (policy already decided this)."""
        for s in self.slots:
            if s.name == name and s.state in (ContextState.READY,
                                              ContextState.ACTIVE):
                if s.state == ContextState.ACTIVE and not demote_ok:
                    raise RuntimeError(
                        f"policy evicted ACTIVE context {name!r} "
                        "without allow_evict_active")
                if self._trace.enabled:
                    self._trace.instant(f"evict:{name}", f"ctxslot{s.idx}",
                                        ts=self._clock())
                s.state = ContextState.EMPTY
                s.name, s.buffers, s.bytes_resident = None, None, 0
                self.stats["evictions"] += 1
                return
        # slot already gone (e.g. explicit evict raced ahead) — fine.

    def _submit_unlocked(self, desc: ContextDescriptor,
                         prefetch: bool = False) -> Future:
        return self._loader.submit(self._do_load, desc, prefetch)

    def preload(self, name: str, block: bool = False,
                allow_evict_active: bool = False) -> Future:
        """Start loading `name` into a non-active slot (overlaps execution).

        This is the paper's dynamic reconfiguration: the call returns
        immediately; the active context keeps executing.  Repeated preloads
        of an in-flight name return the same future.  Victim selection is
        the policy's: it evicts the LRU non-active resident; when every
        slot is pinned (ACTIVE or loading) the request is *deferred* and
        resubmitted automatically as soon as a slot frees up.

        ``allow_evict_active`` marks a quiescent point (no run in flight):
        the policy may then overwrite even the currently selected context,
        exactly like the simulator's between-runs decision.
        """
        desc = self._contexts[name]
        with self._lock:
            slot = self._find_slot(name)
            if slot is not None:                        # already resident
                f: Future = Future()
                f.set_result(slot)
                return f
            pending = self._pending.get(name)
            if pending is not None and not pending.done():
                return pending                          # already in flight
            decision = self.policy.ensure(
                name, active=None if allow_evict_active
                else self._active_name())
            if decision is None:                        # all slots pinned
                ph: Future = Future()
                self._pending[name] = ph
                self._deferred[name] = ph
                fut = ph
            else:
                for v in decision.evictions:
                    self._evict_name_unlocked(
                        v, demote_ok=allow_evict_active)
                fut = self._submit_unlocked(desc)
                self._pending[name] = fut
        if block:
            fut.result()
        return fut

    def prefetch(self, upcoming: "list[str]",
                 limit: Optional[int] = None) -> "list[Future]":
        """Stream upcoming contexts into shadow slots per the policy's
        lookahead plan (hidden behind the active context's execution).

        One atomic policy consultation under the engine lock — the same
        ``ReconfigPolicy.prefetch`` call the simulator makes, so live and
        simulated prefetch/evict decisions are literally the same code.
        """
        futs: list[Future] = []
        with self._lock:
            known = [n for n in upcoming
                     if n in self._contexts and n not in self._deferred]
            for dec in self.policy.prefetch(
                    known, active=self._active_name(), limit=limit):
                for v in dec.evictions:
                    self._evict_name_unlocked(v)
                fut = self._submit_unlocked(self._contexts[dec.net],
                                            prefetch=True)
                self._pending[dec.net] = fut
                futs.append(fut)
            self._kick_deferred_unlocked()   # evictions may free deferred
        return futs

    def note_prefetch_failure(self):
        """Count one failed prefetch (``ctx.prefetch_failures``).  A
        prefetch is advisory — callers keep serving and the context pays
        a demand load later — so the counter is the only trace a failed
        shadow load (e.g. device memory exhausted) leaves."""
        with self._lock:
            self.stats["prefetch_failures"] += 1

    def _kick_deferred_unlocked(self):
        """Resubmit deferred loads whose slot just became available (FIFO:
        the configuration port serves requests in arrival order)."""
        for name in list(self._deferred):
            decision = self.policy.ensure(name, active=self._active_name())
            if decision is None:
                break                                   # still no room
            ph = self._deferred.pop(name)
            for v in decision.evictions:
                self._evict_name_unlocked(v)
            real = self._submit_unlocked(self._contexts[name])

            def _chain(f: Future, ph: Future = ph):
                exc = f.exception()
                if exc is not None:
                    ph.set_exception(exc)
                else:
                    ph.set_result(f.result())
            real.add_done_callback(_chain)

    def _claim_slot(self, name: str) -> ContextSlot:
        """Runs on the loader thread.  The policy freed a slot when this
        load was admitted, so an EMPTY slot exists by the time the single
        port gets to it; the wait loop is a defensive backstop."""
        deadline = time.monotonic() + 60.0
        while True:
            with self._lock:
                for slot in self.slots:
                    if slot.state == ContextState.EMPTY:
                        slot.state = ContextState.LOADING
                        slot.name = name
                        slot.ready_event.clear()
                        return slot
            if time.monotonic() > deadline:             # pragma: no cover
                raise RuntimeError(f"no slot became loadable for {name!r}")
            time.sleep(0.001)

    def _do_load(self, desc: ContextDescriptor, prefetch: bool = False):
        """One load on the loader thread, inside its ``ctx.load`` region
        (children: ``ctx.load.fetch``, ``ctx.load.put``, ``ctx.load.wait``)."""
        with self._trace.region("ctx.load", "ctx-loader", ctx=desc.name,
                                cause="prefetch" if prefetch
                                else "demand") as region:
            return self._load(desc, prefetch, region)

    def _load(self, desc: ContextDescriptor, prefetch: bool, region):
        slot = self._claim_slot(desc.name)
        tr = self._trace
        t0 = self._clock()
        with self._lock:
            self._load_started_at = t0
            self._load_hidden_accum = 0.0
        try:
            with tr.region("ctx.load.fetch", "ctx-loader"):
                host = desc.weights_fn()
            # stream tensor-by-tensor (the two-step WL programming
            # analogue); device_put is async w.r.t. this thread until the
            # final barrier.
            with tr.region("ctx.load.put", "ctx-loader"):
                if desc.shardings is not None:
                    bufs = jax.tree.map(jax.device_put, host,
                                        desc.shardings)
                elif self.mesh is not None:
                    # replicated over the mesh once, here: weights left on
                    # one device would be re-sent to every device each step
                    bufs = jax.device_put(
                        host, NamedSharding(self.mesh, PartitionSpec()))
                else:
                    bufs = jax.tree.map(jax.device_put, host)
            with tr.region("ctx.load.wait", "ctx-loader"):
                jax.block_until_ready(bufs)
            wire_bytes = _nbytes(bufs)        # what actually crossed H2D
            if tr.enabled:
                region.set(bytes=wire_bytes)
            if desc.base is not None:
                # partial reconfiguration: only the delta crossed the wire;
                # unchanged tensors are shared with the base's device
                # buffers (zero-copy on device).
                base_slot = self._find_slot(desc.base)
                if base_slot is None:
                    raise RuntimeError(
                        f"delta context {desc.name!r} needs base "
                        f"{desc.base!r} resident")
                bufs = _overlay(base_slot.buffers, bufs)
        except BaseException:
            with self._lock:                 # failed load never wedges a slot
                slot.state = ContextState.EMPTY
                slot.name, slot.buffers, slot.bytes_resident = None, None, 0
                slot.ready_event.set()
                self.policy.abort(desc.name)
                self._load_started_at = None
                if prefetch:     # counted before the future resolves
                    self.stats["prefetch_failures"] += 1
                self._kick_deferred_unlocked()
            if self._trace.enabled:
                self._trace.instant(f"load-failed:{desc.name}",
                                    f"ctxslot{slot.idx}", ts=self._clock())
            raise
        now = self._clock()
        dt = now - t0
        with self._lock:
            slot.buffers = bufs
            slot.bytes_resident = _nbytes(bufs)
            slot.state = ContextState.READY
            slot.ready_event.set()
            self.policy.complete(desc.name)
            self.stats["loads"] += 1
            self.stats["load_seconds"] += dt
            self.stats["bytes_loaded"] += wire_bytes
            if prefetch:         # a shadow-slot load, not a demand load
                self.stats["prefetch_loads"] += 1
            # overlap accounting: execution time inside [t0, now] counts
            # this load as *hidden* reconfiguration.  Runs that completed
            # during the window accumulated their clamped overlap in
            # _load_hidden_accum (see run()); a run still in flight
            # contributes the part since max(run_start, load_start).
            hidden = self._load_hidden_accum
            if self._run_started_at is not None:
                hidden += now - max(self._run_started_at, t0)
            hidden = max(0.0, min(dt, hidden))
            self.stats["hidden_load_seconds"] += hidden
            self._load_started_at = None
            self._kick_deferred_unlocked()
        if self._trace.enabled:
            # the span carries the SAME t0/now the accounting above used,
            # so a hidden-load fraction recomputed from exported spans
            # reproduces the engine's number (tested to < 1%).
            self._trace.span(f"load:{desc.name}", f"ctxslot{slot.idx}",
                             t0, now, args={"bytes": wire_bytes,
                                            "hidden_s": round(hidden, 6)})
        return slot

    # ------------------------------------------------------------ switching
    def switch(self, name: str, wait: bool = True,
               timeout: float = 120.0) -> float:
        """Activate a resident context.  Returns the switch latency in s.

        O(1): no device data movement.  If the context is still LOADING and
        ``wait``, blocks until READY (the paper's case where t_load >
        t_exec and reconfiguration is only partially hidden).
        """
        t0 = self._clock()
        deadline = t0 + timeout
        checked_done: Optional[Future] = None
        while True:
            # residency check and activation under ONE lock acquisition: a
            # concurrent eviction (loader kick, another client's prefetch)
            # between them could otherwise activate an emptied slot.
            with self._lock:
                slot = self._find_slot(name)
                if slot is not None:
                    prev = None
                    for s in self.slots:
                        if s.state == ContextState.ACTIVE:
                            s.state = ContextState.READY
                            prev = s.name
                    slot.state = ContextState.ACTIVE
                    self.policy.activate(name)
                    now = self._clock()
                    dt = now - t0
                    self.stats["switches"] += 1
                    if prev != name:     # an actual select-signal flip
                        self.stats["context_changes"] += 1
                        if self._trace.enabled:
                            self._trace.instant(
                                f"switch:{name}", f"ctxslot{slot.idx}",
                                ts=now, args={"from": prev})
                    self.stats["switch_seconds"] += dt
                    self._kick_deferred_unlocked()  # prev became evictable
                    return dt
                pending = self._pending.get(name)
            if pending is None:
                raise KeyError(f"context {name!r} not resident; preload first")
            if pending.done():
                if pending.exception() is not None:
                    pending.result()         # surface the load failure
                if pending is checked_done:
                    # re-checked residency under the lock after this future
                    # resolved and the slot is still gone: evicted again
                    raise KeyError(
                        f"context {name!r} not resident; preload first")
                # the load may have finished between our locked residency
                # check and here — loop once to re-check under the lock
                checked_done = pending
                continue
            if not wait:
                raise RuntimeError(f"context {name!r} still loading")
            remaining = deadline - self._clock()
            if remaining <= 0:
                raise TimeoutError(f"context {name!r} did not become READY")
            pending.result(remaining)

    def deactivate(self):
        """Park the select signal: ACTIVE -> READY (slot stays resident)."""
        with self._lock:
            for s in self.slots:
                if s.state == ContextState.ACTIVE:
                    s.state = ContextState.READY
            self.policy.deactivate()
            self._kick_deferred_unlocked()

    @property
    def active(self) -> Optional[ContextSlot]:
        for s in self.slots:
            if s.state == ContextState.ACTIVE:
                return s
        return None

    # ------------------------------------------------------------ execution
    def run(self, *inputs):
        """Execute the active context on `inputs`."""
        slot = self.active
        if slot is None:
            raise RuntimeError("no ACTIVE context; call switch() first")
        fn = self._get_executable(self._contexts[slot.name], inputs)
        return self.run_step(fn, *inputs, slot=slot)

    def run_step(self, fn, *inputs, block: bool = True, slot=None):
        """Token-granular execution: run one externally-jitted program
        against the ACTIVE slot's weight buffers, with the engine's
        hidden-load (overlap) accounting.

        This is how the continuous-batching step engine drives the fabric:
        each decode step is one ``run_step`` call, so a context switch
        between any two steps is an O(1) select flip and a shadow-slot
        load overlaps *steps*, not whole batches.  ``fn`` receives the
        slot buffers as its first argument (``fn(params, *inputs)``) — the
        engine never captures weights, the slot may be evicted and
        reloaded between calls.  ``slot`` pins a pre-resolved slot so a
        caller that looked up an executable for it (``run``) can't race a
        concurrent switch into mismatched fn/buffers.
        """
        if slot is None:
            slot = self.active
        if slot is None:
            raise RuntimeError("no ACTIVE context; call switch() first")
        t0 = self._clock()
        with self._lock:
            self._runs_in_flight += 1
            if self._run_started_at is None:
                self._run_started_at = t0
        try:
            out = fn(slot.buffers, *inputs)
            if block:
                out = jax.block_until_ready(out)
        finally:
            now = self._clock()
            with self._lock:
                self._runs_in_flight -= 1
                self._exec_busy_until = now
                if self._load_started_at is not None:
                    # clamp this run's overlap to the open load window
                    self._load_hidden_accum += max(
                        0.0, now - max(t0, self._load_started_at))
                if self._runs_in_flight == 0:
                    self._run_started_at = None
            if self._trace.enabled:
                # same t0/now as the overlap accounting — see _do_load.
                self._trace.span(f"run:{slot.name}", f"ctxslot{slot.idx}",
                                 t0, now)
        return out

    def run_async(self, *inputs):
        """Dispatch without blocking (JAX async dispatch overlaps the load)."""
        slot = self.active
        if slot is None:
            raise RuntimeError("no ACTIVE context; call switch() first")
        desc = self._contexts[slot.name]
        fn = self._get_executable(desc, inputs)
        return fn(slot.buffers, *inputs)

    # --------------------------------------------------------------- misc
    def hidden_load_fraction(self) -> float:
        """Share of reconfiguration time hidden behind execution (the
        paper's headline metric) — single source for every report."""
        with self._lock:
            return safe_ratio(self.stats["hidden_load_seconds"],
                              self.stats["load_seconds"])

    def resident(self) -> list[str]:
        return [s.name for s in self.slots
                if s.state in (ContextState.READY, ContextState.ACTIVE)]

    def evict(self, name: str):
        with self._lock:
            s = self._find_slot(name)
            if s is None:
                return
            if s.state == ContextState.ACTIVE:
                raise RuntimeError("cannot evict the ACTIVE context")
            s.state = ContextState.EMPTY
            s.name, s.buffers, s.bytes_resident = None, None, 0
            self.stats["evictions"] += 1
            self.policy.release(name)
            self._kick_deferred_unlocked()

    def shutdown(self):
        self._loader.shutdown(wait=True)


# ---------------------------------------------------------------------------
# Non-volatile context store (FeFET retention analogue)
# ---------------------------------------------------------------------------

class ContextStore:
    """Persist contexts to disk; reload without recompute (non-volatility)."""

    def __init__(self, root: str):
        self.root = root

    def save(self, name: str, weights) -> str:
        from repro.train.checkpoint import save_pytree
        import os
        path = os.path.join(self.root, f"ctx_{name}")
        save_pytree(path, weights)
        return path

    def weights_fn(self, name: str) -> Callable[[], Any]:
        from repro.train.checkpoint import load_pytree
        import os
        path = os.path.join(self.root, f"ctx_{name}")
        return lambda: load_pytree(path)
