"""Serving launcher: context-switching inference over N registered models.

``python -m repro.launch.serve --archs supersub-super,supersub-sub --steps 4``

Four modes:

  * ``--mode queue`` (default) — the async ``SwitchScheduler``: requests
    for all models are submitted up front; the scheduler coalesces
    same-model requests into streaks, ranks the next model by queue
    pressure + load cost, and streams it into the shadow slot while the
    active streak executes.  Reports throughput, p50/p99 latency, and the
    hidden-load fraction.
  * ``--mode continuous`` — the token-granular ``ContinuousScheduler``:
    requests join/leave a persistent slot-pooled step engine at every
    decode step; context choice is re-decided at step boundaries and the
    next context streams into the shadow slot behind the remaining steps
    (``--pool`` sets the slot-pool width).  ``--paged --page-size N``
    swaps each context's row-granular KV pool for the paged slot pool:
    per-slot page tables over one shared page bank, so a request only
    holds the pages its own length needs.  ``--multi-step T`` fuses up
    to T decode steps per tick (host bookkeeping amortizes over T
    tokens); ``--quantize-kv int8`` stores the page bank in int8 for
    ~2x pages per HBM budget; ``--prefix-cache`` shares already-written
    prompt pages across admissions (refcounted, copy-on-write), so a
    cache-hit prompt prefills only its divergent suffix.
  * ``--mode speculative`` — continuous batching with speculative cascade
    decode: ``--draft NAME`` names the draft context; every other
    registered context becomes a verify target whose requests run on a
    ``SpecEngine`` (draft proposes ``--spec-k`` tokens per round, the
    target scores them in one multi-token verify pass).  Draft/target
    hand-offs are O(1) select flips with the other side prefetched into
    the shadow slot — the paper's Super-Sub cascade as a serving mode.
  * ``--mode sync``  — the old synchronous round-robin driver (worst case
    for switching; kept as the baseline the paper compares against).

Both route every slot/eviction/prefetch decision through the shared
``ReconfigPolicy`` — there is no scheduling logic in this file.

``--widths published`` serves each arch at the widths it was published
with (e.g. ``tinyllama-1.1b``: 22 layers, d_model 2048) instead of the
smoke-test-sized default; weights are random, drawn from fixed seeds.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from repro.configs import get_arch, override, reduced as make_reduced
from repro.models.model import build_model
from repro.serve.scheduler import ContinuousScheduler, SwitchScheduler
from repro.serve.switching import ServedModel, SwitchableServer
from repro.serve.telemetry import Telemetry


def build_server(names: list[str], slots: int, max_len: int,
                 temperature: float = 0.0,
                 load_delay_s: float = 0.0,
                 arch_overrides: dict | None = None,
                 telemetry: Telemetry | None = None,
                 reduced: bool = True,
                 mesh=None,
                 ) -> tuple[SwitchableServer, dict]:
    """Register `names` behind one SwitchableServer, with weights drawn
    from ``jax.random.key(i)`` for the i-th name.

    ``reduced`` (default) serves the smoke-test-sized config of each
    arch; ``reduced=False`` serves the widths the arch was published
    with.  Weights live on the host after registration, so every context
    load is a real host->device transfer (``ctx.bytes_loaded``).
    ``load_delay_s`` sleeps in each ``weights_fn`` to emulate a slower
    host->device link.  ``arch_overrides`` are extra config fields (e.g.
    float32 dtypes for tests that compare two numerically different
    execution paths bitwise).  ``mesh`` makes every load replicate the
    weights over that mesh once (for engines whose page bank is laid
    out over it)."""
    import jax.numpy as jnp
    server = SwitchableServer(num_slots=slots, mesh=mesh,
                              telemetry=telemetry)
    cfgs = {}
    over = arch_overrides or {}
    for i, name in enumerate(names):
        cfg = get_arch(name)
        cfg = (make_reduced(cfg, **over) if reduced
               else override(cfg, **over))
        cfgs[name] = cfg
        model = build_model(cfg, cache_dtype=jnp.float32
                            if over.get("dtype") == "float32"
                            else jnp.bfloat16)
        host = jax.device_get(model.init(jax.random.key(i)))

        def weights_fn(p=host):
            if load_delay_s:
                time.sleep(load_delay_s)
            return p
        server.register(ServedModel(name=name, model=model,
                                    weights_fn=weights_fn,
                                    max_len=max_len,
                                    temperature=temperature))
    return server, cfgs


def request_stream(names, cfgs, n_requests, batch, seq, seed):
    """Round-robin mixed-model traffic (worst case for switching)."""
    rng = np.random.default_rng(seed)
    for r in range(n_requests):
        name = names[r % len(names)]
        toks = rng.integers(0, cfgs[name].vocab_size, (batch, seq))
        yield name, toks


def shard_mesh(shards: int | None):
    """The device mesh a ``--shards N`` page bank is laid out over, or
    None when fewer than N devices are visible (the bank is then sharded
    only logically: N free-lists on one device)."""
    if shards is None or shards <= 1 or jax.device_count() < shards:
        return None
    from repro.distributed.mesh import make_mesh
    return make_mesh((shards,), ("model",))


def bank_placement(shards: int | None, mesh) -> str:
    """How the paged bank is laid out: ``single`` (one device, one
    free-list), ``logical`` (``shards`` free-lists on one device) or
    ``mesh`` (the page axis split over the devices of ``mesh``)."""
    if mesh is not None:
        return "mesh"
    return "logical" if shards is not None and shards > 1 else "single"


def serve_report(server: SwitchableServer, mode: str, wall: float,
                 n_requests: int, extra: dict) -> dict:
    """The launcher's JSON report: context-engine counters plus the
    scheduler's ``extra`` fields and the environment the run used."""
    from repro.core import env
    stats = server.engine.stats
    return {
        "mode": mode,
        "wall_s": round(wall, 3),
        "requests_per_s": round(n_requests / wall, 2) if wall else 0.0,
        "switches": stats["switches"],
        "context_changes": stats["context_changes"],
        "mean_switch_us": round(1e6 * stats["switch_seconds"]
                                / max(stats["switches"], 1), 1),
        "loads": stats["loads"],
        "mean_load_ms": round(1e3 * stats["load_seconds"]
                              / max(stats["loads"], 1), 2),
        "bytes_loaded": stats["bytes_loaded"],
        "prefetch_loads": stats["prefetch_loads"],
        "prefetch_failures": stats["prefetch_failures"],
        "hidden_load_fraction": round(
            server.engine.hidden_load_fraction(), 3),
        **extra,
        "env": env.describe(),
        "log_tail": server.log[-3:],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--archs", default="supersub-super,supersub-sub")
    ap.add_argument("--mode",
                    choices=("queue", "continuous", "speculative", "sync"),
                    default="queue")
    ap.add_argument("--pool", type=int, default=8,
                    help="continuous/speculative mode: slot-pool width")
    ap.add_argument("--draft", default=None,
                    help="speculative mode: draft context name (must be "
                         "one of --archs; the remaining archs become "
                         "verify targets)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative mode: draft tokens per round (the "
                         "adaptive ceiling when --spec-adaptive is set)")
    ap.add_argument("--spec-tree", type=int, default=1,
                    help="speculative mode: sibling candidates per draft "
                         "depth — 1 is the flat chain; W>1 verifies a "
                         "token tree so a rejected chain can still "
                         "commit an accepted sibling (needs "
                         "1 + K*W <= 31 tree nodes)")
    ap.add_argument("--spec-adaptive", action="store_true",
                    help="speculative mode: let the scheduler walk each "
                         "engine's K inside [1, --spec-k] from the "
                         "measured acceptance rate (EWMA, hysteresis)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="continuous mode: admit prompts in fixed-size "
                         "chunks of this many tokens, one chunk per step "
                         "(bounded admission latency; one jitted chunk "
                         "program instead of one per prompt length)")
    ap.add_argument("--paged", action="store_true",
                    help="continuous mode: paged slot pool — per-slot "
                         "page tables over one shared KV page bank; each "
                         "request holds only the pages its own length "
                         "needs, so the same memory serves more "
                         "concurrent requests")
    ap.add_argument("--page-size", type=int, default=256,
                    help="paged mode: tokens per KV page (must divide "
                         "the serving max_len)")
    ap.add_argument("--multi-step", type=int, default=1,
                    help="continuous mode: fuse up to T decode steps "
                         "into one device program per scheduler tick "
                         "(the host's rank/drain/admit bookkeeping "
                         "amortizes over up to T tokens; streams stay "
                         "bitwise-identical to T single steps)")
    ap.add_argument("--quantize-kv", choices=("none", "int8"),
                    default="none",
                    help="paged mode: store the shared KV page bank in "
                         "int8 with per-token-per-head scales — about "
                         "half the bytes per page, ~2x admitted "
                         "concurrency per HBM budget (outputs are "
                         "tolerance-close, not bitwise)")
    ap.add_argument("--shards", type=int, default=None,
                    help="continuous paged mode: partition each "
                         "engine's KV page bank into this many shards "
                         "with one free-list each; admission routes a request's pages to "
                         "one shard (prefix hits to the shard holding "
                         "the cached pages, cold admissions to the "
                         "least-loaded shard).  When at least this many "
                         "devices are visible the bank is also placed "
                         "over a device mesh (the report's "
                         "bank_placement says which happened)")
    ap.add_argument("--widths", choices=("reduced", "published"),
                    default="reduced",
                    help="serve each arch at smoke-test widths (default) "
                         "or at the widths it was published with")
    ap.add_argument("--platform", default=None,
                    choices=("cpu", "gpu", "tpu"),
                    help="pin jax to one platform (default: jax's own "
                         "detection order)")
    ap.add_argument("--x64", action="store_true",
                    help="enable 64-bit mode (f64/i64 default types)")
    ap.add_argument("--host-devices", type=int, default=None,
                    metavar="N",
                    help="force the host (CPU) platform to expose N "
                         "devices — a fake multi-device topology for "
                         "--shards mesh placement without hardware "
                         "(must be set before jax initializes; the CI "
                         "multi-device job exports XLA_FLAGS instead)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged mode: share already-written prompt pages "
                         "across admissions — a request whose prompt "
                         "starts with a cached whole-page run maps those "
                         "pages read-only and prefills only the "
                         "divergent suffix (copy-on-write on the "
                         "boundary page; streams stay bitwise-identical "
                         "to cold admission); cached pages are evicted "
                         "LRU-first under page pressure")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--steps", type=int, default=1)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record per-request lifecycle spans and export "
                         "Chrome trace-event JSON here on exit (open at "
                         "https://ui.perfetto.dev; one track per context "
                         "slot / pool slot, so hidden context loads show "
                         "as load: spans under run:/tick spans)")
    ap.add_argument("--stats-interval", type=float, default=0.0,
                    metavar="SECONDS",
                    help="while requests are in flight, print a metric "
                         "registry snapshot (one JSON line to stderr) "
                         "every SECONDS; 0 disables")
    args = ap.parse_args(argv)
    if args.shards is not None and (args.shards < 1 or not args.paged
                                    or args.mode != "continuous"):
        # only the continuous step engine lays its bank (and the Pallas
        # calls beside it) out over a mesh; the other modes' engines
        # would run one-device kernels on mesh-replicated weights
        ap.error("--shards needs --mode continuous, --paged and a "
                 "positive shard count")
    from repro.core import env
    env.set_platform(args.platform)
    if args.x64:
        env.enable_x64(True)
    env.set_host_device_count(args.host_devices)
    env.enable_compile_cache()
    if args.quantize_kv != "none" and not args.paged \
            and args.mode != "speculative":
        ap.error("--quantize-kv targets the shared page bank: it "
                 "requires --paged (or --mode speculative, whose cache "
                 "columns are always paged)")
    if args.prefix_cache and not args.paged \
            and args.mode != "speculative":
        ap.error("--prefix-cache shares pages of the pooled bank: it "
                 "requires --paged (or --mode speculative, whose target "
                 "column is always paged)")
    if args.multi_step < 1:
        ap.error("--multi-step must be >= 1")
    if args.spec_k < 1:
        ap.error("--spec-k must be >= 1 (one drafted token per round is "
                 "the minimum speculative step)")
    if args.spec_tree < 1:
        ap.error("--spec-tree must be >= 1 (1 is the flat chain)")
    if args.mode == "speculative":
        if args.draft is None:
            ap.error("--mode speculative requires --draft: name the "
                     "context that proposes tokens (the remaining "
                     "--archs become verify targets)")
        if 1 + args.spec_k * args.spec_tree > 31:
            ap.error(f"--spec-k {args.spec_k} with --spec-tree "
                     f"{args.spec_tree} needs 1 + K*W <= 31 tree nodes "
                     "(ancestor masks live in an int32 bitmask); lower "
                     "one of them")
    else:
        if args.draft is not None:
            ap.error("--draft only applies to --mode speculative")
        if args.spec_tree != 1:
            ap.error("--spec-tree only applies to --mode speculative")
        if args.spec_adaptive:
            ap.error("--spec-adaptive only applies to --mode speculative")

    names = args.archs.split(",")
    slack = args.spec_k if args.mode == "speculative" else 0
    max_len = args.seq + args.steps + slack + 8
    if args.paged:
        # a paged pool's row space is a whole number of pages
        ps = min(args.page_size, max_len)
        max_len = -(-max_len // ps) * ps
    telemetry = Telemetry(trace=args.trace_out is not None)
    mesh = shard_mesh(args.shards)
    server, cfgs = build_server(names, args.slots, max_len,
                                telemetry=telemetry,
                                reduced=args.widths == "reduced",
                                mesh=mesh)
    stats_stop = None
    if args.stats_interval > 0:
        import threading
        stats_stop = threading.Event()

        def _stats_loop():
            while not stats_stop.wait(args.stats_interval):
                print(json.dumps(telemetry.registry.snapshot(),
                                 default=str), file=sys.stderr)
        threading.Thread(target=_stats_loop, daemon=True,
                         name="stats-reporter").start()
    draft_map = {}
    if args.mode == "speculative":
        if args.draft not in names:
            ap.error(f"--draft {args.draft!r} must be one of "
                     f"--archs {names}")
        targets = [n for n in names if n != args.draft]
        draft_map = {t: args.draft for t in targets}
        reqs = list(request_stream(targets, cfgs, args.requests,
                                   args.batch, args.seq, args.seed))
    else:
        reqs = list(request_stream(names, cfgs, args.requests,
                                   args.batch, args.seq, args.seed))

    t0 = time.perf_counter()
    if args.mode in ("queue", "continuous", "speculative"):
        sched_cls = (SwitchScheduler if args.mode == "queue" else
                     lambda s: ContinuousScheduler(
                         s, batch_size=args.pool, draft=draft_map,
                         spec_k=args.spec_k, spec_tree=args.spec_tree,
                         spec_adaptive=args.spec_adaptive,
                         prefill_chunk=args.prefill_chunk,
                         paged=args.paged, page_size=args.page_size,
                         multi_step=args.multi_step,
                         quantize_kv=(None if args.quantize_kv == "none"
                                      else args.quantize_kv),
                         prefix_cache=args.prefix_cache,
                         shards=args.shards, mesh=mesh))
        with sched_cls(server) as sched:
            futs = [(sched.submit(n, t, steps=args.steps),
                     time.perf_counter()) for n, t in reqs]
            lat = []
            for f, t_in in futs:
                f.result()
                lat.append(time.perf_counter() - t_in)
        extra = {**sched.snapshot()}
        if args.paged:
            extra["bank_placement"] = bank_placement(args.shards, mesh)
        if lat:
            extra["latency_p50_s"] = round(float(np.percentile(lat, 50)), 4)
            extra["latency_p99_s"] = round(float(np.percentile(lat, 99)), 4)
    else:
        for i, (name, toks) in enumerate(reqs):
            server.engine.preload(name)
            server.engine.switch(name, wait=True)
            server.engine.prefetch([n for n, _ in reqs[i + 1:]], limit=1)
            server.serve_batch(name, toks, steps=args.steps)
        extra = {}
    wall = time.perf_counter() - t0

    report = serve_report(server, args.mode, wall, args.requests, extra)
    if stats_stop is not None:
        stats_stop.set()
    if args.trace_out:
        report["trace_out"] = telemetry.tracer.export(args.trace_out)
        report["trace_events"] = len(telemetry.tracer)
    print(json.dumps(report, indent=1, default=str))
    server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
