"""Runtime environment control: platform, precision, XLA flags.

Launchers and benchmark drivers call these BEFORE the first jax
computation so the backend initializes the way the run was asked for —
and ``describe()`` afterwards so every BENCH/report records the platform
the numbers actually came from (a "GPU" result measured on a CPU
fallback is the classic silent benchmark lie).

Two kinds of knob live here:

  * jax config (``set_platform``, ``enable_x64``) — effective any time
    before the first computation touches the backend.
  * process environment (``set_host_device_count``, the XLA GPU latency
    flags) — these edit ``XLA_FLAGS``, which XLA reads once at backend
    initialization.  Setting them after jax has initialized its backend
    raises instead of silently doing nothing; subprocess workers (and
    the CI multi-device job) export ``XLA_FLAGS`` before python starts,
    which is always safe.

``set_host_device_count`` is how the sharded-page-bank tests and the CI
``multi-device`` job fake a 4-device mesh on one CPU host:
``--xla_force_host_platform_device_count=N`` splits the host platform
into N devices, enough for ``shard_map`` placement without hardware.

``enable_compile_cache`` turns on JAX's persistent compilation cache for
entry points (the serving launcher, ``benchmarks/run.py``,
``chip_smoke.py``) so a second process on the same checkout skips the
compile.  Tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

__all__ = ["backend_initialized", "compile_cache_dir", "describe",
           "enable_compile_cache", "enable_x64",
           "gpu_latency_hiding_flags", "set_host_device_count",
           "set_platform"]

# <checkout>/.jax_cache: fixed, because the cache directory is part of
# every entry's lookup — a per-run path would never hit
_REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"

# flags vetted for serving-shaped GPU programs: overlap collective /
# host-transfer latency behind compute instead of serializing on it
_GPU_LATENCY_FLAGS = (
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def backend_initialized() -> bool:
    """Whether jax has already initialized a backend (after which the
    process-environment knobs below can no longer take effect)."""
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:                       # pragma: no cover - jax internals
        return False


def _add_xla_flags(*flags: str) -> None:
    if backend_initialized():
        raise RuntimeError(
            "XLA_FLAGS edits are read once at backend initialization and "
            "jax has already initialized; set flags before the first jax "
            f"computation (wanted: {' '.join(flags)})")
    cur = os.environ.get("XLA_FLAGS", "")
    missing = [f for f in flags if f not in cur]
    if missing:
        os.environ["XLA_FLAGS"] = " ".join(([cur] if cur else []) + missing)


def set_platform(name: Optional[str]) -> None:
    """Pin jax to one platform ("cpu", "gpu", "tpu"); None keeps jax's
    own detection order."""
    if name is None:
        return
    import jax
    jax.config.update("jax_platforms", name)


def enable_x64(on: bool = True) -> None:
    """Toggle 64-bit mode (f64/i64 as default wide types)."""
    import jax
    jax.config.update("jax_enable_x64", bool(on))


def set_host_device_count(n: Optional[int]) -> None:
    """Force the host (CPU) platform to expose ``n`` devices — a fake
    multi-device topology for mesh/shard_map runs without hardware.
    Must run before backend initialization; None is a no-op."""
    if n is None:
        return
    if n < 1:
        raise ValueError(f"host device count must be >= 1, got {n}")
    _add_xla_flags(f"--xla_force_host_platform_device_count={n}")


def compile_cache_dir() -> tuple[str, bool]:
    """Where the persistent compilation cache lives, and whether code has
    to set it: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
    (jax reads that variable itself), else ``<checkout>/.jax_cache``."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir, False
    return str(_REPO_CACHE), True


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache before the first compile
    (see ``compile_cache_dir``); returns the directory in use."""
    path, set_in_code = compile_cache_dir()
    if set_in_code:
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def gpu_latency_hiding_flags() -> None:
    """Enable XLA GPU's latency-hiding scheduler flags (no-op for the
    backend on CPU/TPU; the flags are only read by the GPU compiler)."""
    _add_xla_flags(*_GPU_LATENCY_FLAGS)


def describe() -> dict:
    """The environment a run ACTUALLY executed under (initializes the
    backend if nothing has yet): platform, device count/kind, x64 mode,
    and any forced host device count — recorded into BENCH meta so
    cross-machine diffs can tell a real topology from a faked one."""
    import jax
    dev = jax.devices()[0]
    flags = os.environ.get("XLA_FLAGS", "")
    forced = None
    for tok in flags.split():
        if tok.startswith("--xla_force_host_platform_device_count="):
            forced = int(tok.split("=", 1)[1])
    return {
        "backend": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "x64": bool(jax.config.read("jax_enable_x64")),
        "forced_host_devices": forced,
    }
