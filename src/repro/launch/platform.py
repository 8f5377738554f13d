"""Host platform bootstrap for benchmarks and examples (DESIGN.md §12).

One idempotent entry point, `bootstrap()`, to be called before the first
jax dispatch: it pins the jax platform, applies the GPU latency-hiding
XLA scheduler flags (no-ops elsewhere), optionally fans the CPU backend
out into several host devices (`--xla_force_host_platform_device_count`,
useful for mesh dry-runs on a laptop), turns on the persistent
compilation cache, and silences the CPU buffer-donation warning the
compiled hot path would otherwise emit per program. Library code never
calls this — sessions must work under whatever platform the embedder
configured — which is why it lives under `repro.launch` next to the
other entry-point helpers.
"""
from __future__ import annotations

import os
import warnings

_GPU_XLA_FLAGS = (
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)

#: The compile cache's home when JAX_COMPILATION_CACHE_DIR is unset: one
#: fixed directory inside the checkout (listed in .gitignore). The path
#: is part of every cache key, so it must not vary between runs.
CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))

_bootstrapped = False


def _merge_xla_flags(*flags: str) -> None:
    """Append flags to XLA_FLAGS, replacing an existing setting of the
    same flag rather than duplicating it."""
    current = os.environ.get("XLA_FLAGS", "").split()
    keys = {f.split("=", 1)[0] for f in flags}
    kept = [f for f in current if f.split("=", 1)[0] not in keys]
    os.environ["XLA_FLAGS"] = " ".join(kept + list(flags))


def set_host_device_count(n: int) -> None:
    """Split the host platform into `n` devices (CPU mesh dry-runs).
    Must run before the jax backend initializes."""
    _merge_xla_flags(f"--xla_force_host_platform_device_count={int(n)}")


def set_platform(platform: str) -> None:
    """Pin the jax platform ('cpu' | 'gpu' | 'tpu') and apply the
    platform's XLA scheduling flags. Must run before the first jax
    computation."""
    import jax

    if platform == "gpu":
        _merge_xla_flags(*_GPU_XLA_FLAGS)
    jax.config.update("jax_platform_name", platform)


def enable_compile_cache() -> None:
    """Turn on XLA's persistent compilation cache. Where
    JAX_COMPILATION_CACHE_DIR is set, jax reads the directory from it and
    nothing here overrides it; otherwise the cache lives at `CACHE_DIR`.
    Must run before the first jax compile.

    This is the cross-process half of the compiled hot path's
    initialization story (DESIGN.md §12): within one process, sessions
    share programs through the registries in runtime/train_loop.py; with
    the disk cache, a fresh process (the CI sweep, a relaunched edge
    runtime) deserializes earlier programs instead of re-paying XLA
    compiles — the same "amortize system initialization" premise LazyTune
    applies to in-process retraces (paper §IV-B)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # default thresholds skip small/fast programs; an edge deployment
    # wants every program persisted — the point is a compile-free restart
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def bootstrap(platform: str = None, host_devices: int = None,
              enable_x64: bool = False) -> None:
    """Idempotent process setup for entry points (benchmarks, examples,
    microbenches, chip_smoke.py). `platform` defaults to the
    EDGEOL_PLATFORM environment variable when set, else jax's own default
    backend."""
    global _bootstrapped
    if _bootstrapped:
        return
    _bootstrapped = True
    # logging first, so everything after (including jax config paths)
    # reports through the "edgeol" logger tree; level from $EDGEOL_LOG
    from repro.obs.log import configure_logging

    configure_logging()
    if host_devices:
        set_host_device_count(host_devices)
    platform = platform or os.environ.get("EDGEOL_PLATFORM")
    if platform:
        set_platform(platform)
    enable_compile_cache()
    if enable_x64:
        import jax

        jax.config.update("jax_enable_x64", True)
    # CPU backends have no donation support; the donated steps are still
    # correct (see runtime/train_loop.py) and the warning is pure noise
    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable")
