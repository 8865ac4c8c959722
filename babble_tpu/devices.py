"""Device helpers: where the persistent compile cache lives, and the
virtual CPU devices the tests shard over.

Both act through JAX's own environment and config. Neither clears a
backend: a process that must run on the CPU sets the environment
before JAX initializes one (tests/conftest.py)."""

from __future__ import annotations

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Fixed, in-checkout and git-ignored: the path is part of the cache key,
# so a directory that moves between runs never hits.
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def ensure_virtual_devices(count: int) -> None:
    """Run this process's JAX on `count` virtual CPU devices. XLA reads
    the device count when JAX initializes its backend, so call this
    before any JAX computation; raises if the backend is already up
    with fewer devices."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={count}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ["JAX_PLATFORMS"] = "cpu"
    # The CPU client runs every virtual device's thunks on one pool of
    # PJRT_NPROC threads (default: the cores). A GSPMD program whose
    # devices enter two independent all-gathers in different orders
    # blocks a thread per waiting device; on a pool no larger than the
    # device count both rendezvous starve and XLA aborts the process
    # after 40 s (seen on 8 cores: pytest workers lost in the mesh tests).
    os.environ.setdefault("PJRT_NPROC", str(4 * count))
    if "jax" not in sys.modules:
        return
    import jax

    jax.config.update("jax_platforms", "cpu")
    have = len(jax.devices())
    if have < count:
        raise RuntimeError(
            f"could not provision {count} virtual devices (have {have}): "
            "JAX initialized its backend first — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={count} "
            "JAX_PLATFORMS=cpu before starting python")


def ensure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache, so node restarts
    reuse compiled consensus kernels. JAX_COMPILATION_CACHE_DIR, when
    set, is the only directory used (JAX reads it itself); otherwise
    the cache sits at DEFAULT_CACHE_DIR. Idempotent."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # 0.1s floor: engine kernels are worth persisting even when a fast
    # backend compiles them quickly; trivial one-liners are not.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return cache_dir
