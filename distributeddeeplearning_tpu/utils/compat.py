"""Process-environment seams for the one installed jax (0.9.0): how many
CPU devices a child process simulates, and where the persistent compilation
cache lives."""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def set_cpu_device_env(env, n: int):
    """Make ``env`` yield an ``n``-device CPU backend: ``JAX_NUM_CPU_DEVICES``
    wins over any ``--xla_force_host_platform_device_count`` in ``XLA_FLAGS``
    (``jax._src.xla_bridge.make_cpu_client``). Works on ``os.environ`` or a
    plain subprocess env dict; must happen before the backend initializes."""
    env["JAX_NUM_CPU_DEVICES"] = str(n)
    return env


def compile_cache_dir() -> str:
    """The persistent compilation cache directory: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``. The path is part of the cache
    key's neighbourhood on disk — it never moves with a pid, a time or a
    temporary name, so a second process finds what the first one compiled."""
    return os.environ.get(CACHE_DIR_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def setup_compile_cache() -> str:
    """Switch the persistent compilation cache on; called first by every
    entry point that compiles. Where ``JAX_COMPILATION_CACHE_DIR`` is set jax
    reads it itself and no directory is set in code. Thresholds are lowered
    so the small configs' steps persist too. Returns the directory in use."""
    import jax

    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return compile_cache_dir()
