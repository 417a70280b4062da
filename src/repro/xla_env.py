"""Process-wide JAX/XLA settings: host device count and compile cache.

Importing this module does not import jax, so a script can call
:func:`force_host_devices` before its first ``import jax`` (XLA reads
``XLA_FLAGS`` once, when the backend starts).
"""
from __future__ import annotations

import os

#: the checkout root (``src/repro/xla_env.py`` -> three levels up)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def force_host_devices(n) -> None:
    """Give the CPU backend ``n`` host devices (multi-device runs without
    an accelerator).  Must run before jax starts its backend.  The flag is
    appended to ``XLA_FLAGS``, keeping whatever is already set (a later
    occurrence of a flag overrides an earlier one)."""
    flags = os.environ.get("XLA_FLAGS", "").split()
    flags.append(f"--xla_force_host_platform_device_count={int(n)}")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of the cache key, so a
    path that moved between runs would never hit.  Returns the directory
    in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
