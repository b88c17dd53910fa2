"""reference python/paddle/sysconfig.py: include/lib dirs (here: the
package's own paths — there is no compiled libpaddle; native pieces live
under core/native) — plus where the entry points that run on the chip
keep JAX's persistent compilation cache."""
from __future__ import annotations

import os

__all__ = ["get_include", "get_lib", "enable_compile_cache"]

_ROOT = os.path.dirname(os.path.abspath(__file__))


def get_include():
    return os.path.join(_ROOT, "include")


def get_lib():
    return os.path.join(_ROOT, "libs")


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and no
    directory is configured in code.  Unset: ``<repo>/.jax_cache`` — a
    fixed path beside the package (the path is part of the cache key, so
    a directory that moves never hits), listed in ``.gitignore``.

    Called by the entry points that run on the chip (``chip_smoke.py``,
    ``bench.py``, ``tools/tpu_smoke.py``, ``tools/serving_bench.py``,
    ``tools/autotune.py``) before their first compile — never at
    ``import paddle_tpu``: the CPU test suite must not fill a directory
    the chip tool then copies with XLA:CPU executables."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(os.path.dirname(_ROOT), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def compile_cache_dir():
    """Where JAX's persistent compilation cache is right now (None: off)."""
    import jax

    return jax.config.jax_compilation_cache_dir
