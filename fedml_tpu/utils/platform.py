"""Process-level jax set-up shared by every entry point: keeping a process
on the host CPU (tests, dry runs, reference-implementation children) and
placing the persistent compilation cache.

Loaded by ``tests/conftest.py`` straight from this file, so it must not
import the ``fedml_tpu`` package.
"""

from __future__ import annotations

import os

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def force_cpu_backend() -> None:
    """Keep jax on the host-CPU backend.  Must run before jax initializes a
    backend.  Device COUNT (``--xla_force_host_platform_device_count``) must
    still be set via ``XLA_FLAGS`` in the environment before the jax import.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")


def configure_compilation_cache() -> str:
    """Place jax's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and this
    writes nothing.  Otherwise the cache lives at ``.jax_cache`` beside the
    ``fedml_tpu`` package — a path fixed by the checkout (the path is part
    of what a later process must repeat to hit), never by a temp dir, a
    pid, a run id or the clock.  The ONE place the program sets
    ``jax_compilation_cache_dir``; ``fedml_tpu.init()`` calls it, so every
    normal entry point compiles through the same cache.
    """
    from_env = os.environ.get(_CACHE_ENV)
    if from_env:
        return from_env
    import jax

    package_parent = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    cache_dir = os.path.join(package_parent, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
