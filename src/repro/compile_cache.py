"""Persistent XLA compile cache for the device paths (the fluid engine, the
learned engine and the Pallas kernels).

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself: where it is set, the cache
goes there and nothing here changes the path.  Where it is not,
:func:`enable` points ``jax_compilation_cache_dir`` at ``.jax_cache/`` in
the root of the checkout.  The path is fixed so that a later process of the
same checkout finds what an earlier one compiled.

Programs that compile faster than JAX's default threshold of one second
are cached as well, unless ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``
says otherwise: the fluid engine compiles one small program per phase
shape, and together they are most of a cold run.

On the CPU backend nothing is configured: its compiles are cheap, and
XLA:CPU logs a machine-feature error for every executable it reloads.

Modules that compile call :func:`enable` before their first compile.  jax
is imported inside it, so importing this module keeps a spawn worker
jax-free (reprolint S402).
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"

_enabled = False


def enable() -> None:
    """Idempotent: configure the cache once per process."""
    global _enabled
    if _enabled:
        return
    _enabled = True
    import jax
    if jax.default_backend() == "cpu":
        return
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
