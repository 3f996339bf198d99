"""Backend-platform selection helpers.

One installation is supported (jax / jaxlib 0.9.0, libtpu 0.0.34) and JAX
selects its platform the ordinary way: `JAX_PLATFORMS=cpu` in the
environment, or `jax.config.update("jax_platforms", ...)` before the backend
is first used.  This module is the single home for the two start-up
idioms the launchers share: forcing N virtual CPU devices (tests, CPU
drills) and placing the persistent compile cache.
Importing it initialises no backend.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["force_cpu", "enable_compilation_cache"]


#: where the persistent compile cache goes when JAX_COMPILATION_CACHE_DIR is
#: not set: one FIXED path inside the checkout (the path is part of how a
#: cache is found again, so it is never built from a temporary name, a pid
#: or the time)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> Optional[str]:
    """Arm JAX's persistent XLA compilation cache; returns the directory in
    use (None when the directory cannot be created, or with
    ``BIGDL_TPU_XLA_CACHE=0`` — which turns the cache OFF, also one that JAX
    armed by itself from ``JAX_COMPILATION_CACHE_DIR`` or that an earlier
    call here armed).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself keeps its cache
    there and this function sets no directory at all.  Where it is not, the
    cache goes to :data:`CHECKOUT_CACHE_DIR`.  Either way every compile is
    cached, however short.  `Engine.init()` calls this, so every entry point
    (`bigdl-tpu-run`, benchmark/run.py, tools/perf.py, chip_smoke.py) shares this
    one site; the call is idempotent and takes effect mid-process.

    Layering note: this warms the XLA *compiler* per jit function; the AOT
    executable cache (utils/aot.py, BIGDL_TPU_AOT_CACHE) sits one level
    above and skips compilation entirely for whole cached executables.
    An AOT miss still compiles through this cache, and either can be
    disabled independently.  An executable that an AOT miss got out of THIS
    cache is not stored again one level up (serialized a second time it
    loses its kernels on XLA:CPU); see `utils/aot._compile_timed`.
    """
    import jax
    from jax._src import compilation_cache as _cc

    from . import config as _config

    was_on = jax.config.jax_enable_compilation_cache
    if not _config.get_bool("XLA_CACHE", True):
        if was_on:
            jax.config.update("jax_enable_compilation_cache", False)
            _cc.reset_cache()  # jax latches whether it uses a cache
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        try:
            os.makedirs(path, exist_ok=True)
        except OSError:
            return None
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # jax latches whether it uses a cache, and its cache object once it has
    # opened a directory: a process that already compiled without one, or
    # into ANOTHER one, would ignore this call forever.  Back to the
    # pristine state, so that it takes effect mid-process.
    if not was_on or (_cc._cache_initialized and
                      str(getattr(_cc._cache, "_path", None)) != path):
        _cc.reset_cache()
    return path


def force_cpu(n_devices: Optional[int] = None) -> bool:
    """Point jax at the CPU backend with `n_devices` virtual devices.

    Returns True when the config took effect, False when the backend was
    already initialized (caller should then check jax.devices() itself).
    """
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        if n_devices is not None:
            jax.config.update("jax_num_cpu_devices", int(n_devices))
        return True
    except RuntimeError:
        return False  # backend already initialized — use as-is
