"""Process-level runtime facts every entry point shares: where compiled
programs are cached and which device the process actually got.

``run_training``, ``run_prediction``, ``python -m hydragnn_tpu.serve``,
``bench.py``'s child and ``chip_smoke.py`` all call
:func:`setup_compile_cache` before their first jit, and every record that
carries a measurement names its device through :func:`device_info` — a
run that found no accelerator must say so, not look like one that did.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def checkout_root() -> str:
    """Directory holding the ``hydragnn_tpu`` package (the repo checkout)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    The directory is part of the deployment, not of the program: where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    function sets NO directory in code.  Unset, the cache lives at the
    fixed path ``<checkout>/.jax_cache`` (git-ignored) — never a temp
    name, pid or time, because the path is part of the cache key and a
    directory that moves never hits."""
    env_dir = os.environ.get(_CACHE_ENV)
    if env_dir:
        return env_dir
    import jax

    cache_dir = os.path.join(checkout_root(), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def _dist_version(name: str) -> Optional[str]:
    from importlib import metadata

    try:
        return metadata.version(name)
    except metadata.PackageNotFoundError:
        return None


def device_info() -> Dict[str, Any]:
    """The device as JAX reports it, plus the three versions that decide
    what a kernel compiles to.  Initializes the backend."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "jax": jax.__version__,
        "jaxlib": _dist_version("jaxlib"),
        "libtpu": _dist_version("libtpu"),
    }
