"""Where JAX's persistent compilation cache lives.

Entry points that compile the big programs (`chip_smoke.py`,
`benchmarks/run.py`) call `enable_compile_cache()` once, before the first
compile; nothing calls it at import time.  The directory is part of the
cache key, so it is either the one the environment names or a fixed path
inside the checkout — never a temp dir, a pid or a timestamp.
"""
from __future__ import annotations

import os
import pathlib

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    With `JAX_COMPILATION_CACHE_DIR` set JAX already honours it: no directory
    is set in code, and a config that disagrees with the environment raises.
    Unset, the cache goes to `<checkout>/.jax_cache` (git-ignored)."""
    import jax

    env_dir = os.environ.get(_ENV)
    if env_dir:
        in_use = jax.config.jax_compilation_cache_dir
        if in_use != env_dir:
            raise RuntimeError(
                f"{_ENV}={env_dir!r} but jax_compilation_cache_dir is "
                f"{in_use!r}: something set another cache directory in code")
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
