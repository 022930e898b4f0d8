"""Runtime helpers shared by the entry scripts and examples.

``drain`` is the flush of the reference's exit/loop hygiene
(mpi4jax/_src/flush.py:1-12 — device_put+0 noop as a work barrier):
wait for the work, hand back one scalar.  Timing loops do not use it;
they sync with ``jax.block_until_ready`` directly.
"""

import math
import os
import pathlib

import numpy as np

__all__ = ["drain", "best_mesh_shape", "enable_compile_cache"]

# One fixed directory inside the checkout: the path is part of the cache
# key's lookup, so every entry script and every child process it starts
# must agree on it without passing anything along.
_REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def drain(x):
    """Block until device work producing ``x`` has finished; returns
    its first element as a numpy scalar."""
    import jax

    x = jax.block_until_ready(x)
    return np.asarray(jax.device_get(x[(0,) * getattr(x, "ndim", 0)]))


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for an entry script
    (never called at package import).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, so
    nothing is touched and no other directory is set.  Otherwise the
    cache goes to ``<checkout>/.jax_cache``.  Returns the directory in
    use.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE_DIR))
    return str(_REPO_CACHE_DIR)


def best_mesh_shape(n):
    """Closest-to-square (py, px) with py * px == n and py <= px."""
    best = (1, n)
    for py in range(1, int(math.isqrt(n)) + 1):
        if n % py == 0:
            best = (py, n // py)
    return best
