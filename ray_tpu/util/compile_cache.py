"""Where JAX's persistent compilation cache lives: the ONE place that
decides it, for every process of this installation that compiles — the
driver-side engine, the workers the runtime spawns (build_worker_env),
chip_smoke.py's children.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set in code. Otherwise the cache sits at ``<checkout>/.jax_cache``
(git-ignored): a fixed path, never a temp name, pid or timestamp — the
path is part of the cache key, so a directory that moves never hits.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# process-wide, like the cache itself: JAX's monitoring listeners cannot
# be removed, so ONE is registered, the first time the cache is enabled
_events = {"hits": 0, "misses": 0, "listening": False}


def _on_event(name: str, **_kw) -> None:
    if name == "/jax/compilation_cache/cache_hits":
        _events["hits"] += 1
    elif name == "/jax/compilation_cache/cache_misses":
        _events["misses"] += 1


def compile_cache_dir() -> str:
    """The cache directory in use: the environment's, else the checkout's."""
    return os.environ.get(CACHE_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point this process's JAX at compile_cache_dir() before it compiles;
    returns the directory. A no-op when the environment names one (JAX
    already honours it) or the process configured its own."""
    import jax
    if not os.environ.get(CACHE_ENV) and \
            jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    if not _events["listening"]:
        _events["listening"] = True
        jax.monitoring.register_event_listener(_on_event)
    return jax.config.jax_compilation_cache_dir


def compile_cache_stats() -> dict:
    """The directory this process's JAX caches in, and how many of its
    compile requests since enable_compile_cache() the cache answered
    (hits) or had to compile (misses). JAX persists, and so counts, only
    programs that took over a second to compile."""
    import jax
    return {"dir": jax.config.jax_compilation_cache_dir,
            "hits": _events["hits"], "misses": _events["misses"]}
