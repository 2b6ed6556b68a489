"""The one persistent XLA compilation cache of a process.

Every entry point (the service launcher, the bundle CLI, the benchmarks,
``chip_smoke.py``) calls ``configure()`` before it compiles.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and nothing
sets another directory; otherwise it lives at one fixed path inside the
checkout, ``.jax_cache/``.  The directory is part of every cache entry's
identity, so a path that moves (a temporary, pid- or time-derived
directory) would never hit.
"""

from __future__ import annotations

import contextlib
import logging
import os
from collections.abc import Iterator

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the fixed in-checkout default (``<checkout>/.jax_cache``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The process's compilation-cache directory."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def configure() -> str:
    """Point JAX's persistent compilation cache at ``cache_dir()``,
    caching every compiled program (also sub-second ones, which a
    warm-start bundle must carry).  Idempotent; returns the path."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    path = cache_dir()
    os.makedirs(path, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # a cache instance opened on another directory keeps serving it
        # until reset (tests point one process at several directories)
        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def entries(path: str) -> list[str]:
    """Sorted file names of the compiled programs cached under ``path``."""
    try:
        return sorted(n for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return []


#: where JAX reports each persistent-cache lookup, hit or miss, with the
#: program's cache key (at DEBUG)
_COMPILER_LOGGER = "jax._src.compiler"
_LOOKUP_PREFIXES = ("Persistent compilation cache hit",
                    "PERSISTENT COMPILATION CACHE MISS")


@contextlib.contextmanager
def recording() -> Iterator[set[str]]:
    """Collect the cache keys of the programs compiled or loaded from the
    persistent cache inside the block (``<key>-cache`` is the entry's
    file name).  Reads JAX's DEBUG lookup records without letting any
    record through that the logger would not have passed before."""
    keys: set[str] = set()
    log = logging.getLogger(_COMPILER_LOGGER)
    passed = log.getEffectiveLevel()

    def lookups(record: logging.LogRecord) -> bool:
        if (isinstance(record.msg, str)
                and record.msg.startswith(_LOOKUP_PREFIXES)):
            keys.add(record.args[1])
        return record.levelno >= passed

    level = log.level
    log.setLevel(logging.DEBUG)
    log.addFilter(lookups)
    try:
        yield keys
    finally:
        log.removeFilter(lookups)
        log.setLevel(level)
