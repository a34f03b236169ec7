"""Process set-up every entry point shares: the platform the
environment asked for, and where the persistent compilation cache lives.

Both either hold or raise.  A run that asked for one backend and got
another, or that could not place its cache, is not the run the caller
asked for, and nothing downstream could tell.
"""

from __future__ import annotations

import os
import pathlib

#: the cache directory when the environment names none: a fixed path
#: inside the checkout, because a directory that moves between runs
#: never hits
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parent.parent \
    / ".jax_cache"


def compile_cache_dir() -> str:
    """The one place the persistent compilation cache may live:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else
    :data:`DEFAULT_CACHE_DIR`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(DEFAULT_CACHE_DIR)


def apply_compile_cache() -> str:
    """Turn on jax's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory.

    Where the environment named the directory jax has already read it
    and code sets none.  The size and compile-time thresholds drop to
    "cache everything" unless the environment chose its own: a protocol
    deployment compiles many small programs in every process, each
    under jax's 1 s default."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(DEFAULT_CACHE_DIR))
    for knob, value in (
            ("jax_persistent_cache_min_compile_time_secs", 0.0),
            ("jax_persistent_cache_min_entry_size_bytes", -1)):
        if knob.upper() not in os.environ:
            jax.config.update(knob, value)
    return compile_cache_dir()


def apply_platform_env() -> None:
    """Raise unless the effective backend is one ``JAX_PLATFORMS``
    names (no-op when the variable is unset)."""
    plat = os.environ.get("JAX_PLATFORMS")
    if not plat:
        return
    import jax

    got = jax.default_backend()
    if got not in plat.split(","):
        raise RuntimeError(
            f"JAX_PLATFORMS={plat} was requested but the effective "
            f"backend is {got!r}")
