"""Where the persistent JAX compilation cache lives, for every entry point.

`enable_compile_cache` is called once at start-up by `chip_smoke.py`,
`examples/serve_streaming.py` and the `benchmarks/` entry points. It uses
``JAX_COMPILATION_CACHE_DIR`` when that is set and otherwise one fixed
directory inside the checkout (`DEFAULT_DIR`, ignored by git). The path
never depends on the process, the time or a temporary directory, so a
second run from the same checkout finds what the first one compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["DEFAULT_DIR", "enable_compile_cache"]

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every program; returns its path.

    In `DEFAULT_DIR` the cache is unbounded whatever
    ``JAX_COMPILATION_CACHE_MAX_SIZE`` says. A bounded cache keeps an
    access-time file beside each entry and fails every later write
    (FileNotFoundError on ``<key>-atime``) once it meets an entry an
    unbounded process wrote, so all writers of one directory must agree.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_compilation_cache_dir", path)
    # key entries on the programs' metadata too: a program cached by a
    # checkout with other named scopes (`serve_tick`'s kws_*) would
    # otherwise come back with that checkout's op names in its profile
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # the serving programs compile in about a second each; cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
