"""The one place that says where JAX's persistent compilation cache
lives.

Placed from outside: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has
already read it at import and nothing here touches the directory —
whoever runs the program (the chip tool, a CI job) decides, and a cache
kept there is found again by the next run. Otherwise the cache goes to a
fixed path inside the checkout, ``<checkout>/.cache/jax`` (gitignored):
the path is part of every entry's key, so a directory named after a pid,
a temp name or a time would never hit.

``chip_smoke.py``, ``perfbench/harness/cells.py`` and
``tests/conftest.py`` all call `enable`; nothing else in the repo sets a
compilation-cache directory. (``serving/compile_cache.py`` is a
different thing: an opt-in store of serialized executables for one
engine, under a directory the caller names.)
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> Path:
    return Path(__file__).resolve().parents[2] / ".cache" / "jax"


def enable(min_compile_time_secs: Optional[float] = None) -> str:
    """Turn the persistent compilation cache on and return its
    directory. ``min_compile_time_secs`` lowers JAX's keep-threshold
    (default 1 s) for callers whose programs are many and small — the
    CPU test suite passes 0."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(default_dir())
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    if min_compile_time_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_time_secs))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def in_use() -> bool:
    """Whether a persistent compilation cache is configured, by `enable`
    or from outside."""
    import jax

    return bool(jax.config.jax_enable_compilation_cache
                and jax.config.jax_compilation_cache_dir)
