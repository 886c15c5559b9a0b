"""Persistent XLA compile cache, placed from outside or at one fixed path.

`JAX_COMPILATION_CACHE_DIR` set: jax reads it itself and this sets nothing.
Unset: `jax_compilation_cache_dir` becomes `<checkout>/.jax_cache`, computed
from this package's location — the directory is part of every entry's key,
so the driver, gang members and spawned workers must all resolve the same
one, and a path from `tempfile`, a pid or the clock would never hit. Called
where the main path first compiles (engine construction, `make_train_step`,
`chip_smoke.py`, `bench.py`) with the platform it compiles for: only TPU
programs are worth keeping (they take seconds to minutes), and reloading
XLA:CPU executables logs a machine-feature mismatch error for every entry.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def ensure_compile_cache(platform: str) -> str | None:
    """Returns the cache directory in force (None: nothing set, none used)."""
    import jax

    if platform == "tpu" and jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir
