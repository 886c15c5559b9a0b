"""Persistent XLA compile cache, placed from outside or at one fixed path.

`JAX_COMPILATION_CACHE_DIR` set: jax reads it itself and this sets nothing.
Unset: `jax_compilation_cache_dir` becomes `<checkout>/.jax_cache`, computed
from this package's location — the directory is part of every entry's key,
so the driver, gang members and spawned workers must all resolve the same
one, and a path from `tempfile`, a pid or the clock would never hit. Called
where the main path first compiles (engine construction, `make_train_step`,
`chip_smoke.py`) with the platform it compiles for: only TPU
programs are worth keeping (they take seconds to minutes), and reloading
XLA:CPU executables logs a machine-feature mismatch error for every entry.

The same call installs the process's compile counter: one
`jax.monitoring` listener that sums `backend_compile_duration` (every
program jax compiles OR loads from the persistent cache: on jax 0.9 the
event wraps the cache lookup) and, apart from it, the loads alone
(`cache_retrieval_time_sec`, nested inside the first), so that "did this
step compile" is a difference of two reads of `compile_totals()`.
"""

from __future__ import annotations

import os
import threading

# event -> where its count sits in _sums (its seconds follow)
_EVENTS = {"/jax/core/compile/backend_compile_duration": 0,
           "/jax/compilation_cache/cache_retrieval_time_sec": 2}
_lock = threading.Lock()
_sums = [0, 0.0, 0, 0.0]  # compiles, their seconds, cache loads, their seconds
_listening = False

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def _on_duration(event: str, seconds: float, **_kw) -> None:
    at = _EVENTS.get(event)
    if at is not None:
        with _lock:
            _sums[at] += 1
            _sums[at + 1] += seconds


def compile_totals() -> tuple:
    """`(compiles, compile_s, cache_loads, cache_load_s)` of this process
    since `ensure_compile_cache` was first called. A program loaded from the
    persistent cache counts in both pairs; `compile_s` holds its load."""
    return tuple(_sums)


def ensure_compile_cache(platform: str) -> str | None:
    """Returns the cache directory in force (None: nothing set, none used)."""
    global _listening
    import jax

    with _lock:
        listen, _listening = not _listening, True
    if listen:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if platform == "tpu" and jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir
