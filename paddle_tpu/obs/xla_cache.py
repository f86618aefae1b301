"""The persistent XLA compilation cache, placed from outside the program.

One helper owns where compiled executables are kept. :func:`setup` is
called before the first compile by every entry point that compiles
(``chip_smoke.py``, ``bench.py``, ``train/cli.py``,
``serve.replica_proc.main``):

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and
  the program sets no directory in code: whoever runs the program
  (a shell, a launcher, a parent placing a child's cache through the
  child's environment) decides;
- where it is not set, the cache is :data:`DEFAULT_DIR`, one fixed,
  git-ignored directory inside the checkout.

The directory is part of the cache key, so it is never a temporary name,
a pid or a timestamp: a directory that moves never hits.

Every program is cached, however small or quick to compile: the
cold-versus-warm spawn drill runs tiny CPU programs and must see them
hit.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_active: Optional[str] = None


def setup() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory. Call before the first compile."""
    import jax
    d = os.environ.get(ENV_VAR)
    if not d:
        d = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", d)
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    global _active
    _active = d
    return d


def active_dir() -> Optional[str]:
    """The directory a prior :func:`setup` activated in this process
    (None = cache not configured here)."""
    return _active


def cache_entry_count(cache_dir: Optional[str] = None) -> int:
    """Number of serialized executables in ``cache_dir`` (default: the
    active dir). The warmup paths diff this across a compile to report
    ``cache_hit``: no new entries ⇒ the executable came from disk."""
    d = cache_dir or _active
    if not d or not os.path.isdir(d):
        return 0
    return sum(1 for n in os.listdir(d) if n.endswith("-cache"))
