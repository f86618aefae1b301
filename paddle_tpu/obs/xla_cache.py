"""The persistent XLA compilation cache, placed from outside the program.

One helper owns where compiled executables are kept. :func:`setup` is
called before the first compile by every entry point that compiles
(``chip_smoke.py``, ``benchmarks/run.py``, ``train/cli.py``,
``serve.replica_proc.main``, ``tests/drills.py``):

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

The same helper listens to what JAX reports of every compile
(:func:`listen`, which :func:`setup` calls): each trace, lowering and
backend compile with its function's name and seconds, and whether the
cache answered. :func:`compile_log` returns the rows. They say which
step recompiled and split set-up time, with no fence or lowering of the
program's own. Always recorded: compiles are rare and off the hot path.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional

from .trace import live

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
    listen()
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


# -- what JAX reports of every compile ---------------------------------------

# jax.monitoring's duration events, by the phase a row calls them
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"

_rows: collections.deque = collections.deque(maxlen=100_000)
_listening = False
_listen_lock = threading.Lock()
# JAX reports the cache's answer and its retrieval time without a name,
# just before the ``backend_compile`` row of the same program on the
# same thread: held here until that row takes them
_pending = threading.local()


def _on_event(event: str, **kw) -> None:
    if event == _HIT:
        _pending.hit = True
    elif event == _MISS:
        _pending.hit = False


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event == _RETRIEVAL:
        _pending.retrieval_s = float(seconds)
        return
    phase = PHASES.get(event)
    if phase is None:
        return
    row = {"t": time.perf_counter(), "phase": phase,
           "fun_name": str(kw.get("fun_name", "")),
           "seconds": float(seconds)}
    if phase == "backend_compile":
        # None: no persistent cache was asked (none configured)
        row["cache_hit"] = getattr(_pending, "hit", None)
        row["retrieval_s"] = getattr(_pending, "retrieval_s", 0.0)
        _pending.hit, _pending.retrieval_s = None, 0.0
    _rows.append(row)
    tracer = live(None)
    if tracer is not None:
        tracer.instant("compile", **{k: v for k, v in row.items()
                                     if k != "t"})


def listen() -> None:
    """Register the two ``jax.monitoring`` listeners, once a process
    (idempotent; :func:`setup` calls it, a test may call it alone and
    place no cache)."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        from jax import monitoring
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def compile_log() -> List[Dict[str, Any]]:
    """One row per trace, lowering and backend compile JAX has reported
    since :func:`listen`, oldest first: ``t`` (``time.perf_counter()``
    when it ENDED, so it ran from ``t - seconds``), ``phase``
    (``trace`` | ``lower`` | ``backend_compile``), ``fun_name``,
    ``seconds``; a ``backend_compile`` row also has ``cache_hit`` (None
    where no persistent cache was asked) and ``retrieval_s``, the part
    of its seconds spent reading the cache. A function traced inside
    another's trace has a row of its own inside the outer row's
    interval: sum intervals as a union, not row by row."""
    return list(_rows)
