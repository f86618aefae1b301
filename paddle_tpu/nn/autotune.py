"""Per-shape kernel autotuner with on-disk persistence (ISSUE 16).

The Pallas kernels' block sizes were hand-picked on one chip
(``_auto_block``'s v5e measurement); this module makes the selection
empirical and *remembered*: candidate configurations are timed once per
``(kernel, shape, dtype, platform)`` key and the winner lands in a JSON
cache file, so the second process ever to see a shape pays **zero
trials**. The same machinery hosts program-level entries — the serving
engine's warmup registers its prefill/tick timings under its shape key,
which is what lets a supervisor-restarted replica prove it came up warm
(0 trials, cache hit) instead of re-measuring.

Contract (the zero-overhead pin, PR-2/4 style):

- **Disabled by default.** With no cache directory configured —
  :func:`enable` not called and ``PADDLE_TPU_AUTOTUNE_CACHE`` unset —
  :func:`choose` returns the caller's default config untimed, with zero
  trials and zero disk I/O. Callers' dispatch behavior is byte-identical
  to the pre-autotune heuristic path.
- **Explicit overrides bypass everything.** A caller that passes
  explicit ``block_q``/``block_k`` never reaches :func:`choose` at all
  (the kernels resolve explicit blocks before consulting the tuner).
- **A kernel no candidate can run raises.** One failing candidate is
  skipped; when all fail the last error propagates (nothing stored).
- **Corrupt caches degrade silently.** A truncated, unparseable, or
  schema-stale cache file reads as empty and the key re-tunes; the
  atomic-rename write (merge-with-disk, tmp + ``os.replace``, the
  ``save_variables_npz`` pattern) keeps the file a complete JSON
  document under concurrent writers — last writer wins per key, never a
  torn read. A cache is advice, not state: losing it costs trials, not
  correctness.

Trial timing goes through :func:`time_kernel`, which fences with
``jax.block_until_ready`` and discards the first (compile) iteration —
timing the enqueue or the compile instead of the kernel was the bug the
shared util exists to delete.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

SCHEMA_VERSION = 1
ENV_VAR = "PADDLE_TPU_AUTOTUNE_CACHE"
CACHE_BASENAME = "autotune.json"

# tri-state: None = follow the environment variable; "" = forced off
# (disable() beats an inherited env var); non-empty = enable()'d dir
_dir_override: Optional[str] = None

_stats = {"trials": 0, "hits": 0, "misses": 0}

_AUTO = object()          # time_kernel fence sentinel: default jax fence


# -- enable / disable ------------------------------------------------------

def enable(cache_dir: str) -> None:
    """Turn autotuning on with ``cache_dir`` holding the JSON cache."""
    global _dir_override
    _dir_override = str(cache_dir)


def disable() -> None:
    """Force autotuning off (wins over the environment variable)."""
    global _dir_override
    _dir_override = ""


def reset() -> None:
    """Back to environment-variable control (test hygiene)."""
    global _dir_override
    _dir_override = None


def cache_dir() -> Optional[str]:
    """The active cache directory, or None when tuning is off."""
    if _dir_override is not None:
        return _dir_override or None
    return os.environ.get(ENV_VAR) or None


def is_enabled() -> bool:
    return cache_dir() is not None


def cache_file() -> Optional[str]:
    d = cache_dir()
    return os.path.join(d, CACHE_BASENAME) if d else None


# -- stats (the telemetry satellite reads these) ---------------------------

def stats() -> Dict[str, int]:
    """``{"trials", "hits", "misses"}`` counters for this process."""
    return dict(_stats)


def reset_stats() -> None:
    for k in _stats:
        _stats[k] = 0


# -- cache file ------------------------------------------------------------

def _load(path: str) -> Dict[str, Any]:
    """Read the cache's entries. Missing, unparseable, truncated, or
    schema-stale files all read as empty — the silent-re-tune rule."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(data, dict) or data.get("schema") != SCHEMA_VERSION:
        return {}
    entries = data.get("entries")
    return entries if isinstance(entries, dict) else {}


def _store(path: str, key: str, entry: Dict[str, Any]) -> None:
    """Merge ``{key: entry}`` with whatever is on disk and atomically
    replace the file. Two concurrent writers each produce a complete
    document; the loser's *other* keys survive in the winner's merge
    unless both tuned in the same instant — worst case a key re-tunes."""
    entries = _load(path)
    entries[key] = entry
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump({"schema": SCHEMA_VERSION, "entries": entries}, f,
                  indent=0, sort_keys=True)
    os.replace(tmp, path)


def make_key(kernel: str, *, shape: Sequence[int], dtype: Any,
             platform: Optional[str] = None,
             extra: Sequence[Any] = ()) -> str:
    """Canonical cache key: kernel name, operand shape, dtype, platform
    (the pluggable-backend seam — a CPU-tuned block is not a TPU-tuned
    block), plus kernel-specific flags (causal, segmented, ...)."""
    if platform is None:
        import jax
        platform = jax.default_backend()
    parts = [str(kernel), "x".join(str(int(s)) for s in shape),
             str(dtype), str(platform)]
    parts += [str(e) for e in extra]
    return "|".join(parts)


# -- timing ----------------------------------------------------------------

def time_kernel(fn: Callable[..., Any], *args, warmup: int = 1,
                iters: int = 1, fence: Any = _AUTO,
                **kwargs) -> Tuple[float, Any]:
    """Steady-state timing of ``fn(*args, **kwargs)``: run ``warmup``
    discarded iterations first (the first call pays tracing +
    compilation — including it was the classic autotune bug), then time
    ``iters`` iterations, fencing the last result so async dispatch
    can't make the enqueue look like the kernel. Returns
    ``(total_seconds, last_result)`` for the timed iterations.

    ``fence`` defaults to ``jax.block_until_ready``; pass ``fence=None``
    for callables that drain internally (``DecodeEngine.decode_tick``
    ends on a host ``np.asarray``)."""
    if fence is _AUTO:
        import jax
        fence = jax.block_until_ready
    out = None
    for _ in range(max(0, int(warmup))):
        out = fn(*args, **kwargs)
        if fence is not None:
            fence(out)
    iters = max(1, int(iters))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kwargs)
    if fence is not None:
        fence(out)
    return time.perf_counter() - t0, out


# -- selection -------------------------------------------------------------

def choose(kernel: str, *, key: str,
           candidates: Sequence[Dict[str, Any]],
           runner: Callable[..., Any],
           default: Dict[str, Any]) -> Dict[str, Any]:
    """Pick a config for ``kernel`` at cache key ``key``.

    Disabled → ``default``, untimed, no I/O (the zero-overhead pin).
    Cache hit → the stored config, zero trials. Miss → every candidate
    runs once through :func:`time_kernel` via ``runner(**config)`` (one
    discarded compile iteration + one timed), the winner is persisted,
    and candidates that raise (mis-tiled on this backend) are skipped.
    If EVERY candidate fails, the last failure is raised and nothing is
    stored: a kernel that cannot compile at all (a Mosaic refusal) must
    be seen, not papered over with an untried default, and a transient
    failure must not poison the cache."""
    if not is_enabled():
        return dict(default)
    path = cache_file()
    entry = _load(path).get(key)
    if isinstance(entry, dict) and isinstance(entry.get("config"), dict):
        _stats["hits"] += 1
        return dict(entry["config"])
    _stats["misses"] += 1
    best: Optional[Dict[str, Any]] = None
    best_t = float("inf")
    tried = 0
    failure: Optional[Exception] = None
    for cand in (list(candidates) or [dict(default)]):
        try:
            t, _ = time_kernel(lambda: runner(**cand))
        except Exception as e:
            failure = e
            continue
        tried += 1
        _stats["trials"] += 1
        if t < best_t:
            best, best_t = dict(cand), t
    if best is None:
        raise failure
    _store(path, key, {"config": best, "best_s": best_t,
                       "trials": tried, "kernel": kernel})
    return best
