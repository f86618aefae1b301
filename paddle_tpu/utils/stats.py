"""Hierarchical timers / stats — the Stat.h analog.

Reference: ``/root/reference/paddle/utils/Stat.h:63,230`` (``StatSet`` with
``REGISTER_TIMER*`` macros, periodic ``printAllStatus``) used through the hot
loop. TPU-native notes: device work is async, so timers that should include
device time must fence via ``jax.block_until_ready`` (the ``sync`` flag). For
kernel-level traces (the analog of ``hl_profiler_start``/nvprof) see
``paddle_tpu.obs.trace.jax_profile``.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Optional

import jax

__all__ = ["StatSet", "BarrierStat", "global_stats", "timer"]


class _Stat:
    __slots__ = ("total", "count", "max")

    def __init__(self):
        self.total = 0.0
        self.count = 0
        self.max = 0.0

    def add(self, dt: float):
        self.total += dt
        self.count += 1
        self.max = max(self.max, dt)


class StatSet:
    def __init__(self, name: str = "stats"):
        self.name = name
        self._stats: Dict[str, _Stat] = {}
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def time(self, key: str, sync=None):
        """Time a block; pass ``sync=array_or_pytree`` to block on device work."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if sync is not None:
                jax.block_until_ready(sync)
            dt = time.perf_counter() - t0
            with self._lock:
                self._stats.setdefault(key, _Stat()).add(dt)

    def add(self, key: str, dt: float):
        with self._lock:
            self._stats.setdefault(key, _Stat()).add(dt)

    def reset(self):
        with self._lock:
            self._stats.clear()

    def summary(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: {"total_s": s.total, "count": s.count,
                        "avg_ms": 1e3 * s.total / max(1, s.count),
                        "max_ms": 1e3 * s.max}
                    for k, s in self._stats.items()}

    def to_dict(self) -> Dict[str, object]:
        """JSON-ready export: name + the per-key summary (telemetry sinks
        consume this)."""
        return {"name": self.name, "stats": self.summary()}

    def report(self, top_n: Optional[int] = None) -> str:
        """Sorted summary, heaviest total time first — the reference's
        ``printAllStatus`` table (``utils/Stat.h``). ``top_n`` caps the
        rows (None = all)."""
        rows = sorted(self.summary().items(),
                      key=lambda kv: kv[1]["total_s"], reverse=True)
        shown = rows if top_n is None else rows[:top_n]
        lines = [f"=== {self.name} ==="]
        for k, v in shown:
            lines.append(f"  {k:<30s} n={v['count']:<6d} "
                         f"avg={v['avg_ms']:8.2f}ms max={v['max_ms']:8.2f}ms "
                         f"total={v['total_s']:.2f}s")
        if top_n is not None and len(rows) > top_n:
            lines.append(f"  ... {len(rows) - top_n} more")
        return "\n".join(lines)


_global = StatSet("global")


def global_stats() -> StatSet:
    return _global


def timer(key: str, sync=None):
    return _global.time(key, sync=sync)


class BarrierStat:
    """Distributed-imbalance telemetry — the ``BarrierStatSet`` analog
    (``utils/Stat.h:230``; the reference timed how unevenly trainers arrived
    at pserver barriers).

    On TPU the "barrier" is every collective: imbalance shows up as the
    spread of per-host step durations. Each host feeds its local step time
    into :meth:`update`; :meth:`gather` all-gathers the latest sample across
    hosts (one tiny psum-style collective, OUTSIDE the hot loop) and returns
    the spread statistics. Single-process runs report a spread of zero.
    """

    def __init__(self, name: str = "step_time"):
        self.name = name
        self._last: Optional[float] = None
        self._spreads = _Stat()

    def update(self, seconds: float) -> None:
        self._last = float(seconds)

    def gather(self) -> Dict[str, float]:
        """All-gather the latest sample across hosts; returns min/max/mean
        and relative spread ((max-min)/mean).

        Every host MUST call this the same number of times (SPMD contract);
        a host with no sample yet contributes a NaN sentinel rather than
        skipping the collective — an early return here would deadlock the
        other hosts inside the allgather."""
        import numpy as np
        local = np.float32(self._last if self._last is not None else np.nan)
        if jax.process_count() == 1:
            times = np.array([local])
        else:
            from jax.experimental import multihost_utils
            times = np.asarray(multihost_utils.process_allgather(local))
        times = times[np.isfinite(times)]
        if times.size == 0:
            return {}
        mn, mx, mean = float(times.min()), float(times.max()), \
            float(times.mean())
        spread = (mx - mn) / mean if mean else 0.0
        self._spreads.add(spread)
        return {f"{self.name}_min_s": mn, f"{self.name}_max_s": mx,
                f"{self.name}_mean_s": mean, f"{self.name}_spread": spread}

    def summary(self) -> Dict[str, float]:
        s = self._spreads
        return {"mean_spread": s.total / max(1, s.count),
                "max_spread": s.max, "samples": s.count}
