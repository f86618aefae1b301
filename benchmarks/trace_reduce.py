"""From a profiler trace (``*.xplane.pb``, read with nothing but
``jax.profiler.ProfileData``) to numbers: the device's busy seconds, the
time of every named device operation, and the idle gaps by what the host
was doing in them. Kept with the benchmark so that every PR computes the
same number in the same way.

What a trace of this installation looks like (TPU v5 lite): one plane
``/device:TPU:<n>`` per chip, whose line ``XLA Ops`` holds one event per
executed HLO instruction (``%fusion.12 = ...``; a Pallas kernel is the
custom call that carries the kernel's ``name``); host threads are lines
of the ``/host:CPU`` plane, where a ``jax.profiler.TraceAnnotation`` is
an event of its own name. The harness's spans all start with ``bench:``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Tuple

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# instructions that only contain others (their bodies are events too)
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def op_kind(name: str) -> str:
    """``fusion.12`` -> ``fusion``: instances of one kind add up."""
    return re.sub(r"[.\d]+$", "", name) or name


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def _event_text(ev) -> str:
    """The event's name with its string stats (a kernel's ``name`` can sit
    in ``tf_op`` / ``hlo_op`` metadata instead of the instruction name)."""
    parts = [ev.name]
    for _, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def reduce_trace(path: str, kernels: Iterable[str] = ()) -> Dict[str, Any]:
    """Read one ``.xplane.pb``. Times are seconds. Returns

    - ``window_s``: the ``bench:window`` span where the harness wrote one,
      else first device-op start to last device-op end;
    - ``busy_s``: seconds inside the window in which an operation ran on
      the device (union of the ``XLA Ops`` intervals), averaged over chips;
    - ``n_devices``; ``op_seconds``: ``{kind: seconds}`` summed over the
      window and averaged over chips; ``kernel_seconds``: the same for each
      name of ``kernels`` (matched anywhere in the event's text);
    - ``device_ops``: the ten kinds that took most time (``while`` and the
      other instructions that only contain others left out);
    - ``idle_gaps``: the ten host spans (``bench:*``) that account for
      most idle device time, each gap given to the span covering most of
      it, ``unattributed`` where none does.
    """
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    kernels = list(kernels)
    per_device: Dict[int, List[Tuple[float, float, str, str]]] = {}
    host_spans: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = per_device.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    text = _event_text(ev) if kernels else ev.name
                    evs.append((s, s + ev.duration_ns * 1e-9,
                                op_name(ev.name), text))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        host_spans.append((s, s + ev.duration_ns * 1e-9,
                                           ev.name))
    per_device = {d: evs for d, evs in per_device.items() if evs}
    if not per_device:
        raise ValueError(f"{path}: no device operation in the trace")
    windows = [(s, e) for s, e, n in host_spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(e[0] for evs in per_device.values() for e in evs)
        hi = max(e[1] for evs in per_device.values() for e in evs)
    n_dev = len(per_device)
    busy = 0.0
    op_seconds: Dict[str, float] = {}
    kernel_seconds = {k: 0.0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    gaps: List[Interval] = []
    for evs in per_device.values():
        inside = [(max(s, lo), min(e, hi), n, t) for s, e, n, t in evs
                  if min(e, hi) > max(s, lo)]
        merged = union((s, e) for s, e, _, _ in inside)
        busy += total(merged) / n_dev
        for s, e, n, text in inside:
            kind = op_kind(n)
            op_seconds[kind] = op_seconds.get(kind, 0.0) + (e - s) / n_dev
            for k in kernels:
                if k in text:
                    kernel_seconds[k] += (e - s) / n_dev
                    kernel_calls[k] += 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    named = [(s, e, n[len(SPAN_PREFIX):]) for s, e, n in host_spans
             if n != WINDOW_SPAN]
    idle: Dict[str, float] = {}
    for gs, ge in gaps:
        best, best_cover = "unattributed", 0.0
        for s, e, n in named:
            cover = min(e, ge) - max(s, gs)
            if cover > best_cover:
                best, best_cover = n, cover
        idle[best] = idle.get(best, 0.0) + (ge - gs) / n_dev
    top = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:10]]
    leaves = {k: v for k, v in op_seconds.items() if k not in CONTAINERS}
    return {"window_s": hi - lo, "busy_s": busy, "n_devices": n_dev,
            "op_seconds": op_seconds, "kernel_seconds": kernel_seconds,
            "kernel_calls": kernel_calls,
            "device_ops": top(leaves), "idle_gaps": top(idle)}
