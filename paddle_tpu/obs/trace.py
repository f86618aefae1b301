"""Structured tracing for the pipelined hot loop — thread-aware spans
emitted as Chrome Trace Event Format JSON (ISSUE 4 tentpole).

PR 2's telemetry answers "how fast is each call"; PR 3 made the answer
multi-threaded (main loop + `GroupStager` thread + `data.buffered` fill
thread). A scalar-per-call JSONL cannot show *where* the overlap breaks
down — that needs a timeline a human can open. This module records
begin/end spans per thread and serializes them in the Trace Event Format
that Perfetto (https://ui.perfetto.dev) and `chrome://tracing` consume —
the same container the JAX/XLA profiler ecosystem standardized on, so the
host-side story lines up with device profiles side by side.

Design rules:

- **One rule decides whether a span is recorded, and there is no
  knob.** A call site's spans are live when a tracer is attached to
  its object (``Trainer(tracer=)``, ``ContinuousBatchingScheduler(
  tracer=)``, ``engine.tracer``) OR a ``jax.profiler`` session is
  active in the process (``jax.profiler.TraceAnnotation.is_enabled()``,
  which the program can observe by itself). In the second case they go
  to the one process-wide :func:`session_tracer`. :func:`live` is that
  rule; :func:`tspan` and every ``self.tracer`` guard of the trainer,
  the scheduler and the engine go through it. With neither, a call
  site costs the ``is_enabled()`` test and nothing else
  (``tests/test_trace.py`` and ``tests/test_program_spans.py`` pin it).
- **Spans are host-side and cheap.** One `perf_counter_ns` pair + one
  deque append per span; no device interaction, no fences, no extra
  dispatches.
- **On the device trace's clock.** While a profiler session is active
  every span is also a ``jax.profiler.TraceAnnotation`` named
  ``paddle_tpu:<name>`` with the span's facts: an event of the trace's
  ``/host:CPU`` plane, on the same clock as the device's ``XLA Ops``.
  ``jax.profiler.start_trace`` round any entry point is all an
  operator needs to get host and device on one timeline.
- **Thread-aware by construction.** Events carry the OS thread id;
  ``thread_name`` metadata events name the main loop, the
  ``host_pipeline.stager`` thread, and the ``data.buffered.fill`` thread
  in the viewer.
- **Flow events link a group across threads.** A staged group's life —
  stack+shard on the stager thread, dispatch on the main thread, drain
  later still — is connected with ``s``/``t``/``f`` flow events sharing
  one flow id, so host/device overlap (or its absence) is visually
  auditable: the arrows cross threads exactly where the pipeline hides
  work.
- **Bounded memory.** The event buffer is a ring (``max_events``);
  long runs keep the most recent window, which is also what the anomaly
  flight recorder snapshots into a forensics bundle
  (:mod:`paddle_tpu.obs.anomaly`).

Usage::

    from paddle_tpu.obs import Tracer
    tracer = Tracer()
    trainer = Trainer(..., telemetry=tel, tracer=tracer)
    trainer.train(...)
    tracer.save("trace.json")      # open in ui.perfetto.dev
"""

from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import json
import logging
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "tspan", "live", "session_tracer", "self_times",
           "starved_by_span", "starved_in_window", "traced", "jax_profile",
           "ANNOTATION_PREFIX", "NULL_SPAN", "RETROACTIVE"]

_log = logging.getLogger("paddle_tpu.trace")

# Shared no-op context for tracer-off call sites: stateless, so one
# instance is safe across threads and reentrant use. It yields None
# where a live span yields itself (``sp.set(...)`` needs the test).
NULL_SPAN = contextlib.nullcontext()


# what a span is called in the profiler's trace: ``paddle_tpu:<name>``
ANNOTATION_PREFIX = "paddle_tpu:"
# the category of a span stamped after the fact (``Tracer.complete``)
RETROACTIVE = "paddle_tpu.retroactive"

# True while a ``jax.profiler`` session is active in this process
_session_active = TraceAnnotation.is_enabled

_session: Optional["Tracer"] = None
_session_lock = threading.Lock()


def session_tracer() -> "Tracer":
    """The one process-wide tracer (default clock) that call sites with
    no tracer attached record into while a ``jax.profiler`` session is
    active. Readers take its spans with ``between(lo, hi)``, two
    readings of ``time.perf_counter()``."""
    global _session
    if _session is None:
        with _session_lock:
            if _session is None:
                _session = Tracer()
    return _session


def live(tracer: Optional["Tracer"]) -> Optional["Tracer"]:
    """THE rule: the tracer a call site records into, or None. The
    attached ``tracer`` where there is one; the session tracer while a
    ``jax.profiler`` session is active; else None, and the call site
    does nothing more."""
    if tracer is not None:
        return tracer
    if _session_active():
        return session_tracer()
    return None


def tspan(tracer: Optional["Tracer"], name: str, **kw):
    """Null-safe span helper: a real span of :func:`live`'s tracer, or
    the shared no-op context (which yields None) when there is none —
    the hot loop never branches further than this."""
    tracer = live(tracer)
    if tracer is None:
        return NULL_SPAN
    return tracer.span(name, **kw)


def traced(name: str):
    """Decorator: each call of the method is the span ``name`` of
    :func:`live`'s tracer for the object's ``tracer`` attribute (None
    where it has none yet, as inside ``__init__``). For entry points
    that run once and lower little (the engine's ``__init__`` and
    ``warmup``): the wrapper is one more Python frame under everything
    the method calls, and JAX's lowering pays for each frame between
    the entry point and a jit call (PERF.md, PR 25)."""
    def decorate(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kw):
            with tspan(getattr(self, "tracer", None), name):
                return fn(self, *args, **kw)
        return wrapper
    return decorate


@contextlib.contextmanager
def jax_profile(log_dir: str):
    """Best-effort programmatic ``jax.profiler`` capture window. A
    backend/profiler failure (double start, unsupported transport) is
    logged and the body still runs — a diagnostic capture must never
    kill the training it diagnoses."""
    import jax
    started = False
    try:
        jax.profiler.start_trace(log_dir)
        started = True
    except Exception:                        # pragma: no cover - backend
        _log.exception("jax.profiler.start_trace(%r) failed; continuing "
                       "without device capture", log_dir)
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:                # pragma: no cover - backend
                _log.exception("jax.profiler.stop_trace failed")


def _json_safe(v):
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    return str(v)


class _Span:
    """One span being recorded: the context manager ``Tracer.span``
    returns. ``t0_ns`` / ``t1_ns`` are its ``perf_counter_ns`` stamps,
    readable after entry / exit, so a caller that needs the duration
    for its own books (the trainer's ``StatSet`` and telemetry) reads
    this one clock pair instead of taking another; ``t0_us`` / ``t1_us``
    are the same instants on the tracer's time base (what a retroactive
    span that starts or ends with this one is stamped with). :meth:`set`
    adds facts known only once the body ran (tokens retired, admissions
    made)."""

    __slots__ = ("_tracer", "_name", "_args", "_flows", "_tid", "_ann",
                 "t0_ns", "t1_ns", "t0_us", "t1_us")

    def __init__(self, tracer, name, flows, args):
        self._tracer = tracer
        self._name = name
        self._flows = flows
        self._args = args
        self._ann = None

    def set(self, **facts) -> None:
        self._args.update(facts)
        if self._ann is not None:
            self._ann.set_metadata(**facts)

    def __enter__(self):
        tr = self._tracer
        self._tid = tid = threading.get_ident()
        tr._note_thread(tid)
        if _session_active():
            self._ann = TraceAnnotation(ANNOTATION_PREFIX + self._name,
                                        **self._args)
            self._ann.__enter__()
        self.t0_ns = t0 = time.perf_counter_ns()
        self.t0_us = (tr._now_us() if tr._clock is not None
                      else (t0 - tr.epoch_ns) / 1e3)
        return self

    def __exit__(self, *exc):
        tr = self._tracer
        self.t1_ns = t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.t1_us = end = (tr._now_us() if tr._clock is not None
                            else (t1 - tr.epoch_ns) / 1e3)
        tr._emit_span(self._name, self._tid, self.t0_us, end, *self._flows,
                      self._args)
        return False


_NO_FLOWS = (None, None, None)


class Tracer:
    """Thread-aware span recorder emitting Chrome Trace Event Format.

    Every finished span becomes one complete (``ph="X"``) event with
    microsecond ``ts``/``dur`` relative to the tracer's construction;
    optional flow ids attach ``s``/``t``/``f`` flow events at the span's
    start timestamp (inside the slice, so viewers bind the arrow to it).
    All methods are thread-safe; spans may begin and end on any thread
    (each span's events carry the thread it ran on). While a
    ``jax.profiler`` session is active a span is also a
    ``TraceAnnotation`` (``paddle_tpu:<name>``) of the profiler's trace;
    :meth:`complete` and :meth:`instant` stay tracer-only (an annotation
    cannot be written after the fact).

    Args:
      max_events: ring-buffer bound on retained events (oldest dropped;
        ``dropped_events`` counts evictions). Metadata (process/thread
        names) is kept separately and never evicted.
      clock: optional injectable clock (``callable() -> seconds``).
        When set, every timestamp is ``clock() * 1e6`` — an ABSOLUTE
        microsecond time base shared by whoever else reads the same
        clock. This is the fleet-tracing mode (ISSUE 17): parent and
        child replicas all stamp spans with the message-carried fleet
        clock, so a SimClock drill's merged timeline is deterministic
        and cross-process spans land on one comparable axis. Default
        (None): ``perf_counter_ns`` less :attr:`epoch_ns`, the
        ``perf_counter_ns`` reading at construction — so an event's
        ``epoch_ns / 1e9 + ts / 1e6`` is a ``time.perf_counter()``
        reading.
    """

    def __init__(self, max_events: int = 200_000, clock=None):
        self.pid = os.getpid()
        self._clock = clock
        self.epoch_ns = time.perf_counter_ns()
        self._events: collections.deque = collections.deque(
            maxlen=int(max_events))
        self._meta: List[Dict[str, Any]] = [
            {"ph": "M", "name": "process_name", "pid": self.pid, "tid": 0,
             "args": {"name": "paddle_tpu"}}]
        self._lock = threading.Lock()
        self._seen_threads: Dict[int, str] = {}
        self._flow_seq = itertools.count(1)
        self.dropped_events = 0

    # -- clock / bookkeeping -------------------------------------------------

    def _now_us(self) -> float:
        if self._clock is not None:
            return float(self._clock()) * 1e6
        return (time.perf_counter_ns() - self.epoch_ns) / 1e3

    def now_us(self) -> float:
        """The tracer's current timestamp (us) — for callers recording
        already-timed spans via :meth:`complete`."""
        return self._now_us()

    def at_us(self, seconds: float) -> float:
        """The timestamp (us) of ``seconds``, an earlier reading of the
        tracer's clock (``time.perf_counter()`` where none was
        injected): how a retroactive span is put on the time base."""
        if self._clock is not None:
            return float(seconds) * 1e6
        return float(seconds) * 1e6 - self.epoch_ns / 1e3

    def _note_thread(self, tid: int) -> None:
        # Compare the LIVE name every call, not just first-seen: OS thread
        # idents are recycled (a per-pass stager thread can inherit the
        # ident of pass 1's dead fill thread), and a stale cache would
        # merge two distinct threads' spans onto one mislabelled track.
        name = threading.current_thread().name
        if self._seen_threads.get(tid) == name:
            return
        with self._lock:
            if self._seen_threads.get(tid) != name:
                self._seen_threads[tid] = name
                self._meta.append(
                    {"ph": "M", "name": "thread_name", "pid": self.pid,
                     "tid": tid, "args": {"name": name}})

    def _append(self, ev: Dict[str, Any]) -> None:
        # deque.append is atomic, so recording takes no lock (the lock
        # is for the readers' snapshots); under contention the eviction
        # count may run a few short
        events = self._events
        if len(events) == events.maxlen:
            self.dropped_events += 1
        events.append(ev)

    # -- recording -----------------------------------------------------------

    def new_flow(self) -> int:
        """A fresh flow id for linking spans across threads."""
        return next(self._flow_seq)

    def span(self, name: str, flow_start: Optional[int] = None,
             flow_step: Optional[int] = None, flow_end: Optional[int] = None,
             **args) -> _Span:
        """Record one span around the ``with`` body. ``flow_start`` /
        ``flow_step`` / ``flow_end`` emit the matching flow event (phases
        ``s``/``t``/``f``) bound to this span, linking it to the other
        spans carrying the same id. ``with ... as sp`` gives the
        :class:`_Span` (``sp.set(**facts)``, ``sp.t0_ns``)."""
        flows = (_NO_FLOWS if flow_start is flow_step is flow_end is None
                 else (flow_start, flow_step, flow_end))
        return _Span(self, name, flows, args)

    def complete(self, name: str, t0_us: float,
                 t1_us: Optional[float] = None,
                 flow_start: Optional[int] = None,
                 flow_step: Optional[int] = None,
                 flow_end: Optional[int] = None, **args) -> None:
        """Record an ALREADY-TIMED span with explicit microsecond
        timestamps (the tracer's time base, see :meth:`at_us`). This is
        how retroactive spans are stamped: a scheduler records a
        request's queue wait only at admit time, from the request's own
        submit timestamp (ISSUE 17). Their category is
        :data:`RETROACTIVE`: they cover no code of their own."""
        tid = threading.get_ident()
        self._note_thread(tid)
        self._emit_span(name, tid, float(t0_us),
                        float(t0_us if t1_us is None else t1_us),
                        flow_start, flow_step, flow_end, args,
                        cat=RETROACTIVE)

    def _emit_span(self, name, tid, t0, t1, flow_start, flow_step,
                   flow_end, args, cat="paddle_tpu") -> None:
        ev: Dict[str, Any] = {
            "ph": "X", "name": name, "cat": cat,
            "pid": self.pid, "tid": tid,
            "ts": t0, "dur": max(t1 - t0, 0.001)}
        if args:
            ev["args"] = {k: _json_safe(v) for k, v in args.items()}
        self._append(ev)
        for fid, ph in ((flow_start, "s"), (flow_step, "t"),
                        (flow_end, "f")):
            if fid is None:
                continue
            fe = {"ph": ph, "name": "group", "cat": "flow",
                  "id": int(fid), "pid": self.pid, "tid": tid, "ts": t0}
            if ph == "f":
                fe["bp"] = "e"       # bind to the enclosing slice
            self._append(fe)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker (``ph="i"``) — e.g. an anomaly verdict
        pinned onto the timeline at trigger time."""
        tid = threading.get_ident()
        self._note_thread(tid)
        ev: Dict[str, Any] = {
            "ph": "i", "name": name, "cat": "paddle_tpu", "s": "t",
            "pid": self.pid, "tid": tid, "ts": self._now_us()}
        if args:
            ev["args"] = {k: _json_safe(v) for k, v in args.items()}
        self._append(ev)

    # -- output --------------------------------------------------------------

    def events(self) -> List[Dict[str, Any]]:
        """Snapshot: metadata events + every retained span/flow event."""
        with self._lock:
            return list(self._meta) + list(self._events)

    def between(self, lo_s: float, hi_s: float) -> List[Dict[str, Any]]:
        """The retained spans (``ph="X"``) that start and end between
        two readings of the tracer's clock (``time.perf_counter()``
        where none was injected), in the order recorded: how a reader
        keeps what lies inside its own window."""
        lo, hi = self.at_us(lo_s), self.at_us(hi_s)
        with self._lock:
            evs = list(self._events)
        return [e for e in evs if e["ph"] == "X" and e["ts"] >= lo
                and e["ts"] + e["dur"] <= hi]

    def drain_events(self) -> List[Dict[str, Any]]:
        """Pop every buffered span/flow/instant event (metadata stays).
        The child→parent span-batch shipping primitive (ISSUE 17): a
        process replica drains its tracer into each tick reply, so
        spans ride the transport the work already uses — no
        side-channel files, nothing to garbage-collect on a SIGKILL."""
        evs, events = [], self._events
        with self._lock:
            # popleft, not list-then-clear: an append from another
            # thread between the two would be lost
            while events:
                evs.append(events.popleft())
        return evs

    def tail(self, n: int) -> List[Dict[str, Any]]:
        """Metadata + the most recent ``n`` events (the flight-recorder
        window); ``n <= 0`` returns metadata only (``[-0:]`` would be the
        whole ring)."""
        with self._lock:
            evs = list(self._events)
        n = int(n)
        return list(self._meta) + (evs[-n:] if n > 0 else [])

    def chrome_trace(self, events: Optional[List[Dict[str, Any]]] = None
                     ) -> Dict[str, Any]:
        """The Trace Event Format container (`traceEvents` sorted by
        timestamp — viewers do not require it, but the bench gate checks
        monotonicity on exactly this serialization)."""
        evs = self.events() if events is None else list(events)
        evs.sort(key=lambda e: e.get("ts", -1.0))
        clock = ("injected clock (absolute us)"
                 if self._clock is not None else
                 "perf_counter_ns (us since tracer construction)")
        return {"traceEvents": evs, "displayTimeUnit": "ms",
                "otherData": {"producer": "paddle_tpu.obs.trace",
                              "clock": clock,
                              "dropped_events": self.dropped_events}}

    def save(self, path: str) -> str:
        """Write the Chrome trace JSON (open in ui.perfetto.dev)."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def clear(self) -> None:
        with self._lock:
            self._events.clear()


def self_times(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Each span (``ph="X"``) of ``events``, in order of start, copied
    with a ``"self"`` key: its duration (us) less what its child spans
    on the same thread cover. A layer's own time is the sum of the self
    times of its spans; filter ``events`` to a few names first and a
    span's self time is its duration less just those (``train_step``
    less its ``loss_fetch``).

    A child is a span that starts and ends inside another on one
    ``(pid, tid)``. A span that only overlaps the one before it (a
    retroactive ``queue_wait``, stamped from a request's submit time)
    is nobody's child and nobody's parent from where it is overrun."""
    spans = sorted((e for e in events if e.get("ph") == "X"),
                   key=lambda e: (e["pid"], e["tid"], e["ts"], -e["dur"]))
    out: List[Dict[str, Any]] = []
    stack: List[Dict[str, Any]] = []         # open ancestors, one thread
    lane = None
    for ev in spans:
        if (ev["pid"], ev["tid"]) != lane:
            lane, stack = (ev["pid"], ev["tid"]), []
        end = ev["ts"] + ev["dur"]
        # drop what ended before this span starts, and what it overruns
        while stack and stack[-1]["ts"] + stack[-1]["dur"] < end:
            stack.pop()
        row = dict(ev, self=ev["dur"])
        if stack:
            stack[-1]["self"] -= ev["dur"]
        stack.append(row)
        out.append(row)
    out.sort(key=lambda e: e["ts"])
    return out


def starved_by_span(events: List[Dict[str, Any]],
                    window: Optional[Tuple[float, float]] = None
                    ) -> Dict[str, float]:
    """Where the host was while the chip had nothing queued:
    ``{span name: seconds}`` over every ``starved`` stretch of ``events``
    (``serve/engine.py``: from a drain's fetch to the next compiled
    call's return), clipped to ``window`` (``(lo, hi)`` in the events'
    microseconds) where one is given. Each piece of a stretch goes to the
    INNERMOST span around code (not :data:`RETROACTIVE`) that covers it
    on the stretch's own ``(pid, tid)``; ``""`` is a piece no span covers:
    the caller's code between two steps. The values add up to the
    stretches' seconds."""
    lo, hi = window if window is not None else (-math.inf, math.inf)
    lanes: Dict[Tuple[int, int], List[Dict[str, Any]]] = {}
    for e in events:
        if (e.get("ph") == "X" and e["ts"] < hi
                and e["ts"] + e["dur"] > lo):
            lanes.setdefault((e["pid"], e["tid"]), []).append(e)
    out: Dict[str, float] = {}
    for lane in lanes.values():
        stretches = sorted((max(e["ts"], lo), min(e["ts"] + e["dur"], hi))
                           for e in lane if e["name"] == "starved")
        if not stretches:
            continue
        code = [e for e in lane if e.get("cat") != RETROACTIVE]
        starts = sorted(code, key=lambda e: e["ts"])
        ends = sorted(code, key=lambda e: e["ts"] + e["dur"])
        cuts = sorted({t for e in code for t in (e["ts"], e["ts"] + e["dur"])}
                      | {t for s in stretches for t in s})
        active: Dict[int, Dict[str, Any]] = {}
        i = j = k = 0
        # the open spans between two neighbouring cuts never change, and
        # the innermost of them is the one that began last
        for x, y in zip(cuts, cuts[1:]):
            while i < len(starts) and starts[i]["ts"] <= x:
                active[id(starts[i])] = starts[i]
                i += 1
            while j < len(ends) and ends[j]["ts"] + ends[j]["dur"] <= x:
                active.pop(id(ends[j]), None)
                j += 1
            while k < len(stretches) and stretches[k][1] <= x:
                k += 1
            if k == len(stretches):
                break
            if stretches[k][0] > x:
                continue                    # between two stretches
            inner = max(active.values(), key=lambda e: (e["ts"], -e["dur"]),
                        default=None)
            name = inner["name"] if inner is not None else ""
            out[name] = out.get(name, 0.0) + (y - x) / 1e6
    return out


def starved_in_window(tracer: "Tracer", lo_s: float, hi_s: float
                      ) -> Tuple[Dict[str, float], int]:
    """:func:`starved_by_span` over ``tracer``'s events clipped to two
    readings of its clock, and the number of ``engine_tick`` spans that
    lie between them: what the benchmark's ``starved`` readers
    (``benchmarks/layer_metrics/``) sum and divide by."""
    by = starved_by_span(tracer.events(), (tracer.at_us(lo_s),
                                           tracer.at_us(hi_s)))
    ticks = sum(e["name"] == "engine_tick"
                for e in tracer.between(lo_s, hi_s))
    return by, ticks
