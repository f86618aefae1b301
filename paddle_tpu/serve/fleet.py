"""Multi-replica serving fleet (ISSUE 11): replica death is routine.

PR 10 made *training* recovery a supervised, continuously-fault-injected
subsystem; this module applies the same doctrine to serving. A
:class:`ServingFleet` runs N :class:`ReplicaWorker`\\ s — each one a
:class:`~paddle_tpu.serve.engine.DecodeEngine` +
:class:`~paddle_tpu.serve.scheduler.ContinuousBatchingScheduler` pair —
behind a :class:`~paddle_tpu.serve.router.FleetRouter`, and guarantees
that EVERY submitted request reaches a terminal ``finish_reason``
(``"length"|"eos"|"timeout"|"shed"``) no matter which replica dies,
stalls, or drains mid-flight.

The recovery contract, and how each piece is honest about what a
distributed deployment could actually know:

- **Death is observed, not announced.** A killed replica simply stops
  ticking and heartbeating; the router declares it dead only when its
  heartbeat FILE (the PR-10 ``parallel/multihost`` machinery) goes stale
  past the timeout. Until then its requests wait — exactly the
  detection latency a real fleet pays.
- **Resubmission is a reconcile sweep, keyed by request id.** The fleet
  keeps the assignment table (rid → replica). Every tick it verifies
  each non-terminal request is still held by a live replica that
  actually KNOWS it; orphans (dead/released replica, or a delivery the
  ``drop_submit`` fault ate) are resubmitted to a survivor with the
  GLOBAL rid, the ORIGINAL submit timestamp (deadlines never reset),
  and a bumped ``retries`` count. The abandoned attempt emits a
  ``finish_reason="retried"`` request record — the lineage is in the
  telemetry stream, one terminal record per rid, always.
- **Resubmit is idempotent.** A duplicate delivery (the
  ``duplicate_submit`` fault — an RPC retry racing its original) is
  dropped at the replica boundary because the rid is already known
  there; a completion for a superseded attempt is dropped at collection
  because the fleet request is already terminal or re-homed
  (``stale_completions`` counts both, asserting zero surprise).
- **A stalled replica self-fences.** A replica that stops beating long
  enough to be declared dead (a GC pause, a network partition) finds,
  on waking, that its lease is gone: it evicts every slot, frees its
  blocks, and stays out of service — it never completes a request the
  fleet already re-homed (the Bamboo [R2] zombie rule).
- **Drain is the elastic scale-down path.** ``drain(replica)`` stops
  admission, re-routes the replica's QUEUED requests to survivors,
  lets RUNNING slots finish in place, then releases the replica with
  every block back in its pool — scale-down loses zero requests.

Fault injection rides the PR-10 :class:`~paddle_tpu.train.faults.
FaultSchedule` (``kill_replica_at_tick``, ``stall_replica_at_tick``,
``drop_submit_at``, ``duplicate_submit_at``), so the whole fleet path is
deterministically drilled in CI (``tests/drills.py fleet``) the same
way ``run_resilient`` is.

**Process isolation (ISSUE 13).** ``ServingFleet(replica_mode=
"process")`` promotes each replica to a real child process
(:class:`ProcReplicaWorker`): the engine+scheduler pair lives in
``serve/replica_proc.py``, submit/complete ride the length-prefixed
:mod:`~paddle_tpu.serve.transport` frames, and the child beats the same
PR-10 heartbeat files. The parent's ENTIRE view of a process replica is
files + transport — a SIGKILL, a hang, or a corrupt reply is contained
in the child, observed via heartbeat staleness / per-message timeout /
classified parse errors, and healed by the exact reconcile path the
in-process drills already pin. The in-process SimClock fleet stays the
default and is behaviorally unchanged; elastic capacity on top of
``drain()`` and :meth:`ServingFleet.spawn_replica` is the
:class:`~paddle_tpu.serve.autoscaler.Autoscaler`'s policy loop.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import logging
import os
import random
import signal
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

from ..obs.fleet_trace import merge_fleet_trace
from ..obs.fleet_trace import save_fleet_trace as _save_fleet_trace
from ..obs.metrics import MetricsHub
from ..obs.slo import SLOMonitor
from ..obs.trace import Tracer
from ..parallel import multihost
from . import transport as transport_lib
from .engine import AdmitProbe
from .kv_cache import blobs_to_pages, pages_to_blobs
from .router import FleetRouter
from .scheduler import ContinuousBatchingScheduler, Request

__all__ = ["ReplicaWorker", "ProcReplicaWorker", "RemoteRequest",
           "FleetRequest", "ServingFleet", "build_proc_spec"]

_log = logging.getLogger("paddle_tpu.serve.fleet")


class ReplicaWorker:
    """One serving replica: engine + scheduler + heartbeat + lifecycle.

    ``state`` machine: ``"live"`` → (``drain``) → ``"draining"`` →
    ``"released"``; any non-released state → ``"dead"`` (set ONLY by the
    router's heartbeat verdict). ``killed`` and ``stall`` are fault-
    injection flags beneath the state machine — they change what the
    replica *does* (nothing), not what the fleet *knows* (that takes a
    stale heartbeat)."""

    def __init__(self, replica_id: int, engine, scheduler, root: str,
                 role: str = "both"):
        self.replica_id = int(replica_id)
        self.engine = engine
        self.scheduler = scheduler
        self.root = root
        # disaggregation role (ISSUE 18): "prefill"|"decode"|"both".
        # The router filters placement on it; "both" is the colocated
        # default and serves everything.
        self.role = role
        self.state = "live"
        self.killed = False
        self._stall_until: Optional[int] = None
        self._fenced = False
        self.known: set = set()           # rids actually delivered here
        self._collected = 0               # scheduler.completed cursor
        self._hb_seq = 0
        # per-replica Tracer (ISSUE 17): the fleet installs one when
        # tracing is on; spans drain into the merged fleet trace each
        # tick — the in-process twin of the child's span-batch shipping
        self.tracer = None

    # -- fault hooks -------------------------------------------------------

    def kill(self) -> None:
        """Process death: no more ticks, no more beats. The engine's
        blocks die with it (a real process loses its HBM); survivors'
        pools are untouched."""
        self.killed = True

    def stall(self, until_tick: int) -> None:
        """Hang (GC pause / partition) until the fleet tick index
        ``until_tick``: no work, no beats — but unlike ``kill``, the
        replica may wake, and must then self-fence if its lease died."""
        self._stall_until = int(until_tick)

    def stalled(self, tick: int) -> bool:
        return self._stall_until is not None and tick < self._stall_until

    def sigkill(self) -> None:
        """The process-level kill point (``sigkill_replica_at_tick``)
        degrades to the abstract kill for an in-process worker — the
        same schedule drills both replica modes."""
        self.kill()

    # -- the worker seam (shared with ProcReplicaWorker) -------------------

    def join(self, now: float) -> None:
        """Join the fleet: first heartbeat (the process worker's
        blocking hello handshake lands here)."""
        self.beat(now)

    def deliver(self, fr: "FleetRequest",
                now: float) -> Optional[Request]:
        """Hand one fleet request to this replica's scheduler; returns
        the replica-side attempt (None = delivery failed, the reconcile
        sweep re-homes it — in-process delivery cannot fail)."""
        return self.scheduler.submit(
            fr.prompt, fr.max_new_tokens, eos_id=fr.eos_id,
            deadline_s=fr.deadline_s, priority=fr.priority, rid=fr.rid,
            submit_ts=fr.submit_ts, retries=fr.retries)

    def begin_drain(self, now: float) -> List[int]:
        """Stop admitting and surrender the QUEUED (never-admitted)
        requests: returns their rids for the fleet to resubmit (their
        ``local`` attempts stay referenced for the retried-lineage
        record). Running slots finish in place."""
        rids = []
        for local in list(self.scheduler.queue):
            self.scheduler.queue.remove(local)
            self.known.discard(local.rid)
            rids.append(local.rid)
        return rids

    def cancel_drain(self) -> None:
        """Drain cancelled (the raced-capacity yield): nothing to undo
        in-process — admission gating lives in the router's state
        check."""

    def idle(self) -> bool:
        """Nothing queued, running, or prefilling — the drain-release
        condition."""
        return not (self.scheduler.running or self.scheduler.prefilling
                    or self.scheduler.queue)

    def orphan_count(self) -> int:
        return (len(self.scheduler.queue) + len(self.scheduler.running)
                + len(self.scheduler.prefilling))

    def on_declared_dead(self) -> None:
        """Hook run when the router's heartbeat verdict lands. The
        in-process zombie fence stays in :meth:`tick` (a stalled worker
        must fence itself on WAKE); process workers fence by kill."""

    def shutdown(self) -> None:
        """Release-path teardown (a no-op for an in-process object)."""

    def transport_stats(self) -> Optional[Dict[str, int]]:
        return None

    def pop_handoffs(self) -> List[Dict[str, Any]]:
        """Drain finished prefills awaiting transfer, SERIALIZED to the
        wire format even in-process — the wire-byte accounting (and the
        bit-identity claim: decode adopts exactly the bytes that would
        cross a socket) must not depend on replica mode."""
        out = []
        for req, meta, kpages, vpages in self.scheduler.pop_handoffs():
            blobs = pages_to_blobs(kpages, vpages)
            out.append({"rid": req.rid, "meta": meta, "blobs": blobs})
        return out

    def adopt(self, fr: "FleetRequest", pkg: Dict[str, Any],
              now: float) -> Optional[Request]:
        """Decode-side adoption of a streamed prefill package; None =
        can't take it yet (no slot / pool backpressure)."""
        cache = self.engine.cache
        kpages, vpages = blobs_to_pages(
            pkg["blobs"], num_layers=cache.num_layers,
            block_size=cache.block_size, num_heads=cache.num_heads,
            head_dim=cache.head_dim, quantized=cache.quantized,
            dtype=cache.dtype)
        return self.scheduler.adopt(pkg["meta"], kpages, vpages)

    def drain_spans(self) -> List[Dict[str, Any]]:
        """Pop this replica's buffered trace events for the fleet-level
        merge (empty when tracing is off)."""
        if self.tracer is None:
            return []
        return self.tracer.drain_events()

    def drain_metrics(self) -> List[Dict[str, Any]]:
        """Registry deltas to absorb at fleet level — always empty
        in-process: the engine/scheduler write the parent hub directly
        through their ``replica=<i>``-scoped handles (ISSUE 19)."""
        return []

    # -- liveness ----------------------------------------------------------

    def beat(self, now: float) -> None:
        self._hb_seq += 1
        multihost.write_heartbeat(
            self.root, host_id=self.replica_id, seq=self._hb_seq, now=now,
            extra={"role": "serving-replica",
                   # the shared load payload (scheduler.load_report) +
                   # the tick-time EMA: the autoscaler's sensors, and
                   # the same schema a process replica's child beats —
                   # a cross-process router balances on the exact
                   # evidence it health-checks
                   **self.scheduler.load_report(),
                   "est_tick_s": self.scheduler.est_tick_s,
                   "free_blocks": self.engine.cache.free_blocks,
                   "free_slots": len(self.engine.free_slots()),
                   # the prefix-locality payoff rides the beat too
                   "prefix_hit_blocks": self.engine.cache.prefix_hit_blocks})

    def reset(self) -> None:
        """Self-fence: evict every slot (blocks back to the pool), drop
        all bookkeeping. Run by a replica that wakes from a stall to
        find itself declared dead — its requests live elsewhere now."""
        for slot in list(self.scheduler.running):
            self.engine.evict(slot)
        for slot in list(self.scheduler.prefilling):
            self.engine.evict(slot)
        self.scheduler.running.clear()
        self.scheduler.prefilling.clear()
        self.scheduler.queue.clear()
        self.scheduler.handoffs.clear()
        self.known.clear()

    def tick(self, now: float, tick_idx: int) -> None:
        """One replica tick: step the scheduler, then beat. Killed,
        released and stalled replicas do nothing; a dead one that can
        still run (a woken zombie) fences itself exactly once."""
        if self.killed or self.state == "released":
            return
        if self.state == "dead":
            if not self._fenced and not self.stalled(tick_idx):
                _log.warning("replica %d woke fenced (lease lost): "
                             "resetting", self.replica_id)
                self.reset()
                self._fenced = True
            return
        if self.stalled(tick_idx):
            return
        self.scheduler.step()
        self.beat(now)


@dataclasses.dataclass
class FleetRequest:
    """One fleet-level request: the global identity (``rid``), the SLO
    fields, the current assignment, and the resubmission lineage. The
    terminal request record (the one non-"retried" telemetry record for
    this rid) lands in ``record``."""
    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int]
    deadline_s: Optional[float]
    priority: int
    session_id: Optional[int]
    submit_ts: float
    replica: Optional[int] = None
    retries: int = 0
    attempts: List[int] = dataclasses.field(default_factory=list)
    local: Optional[Request] = None       # current replica-side attempt
    record: Optional[Dict[str, Any]] = None

    @property
    def done(self) -> bool:
        return self.record is not None

    @property
    def finish_reason(self) -> Optional[str]:
        return self.record["finish_reason"] if self.record else None

    @property
    def tokens(self) -> List[int]:
        return list(self.local.tokens) if self.local is not None else []


@dataclasses.dataclass
class RemoteRequest(Request):
    """Parent-side mirror of a request delivered to a subprocess
    replica: identity + SLO fields are enough for the retried-lineage
    record (the fleet stamps ``finish_reason="retried"`` and emits
    :meth:`record` exactly as in-process); once the child's completion
    arrives, the CHILD's terminal record is returned verbatim — one
    schema, authored where the work actually ran."""
    child_record: Optional[Dict[str, Any]] = None

    def record(self) -> Dict[str, Any]:
        if (self.child_record is not None
                and self.finish_reason != "retried"):
            return dict(self.child_record)
        return super().record()


class _RemoteSchedulerView:
    """The router/fleet-facing load view of a subprocess replica's
    scheduler. The parent never holds the child's real queue — only the
    evidence the child last reported (heartbeat payloads and tick
    replies), which is exactly what a cross-host router could know."""

    def __init__(self):
        self.max_slots = 1
        self.est_tick_s: Optional[float] = None
        self._pending = 0
        self._prefill_backlog = 0
        self.queue: List[int] = []          # rids, as last reported
        self.running: List[int] = []
        self.prefilling: List[int] = []
        self.completed: List[RemoteRequest] = []
        self.by_rid: Dict[int, RemoteRequest] = {}

    def update(self, load: Dict[str, Any]) -> None:
        self._pending = int(load.get("pending_new_tokens") or 0)
        self._prefill_backlog = int(load.get("prefill_backlog") or 0)
        self.queue = list(load.get("queued_rids") or ())
        self.running = list(load.get("running_rids") or ())
        self.prefilling = list(load.get("prefilling_rids") or ())
        if load.get("est_tick_s") is not None:
            self.est_tick_s = float(load["est_tick_s"])

    def pending_new_tokens(self) -> int:
        return self._pending

    def prefill_backlog(self) -> int:
        return self._prefill_backlog

    def predicted_completion_s(self, max_new_tokens: int
                               ) -> Optional[float]:
        # the ContinuousBatchingScheduler model, over reported evidence
        if self.est_tick_s is None:
            return None
        ticks = (self._pending / max(1, self.max_slots)
                 + max_new_tokens)
        return ticks * self.est_tick_s


class _RemoteEngineView:
    """Engine facade over hello/heartbeat/tick-reply evidence: geometry
    is static (the hello handshake), occupancy is the last report. The
    router's ``admit_probe`` runs the real probe's never-clears-first
    rules against that evidence."""

    def __init__(self):
        self.cache = self       # the fleet reads w.engine.cache.<field>
        self.context_width = 0
        self.max_slots = 1
        self.block_size = 1
        self.num_blocks = 2
        self.free_blocks = 1
        self.free_slots_reported = 1
        self.prefix_hit_blocks = 0
        self.cow_forks = 0
        self.ticks = 0
        self._compile_counts: Dict[str, int] = {}

    def set_geometry(self, hello: Dict[str, Any]) -> None:
        self.context_width = int(hello["context_width"])
        self.max_slots = int(hello["max_slots"])
        self.block_size = int(hello["block_size"])
        self.num_blocks = int(hello["num_blocks"])
        self.free_blocks = self.num_blocks - 1      # null block reserved
        self.free_slots_reported = self.max_slots

    def update(self, load: Dict[str, Any]) -> None:
        if load.get("free_blocks") is not None:
            self.free_blocks = int(load["free_blocks"])
        if load.get("free_slots") is not None:
            self.free_slots_reported = int(load["free_slots"])
        self.ticks = int(load.get("engine_ticks") or self.ticks)
        self.prefix_hit_blocks = int(load.get("prefix_hit_blocks")
                                     or self.prefix_hit_blocks)
        self.cow_forks = int(load.get("cow_forks") or self.cow_forks)
        if load.get("compile_counts"):
            self._compile_counts = dict(load["compile_counts"])

    def blocks_needed(self, length: int) -> int:
        return max(1, -(-int(length) // self.block_size))

    def compile_counts(self) -> Dict[str, int]:
        return dict(self._compile_counts)

    def admit_probe(self, total_len: int,
                    include_slots: bool = True) -> AdmitProbe:
        need = self.blocks_needed(total_len)
        if total_len > self.context_width:
            reason = "width"
        elif include_slots and self.free_slots_reported == 0:
            reason = "slots"
        elif need > self.free_blocks:
            reason = "blocks"
        else:
            reason = None
        return AdmitProbe(ok=reason is None, reason=reason,
                          blocks_needed=need,
                          free_blocks=self.free_blocks,
                          free_slots=self.free_slots_reported)


class ProcReplicaWorker:
    """One serving replica living in its OWN process (ISSUE 13).

    The parent's entire view of this replica is heartbeat FILES plus the
    seq-numbered submit/complete transport — the same worker seam
    :class:`ReplicaWorker` implements in-process, so the router, the
    reconcile sweep, drain, and the autoscaler are mode-blind:

    - a SIGKILL/OOM/segfault in the child stops the beats; the router
      observes staleness and the fleet re-homes the requests — the
      router process never crashes;
    - a hung child (or a lost reply) surfaces as the per-message
      timeout; bounded retransmits recover a lost REPLY from the
      child's seq cache, and exhausted retries quarantine the transport
      (``transport_down``) while the heartbeat verdict decides;
    - a garbled reply is a CLASSIFIED :class:`~paddle_tpu.serve.
      transport.TransportCorrupt`, counted and retried, never an
      exception through the fleet tick;
    - declared-dead process replicas are fenced BY KILL — the
      definitive form of the PR-11 zombie self-fence (a process that
      no longer exists cannot complete a re-homed request).
    """

    is_process = True

    def __init__(self, replica_id: int, spec: Dict[str, Any], root: str,
                 *, faults=None, telemetry=None, timeout_s: float = 2.0,
                 spawn_timeout_s: float = 300.0, stderr=None,
                 mode: str = "process", role: str = "both",
                 chaos=None):
        self.replica_id = int(replica_id)
        self.root = root
        self.state = "live"
        self.killed = False
        self.role = role
        self._stall_until: Optional[int] = None
        self.known: set = set()
        self._collected = 0
        self.faults = faults
        self.telemetry = telemetry
        self.scheduler = _RemoteSchedulerView()
        self.engine = _RemoteEngineView()
        self.transport_down = False
        self.transport_errors = 0
        self._mode = mode
        # the epoch lease (ISSUE 20): granted by the fleet before the
        # hello, bumped on declare-dead. Every op is stamped with it;
        # every reply from a different epoch is discarded wholesale.
        self.lease_epoch = 0
        self.revoked_epoch: Optional[int] = None
        self.fence_reply: Optional[Dict[str, Any]] = None
        self.readmit_info: Optional[Dict[str, Any]] = None
        self.stale_epoch_replies = 0
        self.stale_metric_deltas = 0
        self.readmits = 0
        # readmit probing state (socket mode): capped exponential tick
        # backoff with seeded jitter, so a healed partition doesn't see
        # every fenced replica probed on the same tick
        self._fenced_tick: Optional[int] = None
        self._fenced_at: Optional[float] = None
        self._readmit_attempts = 0
        self._next_readmit_tick = 0
        self._readmit_rng = random.Random(0xFE0CE + self.replica_id)
        # trace events shipped piggybacked on tick replies (ISSUE 17),
        # buffered here until the fleet's per-tick span drain
        self._spans: List[Dict[str, Any]] = []
        # registry deltas shipped the same way (ISSUE 19), buffered
        # until the fleet's per-tick absorb sweep
        self._metrics_deltas: List[Dict[str, Any]] = []
        # KV-page handoff packages shipped on tick replies (ISSUE 18),
        # buffered until the fleet's per-tick handoff sweep
        self._handoffs: List[Dict[str, Any]] = []
        self._spawn_timeout_s = float(spawn_timeout_s)
        spec = dict(spec, replica_id=self.replica_id, root=root)
        if role != "both":
            spec["role"] = role
        if mode == "socket":
            # socket transport (ISSUE 18): listen first, THEN spawn —
            # the child dials on startup. Loopback here; a remote host
            # runs the same child by hand against a routable listener.
            srv = transport_lib.listen()
            host, port = srv.getsockname()
            proc = transport_lib.spawn_replica_process(
                spec, stderr=stderr, connect=f"{host}:{port}")
            try:
                sock, _ = transport_lib.accept_connection(
                    srv, timeout_s=self._spawn_timeout_s)
            except transport_lib.TransportError:
                if proc.poll() is None:
                    proc.kill()
                raise
            finally:
                srv.close()
            reader: Any = transport_lib.SocketFrameReader(sock)
            writer: Any = transport_lib.SocketWriter(sock)
            if chaos is not None and chaos.link(self.replica_id) \
                    is not None:
                # the chaos plane (ISSUE 20) sits at the frame seam:
                # impairments are enacted on real wire bytes, so every
                # pathology surfaces through the real timeout →
                # retransmit → transport_down → heartbeat chain
                from .chaos import ChaosFrameReader
                reader = ChaosFrameReader(sock, chaos, self.replica_id)
                writer = chaos.wrap_writer(self.replica_id, writer)
            self.transport = transport_lib.ReplicaTransport(
                reader, writer, proc=proc, timeout_s=timeout_s,
                backoff_seed=self.replica_id)
        else:
            proc = transport_lib.spawn_replica_process(spec,
                                                       stderr=stderr)
            self.transport = transport_lib.ReplicaTransport(
                proc.stdout, proc.stdin, proc=proc, timeout_s=timeout_s,
                backoff_seed=self.replica_id)

    @property
    def pid(self) -> Optional[int]:
        return self.transport.pid

    def _emit(self, rec: Dict[str, Any]) -> None:
        if self.telemetry is not None:
            self.telemetry.emit_event(rec)

    def _transport_error(self, op: str, err) -> None:
        self.transport_errors += 1
        m = self.transport.metrics
        if m is not None:
            # same site as the attribute counter, so the registry and
            # fleet.stats() totals agree by construction (satellite 2)
            m.counter("transport_errors",
                      "exhausted-retry transport failures").inc()
        kind = getattr(err, "kind", "error")
        _log.warning("replica %d transport %s on %s: %s",
                     self.replica_id, kind, op, err)
        self._emit({"kind": "transport", "event": kind,
                    "replica": self.replica_id, "op": op})
        # every retransmit already failed by the time we get here: stop
        # talking to this replica (no per-tick timeout stalls while a
        # corpse rots) and let the heartbeat verdict make the call
        self.transport_down = True

    def _request(self, op: str, **kw) -> Dict[str, Any]:
        """Every op stamped with this worker's lease epoch (ISSUE 20) —
        the wire half of the fence. A worker never granted an epoch
        (legacy drivers) sends unstamped, unchanged."""
        if self.lease_epoch:
            kw.setdefault("epoch", self.lease_epoch)
        return self.transport.request(op, **kw)

    # -- lifecycle ---------------------------------------------------------

    def join(self, now: float) -> None:
        """Blocking hello handshake: waits for the child to finish its
        jax bring-up, records the engine geometry, and confirms the
        first heartbeat landed (the child beats on hello). The hello is
        also the lease GRANT: it carries the epoch the fleet issued at
        spawn."""
        reply = self._request(
            "hello", now=now, timeout_s=self._spawn_timeout_s,
            max_attempts=1)
        self.engine.set_geometry(reply)
        self.scheduler.max_slots = self.engine.max_slots
        load = reply.get("load") or {}
        self.scheduler.update(load)
        self.engine.update(load)

    def _terminate(self, sig=signal.SIGKILL) -> None:
        proc = self.transport.proc
        if proc is not None and proc.poll() is None:
            try:
                os.kill(proc.pid, sig)
            except (ProcessLookupError, OSError):
                pass
        self.transport.close()
        if proc is not None:
            try:
                proc.wait(timeout=5.0)
            except Exception:               # still dying; reaped later
                pass

    def kill(self) -> None:
        """REAL process death: SIGKILL. The beats stop on their own —
        the fleet learns nothing until the heartbeat goes stale."""
        self.killed = True
        self._terminate(signal.SIGKILL)

    sigkill = kill

    def stall(self, until_tick: int) -> None:
        """Simulated hang from the FLEET's side of the seam: no tick
        traffic (so no work and no beats) until ``until_tick`` — the
        evidence trail of a hung child, with the child itself healthy."""
        self._stall_until = int(until_tick)

    def stalled(self, tick: int) -> bool:
        return self._stall_until is not None and tick < self._stall_until

    def on_declared_dead(self) -> None:
        """Fence-by-kill: the process analog of the PR-11 zombie
        self-fence. A declared-dead replica whose process still runs (a
        stall, a partition) must never complete a re-homed request —
        SIGKILL makes that structural. This is the PIPE-mode fence
        (same host, so the signal always lands — the strongest fence
        available); socket-mode workers are fenced BY EPOCH instead
        (:meth:`fence`), because a kill signal cannot cross hosts."""
        self._terminate(signal.SIGKILL)

    def fence(self, new_epoch: int, now: float,
              tick_idx: Optional[int] = None) -> Optional[Dict[str, Any]]:
        """Epoch fence (ISSUE 20): revoke this worker's lease. The OLD
        epoch becomes invalid the moment the parent adopts the new one
        — every subsequent reply, handoff or metric delta stamped with
        it is discarded, and the child itself rejects ops carrying it —
        so the fence holds even if the revocation NOTICE below never
        arrives (the point of fencing by epoch, not by reachability).
        The notice is one best-effort short-timeout attempt: when the
        send direction is up (asymmetric partition) the child evicts
        its slots immediately instead of at first rejected op."""
        self.revoked_epoch = self.lease_epoch or None
        self.lease_epoch = int(new_epoch)
        self.fence_reply = None
        self.readmit_info = None
        self._fenced_tick = tick_idx
        self._fenced_at = now
        self._readmit_attempts = 0
        self._next_readmit_tick = (tick_idx or 0) + 1
        if (self.transport.closed or self.killed
                or self.transport.proc is None
                or self.transport.proc.poll() is not None):
            return None
        try:
            reply = self._request(
                "fence", now=now, max_attempts=1,
                timeout_s=min(self.transport.timeout_s, 0.5))
        except transport_lib.TransportError:
            return None             # unreachable: the epoch IS the fence
        if reply.get("ok"):
            self.fence_reply = reply.get("fence")
        return self.fence_reply

    def try_readmit(self, new_epoch: int, now: float) -> bool:
        """One readmit probe (partition heal): offer the fenced child a
        FRESH lease (strictly newer than the fence epoch — the child
        rejects a readmit that does not outrank what it holds). On
        success the worker rejoins as an EMPTY live replica —
        parent-side rid bookkeeping is reset, the child already evicted
        everything at fence time, and the reply's fence report
        (tokens_while_fenced, stale_epoch_rejects) is kept as drill
        evidence. A failed probe burns its epoch; the counter is
        monotone, not dense."""
        if (self.transport.closed or self.killed
                or self.transport.proc is None
                or self.transport.proc.poll() is not None):
            return False
        self._readmit_attempts += 1
        try:
            reply = self.transport.request(
                "readmit", epoch=int(new_epoch), now=now,
                max_attempts=1,
                timeout_s=min(self.transport.timeout_s, 0.5))
        except transport_lib.TransportError:
            return False
        if not reply.get("ok"):
            return False
        self.lease_epoch = int(new_epoch)
        self.readmits += 1
        self.readmit_info = {
            "epoch": self.lease_epoch,
            "fence": reply.get("fence"),
            "tokens_while_fenced": reply.get("tokens_while_fenced"),
            "stale_epoch_rejects": reply.get("stale_epoch_rejects")}
        if self.fence_reply is None:
            self.fence_reply = reply.get("fence")
        # clean slate on BOTH sides: the child cleared its rid/dedupe
        # state at fence; any rid we still track for it lives elsewhere
        # now (resubmitted when it was declared dead)
        self.known.clear()
        self.scheduler.by_rid.clear()
        self.state = "live"
        self.transport_down = False
        load = reply.get("load") or {}
        self.scheduler.update(load)
        self.engine.update(load)
        return True

    def shutdown(self) -> None:
        """Graceful stop (release path / fleet teardown): ask the child
        to exit, then make sure."""
        proc = self.transport.proc
        if (not self.transport.closed and not self.transport_down
                and proc is not None and proc.poll() is None):
            try:
                self.transport.request("stop", max_attempts=1)
            except transport_lib.TransportError:
                pass
        self._terminate(signal.SIGKILL)

    # -- the worker seam ---------------------------------------------------

    def deliver(self, fr: "FleetRequest",
                now: float) -> Optional[Request]:
        if self.transport_down:
            return None                 # don't pay timeouts to a corpse
        try:
            reply = self._request(
                "submit", rid=fr.rid, prompt=list(fr.prompt),
                max_new_tokens=fr.max_new_tokens, eos_id=fr.eos_id,
                deadline_s=fr.deadline_s, priority=fr.priority,
                submit_ts=fr.submit_ts, retries=fr.retries, now=now)
        except transport_lib.TransportError as e:
            self._transport_error("submit", e)
            return None
        if not reply.get("ok"):
            return None                 # refused (draining child)
        req = RemoteRequest(
            rid=fr.rid, prompt=list(fr.prompt),
            max_new_tokens=fr.max_new_tokens, eos_id=fr.eos_id,
            deadline_s=fr.deadline_s, priority=fr.priority,
            retries=fr.retries, submit_ts=fr.submit_ts)
        self.scheduler.by_rid[fr.rid] = req
        # optimistic load accounting: the child's shadow view otherwise
        # refreshes only on tick replies, so a burst of submits between
        # ticks would all read this replica at its pre-burst load and
        # pile onto one worker (in-process workers account admission
        # immediately — this keeps socket placement consistent with
        # that). The next real report overwrites the estimate.
        self.scheduler._pending += fr.max_new_tokens
        self.scheduler._prefill_backlog += len(fr.prompt)
        return req

    def tick(self, now: float, tick_idx: int) -> None:
        """One replica tick over the wire: the child steps its
        scheduler, beats, and ships completions + telemetry + load in
        the reply. Transport faults are classified and contained."""
        if (self.killed or self.state in ("released", "dead")
                or self.transport_down or self.stalled(tick_idx)):
            return
        flags = {}
        if self.faults is not None:
            if self.faults.should_hang_transport(tick_idx,
                                                 self.replica_id):
                flags["inject_drop_reply"] = True
            if self.faults.should_corrupt_reply(tick_idx,
                                                self.replica_id):
                flags["inject_corrupt_reply"] = True
        try:
            reply = self._request("tick", now=now,
                                  tick=tick_idx, **flags)
        except transport_lib.TransportError as e:
            self._transport_error("tick", e)
            return
        self._absorb(reply)

    def _absorb(self, reply: Dict[str, Any]) -> None:
        rep_ep = reply.get("epoch")
        if (rep_ep is not None and self.lease_epoch
                and int(rep_ep) != self.lease_epoch):
            # a reply stamped with a revoked lease (ISSUE 20): a
            # fenced-then-superseded child's late work. Discard it
            # WHOLESALE — its completions were resubmitted elsewhere,
            # its load view is of an evicted scheduler, its metric
            # deltas would double-count against the readmitted epoch.
            self.stale_epoch_replies += 1
            return
        load = reply.get("load") or {}
        self.scheduler.update(load)
        self.engine.update(load)
        for ev in reply.get("events") or ():
            self._emit(ev)              # the fleet's ONE telemetry stream
        sp = reply.get("spans")
        if sp:
            self._spans.extend(sp)
        md = reply.get("metrics")
        if md:
            # tagged with the epoch they arrived under: a fence between
            # absorb and the fleet's drain sweep must still kill them
            self._metrics_deltas.append((self.lease_epoch, md))
        for item in reply.get("completed") or ():
            rec = item.get("record") or {}
            rid = rec.get("rid")
            req = self.scheduler.by_rid.pop(rid, None)
            if req is None:             # superseded/unknown: _collect
                req = RemoteRequest(rid=rid, prompt=[],
                                    max_new_tokens=1)
            req.child_record = rec
            req.tokens = list(item.get("tokens") or ())
            req.finish_reason = rec.get("finish_reason")
            req.finish_ts = req.submit_ts   # done marker; truth in rec
            self.scheduler.completed.append(req)
        # KV handoff packages (ISSUE 18): the framed binary payloads
        # landed in reply["blobs"]; each handoff header says how many
        # belong to it. A package only exists here because the WHOLE
        # reply (header + every blob) was absorbed — a child killed
        # mid-transfer never surfaces a partial handoff.
        hoffs = reply.get("handoffs") or ()
        if hoffs:
            blobs = reply.get("blobs") or []
            off = 0
            for h in hoffs:
                nb = int(h.get("nblobs") or 0)
                rid = int(h["rid"])
                self._handoffs.append({
                    "rid": rid, "meta": h["meta"],
                    "blobs": blobs[off:off + nb],
                    # the epoch this package arrived under: the fleet's
                    # handoff sweep discards it if the lease was revoked
                    # before placement (a stale prefill must not be
                    # adopted alongside its resubmitted twin)
                    "epoch": self.lease_epoch})
                off += nb
                # the request now lives between replicas; the child
                # forgot it too, so a later re-delivery must not dedupe
                self.scheduler.by_rid.pop(rid, None)

    def begin_drain(self, now: float) -> List[int]:
        try:
            reply = self._request("drain", now=now)
        except transport_lib.TransportError as e:
            self._transport_error("drain", e)
            return []
        rids = [int(r) for r in reply.get("queued_rids") or ()]
        for rid in rids:
            self.known.discard(rid)
            self.scheduler.by_rid.pop(rid, None)
        self.scheduler.update(reply.get("load") or {})
        return rids

    def cancel_drain(self) -> None:
        """The child refuses submissions while draining; a cancelled
        drain must tell it to admit again."""
        if self.transport_down:
            return
        try:
            self._request("resume")
        except transport_lib.TransportError as e:
            self._transport_error("resume", e)

    def pop_handoffs(self) -> List[Dict[str, Any]]:
        out, self._handoffs = self._handoffs, []
        return out

    def adopt(self, fr: "FleetRequest", pkg: Dict[str, Any],
              now: float) -> Optional[Request]:
        """Ship a finished-prefill package to this (decode) child: one
        "adopt" round with the KV pages as framed binary payloads."""
        if self.transport_down:
            return None
        try:
            reply = self._request(
                "adopt", rid=fr.rid, meta=pkg["meta"],
                blobs=pkg["blobs"], now=now)
        except transport_lib.TransportError as e:
            self._transport_error("adopt", e)
            return None
        if not reply.get("ok"):
            return None                 # refused (capacity/draining)
        meta = pkg["meta"]
        req = RemoteRequest(
            rid=fr.rid, prompt=list(meta["prompt"]),
            max_new_tokens=int(meta["max_new_tokens"]),
            eos_id=meta.get("eos_id"),
            deadline_s=meta.get("deadline_s"),
            priority=int(meta.get("priority") or 0),
            retries=int(meta.get("retries") or 0),
            submit_ts=meta.get("submit_ts"))
        self.scheduler.by_rid[fr.rid] = req
        return req

    def idle(self) -> bool:
        return not (self.scheduler.running or self.scheduler.prefilling
                    or self.scheduler.queue)

    def orphan_count(self) -> int:
        return (len(self.scheduler.queue) + len(self.scheduler.running)
                + len(self.scheduler.prefilling))

    def stats_probe(self, now: float) -> Optional[Dict[str, Any]]:
        """One stats round-trip (the drills' leak/retrace evidence:
        free blocks and compile counts straight from the child)."""
        if (self.transport_down or self.transport.closed
                or self.killed or self.state in ("dead", "released")):
            return None
        try:
            return self._request("stats", now=now)
        except transport_lib.TransportError as e:
            self._transport_error("stats", e)
            return None

    def transport_stats(self) -> Dict[str, int]:
        return {"errors": self.transport_errors,
                "retransmits": self.transport.retransmits,
                "timeouts": self.transport.timeouts,
                "corrupt_replies": self.transport.corrupt_replies}

    def drain_spans(self) -> List[Dict[str, Any]]:
        """Pop the child's shipped trace events (no transport round —
        the spans already rode the tick replies)."""
        sp, self._spans = self._spans, []
        return sp

    def drain_metrics(self) -> List[Dict[str, Any]]:
        """Pop the child's shipped registry deltas (no transport round
        — they already rode the tick replies; deltas a SIGKILL ate
        simply never land here). Deltas tagged with a revoked epoch are
        discarded, not merged (ISSUE 20) — a fenced replica's late
        counters must not pollute the fleet registry."""
        tagged, self._metrics_deltas = self._metrics_deltas, []
        out: List[Dict[str, Any]] = []
        for ep, md in tagged:
            if ep == self.lease_epoch:
                out.extend(md)
            else:
                self.stale_metric_deltas += 1
        return out

    def scrape_metrics(self, now: float) -> Optional[str]:
        """One ``metrics`` op round-trip: the child's full registry as
        Prometheus text exposition. A READ, not a drain — the tick-
        reply delta watermarks are untouched. None when the link is
        down or the child has no registry."""
        if (self.transport_down or self.transport.closed
                or self.killed or self.state in ("dead", "released")):
            return None
        try:
            reply = self._request("metrics", now=now)
        except transport_lib.TransportError as e:
            self._transport_error("metrics", e)
            return None
        if not reply.get("ok"):
            return None
        return reply.get("exposition")


class ServingFleet:
    """N replica workers + a router + the recovery loop (see module
    docstring).

    Args:
      make_engine: ``callable(replica_id) -> DecodeEngine`` — one engine
        per replica (homogeneous capacity assumed for validation).
      n_replicas: fleet width.
      telemetry: shared :class:`~paddle_tpu.obs.Telemetry`; every
        replica's request/evict records and the fleet's shed/replica
        events land in one stream (records carry the GLOBAL rid).
      root: heartbeat directory (a fresh tempdir by default).
      clock: shared injectable clock — heartbeats, deadlines, arrival
        replay and predictions all read it (``SimClock`` for CI).
      heartbeat_timeout_s: staleness after which a replica is dead.
      order / shed / est_tick_s: scheduler admission policy, router
        shedding, and the cold-start tick-time prior (see
        :class:`ContinuousBatchingScheduler`).
      faults: a :class:`~paddle_tpu.train.faults.FaultSchedule` with the
        serving points armed.
      replica_mode: ``"inprocess"`` (default — behaviorally identical
        to PR 11) or ``"process"`` — each replica is a real child
        process behind the submit/complete transport (needs
        ``proc_spec``; use :meth:`from_model`).
      proc_spec: the child-process build spec (:func:`build_proc_spec`):
        model config, engine kwargs, variables npz path.
      transport_timeout_s / spawn_timeout_s: per-message reply timeout
        and the hello-handshake budget (a child pays jax bring-up
        once).
      autoscaler: an :class:`~paddle_tpu.serve.autoscaler.Autoscaler`
        to bind; its policy loop runs inside every fleet tick.
      trace: distributed request tracing (ISSUE 17). The fleet gets a
        router-lane :class:`~paddle_tpu.obs.Tracer` and every replica
        gets its own (a process replica builds one in the child and
        ships span batches back on tick replies); all of them stamp the
        SHARED fleet clock, and :meth:`fleet_trace` merges the lanes
        into one Chrome/Perfetto timeline with ``s``/``t``/``f`` flow
        events linking each rid across processes. Default off —
        tracing off is the byte-identical pre-trace fleet.
      slo: streaming SLO monitoring — ``True`` for a default
        :class:`~paddle_tpu.obs.SLOMonitor`, or a configured instance.
        Every terminal record feeds it; :meth:`slo_report` and the
        ``"slo"`` key of :meth:`stats` surface rolling p50/p95/p99
        TTFT/TPOT and the error-budget burn rate.
      anomaly: a :class:`~paddle_tpu.obs.ServingAnomalyDetector`; the
        fleet feeds it per-tick replica views, terminal records and
        transport counters, and binds fleet evidence sources (live
        heartbeats, the trace tail, transport totals) into its
        forensic bundles.
    """

    def __init__(self, make_engine: Optional[Callable[[int], Any]],
                 n_replicas: int, *, telemetry=None, root: Optional[str]
                 = None, clock=None, heartbeat_timeout_s: float = 3.0,
                 order: str = "fcfs", shed: bool = True,
                 affinity: bool = True,
                 est_tick_s: Optional[float] = None, faults=None,
                 replica_mode: str = "inprocess",
                 proc_spec: Optional[Dict[str, Any]] = None,
                 transport_timeout_s: float = 2.0,
                 spawn_timeout_s: float = 300.0,
                 autoscaler=None, trace: bool = False, slo=None,
                 anomaly=None, roles: Optional[List[str]] = None,
                 metrics=None, chaos=None,
                 death_confirmations: int = 2,
                 lease_timeout_s: Optional[float] = None,
                 degrade_grace_s: Optional[float] = None,
                 readmit_grace_s: Optional[float] = None):
        if n_replicas < 1:
            raise ValueError("n_replicas must be >= 1")
        if replica_mode not in ("inprocess", "process", "socket"):
            raise ValueError(
                f"replica_mode must be 'inprocess'|'process'|'socket', "
                f"got {replica_mode!r}")
        if chaos is not None and replica_mode != "socket":
            # the chaos plane impairs WIRE frames at the socket seam;
            # pipes/in-process have no link to impair — fail loudly
            # rather than run a drill with the chaos silently off
            raise ValueError("chaos requires replica_mode='socket'")
        if replica_mode in ("process", "socket") and proc_spec is None:
            raise ValueError(
                f"replica_mode={replica_mode!r} needs proc_spec — use "
                "ServingFleet.from_model(...) or build_proc_spec()")
        if replica_mode in ("process", "socket"):
            _require_parent_off_chip(replica_mode)
        # prefill/decode disaggregation (ISSUE 18): roles[i] is replica
        # i's role; None = every replica serves "both" (the byte-
        # identical colocated fleet). A mixed fleet needs at least one
        # decode-capable replica or handoffs would have nowhere to land.
        if roles is not None:
            roles = list(roles)
            if len(roles) != n_replicas:
                raise ValueError(
                    f"roles has {len(roles)} entries for "
                    f"{n_replicas} replicas")
            bad = [r for r in roles
                   if r not in ("both", "prefill", "decode")]
            if bad:
                raise ValueError(f"invalid role(s) {bad!r}: must be "
                                 f"'both'|'prefill'|'decode'")
            if (any(r == "prefill" for r in roles)
                    and not any(r in ("decode", "both") for r in roles)):
                raise ValueError("a fleet with prefill replicas needs "
                                 "at least one decode-capable replica")
        self._roles = roles
        self.disagg = bool(roles) and any(r == "prefill" for r in roles)
        self.replica_mode = replica_mode
        self.telemetry = telemetry
        self.clock = clock if clock is not None else time.perf_counter
        self.root = root or tempfile.mkdtemp(prefix="paddle_tpu_fleet_")
        self.faults = faults
        self.make_engine = make_engine
        self.order = order
        self.est_tick_s = est_tick_s
        self._proc_spec = dict(proc_spec or {})
        self._transport_timeout_s = float(transport_timeout_s)
        self._spawn_timeout_s = float(spawn_timeout_s)
        # observability (ISSUE 17) — all default-off; the tracer must
        # exist BEFORE the spawn loop (process replicas read the spec's
        # "trace" key at build, transport observers hook at construction)
        self.tracer = Tracer(clock=self.clock) if trace else None
        self._replica_spans: Dict[int, List[Dict[str, Any]]] = \
            collections.defaultdict(list)
        if self.tracer is not None and replica_mode in ("process",
                                                        "socket"):
            self._proc_spec["trace"] = True
        # metrics registry (ISSUE 19) — same doctrine as the tracer:
        # built BEFORE the spawn loop (process replicas read the spec's
        # "metrics" key at build; in-process workers take scoped
        # handles at construction), default-off, byte-identical dark.
        self.metrics = (MetricsHub(clock=self.clock) if metrics is True
                        else (metrics or None))
        if (self.metrics is not None
                and replica_mode in ("process", "socket")):
            self._proc_spec["metrics"] = True
        self.slo = SLOMonitor() if slo is True else (slo or None)
        if self.slo is not None and self.metrics is not None:
            # the SLO monitor publishes its rolling percentiles and
            # burn rate as gauges into the same registry (satellite 3)
            self.slo.metrics = self.metrics
        self.anomaly = anomaly
        # the network chaos plane (ISSUE 20): bound to the fleet clock
        # so partition/flap windows are SimClock-deterministic; wired
        # per link inside _spawn_worker. None = stock reader/writer —
        # byte-identical to the pre-chaos transport.
        self.chaos = chaos
        if chaos is not None:
            chaos.bind(self.clock)
        # epoch leases (ISSUE 20): one fleet-global monotone counter —
        # every grant (spawn, readmit) is a fresh epoch, so "newer
        # epoch" is a total order across the whole membership history.
        # Must exist BEFORE the spawn loop (spawn grants the first
        # epoch; the hello delivers it).
        self._epochs = itertools.count(1)
        if lease_timeout_s is not None:
            # the child-side half of the lease: absent from the spec
            # (and from child behavior) unless explicitly armed
            self._proc_spec["lease_timeout_s"] = float(lease_timeout_s)
        self.workers: List[Any] = []
        for i in range(n_replicas):       # Popen-spawn (or build) all…
            self._spawn_worker(roles[i] if roles else "both")
        self.router = FleetRouter(
            self.workers, self.root,
            heartbeat_timeout_s=heartbeat_timeout_s, clock=self.clock,
            affinity=affinity, shed=shed, tracer=self.tracer,
            death_confirmations=death_confirmations,
            metrics=self.metrics)
        now = self.clock()
        for w in self.workers:            # …then join: children paid
            w.join(now)                   # their jax bring-up in parallel
        self.autoscaler = autoscaler
        if autoscaler is not None:
            autoscaler.bind(self)
        self.requests: Dict[int, FleetRequest] = {}
        # the non-terminal subset, kept separately so the per-tick
        # reconcile/outstanding sweeps are O(in-flight), not
        # O(everything ever submitted); `requests` is the full ledger
        # (prune_terminal() bounds it for long-lived fleets)
        self._active: Dict[int, FleetRequest] = {}
        self._rid = itertools.count()
        self._unplaced: List[FleetRequest] = []
        self.ticks = 0
        self.resubmits = 0
        self.shed_count = 0
        self.duplicates_dropped = 0
        self.stale_completions = 0
        self.arrived_prompt_tokens = 0
        self.arrived_new_tokens = 0
        # handoff ledger (ISSUE 18): rid -> in-flight KV package. A rid
        # here is owned by the FLEET — no replica holds it, so the
        # reconcile sweep must not resubmit it (fr.replica is None).
        self._pending_handoffs: Dict[int, Dict[str, Any]] = {}
        self.handoff_count = 0
        self.handoff_wire_bytes = 0
        self.handoff_blocks = 0
        self.stale_handoffs = 0
        # membership accounting (ISSUE 20)
        self.fences = 0
        self.readmitted = 0
        self.stale_epoch_handoffs = 0
        # partition degradation (ISSUE 20): when a disagg fleet loses
        # every prefill-capable replica for longer than the grace
        # window, decode replicas temporarily serve colocated prefill
        # (slower, not stuck); heal releases it. Grace defaults to two
        # heartbeat timeouts — long enough that an ordinary death +
        # replacement never engages it.
        self.degraded = False
        self.degrade_grace_s = (float(degrade_grace_s)
                                if degrade_grace_s is not None
                                else 2.0 * float(heartbeat_timeout_s))
        self.readmit_grace_s = (float(readmit_grace_s)
                                if readmit_grace_s is not None
                                else 8.0 * float(heartbeat_timeout_s))
        self._prefill_lost_at: Optional[float] = None
        self.degradations = 0
        self.degrade_releases = 0
        # host-side router/reconcile cost (satellite 1): wall seconds
        # (perf_counter, NEVER the injectable clock — SimClock would
        # report zero) accumulated around placement work, bucketed per
        # fleet tick. Submit-path routing lands in the next tick's
        # bucket.
        self._router_cur_s = 0.0
        self._router_tick_s: List[float] = []
        if self.anomaly is not None:
            # bundles capture fleet-level evidence at trigger time:
            # live heartbeats, the merged-trace tail, transport totals
            self.anomaly.bind(tracer=self.tracer)
            self.anomaly.bind_fleet(
                heartbeats=lambda: multihost.read_heartbeats(self.root),
                trace_tail=((lambda: self.fleet_trace(tail=128))
                            if self.tracer is not None else None),
                transport=self._transport_totals)

    # -- replica lifecycle -------------------------------------------------

    def _spawn_worker(self, role: str = "both"):
        """Construct (but do not yet join) replica ``len(workers)`` in
        the active mode. Ids are append-only — a dead/released worker
        stays as a tombstone — so replica id == list index forever."""
        i = len(self.workers)
        if self.replica_mode in ("process", "socket"):
            w = ProcReplicaWorker(
                i, self._proc_spec, self.root, faults=self.faults,
                telemetry=self.telemetry,
                timeout_s=self._transport_timeout_s,
                spawn_timeout_s=self._spawn_timeout_s,
                mode=self.replica_mode, role=role,
                chaos=self.chaos)
            # the lease grant: the hello (join) carries this epoch to
            # the child, every later op is stamped with it
            w.lease_epoch = next(self._epochs)
            if self.tracer is not None:
                # retransmit/timeout/corrupt verdicts land as instants
                # on the ROUTER lane — the child can't see them (a lost
                # reply is invisible to the process that sent it)
                w.transport.on_event = (
                    lambda event, op, _r=i: self.tracer.instant(
                        f"transport_{event}", replica=_r, op=op))
            if self.metrics is not None:
                # per-LINK wire health (bytes/frames/RTT/failures) is a
                # parent-side property of the connection — the child
                # can't measure its own reply loss any more than it can
                # see its own SIGKILL
                w.transport.metrics = self.metrics.scoped(link=str(i))
        else:
            eng = self.make_engine(i)
            wtr = (Tracer(clock=self.clock)
                   if self.tracer is not None else None)
            mets = (self.metrics.scoped(replica=str(i))
                    if self.metrics is not None else None)
            sched = ContinuousBatchingScheduler(
                eng, telemetry=self.telemetry, order=self.order,
                shed=False, est_tick_s=self.est_tick_s, clock=self.clock,
                tracer=wtr, role=role, metrics=mets)
            w = ReplicaWorker(i, eng, sched, self.root, role=role)
            if wtr is not None:
                eng.tracer = wtr
                w.tracer = wtr
            if mets is not None:
                # in-process replicas write the parent hub directly
                # through a replica=<i>-scoped view — the same label
                # namespace absorb_delta gives a process replica
                eng.metrics = mets
        self.workers.append(w)
        return w

    def spawn_replica(self, role: Optional[str] = None) -> int:
        """Add one replica to the live fleet — the autoscaler's
        scale-up / cold-replacement primitive. Blocks until the
        newcomer is serving and has beaten once (a process replica pays
        its jax bring-up here); the router (shared worker list) can
        place onto it immediately. Returns the new replica id."""
        w = self._spawn_worker(role or "both")
        w.join(self.clock())
        self._replica_event("spawned", w)
        return w.replica_id

    def shutdown(self) -> None:
        """Stop every replica (process replicas get a stop op, then
        SIGKILL). Drills and tests call this; a production fleet runs
        until its supervisor does."""
        for w in self.workers:
            w.shutdown()

    # -- helpers -----------------------------------------------------------

    def _worker(self, replica_id: int) -> ReplicaWorker:
        return self.workers[replica_id]

    def _emit(self, rec: Dict[str, Any]) -> None:
        if self.telemetry is not None:
            self.telemetry.emit_event(rec)

    def _replica_event(self, event: str, worker: ReplicaWorker,
                       **extra) -> None:
        self._emit({"kind": "replica", "event": event,
                    "replica": worker.replica_id, "tick": self.ticks,
                    **extra})

    def _finalize(self, fr: FleetRequest, emit: bool = True) -> None:
        """A request reached its terminal record: drop it from the
        in-flight index (and emit the record when the fleet built it —
        replica-side completions were already emitted by the
        scheduler)."""
        if emit:
            self._emit(fr.record)
        self._active.pop(fr.rid, None)
        if self.tracer is not None:
            # phase "f": the rid's flow ENDS at its terminal record —
            # whichever path produced it (completion, shed, parked
            # timeout), every flow closes exactly once
            self.tracer.complete(
                "terminal", self.tracer.now_us(), flow_end=fr.rid,
                rid=fr.rid, reason=fr.record.get("finish_reason"),
                retries=fr.retries)
        if self.slo is not None:
            self.slo.observe(fr.record)
        if self.anomaly is not None and fr.replica is not None:
            self.anomaly.observe_serving(fr.replica, fr.record)

    def _terminal_record(self, fr: FleetRequest, reason: str, now: float,
                         **extra) -> Dict[str, Any]:
        """Fleet-side terminal record (shed / parked-timeout — requests
        no replica ever ran) built through ``Request.record()`` so the
        schema lives in exactly one place."""
        req = Request(rid=fr.rid, prompt=fr.prompt,
                      max_new_tokens=fr.max_new_tokens, eos_id=fr.eos_id,
                      deadline_s=fr.deadline_s, priority=fr.priority,
                      retries=fr.retries, submit_ts=fr.submit_ts,
                      finish_ts=now, finish_reason=reason)
        rec = req.record()
        rec.update(extra)
        return rec

    def _route_role(self) -> Optional[str]:
        """The submit-path role filter: prefill-first in a disagg
        fleet, EXCEPT while degraded (every prefill replica unreachable
        past the grace window) — then requests place on decode-capable
        replicas, which serve colocated prefill until the heal."""
        return "prefill" if (self.disagg and not self.degraded) else None

    # -- submission --------------------------------------------------------

    def submit(self, prompt: List[int], max_new_tokens: int,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None, priority: int = 0,
               session_id: Optional[int] = None) -> FleetRequest:
        """Route one request into the fleet. Returns a
        :class:`FleetRequest` immediately — possibly already terminal
        (``"shed"``)."""
        width = self.workers[0].engine.context_width
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) + max_new_tokens > width:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens} "
                f"exceeds slot capacity {width}")
        now = self.clock()
        fr = FleetRequest(rid=next(self._rid), prompt=list(prompt),
                          max_new_tokens=max_new_tokens, eos_id=eos_id,
                          deadline_s=deadline_s, priority=priority,
                          session_id=session_id, submit_ts=now)
        self.requests[fr.rid] = fr
        self._active[fr.rid] = fr
        # monotone arrival-work counters (never pruned): the
        # autoscaler's M/M/c arrival-rate estimator diffs these per
        # step — prompt tokens are prefill work, new tokens decode work
        self.arrived_prompt_tokens += len(fr.prompt)
        self.arrived_new_tokens += max_new_tokens
        if self.metrics is not None:
            m = self.metrics
            m.counter("fleet_requests_submitted",
                      "requests routed into the fleet").inc()
            m.counter("fleet_arrived_prompt_tokens",
                      "prefill work arrived").inc(len(fr.prompt))
            m.counter("fleet_arrived_new_tokens",
                      "decode work arrived").inc(max_new_tokens)
        t0 = self.tracer.now_us() if self.tracer is not None else None
        _w0 = time.perf_counter()
        dec = self.router.route(
            prompt_len=len(fr.prompt), max_new_tokens=max_new_tokens,
            deadline_s=deadline_s, session_id=session_id,
            submit_ts=now, now=now, role=self._route_role())
        self._router_cur_s += time.perf_counter() - _w0
        if self.tracer is not None:
            # the rid's flow BEGINS here (phase "s"); every later hop —
            # replica-side queue_wait/decode, a resubmit, the terminal —
            # carries the same id, so the merged trace draws one arrow
            # through every process the request touched
            outcome = ("shed" if dec.shed else "parked"
                       if dec.worker is None
                       else f"replica{dec.worker.replica_id}")
            self.tracer.complete(
                "submit", t0, self.tracer.now_us(), flow_start=fr.rid,
                rid=fr.rid, outcome=outcome)
        if dec.shed:
            self._shed(fr, dec)
            return fr
        if dec.worker is None:
            self._unplaced.append(fr)     # no healthy capacity: park
            return fr
        self._deliver(fr, dec.worker)
        if (self.faults is not None
                and self.faults.should_duplicate_submit(fr.rid)):
            # RPC-retry duplicate: same request delivered again — the
            # replica-boundary rid check must drop it
            self._deliver(fr, dec.worker)
        return fr

    def _deliver(self, fr: FleetRequest, worker: ReplicaWorker) -> None:
        if fr.rid in worker.known:
            self.duplicates_dropped += 1
            if self.tracer is not None:
                self.tracer.instant("dup_dropped", rid=fr.rid,
                                    replica=worker.replica_id)
            return
        fr.replica = worker.replica_id
        fr.attempts.append(worker.replica_id)
        if (self.faults is not None
                and self.faults.should_drop_submit(fr.rid)):
            # delivery lost after assignment: the replica never learns
            # of the rid — the reconcile sweep must notice and resubmit
            fr.local = None
            return
        fr.local = worker.deliver(fr, self.clock())
        if fr.local is None:
            # a real delivery failure (transport error, draining child):
            # same evidence shape as the drop_submit fault — the
            # reconcile sweep re-homes it
            return
        worker.known.add(fr.rid)

    def _shed(self, fr: FleetRequest, dec) -> None:
        self.shed_count += 1
        if self.metrics is not None:
            self.metrics.counter("fleet_shed",
                                 "requests shed at submit").inc()
        fr.record = self._terminal_record(
            fr, "shed", fr.submit_ts,        # shed at submit: wall 0
            shed_reason=dec.shed_reason,
            predicted_completion_s=dec.predicted_completion_s)
        self._finalize(fr)

    # -- recovery ----------------------------------------------------------

    def _resubmit(self, fr: FleetRequest, now: float,
                  reason: str) -> None:
        if fr.local is not None:
            # abandon the old attempt with visible lineage: one
            # "retried" record per abandoned attempt, never terminal
            fr.local.finish_ts = now
            fr.local.finish_reason = "retried"
            self._emit(fr.local.record())
        self.resubmits += 1
        if self.metrics is not None:
            self.metrics.counter(
                "fleet_resubmits",
                "orphaned requests re-homed by the reconcile sweep",
                reason=reason).inc()
        fr.retries += 1
        fr.local, fr.replica = None, None
        if self.tracer is not None:
            # phase "t": the SAME flow id continues — the kill-and-
            # resubmit drill renders as one connected arrow, not two
            # disjoint request lifetimes
            self.tracer.complete(
                "resubmit", now * 1e6, self.tracer.now_us(),
                flow_step=fr.rid, rid=fr.rid, reason=reason,
                retry=fr.retries)
        _log.warning("resubmitting rid=%d (%s), retry %d",
                     fr.rid, reason, fr.retries)
        dec = self.router.route(
            prompt_len=len(fr.prompt),
            max_new_tokens=fr.max_new_tokens, deadline_s=fr.deadline_s,
            session_id=fr.session_id, submit_ts=fr.submit_ts, now=now,
            allow_shed=False, role=self._route_role())
        if dec.worker is None:
            self._unplaced.append(fr)
        else:
            self._deliver(fr, dec.worker)

    def _reconcile(self, now: float) -> None:
        """The anti-entropy sweep: every non-terminal request must be
        held by a live replica that knows its rid. Parked requests
        retry placement first (capacity may have appeared)."""
        self._place_parked(now)
        for fr in list(self._active.values()):
            if fr.record is not None or fr.replica is None:
                continue
            w = self._worker(fr.replica)
            if w.state in ("dead", "released"):
                self._resubmit(fr, now, f"replica-{w.state}")
            elif fr.local is None and w.state in ("live", "draining"):
                self._resubmit(fr, now, "lost-submit")
        if self._unplaced and not self.router.candidates():
            # capacity emergency: parked work and zero live replicas.
            # The drain guard can be raced (a replica killed just before
            # the drain is only OBSERVED dead later), so scale-down
            # yields: cancel a drain rather than strand requests. This
            # check runs AFTER the orphan sweep above: a death verdict
            # (K-confirmed, so one refresh later than it used to be)
            # may park its orphans in this very tick, and the drainer
            # must be recalled before it goes idle and is released.
            w = next((w for w in self.workers if w.state == "draining"),
                     None)
            if w is not None:
                w.state = "live"
                w.cancel_drain()
                _log.warning("drain of replica %d cancelled: no other "
                             "live capacity for %d parked request(s)",
                             w.replica_id, len(self._unplaced))
                self._replica_event("drain-cancelled", w,
                                    parked=len(self._unplaced))
                self._place_parked(now)

    def _place_parked(self, now: float) -> None:
        for fr in list(self._unplaced):
            # a parked request still owns its deadline: no replica will
            # ever run the scheduler's expiry sweep for it, so the fleet
            # does — parked-forever must not exist
            if (fr.deadline_s is not None
                    and now - fr.submit_ts > fr.deadline_s):
                self._unplaced.remove(fr)
                fr.record = self._terminal_record(fr, "timeout", now)
                self._finalize(fr)
                continue
            dec = self.router.route(
                prompt_len=len(fr.prompt),
                max_new_tokens=fr.max_new_tokens,
                deadline_s=fr.deadline_s, session_id=fr.session_id,
                submit_ts=fr.submit_ts, now=now, allow_shed=False,
                role=self._route_role())
            if dec.worker is not None:
                self._unplaced.remove(fr)
                self._deliver(fr, dec.worker)

    def _collect(self) -> None:
        """Drain newly completed replica-side requests into fleet
        terminal records. Completions from superseded attempts (the rid
        was re-homed) or already-terminal rids are counted and dropped —
        the idempotency boundary."""
        for w in self.workers:
            if w.killed or w.state in ("dead", "released"):
                continue
            comp = w.scheduler.completed
            while w._collected < len(comp):
                req = comp[w._collected]
                w._collected += 1
                fr = self.requests.get(req.rid)
                if fr is None:
                    continue
                if fr.record is not None or fr.local is not req:
                    self.stale_completions += 1
                    continue
                fr.record = req.record()
                self._finalize(fr, emit=False)   # scheduler emitted it
                w.known.discard(req.rid)

    # -- prefill→decode handoff (ISSUE 18) ---------------------------------

    def _collect_handoffs(self, now: float) -> None:
        """Sweep finished-prefill KV packages out of the prefill
        replicas into the fleet's handoff ledger. A package is only
        visible once its WHOLE tick reply (header + every framed page
        payload) was absorbed, so a prefill replica killed mid-transfer
        simply never surfaces it — the request still points at the dead
        replica and the ordinary reconcile resubmit re-homes it."""
        for w in self.workers:
            if w.killed or w.state in ("dead", "released"):
                continue
            pop = getattr(w, "pop_handoffs", None)
            if pop is None:
                continue
            for pkg in pop():
                rid = int(pkg["rid"])
                pkg_ep = pkg.get("epoch")
                if (pkg_ep is not None
                        and getattr(w, "lease_epoch", 0)
                        and pkg_ep != w.lease_epoch):
                    # the package arrived under a lease that has since
                    # been revoked (ISSUE 20): its rid was resubmitted
                    # — adopting it would race the retry's own prefill
                    self.stale_epoch_handoffs += 1
                    continue
                fr = self.requests.get(rid)
                if (fr is None or fr.record is not None
                        or fr.replica != w.replica_id):
                    # superseded attempt (the rid was already re-homed
                    # or went terminal): the package is stale evidence
                    self.stale_handoffs += 1
                    continue
                # the request now lives BETWEEN replicas: fleet-owned.
                # reconcile skips replica-None rids; the ledger entry
                # is the liveness obligation instead (deadline-swept
                # in _place_handoffs).
                fr.local, fr.replica = None, None
                w.known.discard(rid)
                self._pending_handoffs[rid] = {
                    "pkg": pkg, "src": w.replica_id,
                    "t0_pc": time.perf_counter(),
                    "t0_us": (self.tracer.now_us()
                              if self.tracer is not None else None)}

    def _place_handoffs(self, now: float) -> None:
        """Adopt every ledgered KV package onto a decode replica:
        least ``pending_new_tokens`` first, try-each until one admits.
        All refused → retry next tick (capacity may appear); zero
        decode-capable replicas → the pages are worthless (their pool
        is gone), drop the package and resubmit through prefill."""
        for rid in list(self._pending_handoffs):
            ho = self._pending_handoffs[rid]
            fr = self.requests[rid]
            if (fr.deadline_s is not None
                    and now - fr.submit_ts > fr.deadline_s):
                del self._pending_handoffs[rid]
                fr.record = self._terminal_record(fr, "timeout", now)
                self._finalize(fr)
                continue
            cands = self.router.candidates("decode")
            if not cands:
                del self._pending_handoffs[rid]
                self._resubmit(fr, now, "handoff-lost")
                continue
            cands.sort(key=lambda w: self.router.load_key(w, None))
            placed = False
            for w in cands:
                req = w.adopt(fr, ho["pkg"], now)
                if req is None:
                    continue
                fr.local, fr.replica = req, w.replica_id
                fr.attempts.append(w.replica_id)
                w.known.add(rid)
                del self._pending_handoffs[rid]
                self._emit_handoff(fr, ho, w, now)
                placed = True
                break
            if not placed and rid in self._pending_handoffs:
                _log.debug("handoff rid=%d found no admitting decode "
                           "replica this tick; retrying", rid)

    def _emit_handoff(self, fr: FleetRequest, ho: Dict[str, Any],
                      dst, now: float) -> None:
        pkg = ho["pkg"]
        meta = pkg["meta"]
        wire = sum(len(b) for b in pkg["blobs"])
        blocks = int(meta.get("blocks") or len(pkg["blobs"]))
        ms = (time.perf_counter() - ho["t0_pc"]) * 1000.0
        self.handoff_count += 1
        self.handoff_wire_bytes += wire
        self.handoff_blocks += blocks
        self._emit({"kind": "kv_handoff", "rid": fr.rid,
                    "blocks": blocks, "wire_bytes": wire,
                    "quant": meta.get("quant"), "transfer_ms": ms,
                    "src_replica": ho["src"],
                    "dst_replica": dst.replica_id, "tick": self.ticks})
        if self.tracer is not None:
            # phase "t": the rid's flow steps THROUGH the handoff span
            # — the merged trace draws prefill-lane → router-lane
            # handoff → decode-lane as one connected arrow
            self.tracer.complete(
                "kv_handoff", ho["t0_us"], self.tracer.now_us(),
                flow_step=fr.rid, rid=fr.rid, blocks=blocks,
                wire_bytes=wire, src=ho["src"], dst=dst.replica_id)

    # -- elastic scale-down ------------------------------------------------

    def drain(self, replica_id: int) -> str:
        """Graceful drain: stop admitting to ``replica_id``, re-route
        its queued (never-admitted) requests, let running slots finish,
        then release. Returns the replica's state."""
        w = self._worker(replica_id)
        if w.state != "live":
            return w.state
        if not any(o.state == "live" for o in self.workers if o is not w):
            raise ValueError(
                f"cannot drain replica {replica_id}: it is the last live "
                f"replica (scale-down below 1 would strand every "
                f"outstanding request)")
        w.state = "draining"
        now = self.clock()
        self._replica_event("draining", w)
        for rid in w.begin_drain(now):
            fr = self.requests.get(rid)
            if fr is not None and fr.record is None:
                self._resubmit(fr, now, "drain")
        return w.state

    # -- partition tolerance (ISSUE 20) ------------------------------------

    def readmit_pending(self) -> List[Any]:
        """Fenced socket workers whose process is still alive and whose
        fence is recent enough (``readmit_grace_s``) that a readmit may
        rescue them. The autoscaler counts these toward role fill —
        fenced is NOT just dead for capacity math, or a heal would land
        a readmitted replica on top of its own replacement."""
        if self.replica_mode != "socket":
            return []
        now = self.clock()
        out = []
        for w in self.workers:
            if (w.state == "dead" and getattr(w, "is_process", False)
                    and not w.killed and not w.transport.closed):
                proc = w.transport.proc
                if (proc is not None and proc.poll() is None
                        and (w._fenced_at is None
                             or now - w._fenced_at
                             <= self.readmit_grace_s)):
                    out.append(w)
        return out

    def _probe_readmits(self, now: float) -> None:
        """Offer every readmit-eligible fenced worker a fresh lease,
        on a capped exponential tick backoff with seeded jitter (a
        healed partition must not see every fenced replica probed on
        the same tick). One short-timeout attempt per probe — cheap
        while the partition holds, immediate once it heals."""
        t = self.ticks
        for w in self.readmit_pending():
            if t < w._next_readmit_tick:
                continue
            if w.try_readmit(next(self._epochs), now):
                self.readmitted += 1
                info = w.readmit_info or {}
                self._replica_event(
                    "readmitted", w, epoch=w.lease_epoch,
                    tokens_while_fenced=info.get("tokens_while_fenced"),
                    stale_epoch_rejects=info.get("stale_epoch_rejects"))
                if self.metrics is not None:
                    self.metrics.counter(
                        "fleet_readmitted_total",
                        "fenced replicas re-admitted after heal").inc()
                if self.tracer is not None:
                    self.tracer.instant("replica_readmitted",
                                        replica=w.replica_id,
                                        epoch=w.lease_epoch)
                # the death verdict is spent: a fresh staleness streak
                # must start from zero for the new incarnation
                self.router._stale_streak.pop(w.replica_id, None)
            else:
                step = 1 << min(w._readmit_attempts, 4)
                w._next_readmit_tick = (
                    t + step + w._readmit_rng.randrange(0, step + 1))

    def _update_degradation(self, now: float) -> None:
        """Disagg partition degradation: zero reachable prefill-capable
        replicas past the grace window flips the fleet to degraded —
        the submit path routes to decode-capable replicas, whose
        schedulers serve colocated prefill (slower, not stuck). Any
        prefill candidate reappearing (heal, readmit, autoscaler
        replacement) releases it immediately."""
        if not self.disagg:
            return
        if self.router.candidates("prefill"):
            self._prefill_lost_at = None
            if self.degraded:
                self.degraded = False
                self.degrade_releases += 1
                self._emit({"kind": "degrade", "event": "released",
                            "t": now, "tick": self.ticks})
                if self.tracer is not None:
                    self.tracer.instant("degrade_released")
            return
        if self._prefill_lost_at is None:
            self._prefill_lost_at = now
            return
        if (not self.degraded
                and now - self._prefill_lost_at >= self.degrade_grace_s):
            self.degraded = True
            self.degradations += 1
            _log.warning("no reachable prefill replica for %.2fs: "
                         "degrading to colocated prefill on decode "
                         "replicas", now - self._prefill_lost_at)
            self._emit({"kind": "degrade", "event": "engaged",
                        "t": now, "tick": self.ticks,
                        "grace_s": self.degrade_grace_s})
            if self.metrics is not None:
                self.metrics.counter(
                    "fleet_degraded_total",
                    "disagg→colocated degradation engagements").inc()
            if self.tracer is not None:
                self.tracer.instant("degrade_engaged")

    # -- the fleet tick ----------------------------------------------------

    def tick(self) -> None:
        """One fleet heartbeat: fire scheduled faults, observe health,
        reconcile assignments, tick every replica, collect completions,
        finalize drains."""
        now = self.clock()
        t = self.ticks
        if self.faults is not None:
            k = self.faults.kill_replica_for_tick(t)
            if k is not None:
                self._worker(k).kill()
            sk = self.faults.sigkill_replica_for_tick(t)
            if sk is not None:
                self._worker(sk).sigkill()
            s = self.faults.stall_replica_for_tick(t)
            if s is not None:
                rep, n = s
                self._worker(rep).stall(t + n)
        for w in self.router.refresh_health(now):
            self._replica_event("dead", w, orphans=w.orphan_count())
            if (getattr(w, "is_process", False)
                    and getattr(w, "_mode", None) == "socket"):
                # fence BY EPOCH (ISSUE 20): a socket replica may live
                # on a host our signals cannot reach — revoke its lease
                # instead of killing. The revocation holds even if the
                # notice below never arrives: every op/reply/handoff/
                # metric-delta of the old epoch is now discarded on
                # both sides of the wire.
                old_ep = w.lease_epoch
                info = w.fence(next(self._epochs), now, tick_idx=t)
                self.fences += 1
                rec = {"kind": "fence", "replica": w.replica_id,
                       "t": now, "tick": t, "reason": "declared-dead",
                       "epoch": old_ep, "new_epoch": w.lease_epoch,
                       "acked": info is not None}
                if info:
                    rec["slots_evicted"] = info.get("slots_evicted")
                    rec["blocks_freed"] = info.get("blocks_freed")
                self._emit(rec)
                if self.metrics is not None:
                    self.metrics.counter(
                        "fleet_fence_total",
                        "lease revocations on declare-dead").inc()
                if self.tracer is not None:
                    self.tracer.instant("replica_fenced",
                                        replica=w.replica_id,
                                        epoch=old_ep)
            else:
                w.on_declared_dead()     # pipe/in-process: fence by
                #                          kill (same host — stronger)
            # retire the ghost's beat (quarantine rename, never delete):
            # watchdogs scanning the root must not re-report it forever
            multihost.retire_heartbeat(self.root, w.replica_id)
        self._probe_readmits(now)
        self._update_degradation(now)
        if self.autoscaler is not None:
            # policy BEFORE reconcile: a cold-spawned replacement is
            # placeable in the same tick that needs it
            self.autoscaler.step(now)
        _w0 = time.perf_counter()
        self._reconcile(now)
        self._router_cur_s += time.perf_counter() - _w0
        for w in self.workers:
            w.tick(now, t)
        _w0 = time.perf_counter()
        self._collect_handoffs(now)
        self._place_handoffs(now)
        self._router_cur_s += time.perf_counter() - _w0
        self._collect()
        if self.tracer is not None:
            for w in self.workers:
                sp = w.drain_spans()
                if sp:
                    self._replica_spans[w.replica_id].extend(sp)
        if self.metrics is not None:
            # absorb the registry deltas that rode this tick's replies,
            # namespaced per replica — the metrics twin of the span
            # drain above (in-process workers return [] here; they
            # already wrote the hub directly)
            for w in self.workers:
                d = w.drain_metrics()
                if d:
                    self.metrics.absorb_delta(
                        d, replica=str(w.replica_id))
        if self.anomaly is not None:
            for w in self.workers:
                if w.killed or w.state in ("dead", "released"):
                    continue
                busy = bool(w.scheduler.running
                            or w.scheduler.prefilling)
                self.anomaly.observe_fleet_tick(
                    w.replica_id, tick=t,
                    engine_ticks=w.engine.ticks,
                    queued=len(w.scheduler.queue), busy=busy)
                ts = w.transport_stats()
                if ts is not None:
                    self.anomaly.observe_transport(w.replica_id, ts)
        for w in self.workers:
            if w.state == "draining" and w.idle():
                w.state = "released"
                w.shutdown()
                multihost.retire_heartbeat(self.root, w.replica_id)
                self._replica_event(
                    "released", w,
                    free_blocks=w.engine.cache.free_blocks)
        self._router_tick_s.append(self._router_cur_s)
        self._router_cur_s = 0.0
        if self.metrics is not None:
            m = self.metrics
            m.counter("fleet_ticks", "fleet heartbeats").inc()
            m.gauge("fleet_active_requests",
                    "non-terminal requests in flight"
                    ).set(len(self._active))
            m.gauge("fleet_unplaced",
                    "parked requests awaiting capacity"
                    ).set(len(self._unplaced))
            m.gauge("fleet_pending_handoffs",
                    "KV packages in the fleet-owned handoff ledger"
                    ).set(len(self._pending_handoffs))
            m.histogram("fleet_router_ms",
                        "host-side placement cost per fleet tick (ms)"
                        ).observe(self._router_tick_s[-1] * 1000.0)
            m.gauge("fleet_degraded",
                    "1 while serving colocated prefill on decode "
                    "replicas (disagg partition degradation)"
                    ).set(1 if self.degraded else 0)
            if self.chaos is not None:
                cs = self.chaos.stats()
                m.gauge("chaos_frames_dropped",
                        "frames discarded by the chaos plane"
                        ).set(cs["frames_dropped"])
                m.gauge("chaos_frames_delayed",
                        "frames held by the chaos plane"
                        ).set(cs["frames_delayed"])
                m.gauge("chaos_bytes_dropped",
                        "wire bytes discarded by the chaos plane"
                        ).set(cs["bytes_dropped"])
                m.gauge("chaos_delay_injected_s",
                        "cumulative injected delay (s)"
                        ).set(cs["delay_injected_s"])
        self.ticks += 1

    def outstanding(self) -> bool:
        return (bool(self._active) or bool(self._pending_handoffs)
                or any(w.state == "draining" for w in self.workers))

    def prune_terminal(self) -> int:
        """Drop terminal requests from the ledger (a long-lived fleet's
        memory bound — the telemetry stream is the durable record).
        Returns how many were pruned."""
        dead = [rid for rid, fr in self.requests.items()
                if fr.record is not None]
        for rid in dead:
            del self.requests[rid]
        return len(dead)

    # -- workload replay ---------------------------------------------------

    def play(self, workload, *, dt_s: Optional[float] = None,
             drain_at_tick: Optional[Dict[int, int]] = None,
             max_ticks: int = 100000) -> List[FleetRequest]:
        """Replay a :func:`~paddle_tpu.serve.loadgen.make_workload`
        trace: submit every arrival whose ``at_s`` has passed, tick,
        advance the clock (``SimClock`` + ``dt_s``; a real clock just
        flows). Arrival times are relative to the START of the replay —
        the clock's epoch (perf_counter's arbitrary origin, a SimClock
        mid-run) must not collapse the trace into one burst.
        ``drain_at_tick`` maps fleet tick index → replica id for
        scripted elastic scale-down. Returns every
        :class:`FleetRequest` in rid order, all terminal."""
        pending = collections.deque(
            sorted(workload, key=lambda g: g.at_s))
        drains = dict(drain_at_tick or {})
        t0 = self.clock()
        for _ in range(max_ticks):
            now = self.clock() - t0
            while pending and pending[0].at_s <= now:
                g = pending.popleft()
                self.submit(g.prompt, g.max_new_tokens, eos_id=g.eos_id,
                            deadline_s=g.deadline_s, priority=g.priority,
                            session_id=g.session_id)
            if self.ticks in drains:
                self.drain(drains.pop(self.ticks))
            if not pending and not drains and not self.outstanding():
                return [self.requests[r] for r in sorted(self.requests)]
            self.tick()
            adv = getattr(self.clock, "advance", None)
            if adv is not None and dt_s is not None:
                adv(dt_s)
        raise RuntimeError(f"fleet did not drain in {max_ticks} ticks "
                           f"({sum(1 for f in self.requests.values() if not f.done)} "
                           f"requests outstanding)")

    # -- fleet observability (ISSUE 17) ------------------------------------

    def fleet_trace(self, tail: Optional[int] = None
                    ) -> Optional[Dict[str, Any]]:
        """Merge the router lane and every replica's shipped spans into
        ONE Chrome/Perfetto trace (``None`` when tracing is off). All
        lanes share the fleet clock, so a rid's ``s``/``t``/``f`` flow
        events connect across processes. ``tail`` keeps only the most
        recent N non-metadata events (the forensic-bundle window)."""
        if self.tracer is None:
            return None
        for w in self.workers:          # sweep spans a tick hasn't yet
            sp = w.drain_spans()
            if sp:
                self._replica_spans[w.replica_id].extend(sp)
        return merge_fleet_trace(self.tracer.events(),
                                 dict(self._replica_spans), tail=tail)

    def save_fleet_trace(self, path: str) -> str:
        """Write the merged fleet trace JSON (open in ui.perfetto.dev).
        Raises when tracing is off — there is nothing to save."""
        tr = self.fleet_trace()
        if tr is None:
            raise ValueError("tracing is off: construct the fleet with "
                             "trace=True")
        return _save_fleet_trace(tr, path)

    def slo_report(self) -> Optional[Dict[str, Any]]:
        """The streaming SLO monitor's snapshot (rolling percentiles,
        goodput, burn rate) — ``None`` when SLO monitoring is off."""
        return self.slo.report() if self.slo is not None else None

    def _transport_totals(self) -> Dict[str, int]:
        """Fleet-wide transport failure counters summed over process
        replicas (all zeros for an in-process fleet). With the registry
        on, the totals READ THROUGH it (satellite 2) — the per-link
        counters are incremented at the exact sites the attribute
        counters are, so both paths agree; the attribute fallback stays
        the dark-mode source of truth."""
        tot = {"errors": 0, "retransmits": 0, "timeouts": 0,
               "corrupt_replies": 0}
        if self.metrics is not None:
            for row in self.metrics.snapshot():
                name = row["name"]
                if (name.startswith("transport_")
                        and row["type"] == "counter"
                        and name[len("transport_"):] in tot):
                    tot[name[len("transport_"):]] += int(row["value"])
            return tot
        for w in self.workers:
            ts = w.transport_stats()
            if ts:
                for k in tot:
                    tot[k] += int(ts.get(k) or 0)
        return tot

    def _membership_stats(self) -> Dict[str, Any]:
        """The epoch-lease membership counters (ISSUE 20): fences
        issued, zombies re-admitted, stale-epoch traffic discarded at
        each merge seam, flap verdicts averted, and the degradation
        state — one dict shared by ``stats()`` and the fleet record."""
        return {
            "fences": self.fences,
            "readmitted": self.readmitted,
            "false_deaths_averted": self.router.false_deaths_averted,
            "stale_epoch_replies": sum(
                getattr(w, "stale_epoch_replies", 0)
                for w in self.workers),
            "stale_epoch_handoffs": self.stale_epoch_handoffs,
            "stale_metric_deltas": sum(
                getattr(w, "stale_metric_deltas", 0)
                for w in self.workers),
            "readmit_pending": len(self.readmit_pending()),
            "degraded": self.degraded,
            "degradations": self.degradations,
            "degrade_releases": self.degrade_releases,
        }

    def emit_stats(self) -> Dict[str, Any]:
        """Emit one ``kind="fleet"`` summary record into the telemetry
        stream (transport totals, recovery counters, the SLO snapshot
        when monitoring is on) — the record ``obs.report`` surfaces as
        the serving transport/SLO blocks. Returns the record."""
        rec: Dict[str, Any] = {
            "kind": "fleet", "tick": self.ticks,
            "resubmits": self.resubmits, "shed": self.shed_count,
            "duplicates_dropped": self.duplicates_dropped,
            "stale_completions": self.stale_completions,
            "transport": self._transport_totals(),
            "membership": self._membership_stats()}
        if self.chaos is not None:
            rec["chaos"] = self.chaos.stats()
        if self.slo is not None:
            rec["slo"] = self.slo.report()
        self._emit(rec)
        if self.metrics is not None:
            # the registry rides the telemetry stream as its own record
            # kind — obs.report/obs.top read it back offline without a
            # live hub (the fleet record's schema is untouched)
            self._emit({"kind": "metrics", "tick": self.ticks,
                        "metrics": self.metrics.snapshot()})
        return rec

    # -- reporting ---------------------------------------------------------

    def _router_ms(self) -> Dict[str, Any]:
        """Host-side placement cost (route + reconcile + handoff
        sweeps) in wall milliseconds, bucketed per fleet tick — the
        hostile-scale loadgen's router-overhead evidence."""
        buckets = self._router_tick_s
        total = sum(buckets) + self._router_cur_s
        return {"total": total * 1000.0,
                "per_tick_mean": ((sum(buckets) / len(buckets)) * 1000.0
                                  if buckets else 0.0),
                "per_tick_max": (max(buckets) * 1000.0
                                 if buckets else 0.0),
                "ticks": len(buckets)}

    def stats(self) -> Dict[str, Any]:
        reasons = collections.Counter(
            fr.record["finish_reason"]
            for fr in self.requests.values() if fr.record)
        per_replica = {}
        for w in self.workers:
            row = {"state": w.state, "killed": w.killed,
                   "role": getattr(w, "role", "both"),
                   "engine_ticks": w.engine.ticks,
                   "free_blocks": w.engine.cache.free_blocks,
                   "prefix_hit_blocks": w.engine.cache.prefix_hit_blocks,
                   "compile_counts": w.engine.compile_counts()}
            ts = w.transport_stats()
            if ts is not None:
                row["transport"] = ts
            if getattr(w, "lease_epoch", 0):
                row["epoch"] = w.lease_epoch
                if getattr(w, "revoked_epoch", None) is not None:
                    row["revoked_epoch"] = w.revoked_epoch
                if getattr(w, "readmits", 0):
                    row["readmits"] = w.readmits
            per_replica[w.replica_id] = row
        scale = ({"scale_events": len(self.autoscaler.events),
                  "desired_replicas": self.autoscaler.desired,
                  "replacements": self.autoscaler.replacements}
                 if self.autoscaler is not None else {})
        out = {
            **scale,
            "submitted": len(self.requests),
            "terminal": sum(1 for fr in self.requests.values()
                            if fr.record is not None),
            "finish_reasons": dict(reasons),
            "resubmits": self.resubmits,
            "shed": self.shed_count,
            "duplicates_dropped": self.duplicates_dropped,
            "stale_completions": self.stale_completions,
            "unplaced": len(self._unplaced),
            "ticks": self.ticks,
            "replica_mode": self.replica_mode,
            "prefix_hit_blocks": sum(
                w.engine.cache.prefix_hit_blocks for w in self.workers),
            "cow_forks": sum(
                w.engine.cache.cow_forks for w in self.workers),
            "transport": self._transport_totals(),
            "replicas": per_replica,
            "handoffs": self.handoff_count,
            "handoff_wire_bytes": self.handoff_wire_bytes,
            "handoff_blocks": self.handoff_blocks,
            "stale_handoffs": self.stale_handoffs,
            "pending_handoffs": len(self._pending_handoffs),
            "router_ms": self._router_ms(),
            # the membership block is UNCONDITIONAL: a dark twin with
            # chaos off must expose the same key set (bench leg 4 pins
            # instrumented-vs-dark stats symmetry) — only "chaos" below
            # is gated on the plane actually being attached
            "membership": self._membership_stats(),
        }
        if self.chaos is not None:
            out["chaos"] = self.chaos.stats()
        if self.slo is not None:
            # burn rate and the rolling percentiles ride the stats dict
            # (ISSUE 17) — the dashboard's one-call snapshot
            out["slo"] = self.slo.report()
        if self.anomaly is not None:
            out["anomalies"] = [v.kind for v in self.anomaly.verdicts]
        return out

    @classmethod
    def from_model(cls, model, variables, n_replicas: int, *,
                   engine_kwargs: Optional[Dict[str, Any]] = None,
                   replica_mode: str = "inprocess",
                   model_spec: Optional[Dict[str, Any]] = None,
                   **kw) -> "ServingFleet":
        """Convenience constructor: N identical engines over one
        checkpoint (the common homogeneous fleet). With
        ``replica_mode="process"`` the model CONFIG plus the variables
        (saved once as an npz under the fleet root) ship to each child
        process, which rebuilds its own engine — the parent never
        shares python objects with a replica. ``model_spec`` overrides
        the introspected TransformerLM constructor kwargs (custom
        models)."""
        from .engine import DecodeEngine
        ek = dict(engine_kwargs or {})
        if replica_mode in ("process", "socket"):
            root = kw.pop("root", None) or tempfile.mkdtemp(
                prefix="paddle_tpu_fleet_")
            spec = build_proc_spec(
                model, variables, root, engine_kwargs=ek,
                model_spec=model_spec, order=kw.get("order", "fcfs"),
                est_tick_s=kw.get("est_tick_s"),
                warmup=kw.pop("warmup", None),
                autotune_cache_dir=kw.pop("autotune_cache_dir", None),
                telemetry_dir=kw.pop("telemetry_dir", None))
            return cls(None, n_replicas, replica_mode=replica_mode,
                       proc_spec=spec, root=root, **kw)

        def mk(_i):
            return DecodeEngine(model, variables, **ek)

        return cls(mk, n_replicas, **kw)


def _require_parent_off_chip(replica_mode: str) -> None:
    """A chip belongs to one process: a parent that has initialised an
    accelerator backend holds it, and a replica child that needs it then
    fails at start-up or waits out the hello timeout. Say so at once, in
    the parent, before anything is spawned. (``xla_bridge`` is private;
    the installed jax 0.9 has no public way to ask whether a backend is
    up without bringing one up.)"""
    import jax
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized() \
            and jax.default_backend() != "cpu":
        raise RuntimeError(
            f"replica_mode={replica_mode!r} starts child processes that "
            f"each need the accelerator, but this process has already "
            f"initialised the {jax.default_backend()!r} backend and holds "
            f"the chip. On a chip host run replicas in-process "
            f"(replica_mode='inprocess'), or start one process per chip "
            f"from a parent that never initialises a JAX backend (build "
            f"the spec under JAX_PLATFORMS=cpu).")


def _introspect_lm(model) -> Dict[str, Any]:
    """Recover the :class:`~paddle_tpu.models.TransformerLM` constructor
    config a child process needs (dense homogeneous blocks — the
    serving contract)."""
    blk = model.blocks[0]
    return {"vocab": model.emb.vocab, "dim": model.emb.dim,
            "num_layers": len(model.blocks),
            "num_heads": blk.attn.num_heads,
            "ffn_hidden": blk.ffn1.features,
            "max_len": model.max_len}


def build_proc_spec(model, variables, root: str, *,
                    engine_kwargs: Optional[Dict[str, Any]] = None,
                    model_spec: Optional[Dict[str, Any]] = None,
                    order: str = "fcfs",
                    est_tick_s: Optional[float] = None,
                    mesh_axes: Optional[Dict[str, int]] = None,
                    warmup: Optional[bool] = None,
                    autotune_cache_dir: Optional[str] = None,
                    telemetry_dir: Optional[str] = None
                    ) -> Dict[str, Any]:
    """The child-process build spec: model constructor kwargs, engine
    kwargs, scheduler policy, and the variables npz (written once under
    ``root``; every replica loads the same file — a training checkpoint
    serves unmodified, just across a process boundary).

    ``mesh_axes`` (ISSUE 15): an optional ``{axis_name: size}`` dict —
    e.g. ``{"model": 2}`` — shipped as ``spec["mesh"]`` so a
    process-mode replica builds its engine TENSOR-PARALLEL over its own
    local devices (a Mesh object cannot cross the JSON wire; the axis
    layout can). Deliberately ABSENT from the spec when None, so a
    single-device spec is byte-identical to the pre-tp schema —
    replicas on old and new code agree on the frame bytes.

    ``warmup`` / ``autotune_cache_dir`` (ISSUE 16): the child executes
    both engine programs before its hello reply, against a
    kernel-autotune cache shared across spawns, so autoscaler
    cold-spawns and supervisor restarts come up warm. Same
    schema-stability rule as ``mesh``: each key is ABSENT when unset.
    The XLA compile cache is not in the spec: every child turns it on
    (``obs.xla_cache.setup``) at the directory its ENVIRONMENT names
    (``JAX_COMPILATION_CACHE_DIR``), else the fixed in-checkout
    default — the same directory for every spawn either way.

    ``telemetry_dir`` (ISSUE 17): a directory where each child replica
    line-flushes its telemetry records to ``replica_<id>.jsonl`` AS
    WELL AS shipping them on tick replies — a SIGKILLed child's records
    up to the kill survive for post-mortem forensics, where the
    reply-shipped copies die with the pipe. ABSENT when unset, like
    every optional key."""
    from .replica_proc import save_variables_npz
    npz = os.path.join(root, "variables.npz")
    save_variables_npz(npz, variables)
    spec = {"model": dict(model_spec or _introspect_lm(model)),
            "engine": dict(engine_kwargs or {}),
            "variables_npz": npz, "order": order,
            "est_tick_s": est_tick_s, "root": root}
    if mesh_axes:
        spec["mesh"] = dict(mesh_axes)
    if warmup is not None:
        spec["warmup"] = bool(warmup)
    if autotune_cache_dir:
        spec["autotune_cache_dir"] = str(autotune_cache_dir)
    if telemetry_dir:
        spec["telemetry_dir"] = str(telemetry_dir)
    return spec
