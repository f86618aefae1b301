"""Serving runtime — continuous batching over a paged KV cache, scaled
out to a fault-drilled multi-replica fleet.

The "millions of users" pillar (ROADMAP #1): training produced a
checkpoint; this package turns it into an incremental-decode server.
The layers mirror the serving literature the design follows (PAPERS.md
[S1] PagedAttention, [S2] Orca, [R2] Bamboo for the death-is-routine
doctrine):

- :mod:`.kv_cache` — the paged KV pool: fixed-size REFCOUNTED blocks
  shared by all concurrent sequences, host-side block
  tables/alloc/free, the content-addressed :class:`PrefixCache` behind
  copy-on-write prefix sharing (ISSUE 12), and the ``jnp``-pure
  gather/scatter used by the compiled programs.
- :mod:`.engine` — :class:`DecodeEngine`: the two compiled fixed-shape
  programs (padded-width or chunked prefill, max-slot decode tick with
  an active mask — optionally the ``[S, 1+k]`` speculative verify
  tick), donated KV carry, greedy or seeded-stochastic sampling
  (:class:`SamplingConfig`), COW fork-on-write, retrace accounting,
  the structured :class:`AdmitProbe` backpressure verdict, and — with
  ``mesh=`` (ISSUE 15) — the whole tick tensor-parallel over a tp mesh
  (megatron-placed params, head-axis-sharded KV pools, token-identical
  to single-device with the host side shard-oblivious).
- :mod:`.scheduler` — :class:`ContinuousBatchingScheduler`: iteration-
  level request admission/eviction between decode ticks with
  FCFS/SJF/priority queue policies, chunked-prefill interleaving,
  submit-time load shedding, deadline eviction, and per-request
  TTFT/TPOT + sharing/speculation telemetry.
- :mod:`.router` / :mod:`.fleet` — :class:`FleetRouter` +
  :class:`ServingFleet` (ISSUE 11): N replica workers behind
  session-affine least-loaded routing, heartbeat health gating (the
  PR-10 machinery), idempotent rid-keyed resubmission of a dead
  replica's requests, graceful drain for elastic scale-down.
- :mod:`.loadgen` — seeded traffic shapes (Poisson/bursty arrivals,
  ragged lengths, shareable-prefix sessions, deadlines/priorities) and
  the :class:`SimClock` that makes fleet fault drills deterministic.
- :mod:`.transport` / :mod:`.replica_proc` (ISSUE 13, sockets +
  binary frames in ISSUE 18) — the length-prefixed submit/complete
  frame protocol (per-message timeout, seq-numbered at-least-once
  delivery, classified corruption), now carried over pipes OR TCP
  sockets (``listen``/``connect``/``SocketFrameReader`` — the SAME
  retry/dedupe brain either way), with CRC-checked binary frames for
  raw payloads (KV pages cross the wire as bytes, not JSON), and the
  child-process replica entrypoint behind
  ``ServingFleet(replica_mode="process"|"socket")``: a SIGKILL, hang,
  or corrupt reply is contained in one process, observed via heartbeat
  staleness, and healed by the same reconcile path.
- **Prefill/decode disaggregation** (ISSUE 18): give
  ``ServingFleet(roles=[...])`` per-replica roles and prefill-role
  replicas run the prompt pass against their LOCAL paged pool, then
  stream the finished KV pages block-by-block to a decode-role replica
  (``export_pages``/``import_pages`` → framed binary payloads → an
  ``adopt`` op), which continues from the first generated token —
  bit-identical to colocated serving, with the handoff rid-keyed
  through the reconcile ledger so mid-transfer death resubmits cleanly.
- :mod:`.chaos` + epoch-fenced membership (ISSUE 20) —
  :class:`NetworkChaos`/:class:`LinkChaos`, the SimClock-deterministic
  network fault plane at the frame seam (per-link delay distributions,
  bandwidth throttle, drop probability, asymmetric partition windows,
  link flap schedules — seeded and ``describe()``-able), driving the
  fleet's partition-tolerant membership: every replica holds a
  monotonically-increasing epoch lease stamped on every frame, a
  declared-dead replica is fenced BY EPOCH (not by kill — no signal
  needs to reach it), a fenced child self-fences on its first stale
  rejection, and a healed partition re-admits the zombie under a fresh
  lease; a disagg fleet that lost every prefill replica degrades to
  colocated prefill on its decoders instead of to stuck.
- :mod:`.autoscaler` — the supervised elastic-capacity policy loop on
  top of ``drain()`` and ``spawn_replica()``, an M/M/c queueing-model
  controller per role (ISSUE 18): Erlang-C predicted delay from an
  arrival-rate EMA + tick-time EMA + role capacity, scale up on
  predicted-delay breach gated on the delay derivative, down on
  sustained idle, hysteresis against flapping, cold-spawn replacement
  of dead replicas under a loud restart budget.
"""

from .kv_cache import (BlockAllocator, PagedKVCache, PrefixCache,
                       PrefixMatch, gather_pages, scatter_prefill,
                       scatter_token, scatter_span,
                       scatter_prefill_pages, write_token, write_span,
                       write_prefill, quantize_rows, dequantize_rows,
                       pages_to_blobs, blobs_to_pages)
from .engine import AdmitProbe, DecodeEngine, SamplingConfig
from .scheduler import ContinuousBatchingScheduler, Request
from .router import FleetRouter, RouteDecision
from .fleet import (FleetRequest, ProcReplicaWorker, ReplicaWorker,
                    ServingFleet, build_proc_spec)
from .loadgen import (GenRequest, SimClock, hostile_workload,
                      make_workload, workload_stats)
from .autoscaler import Autoscaler, AutoscalerGaveUp, erlang_c_wait
from .chaos import LinkChaos, NetworkChaos
from .transport import (BINARY_FLAG, ReplicaTransport,
                        SocketFrameReader, SocketWriter,
                        TransportClosed, TransportCorrupt,
                        TransportError, TransportTimeout,
                        accept_connection, connect,
                        encode_binary_frame, listen,
                        write_binary_frame)

__all__ = ["BlockAllocator", "PagedKVCache", "PrefixCache", "PrefixMatch",
           "DecodeEngine", "AdmitProbe", "SamplingConfig",
           "ContinuousBatchingScheduler", "Request", "gather_pages",
           "scatter_prefill", "scatter_token", "scatter_span",
           "scatter_prefill_pages", "write_token", "write_span",
           "write_prefill", "quantize_rows", "dequantize_rows",
           "FleetRouter", "RouteDecision", "ServingFleet",
           "ReplicaWorker", "ProcReplicaWorker", "FleetRequest",
           "build_proc_spec",
           "pages_to_blobs", "blobs_to_pages",
           "Autoscaler", "AutoscalerGaveUp", "erlang_c_wait",
           "LinkChaos", "NetworkChaos",
           "ReplicaTransport", "TransportError", "TransportTimeout",
           "TransportCorrupt", "TransportClosed",
           "SocketFrameReader", "SocketWriter", "listen", "connect",
           "accept_connection", "encode_binary_frame",
           "write_binary_frame", "BINARY_FLAG",
           "GenRequest", "SimClock", "make_workload",
           "hostile_workload", "workload_stats"]
