"""Serving-replica child process entrypoint (ISSUE 13).

``python -m paddle_tpu.serve.replica_proc --spec '<json>'`` builds its
OWN :class:`~paddle_tpu.serve.engine.DecodeEngine` +
:class:`~paddle_tpu.serve.scheduler.ContinuousBatchingScheduler` pair
from the spec (model config + a variables ``.npz`` the parent saved —
training checkpoints serve unmodified, just like the in-process path),
then serves the :mod:`~paddle_tpu.serve.transport` frame protocol over
stdin/stdout until EOF or a ``stop`` op.

The contract that makes a SIGKILL here a non-event for the router:

- The child writes its OWN PR-10 heartbeat file each handled tick (the
  ``now`` carried on the tick message — the fleet's clock is the one
  time base, so SimClock drills stay deterministic). Kill the process
  and the beats simply stop; the router observes staleness and the
  fleet re-homes the requests. Nothing is announced.
- Every request is handled at-least-once-safely: a ``seq`` already
  processed replays the cached reply bytes (a retransmit after a lost
  or corrupted reply never re-executes a tick), and a ``submit`` whose
  rid is already known acks as a duplicate (the PR-11 idempotency
  boundary, now enforced on BOTH sides of the pipe).
- Telemetry emitted child-side (request records, deadline evictions) is
  buffered and shipped on the next tick reply; the PARENT re-emits it
  into the fleet's single stream — one telemetry stream, one terminal
  record per rid, exactly as in-process.
- Injected faults arrive as flags ON the message (``inject_drop_reply``
  / ``inject_corrupt_reply``): the child does the work, then loses or
  garbles the reply — so the drill exercises the real
  timeout→retransmit→cached-reply path, not a mock of it.

The first thing ``main`` does — before importing jax or building the
model — is dup the real stdout away and point fd 1 at stderr, so no
library print can ever tear a frame.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
from typing import Any, Dict, List, Optional

__all__ = ["save_variables_npz", "load_variables_npz", "serve_loop",
           "EventBuffer", "SettableClock", "main"]

# separator for flattened variable-tree paths in the .npz; module names
# are identifier-like (no "::" can appear in a key)
_SEP = "::"


def save_variables_npz(path: str, variables: Dict[str, Any]) -> str:
    """Flatten a nested variables dict into one ``.npz`` (atomic
    tmp+rename — a crashed writer never leaves a torn file a spawning
    child could half-load)."""
    import numpy as np

    flat: Dict[str, Any] = {}

    def walk(d, prefix):
        for k, v in d.items():
            key = f"{prefix}{_SEP}{k}" if prefix else str(k)
            if isinstance(v, dict):
                walk(v, key)
            else:
                flat[key] = np.asarray(v)

    walk(variables, "")
    # np.savez appends ".npz" to names without it: keep the suffix so
    # the tmp name we rename is the name savez actually wrote
    tmp = f"{path}.tmp.{os.getpid()}.npz"
    np.savez(tmp, **flat)
    os.replace(tmp, path)
    return path


def load_variables_npz(path: str) -> Dict[str, Any]:
    """Rebuild the nested variables dict :func:`save_variables_npz`
    flattened."""
    import numpy as np

    out: Dict[str, Any] = {}
    with np.load(path) as z:
        for key in z.files:
            parts = key.split(_SEP)
            d = out
            for p in parts[:-1]:
                d = d.setdefault(p, {})
            d[parts[-1]] = z[key]
    return out


class EventBuffer:
    """Telemetry shim for the child's scheduler: captures emitted
    records so the tick handler can ship them to the parent (which owns
    the fleet's single telemetry stream).

    ``jsonl_path`` (ISSUE 17 satellite) additionally appends every
    record to a local JSONL, flushed per record — the child's
    decode_tick/request evidence survives a SIGKILL even though the
    buffered copy dies with the process. Shipping is unchanged
    (:meth:`drain` still hands the parent everything); the file is the
    forensic sibling, not a second stream of record."""

    def __init__(self, jsonl_path: Optional[str] = None):
        self.records: List[Dict[str, Any]] = []
        self._f = None
        if jsonl_path:
            d = os.path.dirname(jsonl_path)
            if d:
                os.makedirs(d, exist_ok=True)
            self._f = open(jsonl_path, "a")

    def emit_event(self, rec: Dict[str, Any]) -> None:
        self.records.append(rec)
        if self._f is not None:
            try:
                self._f.write(json.dumps(rec, default=str) + "\n")
                self._f.flush()
            except OSError:
                pass                     # persistence is best-effort

    def drain(self) -> List[Dict[str, Any]]:
        out, self.records = self.records, []
        return out


class SettableClock:
    """The child's clock is SET from each message's ``now`` — the fleet
    clock is the single time base for deadlines, TTFT and heartbeats,
    which is what keeps SimClock drills deterministic across the
    process boundary."""

    def __init__(self, t0: float = 0.0):
        self.t = float(t0)

    def __call__(self) -> float:
        return self.t

    def set(self, now: Optional[float]) -> None:
        if now is not None:
            self.t = float(now)


def _build(spec: Dict[str, Any]):
    """Heavy construction (jax import lives here): model from spec,
    variables from the parent's npz (or a seeded init — bit-identical
    to a parent that used the same seed), engine + scheduler. An
    optional ``spec["mesh"]`` (``{axis_name: size}``, ISSUE 15) builds
    the engine tensor-parallel over this process's local devices — the
    Mesh itself is constructed HERE because device handles cannot cross
    the JSON wire; a spec without the key is the single-device engine,
    bit-identical to the pre-tp build.

    Cold-start elimination (ISSUE 16): ``main`` turns the persistent
    compilation cache on before this runs (``obs.xla_cache.setup``: the
    directory comes from ``JAX_COMPILATION_CACHE_DIR`` in the child's
    environment, else the fixed in-checkout default — never from the
    spec — so every spawn shares it), ``spec["autotune_cache_dir"]``
    enables the kernel autotuner against its JSON cache, and
    ``spec["warmup"]`` executes both engine programs before the hello
    reply — so an autoscaler cold-spawn or supervisor restart answers
    its first request with zero compiles on the serving path. Both keys
    are ABSENT from a default spec (byte-identical schema). Returns
    ``(engine, sched, buf, clock, startup_ms)`` where ``startup_ms``
    is the build/compile/warmup wall breakdown the hello and heartbeat
    payloads carry."""
    import time

    t_start = time.perf_counter()
    import jax
    import jax.numpy as jnp

    from ..models import TransformerLM
    from .engine import DecodeEngine
    from .scheduler import ContinuousBatchingScheduler

    if spec.get("autotune_cache_dir"):
        from ..nn import autotune
        autotune.enable(spec["autotune_cache_dir"])

    model = TransformerLM(**spec["model"])
    if spec.get("variables_npz"):
        loaded = load_variables_npz(spec["variables_npz"])
        vs = jax.tree_util.tree_map(jnp.asarray, loaded)
    else:
        vs = model.init(jax.random.PRNGKey(int(spec.get("seed", 0))),
                        jnp.zeros((1, model.max_len), jnp.int32))
    ek = dict(spec.get("engine") or {})
    mesh_axes = spec.get("mesh")
    if mesh_axes:
        import numpy as np
        from jax.sharding import Mesh
        names = tuple(mesh_axes)
        sizes = tuple(int(mesh_axes[n]) for n in names)
        need = int(np.prod(sizes))
        devs = jax.devices()
        if len(devs) < need:
            raise RuntimeError(
                f"spec mesh {dict(mesh_axes)} needs {need} devices, "
                f"replica has {len(devs)} — spawn with "
                f"--xla_force_host_platform_device_count or drop the "
                f"mesh from the spec")
        ek["mesh"] = Mesh(np.asarray(devs[:need]).reshape(sizes), names)
    engine = DecodeEngine(model, vs, **ek)
    t_built = time.perf_counter()
    dev = jax.devices()[0]
    startup: Dict[str, Any] = {
        "build": round((t_built - t_start) * 1e3, 3),
        # where this replica runs, as jax reports it: a startup or
        # serving number read off the hello names its device
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())}}
    if spec.get("warmup"):
        rep = engine.warmup()
        t_warm = time.perf_counter()
        # "compile" = the programs' first executions (where XLA compile
        # or persistent-cache deserialize happens); "warmup" = the
        # remainder (extra trial iterations, cache bookkeeping)
        compile_ms = (rep["prefill_s"] + rep["tick_s"]) * 1e3
        startup.update({
            "compile": round(compile_ms, 3),
            "warmup": round((t_warm - t_built) * 1e3 - compile_ms, 3),
            "total": round((t_warm - t_start) * 1e3, 3),
            "autotune_trials": rep["autotune_trials"],
            "autotune_cache_hit": rep["autotune_cache_hit"],
            "xla_cache_hit": rep["xla_cache_hit"],
            "xla_cache_entries_added": rep["xla_cache_entries_added"],
        })
    else:
        startup.update({"compile": 0.0, "warmup": 0.0,
                        "total": startup["build"]})
    buf = EventBuffer(jsonl_path=(
        os.path.join(spec["telemetry_dir"],
                     f"replica_{int(spec.get('replica_id', 0))}.jsonl")
        if spec.get("telemetry_dir") else None))
    clock = SettableClock()
    tracer = None
    if spec.get("trace"):
        # distributed tracing (ISSUE 17): the child's spans are stamped
        # with the SettableClock — i.e. the message-carried fleet clock
        # — so the parent's merge puts every process on one time base
        from ..obs.trace import Tracer
        tracer = Tracer(clock=clock)
        engine.tracer = tracer
    metrics = None
    if spec.get("metrics"):
        # fleet metrics (ISSUE 19): the child grows its OWN registry,
        # stamped by the same message-carried fleet clock; deltas ship
        # on tick replies (the span-batch move) and the parent merges
        # them under a replica=<id> label
        from ..obs.metrics import MetricsHub
        metrics = MetricsHub(clock=clock)
        engine.metrics = metrics
    sched = ContinuousBatchingScheduler(
        engine, telemetry=buf, order=spec.get("order", "fcfs"),
        shed=False, est_tick_s=spec.get("est_tick_s"), clock=clock,
        tracer=tracer, role=spec.get("role", "both"), metrics=metrics)
    return engine, sched, buf, clock, startup, metrics


def serve_loop(read_file, write_file, *, engine, sched, buf, clock,
               root: str, replica_id: int,
               reply_cache_size: int = 16,
               startup: Optional[Dict[str, Any]] = None,
               metrics=None,
               lease_timeout_s: Optional[float] = None) -> int:
    """The child's message loop (transport-layer concerns only — the
    handler logic is inline because it IS the replica). Returns the exit
    code; EOF on stdin is a clean shutdown (the parent died or closed
    us).

    **Epoch leases (ISSUE 20).** The hello grants this replica a
    monotonically-increasing epoch; every stamped op must carry it.
    A ``fence`` op (or an op stamped with a NEWER epoch, or — with
    ``lease_timeout_s`` set — a contact gap longer than the lease) makes
    the child **self-fence**: evict every slot, free the blocks, drop
    all bookkeeping, stop heartbeating, and from then on REJECT every
    op carrying the revoked epoch (``error="stale_epoch"``) — so a
    replica the router falsely declared dead behind a partition can
    never double-execute a rid that was resubmitted elsewhere, even
    though no kill signal can reach its host. A ``readmit`` op grants a
    fresh epoch and a clean slate. Unstamped ops (legacy/fake drivers)
    pass unchecked on an unfenced child; a fenced child rejects them
    too — the fence is the stronger invariant. Every reply is stamped
    with the child's lease epoch so the parent can discard replies from
    an epoch it already revoked."""
    from ..parallel import multihost
    from . import transport as tp

    # a pre-built reader (SocketFrameReader in --connect mode) passes
    # through; a file/fd gets the stock FrameReader
    reader = (read_file if isinstance(read_file, tp.FrameReader)
              else tp.FrameReader(read_file))
    tracer = getattr(sched, "tracer", None)
    reply_cache: "collections.OrderedDict[int, bytes]" = \
        collections.OrderedDict()
    known = set()                      # delivered rids (idempotency)
    collected = 0                      # sched.completed cursor
    hb_seq = 0
    draining = False
    # the lease (ISSUE 20): epoch 0 = never granted (unstamped legacy
    # drivers); last_contact tracks the message-carried fleet clock so
    # lease expiry is SimClock-deterministic like everything else
    lease = {"epoch": 0, "timeout_s": lease_timeout_s,
             "last_contact": None}
    fstate: Dict[str, Any] = {"fenced": False, "info": None,
                              "stale_rejects": 0}

    def _self_fence(reason: str) -> Dict[str, Any]:
        """Evict everything, free the blocks, stop beating — the child
        half of the membership protocol. Idempotent; returns the fence
        record. Mirrors the in-process zombie fence
        (``ReplicaWorker.reset``)."""
        if fstate["fenced"]:
            return fstate["info"]
        free_before = engine.cache.free_blocks
        slots = 0
        for slot in list(sched.running):
            engine.evict(slot)
            slots += 1
        for slot in list(sched.prefilling):
            engine.evict(slot)
            slots += 1
        sched.running.clear()
        sched.prefilling.clear()
        sched.queue.clear()
        if getattr(sched, "handoffs", None) is not None:
            sched.handoffs.clear()
        known.clear()
        info = {"kind": "fence", "replica": replica_id,
                "t": clock(), "reason": reason,
                "epoch": lease["epoch"], "slots_evicted": slots,
                "blocks_freed": engine.cache.free_blocks - free_before,
                # the split-brain oracle: tokens generated AFTER this
                # point are zombie work — the drill asserts zero
                "tokens_at_fence": engine.tokens_generated,
                "source": "replica"}
        fstate["fenced"] = True
        fstate["info"] = info
        # forensics: lands in the child's local JSONL immediately and
        # ships to the parent stream on the first post-readmit tick
        buf.emit_event(info)
        if metrics is not None:
            metrics.counter("fleet_fence_total",
                            "self-fence events on this replica",
                            reason=reason).inc()
        return info

    def load_report() -> Dict[str, Any]:
        rep = sched.load_report()
        rep.update({
            "free_blocks": engine.cache.free_blocks,
            "free_slots": len(engine.free_slots()),
            # getattr: the engine surface here is duck-typed (tests and
            # remote views fake it); tp arrived in ISSUE 15
            "tp_degree": getattr(engine, "tp_degree", 1),
            "engine_ticks": engine.ticks,
            "prefix_hit_blocks": engine.cache.prefix_hit_blocks,
            "cow_forks": engine.cache.cow_forks,
            "est_tick_s": sched.est_tick_s,
            "compile_counts": engine.compile_counts(),
            "running_rids": [r.rid for r in sched.running.values()],
            "queued_rids": [r.rid for r in sched.queue],
            "prefilling_rids": [r.rid for r in sched.prefilling.values()],
        })
        return rep

    def beat(now: Optional[float]) -> None:
        nonlocal hb_seq
        if fstate["fenced"]:
            # a fenced replica is out of the membership: beating would
            # advertise capacity the router must not route to
            return
        hb_seq += 1
        multihost.write_heartbeat(
            root, host_id=replica_id, seq=hb_seq, now=now,
            extra={"role": "serving-replica", "pid": os.getpid(),
                   **({"startup_ms": startup} if startup else {}),
                   **{k: v for k, v in load_report().items()
                      if not k.endswith("_rids")
                      and k != "compile_counts"}})

    def _geometry() -> Dict[str, Any]:
        return {"pid": os.getpid(),
                "context_width": engine.context_width,
                "max_slots": engine.max_slots,
                "block_size": engine.cache.block_size,
                "num_blocks": engine.cache.num_blocks}

    def handle(msg: Dict[str, Any]) -> Dict[str, Any]:
        nonlocal collected, draining
        op = msg.get("op")
        clock.set(msg.get("now"))
        ep = msg.get("epoch")
        if op == "hello":
            # the initial lease grant — handled BEFORE the epoch guard
            # (the granted epoch is necessarily newer than the zero
            # lease, which must not read as a supersession)
            if ep is not None and int(ep) > lease["epoch"]:
                lease["epoch"] = int(ep)
            lease["last_contact"] = clock()
            beat(msg.get("now"))
            return {"ok": True, "startup_ms": startup,
                    "load": load_report(), **_geometry()}
        if op == "fence":
            # revocation notice: the router declared us dead and bumped
            # the epoch. Fence NOW (recording the revoked epoch), then
            # adopt the new one so zombie-driven ops carrying the old
            # epoch classify as stale, not merely "fenced".
            info = _self_fence("revoked")
            if ep is not None and int(ep) > lease["epoch"]:
                lease["epoch"] = int(ep)
            return {"ok": True, "fenced": True, "fence": info}
        if op == "readmit":
            # a fresh lease + a clean slate: the partition healed and
            # the router is re-admitting this (empty, fenced) replica
            new_ep = int(msg["epoch"])
            if new_ep <= lease["epoch"] and lease["epoch"]:
                return {"ok": False, "error": "stale_epoch",
                        "epoch": lease["epoch"]}
            info = fstate["info"]
            report = {
                "ok": True, "epoch": new_ep, "fence": info,
                "stale_epoch_rejects": fstate["stale_rejects"],
                # zombie-work oracle: tokens generated between the
                # fence and this readmit (the drill asserts 0)
                "tokens_while_fenced": (
                    engine.tokens_generated - info["tokens_at_fence"]
                    if info else 0),
                **_geometry()}
            lease["epoch"] = new_ep
            lease["last_contact"] = clock()
            fstate["fenced"] = False
            known.clear()
            draining = False
            beat(msg.get("now"))
            report["load"] = load_report()
            return report
        if op == "stop":
            # the shutdown path must work regardless of lease state —
            # a fenced child still exits cleanly
            return {"ok": True, "stopping": True}
        if fstate["fenced"]:
            # THE fence: no op carrying the revoked epoch (or none at
            # all) executes on a fenced replica — a falsely-declared-
            # dead zombie cannot double-run a resubmitted rid
            if ep is not None and int(ep) < lease["epoch"]:
                fstate["stale_rejects"] += 1
                return {"ok": False, "error": "stale_epoch",
                        "epoch": lease["epoch"]}
            return {"ok": False, "error": "fenced",
                    "epoch": lease["epoch"]}
        if ep is not None:
            ep = int(ep)
            if ep > lease["epoch"]:
                if lease["epoch"] == 0:
                    lease["epoch"] = ep     # implicit grant (no hello)
                else:
                    # someone holds a NEWER lease for this replica id:
                    # this process was superseded — fence, don't race
                    _self_fence("superseded")
                    lease["epoch"] = ep
                    return {"ok": False, "error": "fenced",
                            "epoch": lease["epoch"]}
            elif ep < lease["epoch"]:
                fstate["stale_rejects"] += 1
                return {"ok": False, "error": "stale_epoch",
                        "epoch": lease["epoch"]}
            now_t = clock()
            lt = lease["timeout_s"]
            if (lt is not None and lease["last_contact"] is not None
                    and now_t - lease["last_contact"] > float(lt)):
                # the router has been silent longer than the lease: our
                # epoch may be revoked on the other side of a partition
                # — fence unilaterally rather than keep decoding rids
                # that are being resubmitted elsewhere
                _self_fence("lease-expired")
                return {"ok": False, "error": "fenced",
                        "epoch": lease["epoch"]}
            lease["last_contact"] = now_t
        if op == "submit":
            rid = int(msg["rid"])
            if rid in known:
                if tracer is not None:
                    tracer.instant("dup_submit", rid=rid)
                return {"ok": True, "rid": rid, "duplicate": True}
            if draining:
                # the drain contract: admit nothing new; the fleet's
                # reconcile re-homes the request
                return {"ok": False, "rid": rid, "reason": "draining"}
            sched.submit(msg["prompt"], int(msg["max_new_tokens"]),
                         eos_id=msg.get("eos_id"),
                         deadline_s=msg.get("deadline_s"),
                         priority=int(msg.get("priority") or 0),
                         rid=rid, submit_ts=msg.get("submit_ts"),
                         retries=int(msg.get("retries") or 0))
            known.add(rid)
            return {"ok": True, "rid": rid, "duplicate": False}
        if op == "adopt":
            # prefill→decode handoff (ISSUE 18): the KV pages arrived
            # as framed binary payloads riding this message
            rid = int(msg["rid"])
            if rid in known:
                return {"ok": True, "rid": rid, "duplicate": True}
            if draining:
                return {"ok": False, "rid": rid, "reason": "draining"}
            blobs = msg.get("blobs") or []
            if msg.get("_corrupt_blobs") or any(b is None for b in blobs):
                # a payload failed its CRC: frame sync survived (the
                # whole frame was consumed), so refuse cleanly — the
                # fleet retries or re-homes
                return {"ok": False, "rid": rid,
                        "reason": "corrupt-payload"}
            from .kv_cache import blobs_to_pages
            cache = engine.cache
            try:
                kpages, vpages = blobs_to_pages(
                    blobs, num_layers=cache.num_layers,
                    block_size=cache.block_size,
                    num_heads=cache.num_heads, head_dim=cache.head_dim,
                    quantized=cache.quantized, dtype=cache.dtype)
            except ValueError as e:
                return {"ok": False, "rid": rid,
                        "reason": f"corrupt-payload: {e}"}
            req = sched.adopt(msg["meta"], kpages, vpages)
            if req is None:
                return {"ok": False, "rid": rid,
                        "reason": sched.last_backpressure or "capacity"}
            known.add(rid)
            return {"ok": True, "rid": rid, "duplicate": False}
        if op == "tick":
            sched.step()
            beat(msg.get("now"))
            completed = []
            comp = sched.completed
            while collected < len(comp):
                req = comp[collected]
                collected += 1
                known.discard(req.rid)
                completed.append({"record": req.record(),
                                  "tokens": list(req.tokens)})
            reply = {"ok": True, "tick": msg.get("tick"),
                     "completed": completed, "events": buf.drain(),
                     "load": load_report()}
            # finished-prefill KV packages ship on the tick reply as
            # framed binary payloads (a prefill-role replica only;
            # getattr: transport tests drive serve_loop with fakes)
            pop = getattr(sched, "pop_handoffs", None)
            if pop is not None:
                handoffs, out_blobs = [], []
                from .kv_cache import pages_to_blobs
                for req, hmeta, kpages, vpages in pop():
                    hb = pages_to_blobs(kpages, vpages)
                    known.discard(req.rid)   # fleet-owned now: a later
                    # re-delivery (decode death) must not dedupe here
                    handoffs.append({"rid": req.rid, "meta": hmeta,
                                     "nblobs": len(hb)})
                    out_blobs.extend(hb)
                if handoffs:
                    reply["handoffs"] = handoffs
                    reply["_blobs"] = out_blobs
            if tracer is not None:
                # span-batch shipping: spans ride the tick reply the
                # work already uses (no side-channel files; a SIGKILL
                # loses at most one tick's worth)
                reply["spans"] = tracer.drain_events()
            if metrics is not None:
                # registry deltas piggyback the same way: whatever
                # changed since the last drain rides this reply, and a
                # batch undelivered at SIGKILL honestly dies with the
                # process (the drained watermark died too)
                deltas = metrics.drain_delta()
                if deltas:
                    reply["metrics"] = deltas
            return reply
        if op == "drain":
            draining = True
            rids = []
            for req in list(sched.queue):
                sched.queue.remove(req)
                known.discard(req.rid)
                rids.append(req.rid)
            return {"ok": True, "queued_rids": rids,
                    "load": load_report()}
        if op == "resume":
            # drain cancelled (the raced-capacity yield, PR 11): this
            # replica is live again and must admit
            draining = False
            return {"ok": True, "load": load_report()}
        if op == "metrics":
            # remote scrape (ISSUE 19): the full local registry as
            # Prometheus text. A read, not a drain — the tick-reply
            # delta watermarks are untouched, so scraping never steals
            # increments from the parent's merge.
            if metrics is None:
                return {"ok": False, "error": "metrics not enabled"}
            return {"ok": True, "exposition": metrics.render()}
        if op == "stats":
            return {"ok": True, "load": load_report(),
                    "compile_counts": engine.compile_counts(),
                    "free_blocks": engine.cache.free_blocks,
                    "num_blocks": engine.cache.num_blocks,
                    "ticks": engine.ticks,
                    "tokens_generated": engine.tokens_generated,
                    "fenced": fstate["fenced"],
                    "fence": fstate["info"],
                    "stale_epoch_rejects": fstate["stale_rejects"]}
        return {"ok": False, "error": f"unknown op {op!r}"}

    while True:
        try:
            msg = reader.read_frame()
        except tp.TransportClosed:
            return 0                    # parent went away: clean exit
        # the blob channel: a message declaring nblobs is followed by
        # that many binary frames — consumed UNCONDITIONALLY (a cached-
        # seq retransmit resends its blobs too; skipping them would
        # desync the stream). A CRC-failed payload keeps frame sync
        # (the whole frame was consumed) and is classified, not fatal.
        nblobs = int(msg.get("nblobs") or 0)
        if nblobs:
            blobs: List[Optional[bytes]] = []
            corrupt = False
            try:
                for _ in range(nblobs):
                    try:
                        blobs.append(reader.read_binary_frame())
                    except tp.TransportCorrupt:
                        blobs.append(None)
                        corrupt = True
            except tp.TransportClosed:
                return 0
            msg["blobs"] = blobs
            if corrupt:
                msg["_corrupt_blobs"] = True
        seq = msg.get("seq", 0)
        if seq in reply_cache:
            # at-least-once retransmit: replay the cached bytes, never
            # re-execute the work
            try:
                write_file.write(reply_cache[seq])
                write_file.flush()
            except (BrokenPipeError, OSError):
                return 0
            continue
        try:
            reply = handle(msg)
        except Exception as e:          # a handler bug must not kill the
            # replica — classify it; the parent re-homes on not-ok
            reply = {"ok": False,
                     "error": f"{type(e).__name__}: {e}"}
        reply["seq"] = seq
        # every reply carries the child's lease epoch: the parent
        # discards whole replies from an epoch it already revoked
        reply.setdefault("epoch", lease["epoch"])
        out_blobs = reply.pop("_blobs", None) or []
        if out_blobs:
            reply["nblobs"] = len(out_blobs)
        data = tp.encode_frame(reply)
        for b in out_blobs:
            # cached as ONE byte string with the reply: a retransmit
            # replays message + payloads exactly as first sent
            data += tp.encode_binary_frame(b)
        reply_cache[seq] = data
        while len(reply_cache) > reply_cache_size:
            reply_cache.popitem(last=False)
        try:
            if msg.get("inject_drop_reply"):
                pass                    # work done; the reply is "lost"
            elif msg.get("inject_corrupt_reply"):
                # a framed garble: valid length prefix, unparseable body
                garbage = b"\xff\xfe<corrupt-reply>"
                write_file.write(
                    tp._HEADER.pack(len(garbage)) + garbage)
                write_file.flush()
            else:
                write_file.write(data)
                write_file.flush()
        except (BrokenPipeError, OSError):
            return 0
        if reply.get("stopping"):
            return 0


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.serve.replica_proc",
        description="One process-isolated serving replica speaking the "
                    "length-prefixed frame protocol on stdin/stdout.")
    p.add_argument("--spec", required=True,
                   help="JSON spec (or @path to a JSON file): model "
                        "config, engine kwargs, variables npz, root, "
                        "replica_id")
    p.add_argument("--connect", default=None, metavar="HOST:PORT",
                   help="dial the fleet's TCP listener and speak the "
                        "frame protocol over the socket instead of "
                        "stdin/stdout (cross-host serving; loopback "
                        "in CI)")
    args = p.parse_args(argv)

    from . import transport as tp

    if args.connect:
        # socket transport: stdout was already pointed at stderr by the
        # spawner; still shield fd 1 so stray prints go to the log
        os.dup2(2, 1)
        sys.stdout = sys.stderr
        host, _, port = args.connect.rpartition(":")
        sock = tp.connect(host or "127.0.0.1", int(port))
        read_file: Any = tp.SocketFrameReader(sock)
        out: Any = tp.SocketWriter(sock)
    else:
        # claim the transport BEFORE anything can print: dup the real
        # stdout for frames, then point fd 1 at stderr so stray prints
        # (library warnings, user code) can never tear a frame
        out = os.fdopen(os.dup(1), "wb")
        os.dup2(2, 1)
        sys.stdout = sys.stderr
        read_file = sys.stdin.buffer

    raw = args.spec
    if raw.startswith("@"):
        with open(raw[1:]) as f:
            raw = f.read()
    spec = json.loads(raw)
    from ..obs import xla_cache
    xla_cache.setup()
    engine, sched, buf, clock, startup, metrics = _build(spec)
    return serve_loop(
        read_file, out, engine=engine, sched=sched, buf=buf,
        clock=clock, root=spec["root"],
        replica_id=int(spec["replica_id"]), startup=startup,
        metrics=metrics, lease_timeout_s=spec.get("lease_timeout_s"))


if __name__ == "__main__":
    sys.exit(main())
