"""Iteration-level (continuous-batching) request scheduling — the Orca
move (PAPERS.md [S2]): scheduling decisions happen between DECODE TICKS,
not between whole requests.

Classic static batching gangs requests: a batch runs until its LONGEST
member finishes, so every short request's slot sits idle (masked lanes
burning a full tick's work) while the straggler decodes. Iteration-level
scheduling admits a queued request into a slot the moment one frees and
evicts a finished request the moment its last token lands — the decode
tick's fixed ``[S]`` shape never changes (the engine's active mask
absorbs churn), so the scheduler is pure host bookkeeping between
compiled calls.

Host/device overlap reuses the PR-3 host-pipeline move at tick scale:
``decode_tick`` dispatches async, the host does its admission staging
(prompt padding, table edits) and request bookkeeping UNDER the in-flight
call, and the token fetch that closes the tick is the drain.

Per-request telemetry (the serving SLO vocabulary): **TTFT** (time to
first token — submit to prefill's greedy token) and **TPOT** (time per
output token — mean inter-token gap over the decode ticks), emitted as
one ``kind="request"`` record per completed request.

SLO-aware admission (ISSUE 11): because admission happens between ticks,
the QUEUE ORDER is the whole scheduling policy surface — exactly Orca's
point. ``order="fcfs"`` admits in arrival order, ``order="sjf"``
shortest-job-first by decode budget (short requests stop dying behind
stragglers — the goodput-under-deadline win the bench fleet gate
measures), ``order="priority"`` by descending ``priority`` tier. On top,
``shed=True`` rejects a deadline-carrying request AT SUBMIT when the
predicted completion time already blows its deadline
(``finish_reason="shed"``) — under overload the queue stops growing and
p99 for admitted requests stays bounded, instead of every request
timing out after burning a slot reservation.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any, Dict, List, Optional

from ..obs.trace import NULL_SPAN, live, tspan

__all__ = ["Request", "ContinuousBatchingScheduler", "ORDERS"]

# queue-order policies: arrival order, shortest-decode-budget-first,
# descending priority tier (ties broken by arrival in all three)
ORDERS = ("fcfs", "sjf", "priority")


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle timestamps.

    ``deadline_s`` (optional) is a wall-clock budget from submit: a
    request still running (or still queued) past it is evicted between
    ticks with ``finish_reason="timeout"`` and its blocks freed — a
    stuck/long request can no longer occupy a slot and its worst-case
    block reservation forever (ISSUE 10). ``finish_reason`` is
    ``"length"`` | ``"eos"`` | ``"timeout"`` | ``"shed"`` (rejected at
    submit) | ``"retried"`` (attempt abandoned and resubmitted on
    another fleet replica — never terminal), surfaced in the per-request
    telemetry record. ``priority``/``retries`` carry the SLO tier and
    the fleet resubmission lineage; ``seq`` is the scheduler-local
    arrival index the order policies tie-break on."""
    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    deadline_s: Optional[float] = None
    priority: int = 0
    retries: int = 0
    seq: int = 0
    tokens: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    submit_ts: float = 0.0
    first_token_ts: Optional[float] = None
    finish_ts: Optional[float] = None
    finish_reason: Optional[str] = None
    # ISSUE 12 telemetry: prefix-sharing / COW / speculation / chunked-
    # prefill attribution, copied from the engine's per-slot stats at
    # finish (None for requests that never took a slot)
    prefix_hit_blocks: Optional[int] = None
    blocks_reserved: Optional[int] = None
    cow_forks: Optional[int] = None
    prefill_chunks: Optional[int] = None
    draft_proposed: Optional[int] = None
    draft_accepted: Optional[int] = None

    @property
    def done(self) -> bool:
        return self.finish_ts is not None

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.first_token_ts is None:
            return None
        return (self.first_token_ts - self.submit_ts) * 1e3

    @property
    def tpot_ms(self) -> Optional[float]:
        """Mean inter-token time over tokens after the first; None until
        finished or with a single token."""
        if self.finish_ts is None or len(self.tokens) < 2:
            return None
        return ((self.finish_ts - self.first_token_ts) * 1e3
                / (len(self.tokens) - 1))

    def record(self) -> Dict[str, Any]:
        return {
            "kind": "request", "rid": self.rid,
            "prompt_len": len(self.prompt),
            "new_tokens": len(self.tokens),
            "slot": self.slot,
            "finish_reason": self.finish_reason,
            "deadline_s": self.deadline_s,
            "priority": self.priority,
            "retries": self.retries,
            "ttft_ms": round(self.ttft_ms, 4)
            if self.ttft_ms is not None else None,
            "tpot_ms": round(self.tpot_ms, 4)
            if self.tpot_ms is not None else None,
            # `is not None`, not truthiness: a fake-clock run can finish
            # at ts exactly 0.0 and must still record its wall time
            "wall_ms": round((self.finish_ts - self.submit_ts) * 1e3, 4)
            if self.finish_ts is not None else None,
            "prefix_hit_blocks": self.prefix_hit_blocks,
            "blocks_reserved": self.blocks_reserved,
            "cow_forks": self.cow_forks,
            "prefill_chunks": self.prefill_chunks,
            # raw counts ride along so aggregates can weight by volume
            # (a 2-draft request must not average equally with a
            # 500-draft one)
            "draft_proposed": self.draft_proposed,
            "draft_accepted": self.draft_accepted,
            "draft_accept_rate": round(
                self.draft_accepted / self.draft_proposed, 4)
            if self.draft_proposed else None,
        }


class ContinuousBatchingScheduler:
    """Drives a :class:`~paddle_tpu.serve.engine.DecodeEngine` over a
    request queue.

    ``policy="continuous"`` (default) admits between every tick;
    ``policy="static"`` is the gang baseline — a batch is admitted only
    when EVERY slot is free and runs until all its members finish (the
    differential the bench serving gate measures: on ragged lengths
    continuous wins exactly the idle-lane ticks static burns).

    ``order`` picks the admission policy over the queue (see module
    docstring); ``shed=True`` enables submit-time load shedding, which
    needs a tick-time estimate: pass ``est_tick_s`` as the cold-start
    prior (the scheduler keeps an EMA over observed inter-step clock
    deltas thereafter; with no estimate and no observations, nothing is
    shed — reject-fast needs evidence).
    """

    def __init__(self, engine, telemetry=None, policy: str = "continuous",
                 order: str = "fcfs", shed: bool = False,
                 est_tick_s: Optional[float] = None,
                 clock=time.perf_counter, tracer=None,
                 role: str = "both", metrics=None):
        if policy not in ("continuous", "static"):
            raise ValueError(f"policy must be 'continuous'|'static', "
                             f"got {policy!r}")
        if order not in ORDERS:
            raise ValueError(f"order must be one of {ORDERS}, got {order!r}")
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"role must be 'both'|'prefill'|'decode', "
                             f"got {role!r}")
        self.engine = engine
        self.telemetry = (telemetry if telemetry is not None
                          else engine.telemetry)
        # distributed request tracing (ISSUE 17): a Tracer sharing the
        # scheduler's clock. Spans carry the GLOBAL rid as their flow
        # id, so the fleet merge links a request's queue wait, prefill
        # chunks, and decode ticks across replicas. With None the same
        # spans are live while a jax.profiler session is active (every
        # call site goes through obs.trace.live) and cost that one
        # test otherwise.
        self.tracer = tracer
        # typed metrics registry handle (ISSUE 19): a MetricsHub or a
        # replica-scoped facade. Queue-depth gauges per step, one
        # labeled finished counter per terminal reason (shed included),
        # one eviction counter per deadline sweep hit. Same contract as
        # tracer: None = zero overhead.
        self.metrics = metrics
        self.policy = policy
        self.order = order
        # prefill/decode disaggregation (ISSUE 18): a "prefill"-role
        # scheduler runs admission + prefill only — the moment a
        # request's first token lands it exports the slot's KV pages
        # into `handoffs` (the fleet streams them to a decode replica)
        # instead of decoding. A "decode" scheduler additionally accepts
        # `adopt()`ed sequences. "both" (default) is the colocated
        # baseline, byte-identical to pre-disagg behavior.
        self.role = role
        self.shed = shed
        self.est_tick_s = est_tick_s
        # injectable wall clock: deadlines are tested deterministically
        # with a fake clock; production uses perf_counter
        self._clock = clock
        self.queue: List[Request] = []
        self.running: Dict[int, Request] = {}       # slot -> request
        # chunked prefill in flight: slot -> request (the slot is
        # reserved; one chunk advances per step, between decode ticks)
        self.prefilling: Dict[int, Request] = {}
        self.completed: List[Request] = []
        # the last refusal's structured reason ("blocks"|"width"), for
        # router placement/shedding — None while admission is flowing
        self.last_backpressure: Optional[str] = None
        # finished prefills awaiting transfer: (request, meta, kpages,
        # vpages) tuples the fleet drains via pop_handoffs() each tick
        self.handoffs: List[tuple] = []
        self._rid = itertools.count()
        self._seq = itertools.count()
        self._last_step_ts: Optional[float] = None
        self._was_busy = False

    # -- load model --------------------------------------------------------

    def pending_new_tokens(self) -> int:
        """Decode tokens still owed: remaining budget of every running
        slot plus the full budget of every queued request — the
        scheduler's load number (one token per active slot per tick, so
        this is a tick-denominated backlog)."""
        run = sum(r.max_new_tokens - len(r.tokens)
                  for r in self.running.values())
        return (run + sum(r.max_new_tokens for r in self.queue)
                + sum(r.max_new_tokens for r in self.prefilling.values()))

    def load_report(self) -> Dict[str, Any]:
        """The load payload a replica publishes (heartbeat extras and
        the process-replica tick reply both carry it — ISSUE 13): the
        router balances and the autoscaler senses on exactly this
        evidence, whichever side of a process boundary the scheduler
        lives on."""
        return {"pending_new_tokens": self.pending_new_tokens(),
                "running": len(self.running),
                "queued": len(self.queue),
                "prefilling": len(self.prefilling),
                "prefill_backlog": self.prefill_backlog(),
                "role": self.role}

    def prefill_backlog(self) -> int:
        """Prompt tokens not yet prefilled — the PREFILL-role load
        number (pending_new_tokens is decode-denominated and would
        misplace prefill work onto a replica that never decodes).
        Role-aware routing places prefill on the least of this."""
        return (sum(len(r.prompt) for r in self.queue)
                + sum(len(r.prompt) for r in self.prefilling.values()))

    def predicted_completion_s(self, max_new_tokens: int
                               ) -> Optional[float]:
        """Predicted submit-to-finish seconds for a new request under the
        current backlog, or None without a tick-time estimate. The model
        is deliberately coarse — service rate is ``max_slots`` tokens per
        tick (the full-batch upper bound), so the queue delay is
        ``backlog / max_slots`` ticks and the run time ``max_new`` ticks.
        It is an underestimate under partial occupancy, which biases
        shedding conservative (shed less, queue more)."""
        if self.est_tick_s is None:
            return None
        ticks = (self.pending_new_tokens() / max(1, self.engine.max_slots)
                 + max_new_tokens)
        return ticks * self.est_tick_s

    # -- submission --------------------------------------------------------

    def submit(self, prompt: List[int], max_new_tokens: int,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               priority: int = 0, rid: Optional[int] = None,
               submit_ts: Optional[float] = None,
               retries: int = 0) -> Request:
        """Queue one request. ``rid``/``submit_ts``/``retries`` are for
        the fleet path: a resubmitted request keeps its GLOBAL id and its
        ORIGINAL submit time, so TTFT/wall/deadline are end-to-end truth
        (a user's deadline does not reset because a replica died). When
        ``rid`` is supplied the caller owns id uniqueness."""
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if deadline_s is not None and deadline_s < 0:
            raise ValueError("deadline_s must be >= 0")
        now = self._clock()
        req = Request(rid=next(self._rid) if rid is None else rid,
                      prompt=list(prompt),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      deadline_s=deadline_s, priority=priority,
                      retries=retries, seq=next(self._seq),
                      submit_ts=now if submit_ts is None else submit_ts)
        if len(req.prompt) + max_new_tokens > self.engine.context_width:
            raise ValueError(
                f"prompt {len(req.prompt)} + max_new_tokens "
                f"{max_new_tokens} exceeds slot capacity "
                f"{self.engine.context_width}")
        if self.shed and deadline_s is not None:
            est = self.predicted_completion_s(max_new_tokens)
            waited = max(0.0, now - req.submit_ts)
            if est is not None and waited + est > deadline_s:
                # reject-fast: no slot, no blocks, no staging — overload
                # degrades goodput gracefully instead of collapsing p99
                self._finish(req, "shed")
                return req
        # stage the padded prefill array now — admission-path host prep
        # off the tick loop's critical path (the PR-3 staging move)
        req._staged = self.engine.stage_prompt(req.prompt)
        self.queue.append(req)
        return req

    # -- the tick loop -----------------------------------------------------

    def _finish(self, req: Request, reason: str) -> None:
        """Common completion path: stamp reason + timestamp, copy the
        engine's per-slot sharing/speculation stats into the request,
        free the slot's blocks (when running or mid-prefill), record
        telemetry."""
        req.finish_ts = self._clock()
        req.finish_reason = reason
        slot = req.slot
        if slot is not None and (self.running.get(slot) is req
                                 or self.prefilling.get(slot) is req):
            st = self.engine.slot_stats[slot]
            req.prefix_hit_blocks = st.get("prefix_hit_blocks")
            req.blocks_reserved = st.get("blocks_reserved")
            req.cow_forks = st.get("cow_forks")
            req.prefill_chunks = st.get("prefill_chunks")
            req.draft_proposed = st.get("draft_proposed")
            req.draft_accepted = st.get("draft_accepted")
            self.running.pop(slot, None)
            self.prefilling.pop(slot, None)
            self.engine.evict(slot)            # blocks back to the pool
        self.completed.append(req)
        if self.metrics is not None:
            self.metrics.counter(
                "sched_requests_finished",
                "terminal requests by finish reason",
                reason=reason).inc()
        tr = live(self.tracer)
        if tr is not None:
            tr.complete("finish", tr.at_us(req.finish_ts),
                        flow_step=req.rid, rid=req.rid, reason=reason,
                        new_tokens=len(req.tokens))
        if self.telemetry is not None:
            self.telemetry.emit_event(req.record())

    def _emit_evict(self, req: Request, where: str,
                    blocks_freed: int) -> None:
        """One ``kind="evict"`` record per deadline eviction — BOTH the
        running-slot case and the queued-drop case are visible (ISSUE 11
        satellite: a queued request dying of backpressure starvation must
        show up in telemetry, not just slot evictions)."""
        if self.metrics is not None:
            self.metrics.counter(
                "sched_evictions",
                "deadline evictions by where the request was caught",
                where=where).inc()
        if self.telemetry is not None:
            self.telemetry.emit_event({
                "kind": "evict", "rid": req.rid, "where": where,
                "blocks_freed": blocks_freed,
                "deadline_s": req.deadline_s,
                "queued": len(self.queue), "running": len(self.running),
            })

    def _expire(self) -> None:
        """Deadline sweep, run BETWEEN ticks (the same boundary where
        admissions/evictions already happen — the compiled tick shape
        never changes). A running slot past its deadline is evicted and
        its block reservation freed; a queued request past its deadline
        is dropped before ever taking a slot."""
        now = self._clock()

        def expired(req):
            return (req.deadline_s is not None
                    and now - req.submit_ts > req.deadline_s)

        for slot, req in list(self.running.items()):
            if expired(req):
                self._emit_evict(req, "running",
                                 self.engine.cache.owned_count(slot))
                self._finish(req, "timeout")
        for slot, req in list(self.prefilling.items()):
            if expired(req):
                self._emit_evict(req, "prefilling",
                                 self.engine.cache.owned_count(slot))
                self._finish(req, "timeout")
        for req in [r for r in self.queue if expired(r)]:
            self.queue.remove(req)
            self._emit_evict(req, "queued", 0)
            self._finish(req, "timeout")

    def _admit_order(self) -> List[Request]:
        """The queue in admission order under the active policy. FCFS is
        the queue itself; SJF sorts by decode budget (the dominant cost —
        prefill is one tick regardless of prompt length); priority sorts
        by descending tier. Arrival breaks every tie, so equal-key
        requests never starve each other."""
        if self.order == "sjf":
            return sorted(self.queue, key=lambda r: (r.max_new_tokens,
                                                     r.seq))
        if self.order == "priority":
            return sorted(self.queue, key=lambda r: (-r.priority, r.seq))
        return list(self.queue)

    def _admit(self) -> int:
        """Admit from the queue in policy order while slots and blocks
        last; returns how many requests were admitted."""
        self.last_backpressure = None    # cleared even on the gang wait
        if self.policy == "static" and (self.running or self.prefilling):
            return 0                     # gang: wait for the whole batch
        free = self.engine.free_slots()
        tr = live(self.tracer)
        admitted = 0
        for req in self._admit_order():
            if not free:
                break
            # a decode tick appends the pending token BEFORE sampling, so
            # the cache must hold prompt + all generated tokens except
            # the last sampled one: reserve prompt + max_new - 1
            target = max(len(req.prompt) + req.max_new_tokens - 1,
                         len(req.prompt))
            probe = self.engine.admit_probe(target, include_slots=False)
            if not probe.ok:
                # pool backpressure: stop in strict policy order (no
                # smaller-request bypass — bypass would starve the head)
                self.last_backpressure = probe.reason
                if tr is not None:
                    tr.instant("backpressure", rid=req.rid,
                               reason=probe.reason, queued=len(self.queue))
                break
            self.queue.remove(req)
            if tr is not None:
                # retroactive queue-wait span: submit_ts -> now, in the
                # shared clock's time base
                tr.complete("queue_wait", tr.at_us(req.submit_ts),
                            tr.now_us(), flow_step=req.rid, rid=req.rid)
            slot = free.pop(0)
            self.engine.begin_prefill(slot, req.prompt,
                                      reserve_len=target,
                                      staged=getattr(req, "_staged",
                                                     None))
            req.slot = slot
            self.prefilling[slot] = req
            # one prefill call now: the whole prompt on a legacy
            # engine (admission behavior unchanged), the first chunk
            # on a chunked one — the rest interleave with decode ticks
            self._advance_prefill(slot)
            admitted += 1
        return admitted

    def _advance_prefill(self, slot: int) -> None:
        """One compiled prefill call for a reserved slot; promotes the
        request to running when its first token lands."""
        req = self.prefilling[slot]
        with tspan(self.tracer, "prefill_chunk", rid=req.rid, slot=slot):
            tok = self.engine.prefill_step(slot)
        if tok is None:
            return
        del self.prefilling[slot]
        req.tokens.append(tok)
        req.first_token_ts = self._clock()
        self.running[slot] = req
        self._maybe_finish(slot, tok)
        if self.role == "prefill" and not req.done:
            # disaggregation: the prompt's KV and the pending first
            # token leave for a decode replica. A request TERMINAL at
            # its first token (eos, max_new=1) finished above on this
            # replica — shipping zero decode work would be pure wire
            # cost.
            self._hand_off(slot, req)

    def _hand_off(self, slot: int, req: Request) -> None:
        """Export a just-prefilled slot for transfer and release it
        locally. The request leaves this scheduler WITHOUT a completed
        record — the adopting decode replica authors the terminal
        record, carrying the ORIGINAL submit/first-token stamps so
        TTFT/wall stay end-to-end truth. Prefill-side attribution
        (prefix hits, chunks) rides the meta and is merged into the
        decode slot's stats."""
        meta, kpages, vpages = self.engine.export_slot(slot)
        st = self.engine.slot_stats[slot]
        meta.update({
            "rid": req.rid, "prompt": list(req.prompt),
            "max_new_tokens": req.max_new_tokens,
            "eos_id": req.eos_id, "deadline_s": req.deadline_s,
            "priority": req.priority, "retries": req.retries,
            "submit_ts": req.submit_ts,
            "first_token": req.tokens[0],
            "first_token_ts": req.first_token_ts,
            "prefill_stats": {
                "prefix_hit_blocks": st.get("prefix_hit_blocks", 0),
                "shared_len": st.get("shared_len", 0),
                "cow_forks": st.get("cow_forks", 0),
                "prefill_chunks": st.get("prefill_chunks", 0)}})
        del self.running[slot]
        self.engine.evict(slot)
        req.slot = None
        self.handoffs.append((req, meta, kpages, vpages))
        tr = live(self.tracer)
        if tr is not None:
            tr.complete("handoff_out", tr.at_us(self._clock()),
                        flow_step=req.rid, rid=req.rid,
                        blocks=meta["blocks"])

    def pop_handoffs(self) -> List[tuple]:
        """Drain finished prefills awaiting transfer (fleet-facing)."""
        out, self.handoffs = self.handoffs, []
        return out

    def adopt(self, meta: Dict[str, Any], kpages,
              vpages) -> Optional[Request]:
        """Decode-side admission of a streamed prefill: capacity-check,
        import the pages into a free slot, and enter the request
        directly in ``running`` with its first token already generated.
        Returns None (nothing changed) when this replica can't take it
        yet — no free slot or pool backpressure; the fleet retries or
        re-routes."""
        free = self.engine.free_slots()
        if not free:
            self.last_backpressure = "slots"
            return None
        prompt = [int(t) for t in meta["prompt"]]
        max_new = int(meta["max_new_tokens"])
        target = max(len(prompt) + max_new - 1, len(prompt))
        probe = self.engine.admit_probe(target, include_slots=False)
        if not probe.ok:
            self.last_backpressure = probe.reason
            return None
        req = Request(
            rid=int(meta["rid"]), prompt=prompt,
            max_new_tokens=max_new, eos_id=meta.get("eos_id"),
            deadline_s=meta.get("deadline_s"),
            priority=int(meta.get("priority") or 0),
            retries=int(meta.get("retries") or 0),
            seq=next(self._seq), submit_ts=meta["submit_ts"])
        slot = free[0]
        if not self.engine.adopt_slot(slot, prompt,
                                      int(meta["first_token"]),
                                      kpages, vpages,
                                      reserve_len=target):
            self.last_backpressure = "blocks"
            return None
        req.slot = slot
        req.tokens = [int(meta["first_token"])]
        req.first_token_ts = meta.get("first_token_ts")
        self.running[slot] = req
        # end-to-end attribution: the decode slot's stats START from
        # the prefill side's (prefix hits happened over there); _finish
        # copies them into the terminal record as usual
        self.engine.slot_stats[slot].update(
            meta.get("prefill_stats") or {})
        self.last_backpressure = None
        tr = live(self.tracer)
        if tr is not None:
            tr.complete("adopt", tr.at_us(self._clock()),
                        flow_step=req.rid, rid=req.rid, slot=slot,
                        blocks=meta.get("blocks"))
        return req

    def _maybe_finish(self, slot: int, tok: int) -> None:
        req = self.running[slot]
        if req.eos_id is not None and tok == req.eos_id:
            self._finish(req, "eos")
        elif len(req.tokens) >= req.max_new_tokens:
            self._finish(req, "length")

    def step(self) -> bool:
        """Expire deadlines, admit, run one decode tick, collect
        finished requests. Returns True while work remains."""
        tr = live(self.tracer)
        with (NULL_SPAN if tr is None else tr.span(
                "sched_step", queued=len(self.queue),
                running=len(self.running),
                prefilling=len(self.prefilling))):
            now = self._clock()
            if self._last_step_ts is not None and self._was_busy:
                # EMA over inter-step deltas: the shed predictor's
                # tick-time evidence (deterministic under a fake clock —
                # the injected advances ARE the observations). Only
                # deltas between consecutive BUSY steps count: after an
                # idle lull the gap is think time, not tick time, and
                # folding it in would make the predictor shed against an
                # empty engine.
                dt = now - self._last_step_ts
                if dt > 0:
                    self.est_tick_s = (
                        dt if self.est_tick_s is None
                        else 0.7 * self.est_tick_s + 0.3 * dt)
            self._last_step_ts = now
            with tspan(tr, "expire"):
                self._expire()
            # chunked prefill: ONE chunk per already-prefilling slot per
            # step, BETWEEN decode ticks — a 4k-token admit becomes many
            # cheap calls instead of one monolithic stall of every running
            # slot (fresh admissions below run their first chunk inside
            # _admit)
            for slot in list(self.prefilling):
                self._advance_prefill(slot)
            with tspan(tr, "admit") as sp:
                admitted = self._admit()
                if sp is not None:
                    sp.set(admitted=admitted,
                           backpressure=self.last_backpressure)
            if self.running:
                with tspan(tr, "decode_tick",
                           active=len(self.running)) as sp:
                    self.engine.decode_tick()
                    # the tick may retire several tokens per slot
                    # (speculative accepts); feed them through the same
                    # finish rules one at a time so eos/length semantics
                    # match the sequential engine exactly
                    accepted = self.engine.last_accepted
                    n_tok = 0
                    for slot, req in list(self.running.items()):
                        for tok in accepted.get(slot, ()):
                            req.tokens.append(tok)
                            n_tok += 1
                            self._maybe_finish(slot, tok)
                            if req.done:
                                break
                    if sp is not None:
                        sp.set(tokens=n_tok)
            if self.metrics is not None:
                self.metrics.gauge("sched_queue_depth",
                                   "requests queued for admission").set(
                    len(self.queue))
                self.metrics.gauge("sched_running",
                                   "requests holding a decode slot").set(
                    len(self.running))
                self.metrics.gauge("sched_prefilling",
                                   "slots mid chunked prefill").set(
                    len(self.prefilling))
            self._was_busy = bool(self.queue or self.running
                                  or self.prefilling)
            return self._was_busy

    def run(self, max_ticks: int = 100000) -> List[Request]:
        """Drive ticks until the queue drains; returns completed
        requests in completion order."""
        for _ in range(max_ticks):
            if not self.step():
                break
        else:
            raise RuntimeError(f"scheduler did not drain in "
                               f"{max_ticks} ticks")
        return self.completed
