"""Driver of a serving cell: ``ContinuousBatchingScheduler`` over a
``DecodeEngine`` built with its documented defaults, driven from this
process by ``submit`` and ``step()``. Closed loop (``clients`` callers that
each wait for their answer and then ask again) or open loop (requests due
at the mix's arrival times, timed from when they were due).

The engine returns tokens, never logits, so the benchmark stamps every
token itself when ``step()`` returns: that is when a caller of this loop
can see it. Every size comes from the cell's files, and whatever depends
on the architecture (the model, its weights, the reference, the pool's
facts) from the modules the configuration names (``ctx.cell``).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmarks import harness, traffic


class Client:
    """One request as the benchmark sees it."""

    def __init__(self, req, due: float, carried: bool):
        self.req = req
        self.due = due                  # when it was (to be) submitted
        self.carried = carried          # admitted in set-up
        self.stamps: List[float] = []   # when each token became visible


def build(ctx):
    """The engine and its scheduler. The cell file's ``serve`` block gives
    the slots and the pool; its optional ``engine`` object goes to
    ``DecodeEngine`` as further keyword arguments."""
    from paddle_tpu.serve import ContinuousBatchingScheduler, DecodeEngine
    layout, spec = ctx.cell.layout, ctx.cell.file["serve"]
    variables = {"params": layout.program_params(ctx.dims, ctx.seed),
                 "state": {}}
    engine = DecodeEngine(layout.build_model(ctx.dims), variables,
                          max_slots=int(spec["max_slots"]),
                          block_size=int(spec["block_size"]),
                          num_blocks=spec.get("num_blocks"),
                          **spec.get("engine", {}))
    del variables
    return engine, ContinuousBatchingScheduler(engine)


def instrument(engine, rec) -> None:
    """Spans around the engine calls the scheduler makes, from outside."""
    def wrap(name, span, facts=None):
        fn = getattr(engine, name)

        def wrapped(*a, **k):
            with rec.span(span, **(facts() if facts else {})):
                return fn(*a, **k)
        setattr(engine, name, wrapped)

    def tick_facts():
        act = engine.active
        return {"live_tokens": int(engine.cache.lengths[act].sum()
                                   + act.sum()),
                "active": int(act.sum())}

    wrap("decode_tick", "tick", tick_facts)
    wrap("begin_prefill", "begin_prefill")
    wrap("prefill_step", "prefill_step")


def serve_checks(sample, ref_logits, limit: float):
    """The widest gap by which a served token's logit lies below the
    reference's best at its position, over every sampled token."""
    gaps = []
    for (_, tokens), logits in zip(sample, ref_logits):
        best = logits.max(axis=-1)
        served = logits[np.arange(len(tokens)), np.asarray(tokens)]
        gaps.append(float((best - served).max()))
    return [harness.check("served_logit_gap", max(gaps), limit)]


def control_gap(ref_logits, control_logits) -> float:
    """The control's reading: at each position of the same prompts and
    tokens, the gap in the reference of the token that the lower
    precision puts first."""
    return max(float((ref.max(axis=-1) - ref[np.arange(len(ref)),
                                             ctl.argmax(axis=-1)]).max())
               for ref, ctl in zip(ref_logits, control_logits))


def pick_sample(finished: List[Client], n: int, seed: int):
    """``n`` finished requests drawn from the seed, the longest in it."""
    if not finished:
        return []
    longest = max(finished, key=lambda c: len(c.req.prompt)
                  + len(c.req.tokens))
    rest = [c for c in finished if c is not longest]
    order = np.random.RandomState(seed % 2 ** 32).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:max(0, n - 1)]]


def run(ctx) -> Dict[str, Any]:
    from paddle_tpu.core.dtypes import bfloat16_compute, use_policy
    z, rec, mix = ctx.dims, ctx.rec, ctx.cell.traffic
    spec = ctx.cell.file["serve"]
    pool = traffic.serve_requests(mix, z.V, ctx.seed)
    closed = mix["loop"] == "closed"
    n_clients = int(mix["clients"]) if closed else 0
    clients: List[Client] = []
    cursor = 0

    with use_policy(bfloat16_compute):
        with rec.span("engine_build"):
            engine, sched = build(ctx)
        with rec.span("engine_warmup"):
            engine.warmup()
        instrument(engine, rec)

        def submit(due: float, carried: bool = False,
                   share: float = 1.0) -> Client:
            nonlocal cursor
            s = pool[cursor % len(pool)]
            cursor += 1
            budget = max(1, int(round(s["max_new"] * share)))
            c = Client(sched.submit(s["prompt"], budget), due, carried)
            clients.append(c)
            return c

        def stamp(now: float) -> List[Client]:
            """Stamp every token that became visible in the last step;
            return the requests that just finished."""
            done = []
            for c in clients:
                if len(c.stamps) < len(c.req.tokens):
                    c.stamps += [now] * (len(c.req.tokens) - len(c.stamps))
                    if c.req.done:
                        done.append(c)
            return done

        def step() -> List[Client]:
            with rec.span("sched_step"):
                sched.step()
            now = time.perf_counter()
            with rec.span("collect"):
                done = stamp(now)
                if closed and not state["closing"]:
                    for _ in done:
                        submit(now)
            return done

        state = {"closing": False}
        # set-up: each client's first request, its budget cut to a seeded
        # share of itself, admitted before the window opens
        if closed:
            shares = traffic.first_budget_shares(n_clients, ctx.seed)
            now = time.perf_counter()
            for k in range(n_clients):
                submit(now, carried=True, share=shares[k])
            while any(not c.stamps for c in clients):
                step()
        # a traced run takes its trace FIRST, then opens the window
        ctx.profile.start()
        while ctx.trace_on and not ctx.profile.done:
            step()
            ctx.profile.tick()
        t0 = time.perf_counter()
        arrivals = [] if closed else [
            (t0 + p["at_s"], i) for i, p in enumerate(pool)]
        while True:
            now = time.perf_counter()
            if now - t0 >= ctx.seconds:
                break
            while arrivals and arrivals[0][0] <= now:
                submit(arrivals.pop(0)[0])
            if not (sched.queue or sched.running or sched.prefilling):
                time.sleep(min(0.001, max(0.0, arrivals[0][0] - now))
                           if arrivals else 0.001)
                continue
            step()
        t1 = time.perf_counter()
        ctx.window = (t0, t1)
        # after the close: an answer that comes late is late, not wrong.
        # Wait for the first token of everything submitted in the window.
        state["closing"] = True
        while any(not c.stamps for c in clients):
            if time.perf_counter() - t1 > 60.0:
                break
            step()
        counts = engine.compile_counts()
        attention = engine.attention

    in_win = lambda t: t0 <= t <= t1
    tokens = sum(in_win(t) for c in clients for t in c.stamps)
    new = [c for c in clients if not c.carried and in_win(c.due)]
    ttft = [(c.stamps[0] - c.due) * 1e3 for c in new if c.stamps]
    gaps = [(b - a) * 1e3 for c in clients
            for a, b in zip(c.stamps, c.stamps[1:]) if in_win(b)]
    finished = [c for c in clients if c.req.done and in_win(c.stamps[-1])]
    failed = [c for c in clients if (c.req.done and (
        c.req.finish_reason != "length"
        or len(c.req.tokens) != c.req.max_new_tokens
        or not all(0 <= t < z.V for t in c.req.tokens)))
        or not c.stamps]
    prompts = [len(c.req.prompt) for c in clients
               if c.stamps and in_win(c.stamps[0])]
    contexts = [len(c.req.prompt) + i for c in clients
                for i, t in enumerate(c.stamps) if i > 0 and in_win(t)]
    ctx.facts.update(
        tokens=tokens, requests_finished=len(finished),
        requests_submitted=len(new), ttft_samples=len(ttft),
        gap_samples=len(gaps), prompts=prompts, contexts=contexts,
        slots=int(spec["max_slots"]), num_blocks=engine.cache.num_blocks,
        compile_counts=counts, attention=attention,
        **ctx.cell.layout.engine_facts(engine))
    harness.log(
        f"serve: {len(finished)} requests finished, {len(new)} submitted, "
        f"{tokens} tokens, {len(gaps)} gaps in {t1 - t0:.3f}s; "
        f"{engine.ticks} ticks, {engine.prefill_chunks} prefills; pool "
        f"{engine.cache.num_blocks} blocks of {ctx.facts['pool_dtype']}; "
        f"attention {attention}; compiles {counts}")
    peak = harness.memory_peak_bytes(ctx.devices)
    sample = [(list(c.req.prompt), list(c.req.tokens)) for c in pick_sample(
        finished, int(spec["sample_requests"]), ctx.seed)]
    structure_ok = (counts == {"prefill": 1, "tick": 1}
                    and attention == spec.get("attention", attention)
                    and not failed and bool(sample) and bool(ttft))
    del engine, sched
    for c in clients:
        c.req = None
    harness.free_device_memory()
    t_ref = time.perf_counter()
    checks = []
    if sample:
        ref_logits = ctx.cell.reference.serve_reference(
            ctx.cell.config, ctx.seed, sample)
        checks = serve_checks(sample, ref_logits,
                              ctx.cell.file["limits"]["served_logit_gap"])
        ctx.facts.update(sample=sample, reference=ref_logits)
        harness.log(f"serve: reference over {len(sample)} requests, "
                    f"{sum(len(t) for _, t in sample)} served tokens, in "
                    f"{time.perf_counter() - t_ref:.1f}s")
    metrics = {"serve_tokens_per_s": tokens / (t1 - t0)}
    if ttft:
        metrics["ttft_p95_ms"] = harness.percentile(ttft, 95)
    if gaps:
        metrics["itl_p95_ms"] = harness.percentile(gaps, 95)
    return {"metrics": metrics, "attempted": len(clients),
            "failed": len(failed), "checks": checks,
            "memory_peak_bytes": peak,
            "correct": structure_ok and all(c["ok"] for c in checks)}
