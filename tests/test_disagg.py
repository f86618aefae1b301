"""Prefill/decode disaggregation tests (ISSUE 18): role-split fleets
must be a pure PLACEMENT change — prefill on replica A + decode on
replica B produces bit-identical greedy tokens to colocated serving,
across ragged lengths, int8-quantized KV, and CoW-shared session
prefixes; a prefill replica killed mid-stream degrades to the ordinary
dead-replica resubmit (exactly one terminal record per rid); the wire
cost of every handoff is accounted to the byte; and the role-aware
router, the hostile-scale loadgen, the router_ms host-cost meter and
the M/M/c Erlang-C term each hold their contracts.

Everything in-process on a :class:`SimClock` except where noted — the
socket path is exercised end-to-end by tests/test_transport.py and
``tests/test_drills.py::test_drill_leg[fleet-disagg]``."""

import collections
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models import TransformerLM
from paddle_tpu.obs import (InMemorySink, Telemetry, flow_connected,
                            flow_summary, summarize_handoffs)
from paddle_tpu.serve import ServingFleet, SimClock, erlang_c_wait
from paddle_tpu.serve.loadgen import hostile_workload, workload_stats
from paddle_tpu.train import FaultSchedule

V, W, DIM, LAYERS, HEADS, FFN = 64, 24, 32, 2, 4, 64
BS = 4
HD = DIM // HEADS                         # head_dim = 8
DT, HB = 0.1, 0.25

# exact per-block wire bytes for this geometry: K and V pages, each
# [layers, heads, BS, head_dim] per block
F32_BLOCK = 2 * LAYERS * HEADS * BS * HD * 4
INT8_BLOCK = 2 * LAYERS * HEADS * BS * (HD * 1 + 4)   # values + f32 scales


@pytest.fixture(scope="module")
def model_and_vars():
    model = TransformerLM(vocab=V, dim=DIM, num_layers=LAYERS,
                          num_heads=HEADS, ffn_hidden=FFN, max_len=W)
    vs = model.init(jax.random.PRNGKey(0), jnp.zeros((1, W), jnp.int32))
    return model, vs


def _greedy_oracle(model, vs, prompt, n_new):
    fwd = jax.jit(lambda v, i: model.apply(v, i))
    seq, out = list(prompt), []
    for _ in range(n_new):
        pad = np.zeros((1, W), np.int32)
        pad[0, :len(seq)] = seq
        logits = fwd(vs, jnp.asarray(pad))
        tok = int(np.argmax(np.asarray(logits[0, len(seq) - 1])))
        out.append(tok)
        seq.append(tok)
    return out


def _fleet(model, vs, n, *, roles=None, telemetry=None, faults=None,
           engine_kwargs=None, **kw):
    ek = dict(max_slots=2, block_size=BS, num_blocks=24)
    ek.update(engine_kwargs or {})
    return ServingFleet.from_model(
        model, vs, n, engine_kwargs=ek, roles=roles,
        telemetry=telemetry, faults=faults, clock=SimClock(),
        heartbeat_timeout_s=HB, est_tick_s=DT,
        root=tempfile.mkdtemp(prefix="paddle_tpu_disagg_test_"), **kw)


def _run(fleet, jobs, max_ticks=400):
    """Submit (prompt, n_new[, session]) jobs, tick to completion."""
    frs = []
    for job in jobs:
        sid = job[2] if len(job) > 2 else None
        frs.append(fleet.submit(list(job[0]), job[1], session_id=sid))
    for _ in range(max_ticks):
        if not fleet.outstanding():
            break
        fleet.tick()
        fleet.clock.advance(DT)
    assert not fleet.outstanding(), "fleet did not converge"
    return frs


def _ragged_jobs(nprng, n=8, sessions=False):
    jobs = []
    for i in range(n):
        plen = int(nprng.randint(1, 9))           # ragged 1..8
        n_new = int(nprng.randint(2, 7))
        prompt = list(nprng.randint(1, V, plen))
        if sessions and i % 2 == 1:
            # share the previous job's prompt as a prefix (CoW path)
            prev = jobs[-1][0]
            prompt = list(prev) + prompt[: max(1, 8 - len(prev))]
            jobs.append((prompt, n_new, jobs[-1][2]))
        else:
            jobs.append((prompt, n_new, i))
    return jobs


# ---------------------------------------------------------------------------
# token identity: disaggregation is a placement change, not a math change
# ---------------------------------------------------------------------------

def test_disagg_token_identity_vs_colocated_ragged(model_and_vars,
                                                   nprng):
    model, vs = model_and_vars
    jobs = _ragged_jobs(nprng, n=8, sessions=True)
    colo = _run(_fleet(model, vs, 3), jobs)
    dis_fleet = _fleet(model, vs, 3, roles=["prefill", "decode",
                                            "decode"])
    dis = _run(dis_fleet, jobs)
    assert all(fr.finish_reason == "length" for fr in colo + dis)
    for a, b in zip(colo, dis):
        assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
        assert b.tokens == _greedy_oracle(model, vs, b.prompt,
                                          b.max_new_tokens)
    # every request actually crossed the prefill -> decode boundary
    assert dis_fleet.handoff_count == len(jobs)
    # wire accounting is exact: bytes == blocks x per-block f32 bytes
    assert dis_fleet.handoff_wire_bytes == \
        dis_fleet.handoff_blocks * F32_BLOCK
    assert dis_fleet.stale_handoffs == 0
    # no replica leaked KV blocks through the export/adopt cycle
    for w in dis_fleet.workers:
        cache = w.engine.cache
        assert cache.free_blocks == cache.num_blocks - 1, w.replica_id


def test_disagg_int8_identity_and_wire_ratio(model_and_vars, nprng):
    """Quantized KV crosses the wire quantized: int8 disagg matches
    int8 colocated token-for-token, and the measured bytes-per-block
    ratio vs f32 is the analytic (hd*4)/(hd+4) ~ 2.7x (ISSUE 18)."""
    model, vs = model_and_vars
    ek = dict(kv_dtype="int8")
    jobs = _ragged_jobs(nprng, n=6)
    colo = _run(_fleet(model, vs, 2, engine_kwargs=ek), jobs)
    q = _fleet(model, vs, 3, roles=["prefill", "decode", "decode"],
               engine_kwargs=ek)
    dis = _run(q, jobs)
    for a, b in zip(colo, dis):
        assert a.finish_reason == b.finish_reason == "length"
        assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
    assert q.handoff_count == len(jobs)
    assert q.handoff_wire_bytes == q.handoff_blocks * INT8_BLOCK
    ratio = F32_BLOCK / (q.handoff_wire_bytes / q.handoff_blocks)
    assert ratio == pytest.approx((HD * 4) / (HD + 4))   # 2.67x for hd=8
    assert ratio > 2.5


# ---------------------------------------------------------------------------
# role-aware routing + handoff telemetry
# ---------------------------------------------------------------------------

def test_disagg_routing_telemetry_and_connected_flow(model_and_vars,
                                                     nprng):
    model, vs = model_and_vars
    mem = InMemorySink()
    fleet = _fleet(model, vs, 3, roles=["prefill", "decode", "decode"],
                   telemetry=Telemetry(sinks=[mem]), trace=True)
    jobs = _ragged_jobs(nprng, n=6)
    frs = _run(fleet, jobs)
    assert all(fr.finish_reason == "length" for fr in frs)
    # role-aware placement: every request prefills on the prefill
    # replica and terminates on a decode replica
    for fr in frs:
        assert fr.attempts[0] == 0, fr.attempts
        assert fr.attempts[-1] in (1, 2), fr.attempts
        assert fr.replica in (1, 2)
    # per-handoff telemetry: one kv_handoff record per request with the
    # full schema, aggregable by obs.summarize_handoffs
    hos = mem.by_kind("kv_handoff")
    assert len(hos) == len(jobs)
    for h in hos:
        assert h["src_replica"] == 0 and h["dst_replica"] in (1, 2)
        assert h["blocks"] >= 1 and h["wire_bytes"] > 0
        assert h["quant"] == "float32" and h["transfer_ms"] >= 0.0
    agg = summarize_handoffs(mem.records)
    assert agg["handoffs"] == len(jobs)
    assert agg["wire_bytes"] == fleet.handoff_wire_bytes
    assert agg["mean_blocks"] == pytest.approx(
        fleet.handoff_blocks / len(jobs), abs=0.01)
    assert agg["by_quant"] == {"float32": len(jobs)}
    # the run report carries the block
    from paddle_tpu.obs.report import format_summary, summarize
    summ = summarize(mem.records)
    assert summ["serving"]["handoffs"]["handoffs"] == len(jobs)
    assert "kv handoffs" in format_summary(summ)
    # the merged trace: each rid's flow is connected THROUGH the
    # kv_handoff span — prefill lane -> router handoff -> decode lane
    tr = fleet.fleet_trace()
    names = {e["name"] for e in tr["traceEvents"] if e.get("ph") == "X"}
    assert "kv_handoff" in names, names
    for fr in frs:
        assert flow_connected(tr, fr.rid), flow_summary(tr).get(fr.rid)
        pids = {pid for _, pid in flow_summary(tr)[fr.rid]}
        assert len(pids) >= 2, (fr.rid, pids)    # crossed lanes


# ---------------------------------------------------------------------------
# the death drill: prefill dies mid-stream
# ---------------------------------------------------------------------------

def test_disagg_prefill_death_rehomes_with_one_terminal(model_and_vars,
                                                        nprng):
    """Kill a prefill replica while its requests are in flight: the
    in-progress work re-homes to the surviving prefill replica, every
    request still reaches exactly one terminal record with oracle
    tokens, and any handoff caught mid-transfer is accounted (stale or
    re-driven), never double-decoded."""
    model, vs = model_and_vars
    mem = InMemorySink()
    faults = FaultSchedule(kill_replica_at_tick=(1, 0))
    fleet = _fleet(model, vs, 3,
                   roles=["prefill", "prefill", "decode"],
                   telemetry=Telemetry(sinks=[mem]), faults=faults)
    jobs = [(list(nprng.randint(1, V, 4)), 6, None) for _ in range(6)]
    frs = _run(fleet, jobs)
    assert all(fr.finish_reason == "length" for fr in frs)
    assert any(fr.retries > 0 and 0 in fr.attempts for fr in frs), \
        "the kill must catch at least one request on replica 0"
    for fr in frs:
        assert fr.tokens == _greedy_oracle(model, vs, fr.prompt,
                                           fr.max_new_tokens)
        assert fr.replica == 2                   # decoded on the decoder
    # exactly one terminal record per rid (retried lineage intact)
    by_rid = collections.defaultdict(list)
    for r in mem.by_kind("request"):
        by_rid[r["rid"]].append(r)
    for fr in frs:
        terminal = [r for r in by_rid[fr.rid]
                    if r["finish_reason"] != "retried"]
        assert len(terminal) == 1, (fr.rid, by_rid[fr.rid])
        assert terminal[0]["finish_reason"] == "length"
    assert fleet.handoff_count >= len(jobs)      # re-homed ones re-ship
    assert not fleet._pending_handoffs
    for w in fleet.workers:
        if w.replica_id == 0:
            continue
        cache = w.engine.cache
        assert cache.free_blocks == cache.num_blocks - 1, w.replica_id


# ---------------------------------------------------------------------------
# hostile-scale loadgen + the router_ms host-cost meter
# ---------------------------------------------------------------------------

def test_hostile_workload_rate_and_router_cost_meter(model_and_vars):
    model, vs = model_and_vars
    wl = hostile_workload(400, V, max_total=W)
    stats = workload_stats(wl)
    # the hostile preset is genuinely hostile: >= 10k requests/sec of
    # sim-time arrivals, bursty
    span = wl[-1].at_s - wl[0].at_s
    assert span > 0 and len(wl) / span >= 10_000.0, len(wl) / span
    assert stats["n"] == 400
    same = hostile_workload(400, V, max_total=W)
    assert [(g.at_s, g.prompt) for g in wl] == \
        [(g.at_s, g.prompt) for g in same]       # seeded
    # drive a small slice through a disagg fleet and read the meter:
    # router_ms is HOST wall time (perf_counter), present and sane even
    # though the fleet runs on a SimClock
    fleet = _fleet(model, vs, 3, roles=["prefill", "decode", "decode"])
    frs = _run(fleet, [(g.prompt, min(g.max_new_tokens, 4), g.session_id)
                       for g in wl[:40]])
    assert all(fr.finish_reason in ("length", "eos") for fr in frs)
    rm = fleet.stats()["router_ms"]
    assert set(rm) == {"total", "per_tick_mean", "per_tick_max", "ticks"}
    assert rm["ticks"] == fleet.ticks > 0
    assert rm["total"] > 0.0
    assert rm["per_tick_max"] >= rm["per_tick_mean"] > 0.0
    assert rm["total"] == pytest.approx(
        rm["per_tick_mean"] * rm["ticks"], rel=1e-6)


# ---------------------------------------------------------------------------
# the M/M/c term
# ---------------------------------------------------------------------------

def test_erlang_c_wait_units_and_limits():
    # empty / degenerate systems wait zero
    assert erlang_c_wait(0.0, 10.0, 4) == 0.0
    assert erlang_c_wait(5.0, 0.0, 4) == 0.0
    assert erlang_c_wait(5.0, 10.0, 0) == 0.0
    # at or past saturation the wait is unbounded
    assert erlang_c_wait(10.0, 10.0, 1) == float("inf")
    assert erlang_c_wait(45.0, 10.0, 4) == float("inf")
    # M/M/1 closed form: Wq = rho / (mu - lam)
    lam, mu = 6.0, 10.0
    assert erlang_c_wait(lam, mu, 1) == pytest.approx(
        (lam / mu) / (mu - lam))
    # monotone in offered load, relieved by capacity
    w2 = erlang_c_wait(8.0, 10.0, 2)
    assert 0.0 < erlang_c_wait(4.0, 10.0, 2) < w2
    assert erlang_c_wait(8.0, 10.0, 4) < w2
