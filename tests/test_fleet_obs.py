"""Fleet observability tests (ISSUE 17): distributed request tracing
merged across replica lanes, the streaming SLO monitor (P² percentiles,
error-budget burn rate), serving anomaly forensics, and the satellites
— child JSONL telemetry sinks, proc-spec schema stability, the report's
serving transport/SLO blocks, and the ``obs.top`` dashboard.

All fleet drills here are in-process on a :class:`SimClock` (the
process-mode twin is ``tests/test_drills.py::test_drill_leg[fleet-tracing]``), so the
determinism assertions are exact: the same drill must produce the same
merged trace, byte for byte."""

import collections
import json
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models import TransformerLM
from paddle_tpu.obs import (InMemorySink, P2Quantile, SLOMonitor,
                            SLOTargets, ServingAnomalyDetector,
                            Telemetry, flow_connected, flow_summary,
                            lane_monotonic, merge_fleet_trace)
from paddle_tpu.obs import report as report_lib
from paddle_tpu.obs import top as top_lib
from paddle_tpu.parallel import multihost
from paddle_tpu.serve import ServingFleet, SimClock
from paddle_tpu.serve.fleet import build_proc_spec
from paddle_tpu.serve.loadgen import make_workload
from paddle_tpu.serve.replica_proc import EventBuffer
from paddle_tpu.train import FaultSchedule

V, W = 64, 24
DT, HB = 0.1, 0.25


@pytest.fixture(scope="module")
def model_and_vars():
    model = TransformerLM(vocab=V, dim=32, num_layers=2, num_heads=4,
                          ffn_hidden=64, max_len=W)
    vs = model.init(jax.random.PRNGKey(0), jnp.zeros((1, W), jnp.int32))
    return model, vs


def _fleet(model, vs, n, *, telemetry=None, faults=None, clock=None,
           heartbeat_timeout_s=HB, **kw):
    return ServingFleet.from_model(
        model, vs, n, engine_kwargs=dict(max_slots=2, block_size=4),
        telemetry=telemetry, faults=faults,
        clock=clock if clock is not None else SimClock(),
        heartbeat_timeout_s=heartbeat_timeout_s, est_tick_s=DT,
        root=tempfile.mkdtemp(prefix="paddle_tpu_fleet_obs_"), **kw)


def _workload(n=6, seed=7):
    return make_workload(n, V, seed=seed, rate_rps=30.0,
                         prompt_len=(2, 6), max_new=(3, 8), max_total=W)


# ---------------------------------------------------------------------------
# P² streaming quantiles
# ---------------------------------------------------------------------------

def test_p2_quantile_tracks_numpy():
    rng = np.random.RandomState(0)
    xs = rng.lognormal(mean=3.0, sigma=0.7, size=5000)
    for p in (50, 95, 99):
        est = P2Quantile(p)
        for x in xs:
            est.observe(x)
        exact = float(np.percentile(xs, p))
        assert est.value() == pytest.approx(exact, rel=0.05), (p, exact)


def test_p2_quantile_exact_below_five_samples():
    est = P2Quantile(50)
    assert est.value() is None
    for x in (3.0, 1.0, 2.0):
        est.observe(x)
    assert est.value() == 2.0                  # nearest-rank, not a model


# ---------------------------------------------------------------------------
# streaming SLO monitor
# ---------------------------------------------------------------------------

def _rec(reason="length", wall=100.0, ttft=10.0, tokens=4, **kw):
    return {"kind": "request", "finish_reason": reason, "wall_ms": wall,
            "ttft_ms": ttft, "tpot_ms": 5.0, "new_tokens": tokens,
            "deadline_s": kw.pop("deadline_s", None), **kw}


def test_slo_burn_rate_is_windowed_bad_over_budget():
    mon = SLOMonitor(targets=SLOTargets(goodput_pct=90.0), window=10)
    for _ in range(5):
        mon.observe(_rec())
    for _ in range(5):
        mon.observe(_rec(reason="timeout"))
    # 50% bad in-window / 10% budget = 5x burn
    assert mon.burn_rate() == pytest.approx(5.0)
    rep = mon.report()
    assert rep["burn_rate"] == pytest.approx(5.0)
    assert rep["goodput_pct"] == pytest.approx(50.0)
    assert rep["window_goodput_pct"] == pytest.approx(50.0)


def test_slo_retried_lineage_and_shed_semantics():
    mon = SLOMonitor(window=8)
    mon.observe(_rec(reason="retried"))
    mon.observe({"kind": "decode_tick"})       # non-request: ignored
    mon.observe(_rec(reason="shed", wall=0.0, ttft=None))
    mon.observe(_rec(wall=200.0))
    rep = mon.report()
    assert rep["requests"] == 2                # shed + good, not retried
    assert rep["retried_attempts"] == 1
    # the shed's wall_ms=0 must NOT drag the latency estimators down
    assert rep["wall_ms_p50"] == pytest.approx(200.0)
    assert mon.burn_rate() > 0.0               # shed burns budget


def test_slo_deadline_and_absolute_targets():
    mon = SLOMonitor(targets=SLOTargets(goodput_pct=50.0, ttft_ms=50.0))
    mon.observe(_rec(ttft=10.0))                          # good
    mon.observe(_rec(ttft=80.0))                          # ttft target blown
    mon.observe(_rec(wall=3000.0, deadline_s=1.0))        # deadline blown
    assert mon.good == 1
    assert mon.report()["goodput_pct"] == pytest.approx(33.33, abs=0.01)


# ---------------------------------------------------------------------------
# distributed tracing: the merged fleet trace
# ---------------------------------------------------------------------------

def _traced_drill(model, vs, *, anomaly=None):
    mem = InMemorySink()
    clock = SimClock()
    faults = FaultSchedule(kill_replica_at_tick=(4, 0))
    fleet = _fleet(model, vs, 2, telemetry=Telemetry(sinks=[mem]),
                   faults=faults, clock=clock, trace=True, slo=True,
                   anomaly=anomaly)
    frs = fleet.play(_workload(), dt_s=DT)
    return fleet, frs, mem


def test_fleet_trace_kill_resubmit_is_one_connected_flow(model_and_vars):
    model, vs = model_and_vars
    fleet, frs, _ = _traced_drill(model, vs)
    tr = fleet.fleet_trace()
    lanes = sorted({e.get("pid") for e in tr["traceEvents"]
                    if e.get("ph") != "M"})
    assert 0 in lanes and len([p for p in lanes if p > 0]) >= 2
    retried = [fr.rid for fr in frs if fr.retries > 0]
    assert retried, "the kill fault must force at least one resubmit"
    for rid in retried:
        assert flow_connected(tr, rid), flow_summary(tr).get(rid)
        # the resubmitted rid's flow touches more than one lane
        pids = {pid for _, pid in flow_summary(tr)[rid]}
        assert len(pids) >= 2, pids
    # EVERY rid's flow is well-formed, not just the resubmitted ones
    for fr in frs:
        assert flow_connected(tr, fr.rid), fr.rid
    assert lane_monotonic(tr)
    names = {e["name"] for e in tr["traceEvents"] if e.get("ph") == "X"}
    assert {"submit", "queue_wait", "decode_tick", "engine_tick",
            "finish", "resubmit", "terminal"} <= names, names
    # Chrome-parseable: a JSON round trip preserves the container
    rt = json.loads(json.dumps(tr))
    assert rt["traceEvents"] and rt["displayTimeUnit"] == "ms"


def test_fleet_trace_merge_is_deterministic(model_and_vars):
    model, vs = model_and_vars
    fleet_a, _, _ = _traced_drill(model, vs)
    fleet_b, _, _ = _traced_drill(model, vs)
    a, b = fleet_a.fleet_trace(), fleet_b.fleet_trace()
    assert json.dumps(a["traceEvents"]) == json.dumps(b["traceEvents"])


def test_fleet_trace_tail_window(model_and_vars):
    model, vs = model_and_vars
    fleet, _, _ = _traced_drill(model, vs)
    full = fleet.fleet_trace()
    tail = fleet.fleet_trace(tail=10)
    n_meta = sum(1 for e in tail["traceEvents"] if e.get("ph") == "M")
    assert len(tail["traceEvents"]) == n_meta + 10
    assert len(full["traceEvents"]) > len(tail["traceEvents"])


def test_observability_off_is_invisible(model_and_vars):
    """Default-off contract: no tracer anywhere, no new stats keys, no
    new telemetry kinds — and the work itself is identical to an
    instrumented run's."""
    model, vs = model_and_vars

    def run(instrumented):
        mem = InMemorySink()
        fleet = _fleet(model, vs, 2, telemetry=Telemetry(sinks=[mem]),
                       faults=FaultSchedule(kill_replica_at_tick=(4, 0)),
                       clock=SimClock(), trace=instrumented,
                       slo=instrumented)
        frs = fleet.play(_workload(), dt_s=DT)
        return fleet, frs, mem

    dark, frs_dark, mem_dark = run(False)
    lit, frs_lit, _ = run(True)
    assert dark.tracer is None and dark.slo is None
    assert all(w.tracer is None for w in dark.workers)
    assert dark.fleet_trace() is None and dark.slo_report() is None
    st = dark.stats()
    assert "slo" not in st and "anomalies" not in st
    with pytest.raises(ValueError):
        dark.save_fleet_trace("/tmp/nope.json")
    # the pre-PR telemetry vocabulary, exactly — instrumentation adds
    # no record kinds when off
    kinds = {r.get("kind") for r in mem_dark.records}
    assert "fleet" not in kinds
    # zero observer effect: identical tokens + reasons per rid
    assert ({fr.rid: (fr.finish_reason, list(fr.tokens))
             for fr in frs_dark}
            == {fr.rid: (fr.finish_reason, list(fr.tokens))
                for fr in frs_lit})


def test_slo_rides_fleet_stats_and_fleet_record(model_and_vars):
    model, vs = model_and_vars
    fleet, frs, mem = _traced_drill(model, vs)
    st = fleet.stats()
    assert "burn_rate" in st["slo"]
    assert st["slo"]["requests"] == len(frs)
    assert st["transport"] == {"errors": 0, "retransmits": 0,
                               "timeouts": 0, "corrupt_replies": 0}
    rec = fleet.emit_stats()
    assert rec["kind"] == "fleet" and "slo" in rec and "transport" in rec
    assert any(r.get("kind") == "fleet" for r in mem.records)


# ---------------------------------------------------------------------------
# serving anomaly forensics
# ---------------------------------------------------------------------------

def test_tick_stall_fires_with_forensic_bundle(model_and_vars):
    model, vs = model_and_vars
    out = tempfile.mkdtemp(prefix="paddle_tpu_anom_")
    anom = ServingAnomalyDetector(out_dir=out, stall_ticks=2)
    mem = InMemorySink()
    clock = SimClock()
    faults = FaultSchedule(stall_replica_at_tick=(3, 1, 4))
    # long heartbeat so the stall stays a stall, not a death verdict
    fleet = _fleet(model, vs, 2, telemetry=Telemetry(sinks=[mem]),
                   faults=faults, clock=clock, trace=True, slo=True,
                   anomaly=anom, heartbeat_timeout_s=10.0)
    fleet.play(_workload(8), dt_s=DT)
    kinds = [v.kind for v in anom.verdicts]
    assert "tick_stall" in kinds, kinds
    bundle = next(b for b in anom.bundles if "tick_stall_r1" in b)
    files = set(os.listdir(bundle))
    assert {"verdict.json", "tick_ring.jsonl", "records_tail.jsonl",
            "heartbeats.json", "transport.json",
            "fleet_trace_tail.json"} <= files, files
    v = json.load(open(os.path.join(bundle, "verdict.json")))
    assert v["replica"] == 1
    assert v["verdict"]["kind"] == "tick_stall"
    # the bound trace tail is a real merged trace container
    tt = json.load(open(os.path.join(bundle, "fleet_trace_tail.json")))
    assert "traceEvents" in tt
    # one-shot: the same kind cannot fire twice for the same replica
    assert kinds.count("tick_stall") == 1


def test_serving_anomaly_kinds_unit():
    out = tempfile.mkdtemp(prefix="paddle_tpu_anom_unit_")
    det = ServingAnomalyDetector(out_dir=out, stall_ticks=3,
                                 accept_floor=0.2, accept_window=3,
                                 prefix_window=3, retransmit_burst=3,
                                 queue_growth=4, queue_window=4)
    # accept_collapse: healthy then floor-pinned for a full window
    base = {"kind": "request", "finish_reason": "length"}
    det.observe_serving(0, dict(base, draft_proposed=10,
                                draft_accepted=8))
    fired = []
    for _ in range(3):
        fired += det.observe_serving(0, dict(base, draft_proposed=10,
                                             draft_accepted=1))
    assert [v.kind for v in fired] == ["accept_collapse"]
    # prefix_hit_collapse: hits before, none across the window
    det.observe_serving(1, dict(base, prefix_hit_blocks=4))
    fired = []
    for _ in range(3):
        fired += det.observe_serving(1, dict(base, prefix_hit_blocks=0))
    assert [v.kind for v in fired] == ["prefix_hit_collapse"]
    # retransmit_burst: cumulative counter rises >= threshold in-window
    assert det.observe_transport(2, {"retransmits": 0}) == []
    fired = det.observe_transport(2, {"retransmits": 4})
    assert [v.kind for v in fired] == ["retransmit_burst"]
    # queue_divergence: monotone growth across a full window
    fired = []
    for tick, q in enumerate((0, 2, 4, 6)):
        fired += det.observe_fleet_tick(3, tick=tick, engine_ticks=tick,
                                        queued=q, busy=True)
    assert [v.kind for v in fired] == ["queue_divergence"]
    # per-replica one-shot isolation: replica 4 can still fire the kind
    # replica 3 used up
    fired = []
    for tick, q in enumerate((0, 2, 4, 6)):
        fired += det.observe_fleet_tick(4, tick=tick, engine_ticks=tick,
                                        queued=q, busy=True)
    assert [v.kind for v in fired] == ["queue_divergence"]
    # retried lineage records never feed detection
    assert det.observe_serving(0, dict(base, finish_reason="retried",
                                       draft_proposed=10,
                                       draft_accepted=0)) == []
    assert len(det.bundles) == 5


# ---------------------------------------------------------------------------
# satellites: child JSONL sink, spec stability, report, top
# ---------------------------------------------------------------------------

def test_event_buffer_jsonl_sink(tmp_path):
    path = str(tmp_path / "deep" / "replica_0.jsonl")
    buf = EventBuffer(jsonl_path=path)
    buf.emit_event({"kind": "request", "rid": 1})
    buf.emit_event({"kind": "decode_tick", "tick": 0})
    # the file is line-flushed per record: readable NOW, mid-"run",
    # exactly what a post-SIGKILL post-mortem needs
    rows = [json.loads(l) for l in open(path)]
    assert [r["kind"] for r in rows] == ["request", "decode_tick"]
    assert len(buf.drain()) == 2              # shipping unchanged
    assert EventBuffer().drain() == []        # sink-less default


def test_build_proc_spec_schema_stability(model_and_vars):
    model, vs = model_and_vars
    root = tempfile.mkdtemp(prefix="paddle_tpu_spec_")
    old = build_proc_spec(model, vs, root, engine_kwargs={})
    assert "telemetry_dir" not in old and "trace" not in old
    unset = build_proc_spec(model, vs, root, engine_kwargs={},
                            telemetry_dir=None)
    assert unset == old                       # absent-when-unset
    td = os.path.join(root, "tel")
    new = build_proc_spec(model, vs, root, engine_kwargs={},
                          telemetry_dir=td)
    assert new.pop("telemetry_dir") == td
    assert new == old                         # ONLY the new key differs


def test_report_surfaces_transport_and_slo(model_and_vars):
    model, vs = model_and_vars
    fleet, _, mem = _traced_drill(model, vs)
    fleet.emit_stats()
    s = report_lib.summarize(mem.records)
    assert s["serving"]["transport"]["retransmits"] == 0
    assert "burn_rate" in s["serving"]["slo"]
    text = report_lib.format_summary(s)
    assert "transport" in text and "slo (streaming)" in text
    assert "burn rate" in text
    # fallback: no fleet record, classified transport EVENTS only
    evs = [{"kind": "transport", "event": "timeout", "replica": 0,
            "op": "tick"},
           {"kind": "transport", "event": "corrupt", "replica": 0,
            "op": "tick"}]
    s2 = report_lib.summarize(evs)
    assert s2["serving"]["transport"]["events"] == 2
    assert s2["serving"]["transport"]["timeout"] == 1


def test_top_render_and_once(tmp_path):
    root = str(tmp_path / "fleet")
    multihost.write_heartbeat(root, host_id=0, seq=3, now=100.0,
                              extra={"queued": 2, "running": 1,
                                     "free_blocks": 7})
    jsonl = str(tmp_path / "tel.jsonl")
    with open(jsonl, "w") as f:
        f.write(json.dumps(_rec()) + "\n")
        f.write(json.dumps(_rec(reason="timeout")) + "\n")
    frame = top_lib.render(root, jsonl, now=100.5)
    assert "replica" in frame and "0" in frame
    assert "burn_rate" in frame and "ttft_ms" in frame
    assert "length=1" in frame and "timeout=1" in frame
    assert top_lib.main(["--root", root, "--jsonl", jsonl,
                         "--once"]) == 0


def test_merge_fleet_trace_canonicalizes_pids_and_tids():
    router = [{"ph": "M", "name": "process_name", "pid": 999, "tid": 0,
               "args": {"name": "x"}},
              {"ph": "X", "name": "submit", "pid": 999, "tid": 1234,
               "ts": 1.0, "dur": 1.0}]
    replica = {0: [{"ph": "X", "name": "decode_tick", "pid": 31337,
                    "tid": 777, "ts": 2.0, "dur": 1.0}]}
    tr = merge_fleet_trace(router, replica)
    evs = [e for e in tr["traceEvents"] if e.get("ph") != "M"]
    assert [e["pid"] for e in evs] == [0, 1]   # router=0, replica r=r+1
    assert all(e["tid"] == 1 for e in evs)     # first-appearance order
    metas = [e for e in tr["traceEvents"] if e.get("ph") == "M"]
    names = {m["args"]["name"] for m in metas}
    assert {"fleet-router", "replica 0"} <= names
