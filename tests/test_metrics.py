"""Fleet-wide metrics backbone tests (ISSUE 19): the typed registry
(Counter/Gauge/Histogram over bounded ring-buffer series), Prometheus
text exposition round-trip, the cross-host delta-merge protocol (and
its SIGKILL-loss semantics), default-off invisibility through a live
in-process fleet twin drill, the report's registry read-through for
transport totals, the ``obs.top`` sparkline dashboard block, and the
P² quantile adversarial streams (satellite 4).

Fleet drills are in-process on a :class:`SimClock` — the process/socket
twin with real piggybacked deltas is
``tests/test_drills.py::test_drill_leg[fleet-tracing]``."""

import json
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models import TransformerLM
from paddle_tpu.obs import InMemorySink, P2Quantile, Telemetry
from paddle_tpu.obs import report as report_lib
from paddle_tpu.obs import top as top_lib
from paddle_tpu.obs.metrics import (Counter, Gauge, Histogram,
                                    MetricsHub, log_buckets,
                                    parse_exposition)
from paddle_tpu.obs.percentiles import percentile
from paddle_tpu.serve import ServingFleet, SimClock
from paddle_tpu.serve.loadgen import make_workload

V, W = 64, 24
DT = 0.1


@pytest.fixture(scope="module")
def model_and_vars():
    model = TransformerLM(vocab=V, dim=16, num_layers=1, num_heads=2,
                          ffn_hidden=32, max_len=W)
    vs = model.init(jax.random.PRNGKey(0), jnp.zeros((1, W), jnp.int32))
    return model, vs


@pytest.fixture(scope="module")
def fleet_runs(model_and_vars):
    """One instrumented + one dark fleet twin, played ONCE and shared
    by every fleet-level test below (the drills dominate this module's
    runtime; the assertions are all on the captured evidence)."""
    model, vs = model_and_vars
    runs = {}
    for on in (True, False):
        mem = InMemorySink()
        f = _fleet(model, vs, 2, metrics=on,
                   telemetry=Telemetry(sinks=[mem]))
        try:
            wl = _workload()
            frs = f.play(wl, dt_s=DT)
            f.emit_stats()
            stats = f.stats()
            runs[on] = {
                "n_requests": len(wl),
                "tokens": {fr.rid: (fr.finish_reason, list(fr.tokens))
                           for fr in frs},
                "stats_keys": set(stats),
                "transport": stats["transport"],
                "hub": f.metrics,
                "records": list(mem.records),
            }
        finally:
            f.shutdown()
    return runs


def _fleet(model, vs, n, **kw):
    return ServingFleet.from_model(
        model, vs, n, engine_kwargs=dict(max_slots=2, block_size=4),
        clock=SimClock(), heartbeat_timeout_s=0.25, est_tick_s=DT,
        root=tempfile.mkdtemp(prefix="paddle_tpu_metrics_"), **kw)


def _workload(n=6, seed=7):
    return make_workload(n, V, seed=seed, rate_rps=30.0,
                         prompt_len=(2, 6), max_new=(3, 8), max_total=W)


def _ticking_hub(retention=512):
    """A hub on a fake clock that advances 1s per stamp — deterministic
    timestamps without SimClock plumbing."""
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    return MetricsHub(clock=clock, retention=retention)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_is_monotone():
    hub = _ticking_hub()
    c = hub.counter("requests", "total requests")
    c.inc()
    c.inc(4)
    assert c.value == 5.0
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 5.0            # rejected inc must not corrupt
    c.inc(0)                         # zero is a no-op, not a sample
    assert len(c.samples()) == 2


def test_gauge_last_write_wins():
    hub = _ticking_hub()
    g = hub.gauge("depth", "queue depth")
    assert g.value is None
    g.set(3)
    g.inc(2)
    g.dec()
    assert g.value == 4.0
    assert [v for _, v in g.samples()] == [3.0, 5.0, 4.0]


def test_log_buckets_policy():
    bs = log_buckets(lo=1e-3, hi=1e3, per_decade=1)
    assert bs == [1e-3, 1e-2, 1e-1, 1.0, 10.0, 100.0, 1000.0]
    assert bs == sorted(bs)
    # 6-sig-digit stability: recomputing yields identical floats
    assert log_buckets() == log_buckets()
    with pytest.raises(ValueError):
        log_buckets(lo=0.0)
    with pytest.raises(ValueError):
        log_buckets(lo=10.0, hi=1.0)


def test_histogram_bucket_math_vs_numpy():
    hub = _ticking_hub()
    h = hub.histogram("lat", "latency", buckets=[1.0, 10.0, 100.0])
    rng = np.random.RandomState(0)
    vals = rng.lognormal(mean=1.5, sigma=1.5, size=500)
    for v in vals:
        h.observe(float(v))
    # le semantics: bucket i owns v <= bound[i] (and > bound[i-1]);
    # the trailing slot is the +Inf overflow
    bounds = np.array([1.0, 10.0, 100.0])
    expect = [int(np.sum(vals <= 1.0)),
              int(np.sum((vals > 1.0) & (vals <= 10.0))),
              int(np.sum((vals > 10.0) & (vals <= 100.0))),
              int(np.sum(vals > 100.0))]
    assert h.counts == expect
    assert h.count == 500 and sum(h.counts) == 500
    assert h.sum == pytest.approx(float(np.sum(vals)))
    # a value exactly on a bound lands IN that bound's bucket
    h2 = hub.histogram("lat2", buckets=[1.0, 10.0])
    h2.observe(10.0)
    assert h2.counts == [0, 1, 0]
    with pytest.raises(ValueError):
        hub.histogram("bad", buckets=[2.0, 1.0])


def test_ring_buffer_eviction_oldest_first():
    hub = _ticking_hub(retention=4)
    c = hub.counter("ticks")
    for _ in range(7):
        c.inc()
    s = c.samples()
    assert len(s) == 4
    # cumulative values 4..7 survive; 1..3 were evicted oldest-first
    assert [v for _, v in s] == [4.0, 5.0, 6.0, 7.0]
    assert s[0][0] < s[-1][0]
    # since= filters on the stamped clock
    assert c.samples(since=s[-1][0]) == [s[-1]]


def test_label_isolation_and_type_conflict():
    hub = _ticking_hub()
    a = hub.counter("rpc", "per-link", link="0")
    b = hub.counter("rpc", "per-link", link="1")
    assert a is not b
    a.inc(3)
    assert b.value == 0.0
    assert hub.counter("rpc", link="0") is a       # get-or-create
    with pytest.raises(ValueError):
        hub.gauge("rpc", link="2")                 # kind conflict
    rows = {(r["labels"].get("link")): r["value"]
            for r in hub.snapshot() if r["name"] == "rpc"}
    assert rows == {"0": 3.0, "1": 0.0}


def test_scoped_facade_merges_labels():
    hub = _ticking_hub()
    sc = hub.scoped(replica="2").scoped(role="decode")
    sc.counter("ticks").inc()
    (row,) = hub.snapshot()
    assert row["labels"] == {"replica": "2", "role": "decode"}
    assert sc.clock is hub.clock


def test_query_label_superset():
    hub = _ticking_hub()
    hub.counter("x", a="1", b="2").inc(5)
    hub.counter("x", a="1", b="3").inc(7)
    got = hub.query("x", a="1")
    assert len(got) == 2
    got = hub.query("x", b="3")
    assert len(got) == 1 and got[0]["samples"][-1][1] == 7.0
    assert hub.query("x", a="9") == []


# ---------------------------------------------------------------------------
# Prometheus text exposition round-trip
# ---------------------------------------------------------------------------

def test_exposition_round_trip():
    hub = _ticking_hub()
    hub.counter("reqs", "total reqs", path='/v1/"gen"\\x').inc(12)
    hub.gauge("depth", "queue depth", replica="0").set(2.5)
    h = hub.histogram("lat_ms", "tick latency", buckets=[1.0, 10.0])
    for v in (0.5, 3.0, 3.0, 50.0):
        h.observe(v)
    text = hub.render()
    assert "# HELP reqs total reqs" in text
    parsed = parse_exposition(text)
    assert parsed["types"] == {"reqs": "counter", "depth": "gauge",
                               "lat_ms": "histogram"}
    samples = {(n, tuple(sorted(l.items()))): v
               for n, l, v in parsed["samples"]}
    # label escaping survives the round trip
    assert samples[("reqs",
                    (("path", '/v1/"gen"\\x'),))] == 12.0
    assert samples[("depth", (("replica", "0"),))] == 2.5
    # histogram renders CUMULATIVE le-buckets plus sum/count
    assert samples[("lat_ms_bucket", (("le", "1"),))] == 1.0
    assert samples[("lat_ms_bucket", (("le", "10"),))] == 3.0
    assert samples[("lat_ms_bucket", (("le", "+Inf"),))] == 4.0
    assert samples[("lat_ms_count", ())] == 4.0
    assert samples[("lat_ms_sum", ())] == pytest.approx(56.5)


# ---------------------------------------------------------------------------
# cross-host delta protocol
# ---------------------------------------------------------------------------

def test_delta_drain_absorb_namespaced_merge():
    child, parent = _ticking_hub(), _ticking_hub()
    child.counter("ticks").inc(3)
    child.gauge("depth").set(2)
    h = child.histogram("lat", buckets=[1.0, 10.0])
    h.observe(0.5)
    h.observe(5.0)
    batch = child.drain_delta()
    assert child.drain_delta() == []           # watermark advanced
    parent.absorb_delta(json.loads(json.dumps(batch)), replica="0")
    # second child round: only the NEW increments travel
    child.counter("ticks").inc(2)
    h.observe(100.0)
    batch2 = child.drain_delta()
    (cinc,) = [d for d in batch2 if d["kind"] == "counter"]
    assert cinc["inc"] == 2.0
    parent.absorb_delta(batch2, replica="0")
    rows = {r["name"]: r for r in parent.snapshot()}
    assert rows["ticks"]["value"] == 5.0
    assert rows["ticks"]["labels"] == {"replica": "0"}
    assert rows["lat"]["count"] == 3
    assert rows["lat"]["counts"] == [1, 1, 1]
    assert rows["lat"]["sum"] == pytest.approx(105.5)
    assert rows["depth"]["value"] == 2.0


def test_delta_lost_with_sigkilled_child_stays_lost():
    child, parent = _ticking_hub(), _ticking_hub()
    child.counter("ticks").inc(4)
    child.drain_delta()                        # shipped... and lost
    child.counter("ticks").inc(1)
    parent.absorb_delta(child.drain_delta(), replica="0")
    # the parent honestly shows only what was delivered — no
    # resynthesis of the batch that died with the process
    (row,) = [r for r in parent.snapshot() if r["name"] == "ticks"]
    assert row["value"] == 1.0


def test_histogram_merge_rejects_mismatched_buckets():
    hub = _ticking_hub()
    h = hub.histogram("lat", buckets=[1.0, 10.0])
    with pytest.raises(ValueError):
        h.merge([1, 2], 3.0, 3)                # 2 counts vs 3 slots


# ---------------------------------------------------------------------------
# fleet integration: default-off invisibility + registry contents
# ---------------------------------------------------------------------------

def test_fleet_metrics_dark_twin_identical(fleet_runs):
    runs = fleet_runs
    assert runs[True]["tokens"] == runs[False]["tokens"]
    # the registry adds ZERO new stats keys — fleet.stats() reads
    # through it, it does not grow because of it
    assert runs[True]["stats_keys"] == runs[False]["stats_keys"]
    assert runs[False]["hub"] is None
    assert runs[True]["hub"] is not None


def test_fleet_registry_contents_and_emit(fleet_runs):
    run = fleet_runs[True]
    snap = run["hub"].snapshot()
    rows = {(r["name"], tuple(sorted(r["labels"].items()))): r
            for r in snap}
    ticks = rows[("fleet_ticks", ())]
    assert ticks["type"] == "counter" and ticks["value"] > 0
    assert (rows[("fleet_requests_submitted", ())]["value"]
            == run["n_requests"])
    # per-replica namespacing from the scoped handles
    for rep in ("0", "1"):
        assert any(n == "engine_ticks"
                   and dict(l).get("replica") == rep
                   for (n, l) in rows), rep
    # router tick-duration histogram accumulates real observations
    hist = rows[("fleet_router_ms", ())]
    assert hist["type"] == "histogram"
    assert hist["count"] == ticks["value"]
    # exposition parses and types agree with the snapshot
    parsed = parse_exposition(run["hub"].render())
    assert parsed["types"]["fleet_ticks"] == "counter"
    assert parsed["types"]["fleet_router_ms"] == "histogram"
    # ring history is queryable
    (q,) = run["hub"].query("fleet_ticks")
    assert len(q["samples"]) >= 2
    # emit_stats ships one kind="metrics" snapshot record
    mets = [r for r in run["records"] if r.get("kind") == "metrics"]
    assert len(mets) == 1
    assert any(r["name"] == "fleet_ticks" for r in mets[0]["metrics"])


def test_transport_totals_read_through_matches_dark(fleet_runs):
    """Satellite 2: fleet.stats() transport totals must be identical
    whether they come from the registry (metrics on) or the legacy
    attribute counters (metrics off) — same drill, same totals."""
    assert fleet_runs[True]["transport"] == fleet_runs[False]["transport"]
    assert set(fleet_runs[True]["transport"]) == {
        "errors", "retransmits", "timeouts", "corrupt_replies"}


def test_report_prefers_registry_transport_totals():
    """Satellite 2, reader side: a kind="metrics" snapshot in the
    stream IS the transport-totals source; classified transport events
    remain the fallback — and on a clean stream both agree."""
    tev = [{"kind": "transport", "event": "timeouts", "replica": 0},
           {"kind": "transport", "event": "timeouts", "replica": 1},
           {"kind": "transport", "event": "corrupt_replies",
            "replica": 0}]
    met = {"kind": "metrics", "metrics": [
        {"name": "transport_timeouts", "type": "counter",
         "labels": {"link": "0"}, "value": 1},
        {"name": "transport_timeouts", "type": "counter",
         "labels": {"link": "1"}, "value": 1},
        {"name": "transport_corrupt_replies", "type": "counter",
         "labels": {"link": "0"}, "value": 1},
        {"name": "transport_rtt_ms", "type": "histogram",
         "labels": {"link": "0"}, "count": 3, "sum": 1.0,
         "buckets": [1.0], "counts": [3, 0]}]}
    with_reg = report_lib.summarize(tev + [met])
    fallback = report_lib.summarize(tev)
    tr_reg = with_reg["serving"]["transport"]
    tr_ev = fallback["serving"]["transport"]
    for k in ("timeouts", "corrupt_replies"):
        assert tr_reg[k] == tr_ev[k], k
    assert tr_reg["retransmits"] == 0          # zero-filled, not absent
    assert tr_reg["events"] == 3


# ---------------------------------------------------------------------------
# obs.top: sparklines + the metrics dashboard block
# ---------------------------------------------------------------------------

def test_sparkline_shapes():
    assert top_lib.sparkline([]) == ""
    assert top_lib.sparkline([5, 5, 5]) == "▁▁▁"
    ramp = top_lib.sparkline([0, 1, 2, 3, 4, 5, 6, 7])
    assert ramp[0] == "▁" and ramp[-1] == "█"
    assert len(top_lib.sparkline(list(range(100)), width=24)) == 24


def test_top_renders_registry_block_from_hub():
    hub = _ticking_hub()
    c = hub.counter("fleet_ticks", "ticks", replica="0")
    for _ in range(6):
        c.inc()
    hub.gauge("depth").set(3)
    h = hub.histogram("lat_ms", buckets=[1.0, 10.0])
    for v in (0.5, 2.0, 2.0, 20.0):
        h.observe(v)
    frame = top_lib.render(hub=hub)
    assert "-- metrics (registry) --" in frame
    assert "fleet_ticks{replica=0}" in frame
    assert "total=6.00" in frame
    assert "n=4" in frame                      # histogram line
    assert any(ch in frame for ch in "▁▂▃▄▅▆▇█")


def test_top_once_renders_metrics_from_jsonl(tmp_path, capsys):
    """The offline path the --once CLI exercises: kind="metrics"
    snapshots in the telemetry JSONL become sparkline history."""
    snaps = []
    for tick in (1, 2, 3):
        snaps.append({"kind": "metrics", "tick": tick, "metrics": [
            {"name": "fleet_ticks", "type": "counter", "labels": {},
             "value": float(tick * 2)}]})
    p = tmp_path / "tel.jsonl"
    p.write_text("\n".join(json.dumps(s) for s in snaps) + "\n")
    rc = top_lib.main(["--jsonl", str(p), "--once"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "-- metrics (registry) --" in out
    assert "fleet_ticks" in out and "total=6.00" in out


# ---------------------------------------------------------------------------
# SLO monitor → registry gauges (satellite 3)
# ---------------------------------------------------------------------------

def _req(ms):
    return {"kind": "request", "finish_reason": "length",
            "ttft_ms": ms, "tpot_ms": ms / 10.0, "wall_ms": ms * 2.0,
            "new_tokens": 4}


def test_slo_monitor_publishes_gauges_report_identical():
    from paddle_tpu.obs import SLOMonitor
    hub = _ticking_hub()
    with_m = SLOMonitor(metrics=hub)
    without = SLOMonitor()
    for i in range(20):
        rec = _req(10.0 + i)
        with_m.observe(rec)
        without.observe(rec)
    # report() is byte-identical with the registry attached
    assert (json.dumps(with_m.report(), sort_keys=True)
            == json.dumps(without.report(), sort_keys=True))
    rows = {r["name"]: r["value"] for r in hub.snapshot()}
    rep = with_m.report()
    for m in ("ttft_ms", "tpot_ms", "wall_ms"):
        for p in (50, 95, 99):
            assert rows[f"slo_{m}_p{p}"] == pytest.approx(
                rep[f"{m}_p{p}"]), (m, p)
    assert rows["slo_burn_rate"] == pytest.approx(rep["burn_rate"])


# ---------------------------------------------------------------------------
# P² adversarial streams (satellite 4)
# ---------------------------------------------------------------------------

def test_p2_constant_stream_is_exact_at_any_length():
    for n in (1, 4, 5, 6, 100):
        for p in (50, 95, 99):
            est = P2Quantile(p)
            for _ in range(n):
                est.observe(7.25)
            assert est.value() == 7.25, (n, p)


def test_p2_two_value_alternation():
    vals = []
    ests = {p: P2Quantile(p) for p in (50, 95, 99)}
    for i in range(1000):
        v = float(i % 2)
        vals.append(v)
        for est in ests.values():
            est.observe(v)
    # tails pin to the upper value like the exact rule; the median may
    # sit anywhere inside the two-point support but never outside it
    assert ests[95].value() == pytest.approx(1.0)
    assert ests[99].value() == pytest.approx(1.0)
    assert 0.0 <= ests[50].value() <= 1.0


def test_p2_monotone_ramps_track_nearest_rank():
    for direction in (1, -1):
        stream = [float(i) for i in range(1, 1001)][::direction]
        for p in (50, 95, 99):
            est = P2Quantile(p)
            for v in stream:
                est.observe(v)
            exact = percentile(stream, p)
            assert est.value() == pytest.approx(exact, rel=0.01), (
                direction, p, est.value(), exact)


def test_p2_five_sample_boundary():
    """n < 5 answers the exact nearest-rank rule; crossing into marker
    mode the estimate may jump (markers initialize to the 5 sorted
    samples regardless of p) but stays inside the observed range."""
    stream = [5.0, 1.0, 4.0, 2.0, 3.0, 6.0, 0.5]
    for p in (50, 95, 99):
        est = P2Quantile(p)
        seen = []
        for v in stream:
            est.observe(v)
            seen.append(v)
            exact = percentile(seen, p)
            if len(seen) < 5:
                assert est.value() == exact, (p, len(seen))
            else:
                assert min(seen) <= est.value() <= max(seen)
                assert abs(est.value() - exact) <= max(seen) - min(seen)
    # p50 specifically stays exact THROUGH the boundary: the middle
    # marker initializes to the median
    est = P2Quantile(50)
    for v in stream[:5]:
        est.observe(v)
    assert est.value() == 3.0
