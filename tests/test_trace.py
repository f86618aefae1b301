"""Structured tracing + anomaly flight recorder (ISSUE 4): Chrome Trace
Event emission (thread-aware spans, flow linking, ring bound), the traced
pipelined Trainer run, every anomaly-detector trigger kind (one bundle
each, off-by-default none), the stager-leak close() contract, and the
fill-thread spans in data.buffered."""

import json
import logging
import os
import threading
import time

import numpy as np
import jax
import pytest

from paddle_tpu import optim
from paddle_tpu.data import reader as data
from paddle_tpu.models import MnistMLP
from paddle_tpu.nn import costs
from paddle_tpu.obs import (AnomalyDetector, InMemorySink, Telemetry,
                            Tracer, tspan)
from paddle_tpu.obs import trace as trace_lib
from paddle_tpu.obs.anomaly import Verdict
from paddle_tpu.train import Trainer
from paddle_tpu.train.host_pipeline import GroupStager

BS, DIM = 16, 12


def make_batches(n, bs=BS, dim=DIM, seed=0):
    rng = np.random.RandomState(seed)
    return [{"x": rng.normal(size=(bs, dim)).astype(np.float32),
             "label": rng.randint(0, 4, size=bs).astype(np.int32)}
            for _ in range(n)]


def make_trainer(K=2, M=2, **kw):
    return Trainer(
        model=MnistMLP(num_classes=4, hidden=(8,)),
        loss_fn=lambda out, b: costs.softmax_cross_entropy(out, b["label"]),
        optimizer=optim.adam(1e-3),
        steps_per_call=K, grad_accum=M, **kw)


def step_rec(step, *, wall=10.0, retrace=0, drain=None, mem=None,
             nonfinite=0, loss=0.5):
    """A synthetic telemetry step record with a controllable wall time."""
    return {"kind": "step", "ts": time.time(), "step": step, "k_steps": 1,
            "m": 1, "loss": loss, "host_stack_ms": None, "shard_ms": wall / 2,
            "dispatch_ms": wall / 2, "device_ms": None, "replay_ms": None,
            "drain_wait_ms": drain, "bytes_in_use": mem,
            "retrace_count": retrace, "nonfinite_count": nonfinite}


# ---------------------------------------------------------------------------
# Tracer: Chrome Trace Event format
# ---------------------------------------------------------------------------

def test_tracer_spans_flows_and_chrome_format(tmp_path):
    tracer = Tracer()
    fid = tracer.new_flow()
    with tracer.span("stage", flow_start=fid, group=0):
        time.sleep(0.001)

    def other_thread():
        with tracer.span("dispatch", flow_step=fid):
            time.sleep(0.001)

    t = threading.Thread(target=other_thread, name="worker")
    t.start()
    t.join()
    with tracer.span("drain", flow_end=fid):
        pass
    tracer.instant("marker", step=3)

    path = tracer.save(str(tmp_path / "trace.json"))
    doc = json.load(open(path))                   # valid JSON by parse
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"stage", "dispatch", "drain"}
    assert len({e["tid"] for e in xs}) == 2       # two threads recorded
    # every span has a positive duration and args survived
    assert all(e["dur"] > 0 for e in xs)
    assert [e for e in xs if e["name"] == "stage"][0]["args"]["group"] == 0
    # flow events: s/t/f share the id; the "f" binds to its enclosing slice
    flows = {e["ph"]: e for e in evs if e.get("cat") == "flow"}
    assert set(flows) == {"s", "t", "f"}
    assert len({e["id"] for e in flows.values()}) == 1
    assert flows["f"]["bp"] == "e"
    # thread metadata names both threads; instant marker present
    names = [e for e in evs if e.get("ph") == "M"
             and e["name"] == "thread_name"]
    assert len(names) == 2
    assert any(e["ph"] == "i" and e["name"] == "marker" for e in evs)
    # serialized traceEvents are timestamp-sorted
    ts = [e.get("ts", -1.0) for e in evs]
    assert ts == sorted(ts)


def test_tracer_ring_bound_and_tspan_null():
    tracer = Tracer(max_events=10)
    for i in range(50):
        with tracer.span("s", i=i):
            pass
    evs = [e for e in tracer.events() if e["ph"] == "X"]
    assert len(evs) == 10                         # ring kept the tail
    assert evs[-1]["args"]["i"] == 49
    assert tracer.dropped_events == 40
    # tspan with tracer=None is a shared no-op context
    with tspan(None, "anything", junk=1) as v:
        assert v is None


def test_tracer_concurrent_span_emission():
    """Spans finishing on many threads concurrently must all land (the
    lock contract the stager/fill threads rely on)."""
    tracer = Tracer()

    def worker(n):
        for i in range(50):
            with tracer.span("w", n=n):
                pass

    threads = [threading.Thread(target=worker, args=(n,)) for n in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    xs = [e for e in tracer.events() if e["ph"] == "X"]
    assert len(xs) == 200


# ---------------------------------------------------------------------------
# traced pipelined Trainer run
# ---------------------------------------------------------------------------

def test_traced_pipelined_run_two_threads_flows_pair(tmp_path):
    """pipeline_depth=2 with a tracer: staging spans come from the stager
    thread, dispatch/drain spans from the main thread, and every staging
    flow pairs with a drain flow."""
    tracer = Tracer()
    tel = Telemetry(sinks=[InMemorySink()])
    tr = make_trainer(telemetry=tel, tracer=tracer, pipeline_depth=2)
    batches = make_batches(2 * 2 * 3)
    tr.init(jax.random.PRNGKey(0), batches[0])
    tr.train(lambda: iter(batches), num_passes=1, log_period=0)
    evs = tracer.events()
    xs = [e for e in evs if e["ph"] == "X"]
    by_name = {}
    for e in xs:
        by_name.setdefault(e["name"], []).append(e)
    for required in ("stage", "stack", "shard", "dispatch", "drain",
                     "drain_wait", "events_replay"):
        assert required in by_name, f"no {required!r} spans"
    stage_tids = {e["tid"] for e in by_name["stage"]}
    main_tids = {e["tid"] for e in by_name["dispatch"]}
    assert stage_tids and main_tids and not (stage_tids & main_tids)
    s_ids = {e["id"] for e in evs if e.get("ph") == "s"}
    f_ids = {e["id"] for e in evs if e.get("ph") == "f"}
    assert s_ids and s_ids == f_ids               # every flow pairs up
    # the whole document serializes as valid Chrome trace JSON
    tracer.save(str(tmp_path / "t.json"))
    json.load(open(str(tmp_path / "t.json")))


def test_traced_pipelined_stage_span_concurrent_with_main_thread():
    """The overlap the trace exists to make auditable: in a pipelined run
    some stager-thread ``stage`` span INTERSECTS IN TIME one main-thread
    span (a union-window check would pass for fully serialized staging
    too), and every span sits on the tracer's clock (``ts >= 0``,
    ``dur > 0``). Which pass shows it is the host's scheduling: a fast
    stager can finish between two main-thread spans, so any of six
    post-compile passes may."""
    tr = make_trainer(K=4, tracer=Tracer(), pipeline_depth=2)
    batches = make_batches(4 * 2 * 4)
    tr.init(jax.random.PRNGKey(0), batches[0])
    tr.train(lambda: iter(batches), num_passes=1, log_period=0)   # compiles
    concurrent = False
    for _ in range(6):
        tr.tracer = Tracer()
        tr.train(lambda: iter(batches), num_passes=1, log_period=0)
        xs = [e for e in tr.tracer.events() if e["ph"] == "X"]
        assert all(e["ts"] >= 0 and e["dur"] > 0 for e in xs)
        stage = [e for e in xs if e["name"] == "stage"]
        stage_tids = {e["tid"] for e in stage}
        main = [e for e in xs if e["tid"] not in stage_tids]
        assert stage and main
        concurrent = any(
            s["ts"] < m["ts"] + m["dur"] and s["ts"] + s["dur"] > m["ts"]
            for s in stage for m in main)
        if concurrent:
            break
    assert concurrent


def test_tracer_off_is_byte_identical_params_and_dispatches():
    """ISSUE 4 acceptance: tracer=None, anomaly=None is the pre-PR-4 hot
    loop — same dispatch count and bit-identical params vs a fully
    instrumented run (tracing/anomaly must not perturb the math)."""
    batches = make_batches(2 * 2 * 3)

    def run(**kw):
        tr = make_trainer(**kw)
        tr.init(jax.random.PRNGKey(0), batches[0])
        calls = {"n": 0}
        orig = tr._dispatch_fused

        def counting(stacked, rng, **k):
            calls["n"] += 1
            return orig(stacked, rng, **k)

        tr._dispatch_fused = counting
        tr.train(lambda: iter(batches), num_passes=1, log_period=0)
        return tr, calls["n"]

    tr_off, n_off = run(telemetry=None)
    import tempfile
    tr_on, n_on = run(
        telemetry=Telemetry(sinks=[InMemorySink()]), tracer=Tracer(),
        anomaly=AnomalyDetector(out_dir=tempfile.mkdtemp()))
    assert n_on == n_off
    for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(
                tr_off.train_state.params)),
            jax.tree_util.tree_leaves(jax.device_get(
                tr_on.train_state.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_anomaly_without_telemetry_rejected():
    with pytest.raises(ValueError, match="telemetry"):
        make_trainer(anomaly=AnomalyDetector(out_dir="/tmp/x"))


# ---------------------------------------------------------------------------
# anomaly detector: every trigger kind, one bundle each
# ---------------------------------------------------------------------------

def _bundle_dirs(root):
    return sorted(d for d in os.listdir(root) if d.startswith("anomaly_"))


def test_anomaly_slow_step_outlier(tmp_path):
    det = AnomalyDetector(out_dir=str(tmp_path), warmup=8)
    for i in range(20):
        assert det.observe(step_rec(i, wall=10.0 + 0.01 * i)) == []
    v = det.observe(step_rec(99, wall=500.0))     # 50x the median
    assert [x.kind for x in v] == ["slow_step"]
    det.observe(step_rec(100, wall=500.0))        # one-shot: no 2nd bundle
    assert _bundle_dirs(str(tmp_path)) == ["anomaly_000_slow_step"]
    verdict = json.load(open(
        tmp_path / "anomaly_000_slow_step" / "verdict.json"))
    assert verdict["verdict"]["kind"] == "slow_step"
    assert verdict["trigger_record"]["step"] == 99


def test_anomaly_retrace_burst(tmp_path):
    det = AnomalyDetector(out_dir=str(tmp_path), retrace_burst=3)
    for i in range(5):
        assert det.observe(step_rec(i, retrace=0)) == []
    for i, rc in enumerate((1, 2, 2)):
        det.observe(step_rec(5 + i, retrace=rc))
    v = det.observe(step_rec(9, retrace=3))       # +3 within the window
    assert [x.kind for x in v] == ["retrace_burst"]
    assert _bundle_dirs(str(tmp_path)) == ["anomaly_000_retrace_burst"]


def test_anomaly_drain_stall_and_memory(tmp_path):
    det = AnomalyDetector(out_dir=str(tmp_path), drain_stall_ms=100.0,
                          memory_frac=0.9, memory_bytes_limit=1000)
    for i in range(4):                        # baseline: healthy ~50ms drains
        assert det.observe(step_rec(i, drain=50.0, mem=500)) == []
    # above the floor but only 2.4x the median: a big healthy group, not a
    # stall (the device-bound steady state drains ~group time every call)
    assert det.observe(step_rec(4, drain=120.0)) == []
    v = det.observe(step_rec(5, drain=400.0))   # floor AND >3x median
    assert [x.kind for x in v] == ["drain_stall"]
    v = det.observe(step_rec(6, mem=950))
    assert [x.kind for x in v] == ["memory_high_water"]
    assert _bundle_dirs(str(tmp_path)) == [
        "anomaly_000_drain_stall", "anomaly_001_memory_high_water"]


def test_anomaly_nonfinite_and_ring_content(tmp_path):
    det = AnomalyDetector(out_dir=str(tmp_path), ring_size=4)
    for i in range(6):
        det.observe(step_rec(i))
    v = det.observe(step_rec(6, nonfinite=3, loss=None))
    assert [x.kind for x in v] == ["nonfinite"]
    bundle = tmp_path / "anomaly_000_nonfinite"
    ring = [json.loads(l) for l in
            open(bundle / "telemetry_ring.jsonl") if l.strip()]
    assert len(ring) == 4                          # bounded ring
    assert ring[-1]["step"] == 6                   # trigger record included
    # healthy records never trigger; nothing else fired
    assert _bundle_dirs(str(tmp_path)) == ["anomaly_000_nonfinite"]


def test_anomaly_staged_wall_excludes_stager_time(tmp_path):
    """Stager-staged records (stage_ms present) measure host_stack/shard
    on the STAGER thread (hidden cost); the slow-step wall must count
    only dispatch + drain_wait there — a hidden staging spike is not a
    slow step. A genuinely exposed drain stall still is."""
    det = AnomalyDetector(out_dir=str(tmp_path), warmup=8)

    def staged(step, shard=1.0, drain=10.0):
        r = step_rec(step, wall=2.0, drain=drain)   # dispatch_ms = 1.0
        r["shard_ms"], r["host_stack_ms"], r["stage_ms"] = shard, 1.0, 2.0
        return r

    for i in range(16):
        assert det.observe(staged(i)) == []
    assert det.observe(staged(99, shard=800.0)) == []   # hidden: no verdict
    assert _bundle_dirs(str(tmp_path)) == []
    stall = staged(100, drain=500.0)                    # exposed: real
    assert [v.kind for v in det.observe(stall)] == ["slow_step"]


def test_anomaly_plain_deferred_wall_counts_main_thread_shard(tmp_path):
    """The plain deferred-fetch loop (drain_wait_ms set, NO stage_ms)
    shards on the MAIN thread — a device_put spike there is critical-path
    and must still trigger slow_step."""
    det = AnomalyDetector(out_dir=str(tmp_path), warmup=8)
    for i in range(16):
        r = step_rec(i, wall=2.0, drain=1.0)
        assert det.observe(r) == []
    spike = step_rec(99, wall=2.0, drain=1.0)
    spike["shard_ms"] = 500.0                  # main-thread device_put stall
    assert [v.kind for v in det.observe(spike)] == ["slow_step"]


def test_anomaly_profiled_record_skipped(tmp_path):
    """An anomaly-armed profiler capture fences inside its dispatch window
    — that record must not feed slow_step (the flight recorder must not
    trigger the detector that armed it)."""
    det = AnomalyDetector(out_dir=str(tmp_path), warmup=8)
    for i in range(16):
        det.observe(step_rec(i))
    prof = step_rec(99, wall=5000.0)
    prof["profiled"] = True
    assert det.observe(prof) == []
    assert _bundle_dirs(str(tmp_path)) == []


def test_tracer_tail_zero(tmp_path):
    tracer = Tracer()
    with tracer.span("a"):
        pass
    assert [e for e in tracer.tail(0) if e["ph"] == "X"] == []
    assert len([e for e in tracer.tail(5) if e["ph"] == "X"]) == 1


def _x(name, ts, dur, tid=1, **kw):
    return dict({"ph": "X", "name": name, "pid": 1, "tid": tid, "ts": ts,
                 "dur": dur, "cat": "paddle_tpu"}, **kw)


def test_starved_by_span_gives_each_piece_to_the_innermost_span():
    events = [
        _x("step", 0, 100), _x("tick", 10, 60), _x("stage", 20, 10),
        _x("dispatch", 30, 10), _x("retire", 50, 10),
        _x("step", 120, 50), _x("stage", 130, 20),
        # one stretch: 15..35 (stage's 10 of it split with tick's own 5
        # and dispatch's 5); another: 55..140 (retire 5, tick 10, step
        # 30, nothing 20, step 10, stage 10)
        _x("starved", 15, 20, cat=trace_lib.RETROACTIVE),
        _x("starved", 55, 85, cat=trace_lib.RETROACTIVE),
        # retroactive and on another thread: never innermost
        _x("queue_wait", 0, 200, cat=trace_lib.RETROACTIVE),
        _x("other", 0, 200, tid=2),
        {"ph": "i", "name": "mark", "pid": 1, "tid": 1, "ts": 60}]
    by = trace_lib.starved_by_span(events)
    assert by == pytest.approx({"tick": 15e-6, "stage": 20e-6,
                                "dispatch": 5e-6, "retire": 5e-6,
                                "step": 40e-6, "": 20e-6})
    assert sum(by.values()) == pytest.approx(105e-6)
    # clipped to the window 30..135: dispatch 5, retire 5, tick 10, step
    # 30 + 10, nothing 20, stage 5
    clipped = trace_lib.starved_by_span(events, (30, 135))
    assert clipped == pytest.approx({"dispatch": 5e-6, "retire": 5e-6,
                                     "tick": 10e-6, "step": 40e-6,
                                     "": 20e-6, "stage": 5e-6})
    assert trace_lib.starved_by_span(
        [e for e in events if e["name"] != "starved"]) == {}


def test_complete_marks_its_spans_retroactive():
    tracer = Tracer()
    with tracer.span("code"):
        pass
    tracer.complete("after", tracer.now_us() - 5, tracer.now_us())
    cats = {e["name"]: e["cat"] for e in tracer.events() if e["ph"] == "X"}
    assert cats == {"code": "paddle_tpu", "after": trace_lib.RETROACTIVE}


def test_anomaly_profiler_arming(tmp_path):
    det = AnomalyDetector(out_dir=str(tmp_path), arm_profiler=True)
    assert det.take_profiler_request() is None
    det.observe(step_rec(0, nonfinite=1))
    req = det.take_profiler_request()
    assert req is not None and req.startswith(str(tmp_path))
    assert det.take_profiler_request() is None     # one-shot pop


def test_anomaly_injected_nan_run_leaves_one_bundle(tmp_path):
    """ISSUE 4 acceptance: an injected-NaN pipelined run leaves exactly ONE
    forensics bundle on disk with the nonfinite verdict, the telemetry
    ring, the config snapshot, and the trace tail."""
    out = str(tmp_path / "forensics")
    os.makedirs(out)
    tracer = Tracer()
    tel = Telemetry(sinks=[InMemorySink()])
    tr = make_trainer(K=2, M=1, telemetry=tel, tracer=tracer,
                      anomaly=AnomalyDetector(out_dir=out),
                      pipeline_depth=2)
    batches = make_batches(8)
    batches[4]["x"][0, 0] = np.nan
    tr.init(jax.random.PRNGKey(0), batches[0])
    tr.train(lambda: iter(batches), num_passes=1, log_period=0)
    assert _bundle_dirs(out) == ["anomaly_000_nonfinite"]
    bundle = os.path.join(out, "anomaly_000_nonfinite")
    assert sorted(os.listdir(bundle)) == [
        "snapshot.json", "telemetry_ring.jsonl", "trace_tail.json",
        "verdict.json"]
    snap = json.load(open(os.path.join(bundle, "snapshot.json")))
    assert snap["steps_per_call"] == 2 and snap["pipeline_depth"] == 2
    assert snap["model"] == "MnistMLP" and "mesh_axes" in snap
    tail = json.load(open(os.path.join(bundle, "trace_tail.json")))
    assert any(e.get("ph") == "X" for e in tail["traceEvents"])


def test_anomaly_bundle_written_even_when_nan_check_raises(tmp_path):
    """Fused mode + nan_check=True: the FloatingPointError trap unwinds
    the replay, but the flight recorder must still have written its
    nonfinite bundle first — a poisoned run is exactly when the
    forensics matter (the plain loop observes before raising; fused must
    match)."""
    out = str(tmp_path / "forensics")
    os.makedirs(out)
    tr = make_trainer(K=2, M=2, telemetry=Telemetry(sinks=[InMemorySink()]),
                      anomaly=AnomalyDetector(out_dir=out), nan_check=True)
    batches = make_batches(8)
    batches[2]["x"][0, 0] = np.nan
    tr.init(jax.random.PRNGKey(0), batches[0])
    with pytest.raises(FloatingPointError, match="non-finite loss"):
        tr.train(lambda: iter(batches), num_passes=1, log_period=0)
    assert _bundle_dirs(out) == ["anomaly_000_nonfinite"]


def test_nan_check_error_not_masked_by_raising_handler(tmp_path):
    """A handler that raises on TelemetryRecord during the nan_check
    unwind must not mask the original FloatingPointError (whose message
    carries the nonfinite-leaves postmortem)."""
    out = str(tmp_path / "forensics")
    os.makedirs(out)
    tr = make_trainer(K=2, M=2, telemetry=Telemetry(sinks=[InMemorySink()]),
                      anomaly=AnomalyDetector(out_dir=out), nan_check=True)
    batches = make_batches(8)
    batches[2]["x"][0, 0] = np.nan
    tr.init(jax.random.PRNGKey(0), batches[0])

    def bad_handler(e):
        if type(e).__name__ == "TelemetryRecord":
            raise RuntimeError("handler bug")

    with pytest.raises(FloatingPointError, match="non-finite loss"):
        tr.train(lambda: iter(batches), num_passes=1, log_period=0,
                 event_handler=bad_handler)
    # the healthy path still propagates handler bugs (no silent eating)
    tr2 = make_trainer(K=2, M=2, telemetry=Telemetry(sinks=[InMemorySink()]))
    clean = make_batches(4)
    tr2.init(jax.random.PRNGKey(0), clean[0])
    with pytest.raises(RuntimeError, match="handler bug"):
        tr2.train(lambda: iter(clean), num_passes=1, log_period=0,
                  event_handler=bad_handler)


def test_plain_loop_profiler_arming(tmp_path, monkeypatch):
    """arm_profiler must capture in the plain (K=1, M=1) loop too, not
    only the fused path — every dispatch path polls the armed request."""
    import contextlib
    from paddle_tpu.obs import trace as trace_mod
    captured = []

    @contextlib.contextmanager
    def fake_profile(log_dir):
        captured.append(log_dir)
        yield

    monkeypatch.setattr(trace_mod, "jax_profile", fake_profile)
    tel = Telemetry(sinks=[InMemorySink()])
    tr = make_trainer(K=1, M=1, telemetry=tel,
                      anomaly=AnomalyDetector(out_dir=str(tmp_path),
                                              arm_profiler=True))
    batches = make_batches(6)
    batches[2]["x"][0, 0] = np.nan          # trigger at record 2
    tr.init(jax.random.PRNGKey(0), batches[0])
    tr.train(lambda: iter(batches), num_passes=1, log_period=0)
    assert len(captured) == 1               # next dispatch was captured
    assert captured[0].endswith("jax_profile")
    recs = tel.sinks[0].by_kind("step")
    assert [r["profiled"] for r in recs].count(True) == 1


def test_no_anomaly_attached_no_bundles(tmp_path):
    """Off by default: the same poisoned run without a detector writes
    nothing anywhere."""
    before = set(os.listdir(str(tmp_path)))
    tr = make_trainer(K=2, M=1, telemetry=Telemetry(sinks=[InMemorySink()]))
    batches = make_batches(4)
    batches[2]["x"][0, 0] = np.nan
    tr.init(jax.random.PRNGKey(0), batches[0])
    tr.train(lambda: iter(batches), num_passes=1, log_period=0)
    assert set(os.listdir(str(tmp_path))) == before


def test_anomaly_detector_crash_never_kills_training(tmp_path, caplog):
    class Boom(AnomalyDetector):
        def observe(self, rec):
            raise RuntimeError("detector died")

    tr = make_trainer(telemetry=Telemetry(sinks=[InMemorySink()]),
                      anomaly=Boom(out_dir=str(tmp_path)))
    batches = make_batches(2 * 2 * 2)
    tr.init(jax.random.PRNGKey(0), batches[0])
    with caplog.at_level(logging.ERROR, logger="paddle_tpu.trainer"):
        tr.train(lambda: iter(batches), num_passes=1, log_period=0)
    assert "anomaly detector failed" in caplog.text


def test_anomaly_reset_rearms(tmp_path):
    det = AnomalyDetector(out_dir=str(tmp_path))
    det.observe(step_rec(0, nonfinite=1))
    det.observe(step_rec(1, nonfinite=1))
    assert len(det.bundles) == 1
    det.reset()
    det.observe(step_rec(2, nonfinite=1))
    assert len(det.bundles) == 2


def test_anomaly_rearm_true_fires_every_onset(tmp_path):
    """ISSUE 6 satellite: rearm=True makes every trigger of the same kind
    dump its own bundle — no reset() needed between onsets — and the
    bundle sequence numbers stay distinct."""
    det = AnomalyDetector(out_dir=str(tmp_path), rearm=True)
    det.observe(step_rec(0, nonfinite=1))
    det.observe(step_rec(1, nonfinite=1))
    det.observe(step_rec(2, nonfinite=1))
    assert len(det.bundles) == 3
    assert len(set(det.bundles)) == 3
    assert [v.kind for v in det.verdicts] == ["nonfinite"] * 3
    # the default (rearm=False) under the identical stream fires once
    det2 = AnomalyDetector(out_dir=str(tmp_path / "oneshot"))
    for i in range(3):
        det2.observe(step_rec(i, nonfinite=1))
    assert len(det2.bundles) == 1


def test_anomaly_reset_clears_one_shot_and_rolling_state(tmp_path):
    """ISSUE 6 satellite: reset() re-arms every kind AND clears the
    rolling windows + any pending armed-profiler request; bundles on
    disk stay."""
    det = AnomalyDetector(out_dir=str(tmp_path), arm_profiler=True)
    for i in range(6):
        det.observe(step_rec(i, wall=10.0))
    assert len(det._walls) == 6
    det.observe(step_rec(6, nonfinite=1))
    assert det._fired == {"nonfinite"}
    assert det._profiler_request is not None       # armed by the trigger
    bundles_before = list(det.bundles)
    det.reset()
    assert det._fired == set()
    assert det.take_profiler_request() is None     # request cleared
    assert len(det._walls) == 0 and len(det._ring) == 0
    assert det.bundles == bundles_before           # evidence persists
    det.observe(step_rec(7, nonfinite=1))          # fires again post-reset
    assert len(det.bundles) == len(bundles_before) + 1


# ---------------------------------------------------------------------------
# stager-leak close() contract (ISSUE 4 satellite)
# ---------------------------------------------------------------------------

def test_group_stager_close_flags_stuck_thread(caplog):
    release = threading.Event()

    def wedge(work):
        release.wait(20.0)                        # simulates a wedged put
        return work

    stager = GroupStager(wedge, join_timeout=0.3)
    stager.submit(("work", 0, False))
    time.sleep(0.05)                              # let the worker pick it up
    with caplog.at_level(logging.WARNING,
                         logger="paddle_tpu.host_pipeline"):
        leaked = stager.close()
    assert leaked is True
    assert "did not exit" in caplog.text
    assert "paddle_tpu.host_pipeline.stager" in caplog.text
    release.set()                                 # let the thread die (and
    stager._thread.join(timeout=5.0)              # don't leak it into later
    assert not stager._thread.is_alive()          # tests' thread scans)

    clean = GroupStager(lambda w: w, join_timeout=5.0)
    assert clean.close() is False


def test_stager_leak_surfaces_in_telemetry_summary(monkeypatch):
    tel = Telemetry(sinks=[InMemorySink()])
    tr = make_trainer(telemetry=tel, pipeline_depth=2)
    batches = make_batches(2 * 2 * 2)
    tr.init(jax.random.PRNGKey(0), batches[0])
    orig_close, stagers = GroupStager.close, []

    def fake_close(self):
        stagers.append(self)
        return True                               # report "missed deadline"

    monkeypatch.setattr(GroupStager, "close", fake_close)
    try:
        tr.train(lambda: iter(batches), num_passes=1, log_period=0)
        assert tel.summary()["stager_leaked"] is True
        # and the close-time summary record carries the flag into the JSONL
        tel.close()
        summaries = tel.sinks[0].by_kind("summary")
        assert len(summaries) == 1 and summaries[0]["stager_leaked"] is True
    finally:
        for s in stagers:                         # actually stop the thread
            orig_close(s)                         # (don't leak it into
    assert all(not s._thread.is_alive() for s in stagers)  # later tests)


# ---------------------------------------------------------------------------
# data.buffered fill-thread spans (ISSUE 4 satellite)
# ---------------------------------------------------------------------------

def test_buffered_fill_thread_spans():
    tracer = Tracer()

    def src():
        yield from range(5)

    out = list(data.buffered(src, 2, tracer=tracer)())
    assert out == [0, 1, 2, 3, 4]
    fills = [e for e in tracer.events()
             if e["ph"] == "X" and e["name"] == "data.fill"]
    assert len(fills) >= 5                        # one span per item (+ end)
    assert {e["tid"] for e in fills} != {threading.get_ident()}
    names = {e["args"]["name"] for e in tracer.events()
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert "paddle_tpu.data.buffered.fill" in names
