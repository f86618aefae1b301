"""Telemetry subsystem tests (ISSUE 2): JSONL sink schema round-trip,
retrace counter keyed by step fingerprint, health monitors flagging an
injected NaN, and the telemetry-off zero-overhead invariant (no extra
dispatches, no fences, no health outputs, bit-identical params).

ISSUE 4 satellites ride here too: thread-safe sink emit, the final
`summary` record at Telemetry.close(), and the PEAK_FLOPS v6e entry +
one-shot unknown-TPU-kind log (the tracer/anomaly layer itself is
tests/test_trace.py, including the tracing-off zero-overhead pin)."""

import json
import logging
import os

import numpy as np
import jax
import pytest

from paddle_tpu import optim
from paddle_tpu.models import MnistMLP
from paddle_tpu.nn import costs
from paddle_tpu.train import Trainer, events as ev
from paddle_tpu.obs import (HEALTH_KEYS, InMemorySink, JsonlSink,
                            LoggingSink, Telemetry)
from paddle_tpu.utils.stats import StatSet

BS, DIM = 16, 12


def make_batches(n, bs=BS, dim=DIM, seed=0):
    rng = np.random.RandomState(seed)
    return [{"x": rng.normal(size=(bs, dim)).astype(np.float32),
             "label": rng.randint(0, 4, size=bs).astype(np.int32)}
            for _ in range(n)]


def make_trainer(K=2, M=2, telemetry=None):
    return Trainer(
        model=MnistMLP(num_classes=4, hidden=(8,)),
        loss_fn=lambda out, b: costs.softmax_cross_entropy(out, b["label"]),
        optimizer=optim.adam(1e-3),
        steps_per_call=K, grad_accum=M, telemetry=telemetry)


def run_fused(trainer, batches, log_period=0):
    trainer.init(jax.random.PRNGKey(0), batches[0])
    trainer.train(lambda: iter(batches), num_passes=1,
                  log_period=log_period)
    return trainer


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

def test_jsonl_sink_schema_roundtrip(tmp_path):
    """Records written through JsonlSink parse back identical to what the
    in-memory sink saw — the schema survives the serialization."""
    path = str(tmp_path / "tel.jsonl")
    mem = InMemorySink()
    tel = Telemetry(sinks=[mem, JsonlSink(path)])
    batches = make_batches(2 * 2 * 2 + 1)     # +1 ragged tail
    run_fused(make_trainer(telemetry=tel), batches)
    tel.close()
    from_disk = JsonlSink.read(path)
    assert from_disk == mem.records
    steps = [r for r in from_disk if r["kind"] == "step"]
    compiles = [r for r in from_disk if r["kind"] == "compile"]
    assert steps and compiles
    for r in steps:
        for key in ("ts", "pass", "step", "k_steps", "m", "loss",
                    "host_stack_ms", "shard_ms", "dispatch_ms", "device_ms",
                    "replay_ms", "stage_ms", "drain_wait_ms", "overlap_frac",
                    "compile_count", "retrace_count", "bytes_in_use",
                    "peak_bytes", "fenced") + HEALTH_KEYS:
            assert key in r, f"missing {key}"
        assert r["fenced"] is True and r["device_ms"] is not None
    for r in compiles:
        assert r["wall_s"] > 0
        assert "hlo_flops" in r


def test_sink_emit_thread_safe(tmp_path):
    """ISSUE 4 satellite: tracer spans finish on the stager thread, so
    sinks are written from two threads — concurrent emits must all land
    (InMemorySink) and JSONL lines must never interleave (JsonlSink)."""
    import threading
    path = str(tmp_path / "conc.jsonl")
    mem, jsonl = InMemorySink(), JsonlSink(path)
    n_threads, per_thread = 8, 200

    def worker(tid):
        for i in range(per_thread):
            rec = {"kind": "step", "tid": tid, "i": i,
                   "pad": "x" * 200}            # long enough to tear
            mem.emit(rec)
            jsonl.emit(rec)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    jsonl.close()
    assert len(mem.records) == n_threads * per_thread
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert len(lines) == n_threads * per_thread   # every line parses whole
    for tid in range(n_threads):
        assert [r["i"] for r in lines if r["tid"] == tid] == \
            list(range(per_thread))


def test_jsonl_sink_rotation_bounds_the_file(tmp_path):
    """ISSUE 6 satellite: JsonlSink(max_bytes=) rotates to <path>.1 and
    keeps writing — no record lost, no record split, both files bounded,
    and the telemetry JSONL of a long run stops growing unboundedly."""
    path = str(tmp_path / "tel.jsonl")
    rec = {"kind": "step", "i": 0, "pad": "x" * 80}
    line_len = len(json.dumps(rec)) + 1
    sink = JsonlSink(path, max_bytes=4 * line_len)
    n = 11
    for i in range(n):
        sink.emit({**rec, "i": i})
    sink.close()
    assert sink.rotations >= 1
    assert os.path.exists(path + ".1")
    main = JsonlSink.read(path)
    rotated = JsonlSink.read(path + ".1")
    # the retained window is the most recent records, contiguous across
    # .1 -> live with no record split, duplicated, or reordered (older
    # rotations are dropped by design — that IS the disk bound)
    window = [r["i"] for r in rotated + main]
    assert window == list(range(n - len(window), n))
    assert len(rotated) >= 1 and main[-1]["i"] == n - 1
    assert os.path.getsize(path) <= 4 * line_len
    assert os.path.getsize(path + ".1") <= 4 * line_len
    # a second sink on the same path resumes the byte count (append mode)
    sink2 = JsonlSink(path, max_bytes=4 * line_len)
    for i in range(n, n + 6):
        sink2.emit({**rec, "i": i})
    sink2.close()
    assert os.path.getsize(path) <= 4 * line_len
    assert JsonlSink.read(path)[-1]["i"] == n + 5


def test_jsonl_sink_oversized_record_still_lands(tmp_path):
    path = str(tmp_path / "big.jsonl")
    sink = JsonlSink(path, max_bytes=16)
    sink.emit({"kind": "step", "pad": "y" * 100})   # one line > max_bytes
    sink.close()
    assert len(JsonlSink.read(path)) == 1


def test_report_cli_summarizes_run(tmp_path):
    """ISSUE 6 satellite: `python -m paddle_tpu.obs.report run.jsonl`
    prints throughput / MFU / retraces / overlap / anomalies, preferring
    the final summary record, and --json round-trips."""
    from paddle_tpu.obs import report as report_cli
    path = str(tmp_path / "run.jsonl")
    tel = Telemetry(sinks=[JsonlSink(path)], tokens_per_step=128,
                    flops_per_step=1e9, peak_flops=1e12)
    run_fused(make_trainer(telemetry=tel), make_batches(2 * 2 * 2))
    # anomaly + attribution records ride the same stream
    tel.emit_event({"kind": "anomaly", "anomaly_kind": "slow_step",
                    "step": 3, "detail": "test"})
    tel.close()
    records = report_cli.load_records(path)
    s = report_cli.summarize(records)
    assert s["from_summary_record"] is True
    assert s["steps"] > 0 and s["optimizer_steps"] >= s["steps"]
    assert s["compiles"] >= 1
    assert s["anomalies"] == 1 and s["anomaly_kinds"] == ["slow_step"]
    assert s["est_mfu_pct"] is not None
    assert s["mean_dispatch_ms"] is not None
    table = report_cli.format_summary(s)
    assert "est MFU" in table and "anomalies" in table
    # CLI entry: table and --json modes both exit 0
    assert report_cli.main([path]) == 0
    assert report_cli.main([path, "--json"]) == 0
    assert report_cli.main([str(tmp_path / "missing.jsonl")]) == 2


def test_report_cli_without_summary_record(tmp_path):
    """A crashed run (no close, no summary record) still reports from
    the step records."""
    from paddle_tpu.obs import report as report_cli
    path = str(tmp_path / "crash.jsonl")
    tel = Telemetry(sinks=[JsonlSink(path)])
    run_fused(make_trainer(telemetry=tel), make_batches(2 * 2 * 2))
    for s in tel.sinks:                        # flush without summary
        s.close()
    s = report_cli.summarize(report_cli.load_records(path))
    assert s["from_summary_record"] is False
    assert s["steps"] > 0 and s["last_loss"] is not None


def test_anomaly_verdicts_echoed_into_telemetry_stream(tmp_path):
    """The Trainer echoes each detector verdict as a kind="anomaly"
    record so the JSONL is self-contained (the report CLI counts them
    without reading bundle directories)."""
    from paddle_tpu.models import MnistMLP
    from paddle_tpu.nn import costs as nn_costs
    from paddle_tpu.obs import AnomalyDetector
    from paddle_tpu import optim as optim_lib
    mem = InMemorySink()
    tr = Trainer(
        model=MnistMLP(num_classes=4, hidden=(8,)),
        loss_fn=lambda out, b: nn_costs.softmax_cross_entropy(
            out, b["label"]),
        optimizer=optim_lib.adam(1e-3), steps_per_call=2, grad_accum=1,
        telemetry=Telemetry(sinks=[mem]),
        anomaly=AnomalyDetector(out_dir=str(tmp_path)))
    batches = make_batches(4)
    batches[2]["x"][0, 0] = np.nan
    tr.init(jax.random.PRNGKey(0), batches[0])
    tr.train(lambda: iter(batches), num_passes=1, log_period=0)
    anomalies = mem.by_kind("anomaly")
    assert len(anomalies) == 1
    assert anomalies[0]["anomaly_kind"] == "nonfinite"
    assert anomalies[0]["bundle"]


def test_telemetry_close_emits_summary_record(tmp_path):
    """ISSUE 4 satellite: close() writes one final `summary` record so the
    JSONL is self-contained; a second close neither re-emits nor fails."""
    path = str(tmp_path / "tel.jsonl")
    mem = InMemorySink()
    tel = Telemetry(sinks=[mem, JsonlSink(path)])
    run_fused(make_trainer(telemetry=tel), make_batches(2 * 2 * 2))
    tel.close()
    tel.close()                                   # idempotent
    on_disk = JsonlSink.read(path)
    summaries = [r for r in on_disk if r["kind"] == "summary"]
    assert len(summaries) == 1 and on_disk[-1]["kind"] == "summary"
    s = summaries[0]
    assert s["steps_emitted"] == 2 and s["compile_count"] >= 1
    assert s["stager_leaked"] is False
    assert "mean_dispatch_ms" in s                # the aggregate view
    assert mem.by_kind("summary") == summaries    # every sink got it


def test_profiled_records_excluded_from_rates_and_means():
    """A profiled call (anomaly-armed jax.profiler capture) fences inside
    its dispatch window — emit_step must not derive a rate from it and
    summary() must not average its breakdown."""
    mem = InMemorySink()
    tel = Telemetry(sinks=[mem], tokens_per_step=100, peak_flops=1e12,
                    flops_per_step=1e9)
    tel.emit_step({"k_steps": 1, "dispatch_ms": 1.0, "device_ms": 1.0})
    tel.emit_step({"k_steps": 1, "dispatch_ms": 1.0, "device_ms": 1.0})
    rec = tel.emit_step({"k_steps": 1, "dispatch_ms": 5000.0,
                         "profiled": True})
    assert rec.get("tokens_per_sec") is None     # no rate from a fenced
    assert rec.get("est_mfu_pct") is None        # dispatch window
    s = tel.summary()
    assert s["mean_dispatch_ms"] == 1.0          # profiled not averaged
    # unprofiled records carry profiled=False in the fixed schema
    assert mem.by_kind("step")[0]["profiled"] is False


def test_peak_flops_v6e_and_unknown_kind_one_shot_log(caplog):
    """ISSUE 4 satellite: TPU v6e is in the MFU table, and an unknown TPU
    kind logs a one-shot WARNING instead of silently returning None."""
    from paddle_tpu.obs import PEAK_FLOPS, device_peak_flops
    from paddle_tpu.obs import telemetry as tel_mod
    assert PEAK_FLOPS["TPU v6 lite"] == PEAK_FLOPS["TPU v6e"] == 918e12

    class FakeDev:
        device_kind = "TPU v99 hyper"

    tel_mod._unknown_kinds_logged.discard("TPU v99 hyper")
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.telemetry"):
        assert device_peak_flops(FakeDev()) is None
        assert device_peak_flops(FakeDev()) is None   # second call silent
    hits = [r for r in caplog.records if "TPU v99 hyper" in r.getMessage()]
    assert len(hits) == 1
    assert "PEAK_FLOPS" in hits[0].getMessage()

    class Known:
        device_kind = "TPU v6e"

    assert device_peak_flops(Known()) == 918e12


def test_logging_sink_emits(caplog):
    sink = LoggingSink(level=logging.INFO)
    with caplog.at_level(logging.INFO, logger="paddle_tpu.telemetry"):
        sink.emit({"kind": "step", "step": 3, "dispatch_ms": 1.25,
                   "grad_norm": 0.5})
        sink.emit({"kind": "compile", "compile_count": 1, "wall_s": 0.1,
                   "hlo_flops": 100.0, "fingerprint": "fp"})
    text = caplog.text
    assert "step=3" in text and "compile" in text


def test_broken_sink_never_kills_training():
    class Boom:
        def emit(self, record):
            raise RuntimeError("sink died")

    tel = Telemetry(sinks=[Boom(), InMemorySink()])
    batches = make_batches(2 * 2 * 2)
    run_fused(make_trainer(telemetry=tel), batches)   # must not raise
    assert tel.compile_count >= 1


# ---------------------------------------------------------------------------
# retrace / compile tracking
# ---------------------------------------------------------------------------

def test_retrace_counter_increments_once_per_fingerprint():
    tel = Telemetry(sinks=[InMemorySink()])
    assert tel.observe_fingerprint(("a",)) is True
    assert tel.observe_fingerprint(("a",)) is False
    assert tel.observe_fingerprint(("a",)) is False
    assert (tel.compile_count, tel.retrace_count) == (1, 0)
    assert tel.observe_fingerprint(("b",)) is True
    assert tel.observe_fingerprint(("b",)) is False
    assert tel.observe_fingerprint(("a",)) is False
    assert (tel.compile_count, tel.retrace_count) == (2, 1)


def test_trainer_retrace_tracking_ragged_tail():
    """K*M-uniform groups compile once; the ragged pass tail is a second
    fingerprint (ONE retrace), and a second pass over the same stream adds
    none — the counter keys on fingerprints, not dispatches."""
    mem = InMemorySink()
    tel = Telemetry(sinks=[mem])
    tr = make_trainer(K=2, M=2, telemetry=tel)
    batches = make_batches(2 * 2 * 2 + 1)      # two full groups + tail 1
    tr.init(jax.random.PRNGKey(0), batches[0])
    tr.train(lambda: iter(batches), num_passes=1, log_period=0)
    assert tel.compile_count == 2              # full-group + tail shapes
    assert tel.retrace_count == 1
    tr.train(lambda: iter(batches), num_passes=1, log_period=0)
    assert tel.compile_count == 2              # nothing new the 2nd pass
    assert tel.retrace_count == 1
    assert len(mem.by_kind("compile")) == 2
    # compile records carry wall time and the HLO FLOPs estimate
    for r in mem.by_kind("compile"):
        assert r["wall_s"] > 0


def test_retrace_warning_one_shot(caplog):
    """ISSUE 3 satellite: crossing the distinct-fingerprint threshold logs
    ONE warning pointing at drop_last/padding — and only once."""
    tel = Telemetry(sinks=[InMemorySink()], retrace_warn_threshold=2)
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.telemetry"):
        tel.observe_fingerprint(("a",))       # initial compile
        tel.observe_fingerprint(("b",))       # retrace 1: below threshold
        assert "drop_last" not in caplog.text
        tel.observe_fingerprint(("c",))       # retrace 2: fires
        tel.observe_fingerprint(("d",))       # retrace 3: already warned
    warnings = [r for r in caplog.records
                if "drop_last" in r.getMessage()]
    assert len(warnings) == 1
    assert "recompile" in warnings[0].getMessage()


def test_mfu_and_tokens_per_sec_accounting():
    """With an explicit peak-FLOPs denominator (the CPU table has none)
    emit_step derives est_mfu_pct from the analytic flops_per_step."""
    mem = InMemorySink()
    tel = Telemetry(sinks=[mem], flops_per_step=1e9, tokens_per_step=1024,
                    peak_flops=1e12)
    tel.emit_step({"k_steps": 2, "dispatch_ms": 1.0, "device_ms": 9.0})
    rec = mem.records[-1]
    # per-step time = 10ms/2 = 5ms -> 1e9 / 5e-3 / 1e12 = 20% MFU
    assert rec["est_mfu_pct"] == pytest.approx(20.0)
    assert rec["tokens_per_sec"] == pytest.approx(1024 / 5e-3)


# ---------------------------------------------------------------------------
# health monitors
# ---------------------------------------------------------------------------

def test_health_monitors_flag_injected_nan(tmp_path):
    path = str(tmp_path / "nan.jsonl")
    mem = InMemorySink()
    tel = Telemetry(sinks=[mem, JsonlSink(path)])
    tr = make_trainer(K=2, M=1, telemetry=tel)
    batches = make_batches(4)
    batches[2]["x"][0, 0] = np.nan            # poison one microbatch
    tr.init(jax.random.PRNGKey(0), batches[0])
    tr.train(lambda: iter(batches), num_passes=1, log_period=0)
    tel.close()
    steps = mem.by_kind("step")
    assert len(steps) == 2                    # 4 batches / K=2 per call
    assert steps[0]["nonfinite_count"] == 0
    assert steps[0]["grad_norm"] > 0
    # the poisoned call: the sentinel trips; the NaN norms/loss are
    # sanitized to None so the JSONL stays strict-RFC-8259 parseable
    assert steps[1]["nonfinite_count"] > 0
    assert steps[1]["grad_norm"] is None
    assert steps[1]["loss"] is None

    def no_nan_literals(name):
        raise AssertionError(f"bare {name} literal in JSONL")

    with open(path) as f:
        for line in f:                        # strict parse: NaN/Inf reject
            json.loads(line, parse_constant=no_nan_literals)


def test_healthy_run_monitor_values():
    mem = InMemorySink()
    tel = Telemetry(sinks=[mem])
    run_fused(make_trainer(telemetry=tel), make_batches(8))
    for r in mem.by_kind("step"):
        assert r["nonfinite_count"] == 0
        assert r["grad_norm"] > 0
        assert r["param_norm"] > 0
        assert 0 < r["update_ratio"] < 1


# ---------------------------------------------------------------------------
# the telemetry-off zero-overhead invariant
# ---------------------------------------------------------------------------

def _count_dispatches(tr, batches, monkeypatch_fence=None):
    """Run one pass counting fused-step dispatches (and optionally
    block_until_ready fences)."""
    tr.init(jax.random.PRNGKey(0), batches[0])
    calls = {"n": 0}
    orig_dispatch = tr._dispatch_fused

    def counting_dispatch(stacked, rng, **kw):
        calls["n"] += 1
        return orig_dispatch(stacked, rng, **kw)

    tr._dispatch_fused = counting_dispatch
    tr.train(lambda: iter(batches), num_passes=1, log_period=0)
    return calls["n"]


def test_telemetry_off_zero_dispatch_and_fence_overhead(monkeypatch):
    """With telemetry off the fused loop adds NOTHING: same dispatch count
    as the telemetered run, zero block_until_ready fences, no health
    outputs in the traced step, and bit-identical trained params."""
    batches = make_batches(2 * 2 * 3)
    fences = {"n": 0}
    orig_fence = jax.block_until_ready

    def counting_fence(x):
        fences["n"] += 1
        return orig_fence(x)

    monkeypatch.setattr(jax, "block_until_ready", counting_fence)

    tr_off = make_trainer(telemetry=None)
    n_off = _count_dispatches(tr_off, batches)
    fences_off = fences["n"]
    assert fences_off == 0                    # telemetry owns the fence
    # no health outputs traced into the step: 6-tuple contract
    out = tr_off._fused_step
    assert out is not None
    assert not tr_off._health_on()

    tel = Telemetry(sinks=[InMemorySink()])
    tr_on = make_trainer(telemetry=tel)
    n_on = _count_dispatches(tr_on, batches)
    assert n_on == n_off                      # telemetry adds no dispatch
    assert fences["n"] > 0                    # ...but does fence when on
    assert tr_on._health_on()

    # telemetry (health outputs included) must not perturb the math
    for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(
                tr_off.train_state.params)),
            jax.tree_util.tree_leaves(jax.device_get(
                tr_on.train_state.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_telemetry_event_fires_only_when_attached():
    batches = make_batches(2 * 2 * 2)
    seen = {"on": 0, "off": 0}

    tr = make_trainer(telemetry=None)
    tr.init(jax.random.PRNGKey(0), batches[0])
    tr.train(lambda: iter(batches), num_passes=1, log_period=0,
             event_handler=lambda e: seen.__setitem__(
                 "off", seen["off"] + isinstance(e, ev.TelemetryRecord)))
    assert seen["off"] == 0

    tr = make_trainer(telemetry=Telemetry(sinks=[InMemorySink()]))
    tr.init(jax.random.PRNGKey(0), batches[0])
    tr.train(lambda: iter(batches), num_passes=1, log_period=0,
             event_handler=lambda e: seen.__setitem__(
                 "on", seen["on"] + isinstance(e, ev.TelemetryRecord)))
    assert seen["on"] == 2                    # one per fused call


def test_plain_loop_telemetry_records():
    """steps_per_call=1, grad_accum=1 (the unfused loop) also records a
    per-step breakdown and retraces."""
    mem = InMemorySink()
    tr = make_trainer(K=1, M=1, telemetry=Telemetry(sinks=[mem]))
    batches = make_batches(3)
    tr.init(jax.random.PRNGKey(0), batches[0])
    tr.train(lambda: iter(batches), num_passes=1, log_period=0)
    steps = mem.by_kind("step")
    assert len(steps) == 3
    for r in steps:
        assert r["k_steps"] == 1
        assert r["shard_ms"] is not None and r["dispatch_ms"] is not None
        assert r["device_ms"] is not None and r["fenced"] is True
        assert r["grad_norm"] > 0
    assert len(mem.by_kind("compile")) == 1


# ---------------------------------------------------------------------------
# StatSet satellite
# ---------------------------------------------------------------------------

def test_statset_report_topn_and_to_dict():
    s = StatSet("t")
    s.add("slow", 2.0)
    s.add("fast", 0.1)
    s.add("mid", 0.5)
    rep = s.report(top_n=2)
    lines = rep.splitlines()
    assert "slow" in lines[1]                 # sorted by total desc
    assert "mid" in lines[2]
    assert "fast" not in rep
    assert "1 more" in lines[-1]
    d = s.to_dict()
    assert d["name"] == "t"
    assert d["stats"]["slow"]["count"] == 1
    json.dumps(d)                             # JSON-ready
    s.reset()
    assert s.summary() == {}


# ---------------------------------------------------------------------------
# named_scope satellite: profiler traces show model structure
# ---------------------------------------------------------------------------

def test_transformer_named_scopes_reach_compiled_hlo():
    from paddle_tpu.models import TransformerLM
    import jax.numpy as jnp

    model = TransformerLM(vocab=32, dim=16, num_layers=2, num_heads=2,
                          ffn_hidden=32, max_len=8)
    ids = jnp.zeros((2, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    compiled = jax.jit(
        lambda p, i: model.apply(p, i)).lower(variables, ids).compile()
    txt = compiled.as_text()
    for scope in ("embed", "block0", "block1", "attn", "ffn", "head",
                  "qkv_proj", "sdpa_xla", "out_proj"):
        assert scope in txt, f"named_scope {scope!r} missing from HLO"
