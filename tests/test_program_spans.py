"""The program's own spans (ISSUE 25): live whenever a tracer is attached
OR a ``jax.profiler`` session is active, and then on the profiler's clock
as ``paddle_tpu:<name>`` annotations. Every span of the trainer's plain
loop, the scheduler and the engine, their nesting and facts; the compile
log; ``self_times``; one clock pair per region of the trainer; the
engine's ``starved`` stretches (PR 37); and the per-layer readers of
``benchmarks/layer_metrics/`` that read them, on the benchmark's toy
cells."""

import glob
import importlib.util
import os
import re
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import optim
from paddle_tpu.models import MnistMLP, TransformerLM
from paddle_tpu.nn import costs
from paddle_tpu.obs import InMemorySink, Telemetry, Tracer, xla_cache
from paddle_tpu.obs import trace as trace_lib
from paddle_tpu.obs.trace import live, self_times, session_tracer, tspan
from paddle_tpu.serve import ContinuousBatchingScheduler, DecodeEngine
from paddle_tpu.train import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, W = 64, 24

TRAINER_SPANS = {"trainer_init", "train_step", "reader_wait", "device_put",
                 "dispatch", "loss_fetch", "events"}
SCHEDULER_SPANS = {"sched_step", "expire", "admit", "queue_wait",
                   "prefill_chunk", "decode_tick", "finish"}
ENGINE_SPANS = {"engine_init", "engine_prepare", "engine_warmup",
                "begin_prefill",
                "prefill_dispatch", "prefill_drain", "engine_tick",
                "tick_stage", "tick_dispatch", "tick_drain", "tick_retire",
                "tick_fetch", "prefill_fetch", "prefill_retire"}
# child -> the span it must lie inside, on the same thread
PARENT = {"device_put": "train_step", "dispatch": "train_step",
          "loss_fetch": "train_step", "events": "train_step",
          "expire": "sched_step", "admit": "sched_step",
          "decode_tick": "sched_step", "begin_prefill": "admit",
          "prefill_chunk": "admit", "prefill_dispatch": "prefill_chunk",
          "prefill_drain": "prefill_chunk", "engine_tick": "decode_tick",
          "tick_stage": "engine_tick", "tick_dispatch": "engine_tick",
          "tick_drain": "engine_tick", "tick_retire": "engine_tick",
          "engine_prepare": "engine_init", "tick_fetch": "tick_drain",
          "prefill_fetch": "prefill_drain",
          "prefill_retire": "prefill_drain"}


def make_batches(n, bs=16, dim=12, seed=0):
    rng = np.random.RandomState(seed)
    return [{"x": rng.normal(size=(bs, dim)).astype(np.float32),
             "label": rng.randint(0, 4, size=bs).astype(np.int32)}
            for _ in range(n)]


def toy_train(n=4, K=1, M=1, **kw):
    trainer = Trainer(
        model=MnistMLP(num_classes=4, hidden=(8,)),
        loss_fn=lambda out, b: costs.softmax_cross_entropy(out, b["label"]),
        optimizer=optim.adam(1e-3), steps_per_call=K, grad_accum=M, **kw)
    batches = make_batches(n * K * M)
    trainer.init(jax.random.PRNGKey(0), batches[0])
    trainer.train(lambda: iter(batches), num_passes=1, log_period=0)
    return trainer


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab=V, dim=32, num_layers=2, num_heads=4,
                          ffn_hidden=64, max_len=W)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, W), jnp.int32))


def toy_serve(lm, n_requests=3, **kw):
    """Two slots and three requests: the third waits in the queue."""
    model, variables = lm
    engine = DecodeEngine(model, variables, max_slots=2, block_size=4)
    engine.warmup()
    sched = ContinuousBatchingScheduler(engine, **kw)
    rng = np.random.RandomState(1)
    for i in range(n_requests):
        sched.submit(list(rng.randint(1, V, size=3 + i)), 3 + i)
    return sched, sched.run()


def spans_of(events):
    return [e for e in events if e["ph"] == "X"]


def names_of(events):
    return {e["name"] for e in spans_of(events)}


def inside(child, parent):
    return (child["tid"] == parent["tid"] and parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"])


@pytest.fixture(scope="module")
def traced(lm, tmp_path_factory):
    """One toy ``Trainer.train`` and one toy ``sched.run()`` with NO
    tracer attached, inside a ``jax.profiler`` session on the CPU: what
    the session tracer recorded, and the trace directory."""
    log_dir = str(tmp_path_factory.mktemp("profile"))
    tracer = session_tracer()
    tracer.clear()
    t0 = time.perf_counter()
    jax.profiler.start_trace(log_dir)
    try:
        toy_train()
        _, done = toy_serve(lm)
    finally:
        jax.profiler.stop_trace()
    return {"events": tracer.between(t0, time.perf_counter()),
            "log_dir": log_dir, "done": done}


# -- the one rule --------------------------------------------------------------

def test_no_tracer_and_no_session_records_nothing(lm):
    tracer = session_tracer()
    before = len(tracer.events())
    assert live(None) is None
    assert tspan(None, "anything") is trace_lib.NULL_SPAN
    trainer = toy_train()
    sched, done = toy_serve(lm)
    assert trainer.tracer is None and sched.tracer is None \
        and sched.engine.tracer is None
    assert len(done) == 3 and all(r.finish_reason == "length" for r in done)
    assert len(tracer.events()) == before
    own = Tracer()
    assert live(own) is own                  # an attached tracer wins


def test_session_records_every_trainer_span(traced):
    spans = spans_of(traced["events"])
    assert TRAINER_SPANS <= names_of(spans), names_of(spans)
    steps = [e for e in spans if e["name"] == "train_step"]
    assert len(steps) == 4
    assert [e["args"]["batch"] for e in steps] == [0, 1, 2, 3]
    assert [e["args"]["step"] for e in steps] == [0, 1, 2, 3]
    # every iteration: one device_put, dispatch and loss_fetch, and the
    # Begin/EndIteration handler calls
    for step in steps:
        kids = [e for e in spans if e is not step and inside(e, step)]
        got = sorted(e["name"] for e in kids)
        assert got == ["device_put", "dispatch", "events", "events",
                       "loss_fetch"], got
    # the first dispatch compiled the step, the others did not
    compiled = [bool((e.get("args") or {}).get("compiled"))
                for e in spans if e["name"] == "dispatch"]
    assert compiled == [True, False, False, False]
    # the reader is asked once more than it yields
    assert sum(e["name"] == "reader_wait" for e in spans) == 5


def test_session_records_every_scheduler_and_engine_span(traced):
    spans = spans_of(traced["events"])
    names = names_of(spans)
    assert SCHEDULER_SPANS <= names and ENGINE_SPANS <= names, names
    step = next(e for e in spans if e["name"] == "sched_step")
    assert set(step["args"]) >= {"queued", "running", "prefilling"}
    assert step["args"]["queued"] == 3
    admits = [e for e in spans if e["name"] == "admit"]
    assert admits[0]["args"]["admitted"] == 2      # two slots
    assert admits[0]["args"]["backpressure"] is None
    assert sum(e["args"]["admitted"] for e in admits) == 3
    begin = [e for e in spans if e["name"] == "begin_prefill"]
    assert [e["args"]["prompt_len"] for e in begin] == [3, 4, 5]
    assert all({"slot", "prefix_hit_blocks"} <= set(e["args"])
               for e in begin)
    ticks = [e for e in spans if e["name"] == "engine_tick"]
    assert ticks and all({"active", "live_tokens", "tokens", "tick"}
                         <= set(e["args"]) for e in ticks)
    assert sum(e["args"]["tokens"] for e in ticks) \
        == sum(len(r.tokens) - 1 for r in traced["done"])
    assert all(e["args"]["done"] is True for e in spans
               if e["name"] == "prefill_dispatch")


def test_children_lie_inside_their_parents_on_one_thread(traced):
    spans = spans_of(traced["events"])
    assert len({e["tid"] for e in spans}) == 1
    for child in spans:
        parent = PARENT.get(child["name"])
        if parent is None:
            continue
        assert any(inside(child, p) for p in spans
                   if p["name"] == parent), child


def test_every_span_of_one_request_carries_its_rid(traced):
    spans = spans_of(traced["events"])
    for rid in (r.rid for r in traced["done"]):
        mine = [e["name"] for e in spans
                if (e.get("args") or {}).get("rid") == rid]
        assert sorted(mine) == ["finish", "prefill_chunk", "queue_wait"]
    waits = sorted(e["dur"] for e in spans if e["name"] == "queue_wait")
    assert waits[-1] > 3 * waits[0]     # the third request waited for a slot


def test_xplane_host_plane_holds_the_program_spans(traced):
    path = glob.glob(os.path.join(traced["log_dir"], "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    host = [p for p in data.planes if p.name == "/host:CPU"]
    assert host
    seen = {}
    for line in host[0].lines:
        for ev in line.events:
            if ev.name.startswith(trace_lib.ANNOTATION_PREFIX):
                seen.setdefault(ev.name, ev)
    want = {trace_lib.ANNOTATION_PREFIX + n for n in
            TRAINER_SPANS | ENGINE_SPANS | SCHEDULER_SPANS
            if n not in ("queue_wait", "finish")}     # retroactive: no
    assert want <= set(seen), want - set(seen)
    facts = dict(seen["paddle_tpu:engine_tick"].stats)
    assert {"tick", "active", "tokens"} <= set(facts)


def test_fused_and_pipelined_loops_obey_the_same_rule(tmp_path):
    tracer = session_tracer()
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        toy_train(n=3, K=2, M=2)
        toy_train(n=3, K=2, M=2, pipeline_depth=2)
    finally:
        jax.profiler.stop_trace()
    names = names_of(tracer.between(t0, time.perf_counter()))
    assert {"plan", "stack", "device_put", "dispatch", "events_replay",
            "stage", "shard", "drain", "drain_wait"} <= names, names
    assert "train_step" not in names        # the plain loop's own


def test_attached_tracer_records_without_a_session(lm):
    own = Tracer()
    session = session_tracer()
    before = len(session.events())
    toy_train(tracer=own)
    sched, _ = toy_serve(lm, tracer=own)
    assert sched.engine.tracer is None      # the engine's is set apart
    names = names_of(own.events())
    assert {"train_step", "dispatch", "loss_fetch", "sched_step", "admit",
            "queue_wait", "decode_tick", "finish"} <= names
    assert "engine_tick" not in names
    assert len(session.events()) == before


# -- starved stretches: from a drain's fetch to the next compiled call ------------

@pytest.fixture(scope="module", params=[None, 2], ids=["one_shot", "chunk2"])
def starved_run(lm, request):
    """The toy scheduler run with one tracer on the scheduler AND the
    engine: the one-shot prefill, and chunks of two tokens (prompts of 3
    to 5 take two or three calls)."""
    model, variables = lm
    engine = DecodeEngine(model, variables, max_slots=2, block_size=4,
                          prefill_chunk=request.param)
    engine.warmup()
    assert engine._starved_since is None      # warmup leaves none open
    own = Tracer()
    engine.tracer = own
    sched = ContinuousBatchingScheduler(engine, tracer=own)
    rng = np.random.RandomState(1)
    for i in range(3):
        sched.submit(list(rng.randint(1, V, size=3 + i)), 3 + i)
    before = engine.starved_s
    sched.run()
    spans = spans_of(own.events())
    return {"spans": spans, "grown": engine.starved_s - before,
            "stretches": [e for e in spans if e["name"] == "starved"],
            "chunked": request.param is not None}


def _end(e):
    return e["ts"] + e["dur"]


def test_a_stretch_runs_from_a_fetch_start_to_a_dispatch_end(starved_run):
    spans, stretches = starved_run["spans"], starved_run["stretches"]
    fetches = [e for e in spans if e["name"] in ("tick_fetch",
                                                 "prefill_fetch")]
    dispatches = [e for e in spans if e["name"] in ("tick_dispatch",
                                                    "prefill_dispatch")]
    # every fetch opens one; the run's last stays open (no call after it)
    assert stretches and len(stretches) == len(fetches) - 1
    for st in stretches:
        start = next(f for f in fetches
                     if f["ts"] == pytest.approx(st["ts"], abs=0.01))
        end = next(d for d in dispatches
                   if _end(d) == pytest.approx(_end(st), abs=0.01))
        assert st["args"]["after"] == start["name"].split("_")[0]
        assert st["args"]["by"] == end["name"].split("_")[0]
        # the first dispatch after its fetch closes it
        assert end is min((d for d in dispatches if d["ts"] > st["ts"]),
                          key=lambda d: d["ts"])


def test_no_stretch_overlaps_a_wait_or_another(starved_run):
    spans, stretches = starved_run["spans"], starved_run["stretches"]
    waits = [(d["ts"], f["ts"]) for d in spans
             if d["name"] in ("tick_drain", "prefill_drain")
             for f in spans if f["name"] in ("tick_fetch", "prefill_fetch")
             and inside(f, d)]
    assert len(waits) == len(stretches) + 1
    for st in stretches:
        assert all(_end(st) <= lo or st["ts"] >= hi - 0.01
                   for lo, hi in waits)
    ordered = sorted(stretches, key=lambda e: e["ts"])
    assert all(_end(a) <= b["ts"] for a, b in zip(ordered, ordered[1:]))


def test_a_chunk_dispatch_ends_a_stretch_and_opens_none(starved_run):
    spans, stretches = starved_run["spans"], starved_run["stretches"]
    chunks = [e for e in spans if e["name"] == "prefill_dispatch"
              and e["args"]["done"] is False]
    assert bool(chunks) == starved_run["chunked"]
    fetches = [e["ts"] for e in spans if e["name"].endswith("_fetch")]
    assert not chunks or any(
        _end(st) == pytest.approx(_end(c), abs=0.01)
        for c in chunks for st in stretches)
    for c in chunks:
        # the next stretch starts at the next fetch, not at the chunk
        later = [st["ts"] for st in stretches if st["ts"] > c["ts"]]
        if later:
            assert min(later) == pytest.approx(
                min(f for f in fetches if f > _end(c)), abs=0.01)


def test_stretches_add_up_to_the_counter(starved_run):
    total = sum(e["dur"] for e in starved_run["stretches"]) / 1e6
    assert starved_run["grown"] > 0
    assert total == pytest.approx(starved_run["grown"], rel=0.01)


def test_the_counter_grows_with_no_tracer_and_no_session(lm):
    session = session_tracer()
    before = len(session.events())
    sched, _ = toy_serve(lm)
    assert sched.engine.tracer is None and live(None) is None
    assert sched.engine.starved_s > 0
    assert len(session.events()) == before


def test_a_page_import_ends_a_stretch_and_opens_none(lm):
    model, variables = lm
    engine = DecodeEngine(model, variables, max_slots=2, block_size=4)
    engine.warmup()
    own = Tracer()
    engine.tracer = own
    prompt = [3, 1, 4, 1, 5]
    engine.begin_prefill(0, prompt)
    tok = engine.prefill_step(0)            # drained: a stretch is open
    assert engine._starved_since is not None
    _, kpages, vpages = engine.cache.export_pages(0)
    assert engine.adopt_slot(1, prompt, tok, kpages, vpages)
    assert engine._starved_since is None
    last = [e for e in spans_of(own.events()) if e["name"] == "starved"][-1]
    assert last["args"] == {"after": "prefill", "by": "import"}
    assert engine.starved_s == pytest.approx(last["dur"] / 1e6, rel=0.01)


def test_starved_time_by_innermost_span_on_the_toy_run(starved_run):
    by = trace_lib.starved_by_span(starved_run["spans"])
    assert sum(by.values()) == pytest.approx(starved_run["grown"], rel=0.01)
    leaves = {"tick_stage", "tick_dispatch", "tick_fetch", "tick_retire",
              "begin_prefill", "prefill_dispatch", "prefill_fetch",
              "prefill_retire", "expire", "decode_tick"}
    assert {"tick_stage", "tick_dispatch", "tick_fetch", "tick_retire",
            "prefill_fetch", "decode_tick"} <= set(by)
    # a stretch is never its own innermost span
    assert "starved" not in by and all(by[n] > 0 for n in by)
    assert sum(by[n] for n in leaves & set(by)) > 0.5 * sum(by.values())


# -- one clock pair per region ---------------------------------------------------

def test_statset_telemetry_and_span_read_one_clock_pair():
    own, sink = Tracer(), InMemorySink()
    trainer = toy_train(tracer=own, telemetry=Telemetry(sinks=[sink]))
    recs = [r for r in sink.records if r.get("kind") == "step"]
    spans = spans_of(own.events())
    for name, field, key in (("dispatch", "dispatch_ms", "train_step"),
                             ("device_put", "shard_ms", "shard_batch"),
                             ("fence", "device_ms", "device_wait")):
        durs = [e["dur"] / 1e3 for e in spans if e["name"] == name]
        assert len(durs) == len(recs) == 4
        assert [r[field] for r in recs] \
            == pytest.approx(durs, abs=1e-3)     # the record rounds to us
        row = trainer.stats.summary()[key]
        assert row["count"] == 4
        assert row["total_s"] == pytest.approx(sum(durs) / 1e3, rel=1e-6)


def test_trainer_wraps_no_region_twice():
    src = open(os.path.join(ROOT, "paddle_tpu", "train",
                            "trainer.py")).read()
    assert "stats.time(" not in src
    assert not re.search(r"with [^\n]*\\\n\s*tspan\(", src)
    assert "perf_counter()" not in src


def test_only_retroactive_spans_use_complete():
    for mod, allowed in (("scheduler", {"queue_wait", "finish",
                                        "handoff_out", "adopt"}),
                         ("engine", {"starved"})):
        src = open(os.path.join(ROOT, "paddle_tpu", "serve",
                                mod + ".py")).read()
        assert set(re.findall(r'\.complete\(\s*"(\w+)"', src)) == allowed
        # queue_wait and a stretch a copy-on-write fork ends are stamped
        # "now"; nothing else reads the tracer's clock
        assert src.count("now_us()") == 1


# -- the tracer's own additions ----------------------------------------------------

def test_self_times_on_a_hand_made_nest():
    def x(name, ts, dur, tid=1):
        return {"ph": "X", "name": name, "pid": 1, "tid": tid, "ts": ts,
                "dur": dur}
    events = [x("a", 0, 100), x("b", 10, 20), x("c", 40, 50),
              x("d", 45, 5), x("d", 60, 10),
              x("late", 95, 30),              # overruns a: nobody's child
              x("other", 0, 100, tid=2),      # another thread
              {"ph": "i", "name": "mark", "pid": 1, "tid": 1, "ts": 5}]
    rows = self_times(events)
    assert len(rows) == 7 and "self" not in events[0]
    by = {}
    for r in rows:
        by.setdefault(r["name"], []).append(r["self"])
    assert by == {"a": [30], "b": [20], "c": [35], "d": [5, 10],
                  "late": [30], "other": [100]}
    only = self_times([e for e in events if e["name"] in ("a", "d")])
    assert [r["self"] for r in only if r["name"] == "a"] == [85]


def test_span_facts_epoch_and_window():
    tracer = Tracer()
    t0 = time.perf_counter()
    with tracer.span("outer", a=1) as sp:
        with tracer.span("inner"):
            pass
        sp.set(b=2)
    t1 = time.perf_counter()
    with tracer.span("later"):
        pass
    outer = tracer.between(t0, t1)
    assert [e["name"] for e in outer] == ["inner", "outer"]
    assert outer[1]["args"] == {"a": 1, "b": 2}
    assert sp.t1_ns - sp.t0_ns == pytest.approx(outer[1]["dur"] * 1e3)
    # ts counts from epoch_ns, so a span lands on time.perf_counter()
    start = tracer.epoch_ns / 1e9 + outer[1]["ts"] / 1e6
    assert t0 <= start <= t1
    assert tracer.at_us(t0) <= outer[1]["ts"]
    fake = Tracer(clock=lambda: 7.0)
    assert fake.at_us(7.0) == fake.now_us() == 7e6
    assert [e["name"] for e in tracer.drain_events()] \
        == ["inner", "outer", "later"] and not tracer.drain_events()


# -- compile events ------------------------------------------------------------------

def test_compile_log_one_backend_row_for_a_new_shape():
    xla_cache.listen()
    xla_cache.listen()                      # idempotent

    @jax.jit
    def spans_probe(x):
        return x * 2 + 1

    def mine():
        return [r for r in xla_cache.compile_log()
                if "spans_probe" in r["fun_name"]]

    assert mine() == []
    t0 = time.perf_counter()
    spans_probe(jnp.ones(3))
    rows = mine()
    assert [r["phase"] for r in rows] == ["trace", "lower",
                                          "backend_compile"]
    assert rows[2]["fun_name"] == "jit(spans_probe)"
    assert all(r["seconds"] > 0 and t0 <= r["t"] <= time.perf_counter()
               for r in rows)
    assert set(rows[2]) >= {"cache_hit", "retrieval_s"}
    spans_probe(jnp.ones(3))                # a repeated shape adds none
    assert len(mine()) == 3
    spans_probe(jnp.ones(4))                # a new one adds one of each
    assert [r["phase"] for r in mine()].count("backend_compile") == 2


def test_compile_is_an_instant_on_the_live_tracer(tmp_path):
    xla_cache.listen()
    tracer = session_tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.jit(lambda x: x - 3)(jnp.ones(5))
    finally:
        jax.profiler.stop_trace()
    marks = [e for e in tracer.events() if e["ph"] == "i"
             and e["name"] == "compile"]
    assert {"trace", "lower", "backend_compile"} \
        <= {e["args"]["phase"] for e in marks}


# -- the readers, on the benchmark's toy cells -----------------------------------------

def _bench():
    spec = importlib.util.spec_from_file_location(
        "bench_tests", os.path.join(ROOT, "tests", "benchmark",
                                    "test_benchmark.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


READERS = {"toy-train": ["trainer_host_ms_p50", "trainer_dispatch_ms_p50",
                         "setup_compile_s"],
           "toy-serve": ["sched_self_ms_p50", "tick_host_ms_p50",
                         "prefill_host_ms_p50", "setup_compile_s",
                         "host_starved_pct.serve", "starved_feed_ms",
                         "starved_fetch_ms", "starved_retire_ms"]}


@pytest.fixture(scope="module")
def toy_lines(tmp_path_factory):
    """A traced toy run of each driver through the cell runner: the
    per-layer line and the context the readers read."""
    from benchmarks import harness
    bench = _bench()
    root = bench.make_toy_root(str(tmp_path_factory.mktemp("toy")))
    # tests/benchmark runs its own traced cells in another worker
    trace_dir, harness.TRACE_DIR = harness.TRACE_DIR, str(
        tmp_path_factory.mktemp("bench_trace"))
    kept = {}
    finish = harness.finish

    def keeping(ctx, out, xplane=None):
        kept[ctx.cell.name] = ctx
        return finish(ctx, out, xplane)

    harness.finish = keeping
    try:
        lines = {name: bench.run_toy(root, name, seconds=0.5, trace=True)[1]
                 for name in READERS}
    finally:
        harness.finish, harness.TRACE_DIR = finish, trace_dir
    return lines, kept, root


@pytest.mark.parametrize("cell", sorted(READERS))
def test_readers_report_on_a_traced_toy_cell(toy_lines, cell):
    lines, kept, _ = toy_lines
    metrics = lines[cell]["metrics"]
    for name in READERS[cell]:
        assert name in metrics, (name, sorted(metrics))
        assert metrics[name]["value"] > 0
        assert metrics[name]["unit"] == (
            "%" if "_pct" in name else "s" if name.endswith("_s") else "ms")
    other = [n for c, names in READERS.items() if c != cell for n in names
             if n not in READERS[cell]]
    assert not set(other) & set(metrics)
    if cell == "toy-serve":
        # the inside twins lie under what the harness times from outside
        assert metrics["tick_host_ms_p50"]["value"] \
            < metrics["tick_ms_p50"]["value"]
        assert metrics["prefill_host_ms_p50"]["value"] \
            < metrics["prefill_ms_p50"]["value"]
    else:
        assert metrics["trainer_dispatch_ms_p50"]["value"] \
            < metrics["train_step_ms_p50"]["value"]
    assert metrics["setup_compile_s"]["value"] \
        < kept[cell].window[0] - kept[cell].t_start


def test_starved_parts_are_no_more_than_the_whole_a_tick(toy_lines):
    lines, kept, _ = toy_lines
    metrics = {k: v["value"] for k, v in lines["toy-serve"]["metrics"].items()}
    lo, hi = kept["toy-serve"].rec.spans["window"][0][:2]
    ticks = sum(e["name"] == "engine_tick"
                for e in session_tracer().between(lo, hi))
    whole = metrics["host_starved_pct.serve"] / 100 * (hi - lo) * 1e3 / ticks
    parts = sum(metrics[f"starved_{p}_ms"] for p in ("feed", "fetch",
                                                      "retire"))
    assert 0 < parts <= whole * (1 + 1e-9)
    # under the device's idle: a stretch is the host's, and no wait
    assert metrics["host_starved_pct.serve"] < 100


@pytest.mark.parametrize("metric", sorted({n for names in READERS.values()
                                           for n in names}))
def test_readers_return_none_on_an_untraced_context(toy_lines, metric):
    from benchmarks import harness
    _, _, root = toy_lines
    cell = harness.Cell("toy-train" if metric.startswith(("trainer", "setup"))
                        else "toy-serve", root=root)
    ctx = harness.make_context(cell, 3, 0.5, False, time.perf_counter(),
                               jax.devices()[:1],
                               harness.load_peaks("TPU v5 lite"))
    assert harness.load_reader(metric, root)(ctx) is None
