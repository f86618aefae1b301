"""The ``moe_rows_per_pair`` reader
(``benchmarks/layer_metrics/moe_rows_per_pair.py``) on made-up facts of
the engine's ``engine_tick`` and ``prefill_drain`` spans, and its entry
in ``BENCHMARK.json``."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness                               # noqa: E402

TICK = {"tokens": 64, "expert_pairs": 30, "expert_hits": 14,
        "expert_rows": 512}
DRAIN = {"slot": 3, "expert_pairs": 610, "expert_hits": 60,
         "expert_rows": 1024}
COUNTED = [{"name": "engine_tick", "ts": 1, "dur": 1, "args": TICK},
           {"name": "prefill_drain", "ts": 2, "dur": 1, "args": DRAIN},
           {"name": "tick_stage", "ts": 3, "dur": 1,
            "args": {"expert_rows": 9999, "expert_pairs": 1}}]
WITHOUT_ROWS = [{"name": e["name"], "ts": e["ts"], "dur": 1,
                 "args": {k: v for k, v in e["args"].items()
                          if k != "expert_rows"}} for e in COUNTED[:2]]
NO_PAIRS = [{"name": "engine_tick", "ts": 1, "dur": 1,
             "args": {"tokens": 64, "expert_pairs": 0, "expert_hits": 0,
                      "expert_rows": 0}}]


@pytest.mark.parametrize("events, window, want", [
    # 1,536 rows over 640 pairs; a span that is neither is not read
    (COUNTED, [(0.0, 10.0, {})], (512 + 1024) / (30 + 610)),
    # the parent's spans: pairs and no rows
    (WITHOUT_ROWS, [(0.0, 10.0, {})], None),
    # part of the spans counted: the ratio of those that were
    (WITHOUT_ROWS + COUNTED[:1], [(0.0, 10.0, {})], 512 / 30),
    (NO_PAIRS, [(0.0, 10.0, {})], None),
    ([], [(0.0, 10.0, {})], None),
    (COUNTED, None, None),                       # no window span
], ids=["ratio", "no_rows_counted", "some_spans_counted", "no_pairs",
        "no_spans", "no_window"])
def test_rows_per_pair_reader(monkeypatch, events, window, want):
    from paddle_tpu.obs import trace as obs_trace
    monkeypatch.setattr(
        obs_trace, "session_tracer",
        lambda: types.SimpleNamespace(between=lambda lo, hi: events))
    rec = types.SimpleNamespace(spans={"window": window} if window else {})
    got = harness.load_reader("moe_rows_per_pair")(
        types.SimpleNamespace(rec=rec))
    assert got == (want if want is None else pytest.approx(want))


def test_rows_per_pair_is_declared_for_the_latent_cells():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": "moe_rows_per_pair", "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "model step",
        "moves": "serve_tokens_per_s",
        "workloads": ["serve-pangu718b-closed64",
                      "serve-longcat560b-closed128"]}
    for name in entry["workloads"]:
        assert entry in harness.Cell(name).per_layer
