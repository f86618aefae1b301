"""The ``allreduce_ms_per_step`` reader
(``benchmarks/layer_metrics/allreduce_ms_per_step.py``) on made-up
reductions of a trace, and its entry in ``BENCHMARK.json``: both training
cells, the one whose step has no collective reading 0."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness                               # noqa: E402

TRAIN_CELLS = ["train-590m-seq2048", "train-590m-dp4"]


def context(op_seconds, steps):
    trace = None if op_seconds is None else {"op_seconds": op_seconds}
    return types.SimpleNamespace(trace=trace,
                                 facts={"traced_steps": steps})


@pytest.mark.parametrize("op_seconds, steps, want", [
    # the synchronous instruction alone: 0.420 s over 9 steps
    ({"all-reduce": 0.420, "fusion": 0.712}, 9, 420.0 / 9),
    # the asynchronous kinds add up with the synchronous one
    ({"all-reduce": 0.010, "all-reduce-start": 0.030,
      "all-reduce-done": 0.050, "all-reduce-scatter-fusion": 0.002,
      "all-gather": 0.5, "fusion": 0.712}, 4, 92.0 / 4),
    # a step with no collective
    ({"fusion": 0.787, "divide_add_fusion": 0.628}, 10, 0.0),
    # no trace, or no step ended in the traced window: nothing to read
    (None, 9, None),
    ({"all-reduce": 0.420}, 0, None),
    ({"all-reduce": 0.420}, None, None),
])
def test_reader_sums_the_collective_kinds_over_the_traced_steps(
        op_seconds, steps, want):
    got = harness.load_reader("allreduce_ms_per_step")(
        context(op_seconds, steps))
    assert got == (None if want is None else pytest.approx(want))


def test_metric_is_declared_for_both_training_cells():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {m["name"]: m for m in bench["per_layer"]}[
        "allreduce_ms_per_step"]
    assert entry == {"name": "allreduce_ms_per_step", "unit": "ms",
                     "better": "lower", "source": "device_trace",
                     "layer": "trainer loop",
                     "moves": "train_tokens_per_s",
                     "workloads": TRAIN_CELLS}
    for name in TRAIN_CELLS:
        assert entry in harness.Cell(name).per_layer
