"""The benchmark's files of the latent-attention expert configuration
(``benchmarks/pangu_*.py``, the three readers it adds, its configuration
file): a toy cell of this architecture, a small configuration file of its
own in a temporary root, through ``harness.run_cell``'s own steps on the
CPU reading ``correct``; the int8 control against the cell's limit; the
cost functions and the new readers against hand counts; the
configuration file against the catalog row it was written from.
"""

import copy
import glob
import json
import os
import shutil
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import costs, harness                       # noqa: E402
from benchmarks import pangu_costs, pangu_reference        # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "serve-pangu718b-closed64"
CONFIG = "openpangu-ultra-moe-718b-ep16"
XPLANE = glob.glob(os.path.join(
    ROOT, "experiments", "trace_resnet50", "**", "*.xplane.pb"),
    recursive=True)[0]
PEAKS = harness.load_peaks("TPU v5 lite")
# every mechanism at a small size: a dense layer and two expert layers, a
# router of 16 outputs with 4 experts held (ids 4 to 7) and 2 a token, one
# shared expert, 4 heads, both latents, a slice of the vocabulary
TOY_CONFIG = {
    "source": "a toy of the latent-attention expert model for the CPU tests",
    "hidden_size": 32, "num_attention_heads": 4, "q_lora_rank": 16,
    "kv_lora_rank": 24, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "intermediate_size": 64, "moe_intermediate_size": 16,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.5, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "max_position_embeddings": 64, "vocab_size": 96,
    "published": {"n_routed_experts": 16, "vocab_size": 768},
    "deployment": {"experts_held": [4, 4]},
    "assumed": {"norm_scale_jitter": 0.1},
    "reference": "benchmarks/pangu_reference.py",
    "layout": "benchmarks/pangu_layout.py",
    "costs": "benchmarks/pangu_costs.py"}
# the toy holds bfloat16 weights and a bfloat16 pool, as the real cell
# does, and is driven by the real cell's driver (``serve_quantile``: the
# widest gap and a percentile of the same gaps). Readings on ten seeds (my
# CPU runs, PR 29, 24 sampled requests, 318 to 397 tokens): the WIDEST gap
# does not part the program from the int8 control (program 0.001 to 0.51:
# where bfloat16 noise swaps a token's second and third expert, one logit
# moves by as much as int8 moves it; control 0.36 to 0.96), its limit here
# only fails a wrong block; the 99th percentile does on every seed
# (program 0 to 0.0046, control 0.053 to 0.29): the limit 0.02 lies
# between. The published widths are the chip's to judge (PERF.md).
TOY_CELL = {
    "driver": "serve_quantile", "trace_seconds": 0.2,
    "kernels": ["latent_decode", "ragged-dot-none"],
    "serve": {"max_slots": 3, "block_size": 4, "sample_requests": 24,
              "engine": {"prefill_chunk": 8, "max_blocks_per_seq": 15,
                         "dtype": "bfloat16"}},
    "limits": {"served_logit_gap": 1.5, "served_logit_gap_p99": 0.02}}
TOY_TRAFFIC = {"kind": "serve", "loop": "closed", "clients": 3, "pool": 24,
               "prompt_len": [4, 30], "max_new": [8, 24], "sigma": 0.6,
               "max_total": 60, "balance": 3}


def make_toy_root(root):
    """A checkout-shaped directory holding ONLY new files: the toy
    configuration, its cell and its mix beside a copy of the per-layer
    readers; the architecture's three modules are found in the
    harness's own checkout."""
    bdir = os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(bdir, sub))
    shutil.copytree(os.path.join(ROOT, "benchmarks", "layer_metrics"),
                    os.path.join(bdir, "layer_metrics"))
    for path, data in (("configs/toy-latent.json", TOY_CONFIG),
                       ("traffic/toy-closed3.json", TOY_TRAFFIC),
                       ("workloads/toy-latent-serve.json", TOY_CELL)):
        with open(os.path.join(bdir, path), "w") as f:
            json.dump(data, f)
    bench = copy.deepcopy(BENCH)
    bench["configs"] = [{"name": "toy-latent", "source": "none",
                         "reduced": [], "why": "toy",
                         "file": "benchmarks/configs/toy-latent.json"}]
    bench["workloads"] = [{"name": "toy-latent-serve", "chips": 1,
                           "config": "toy-latent", "why": "toy",
                           "traffic": "toy-closed3"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["toy-latent-serve"] * (CELL in m["workloads"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_toy_root(str(tmp_path_factory.mktemp("toy_latent")))


def run_toy(root, seed, trace):
    import jax
    cell = harness.Cell("toy-latent-serve", root=root)
    ctx = harness.make_context(cell, seed, 1.0, trace, time.perf_counter(),
                               jax.devices()[:1], PEAKS)
    out = harness.load_driver(cell).run(ctx)
    return ctx, harness.finish(ctx, out, xplane=XPLANE if trace else None)


def test_toy_cell_of_this_architecture_reads_correct(toy_root):
    """Through the serve driver as it builds any engine: chunked prefill,
    bfloat16 weights and pool, the scheduler's closed loop, then the
    float32 reference over the sampled requests."""
    ctx, line = run_toy(toy_root, 2 ** 31 + 31, trace=True)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert ctx.facts["compile_counts"] == {"prefill": 1, "tick": 1}
    assert ctx.facts["pool_dtype"] == "bfloat16"
    assert ctx.facts["latent_width"] == 32
    assert ctx.facts["latent_row_stored"] == 128
    assert ctx.facts["expert_slots"] == 2 * 4
    assert 0 <= line["checks"]["served_logit_gap"]["value"] < 0.2
    assert 0 <= line["checks"]["served_logit_gap_p99"]["value"] < 0.02
    reported = set(line["metrics"])
    assert {"sched_overhead_ms", "serve_step_mfu_pct", "sched_self_ms_p50",
            "tick_host_ms_p50", "device_idle_pct.serve",
            "expert_load_max_over_mean", "tick_ms_p50.saturated",
            "itl_p95_ms.saturated", "ttft_p95_ms.saturated"} <= reported
    # end to end the cell reports throughput and set-up: a closed loop
    # that is always full is judged on tokens per second, and its tails
    # (which swing with the overlap of admissions, PERF.md section 6) are
    # per-layer metrics of their own, read in every traced run
    e2e = {m["name"] for m in harness.Cell(CELL).end_to_end}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    assert line["metrics"]["itl_p95_ms.saturated"]["value"] \
        >= line["metrics"]["tick_ms_p50.saturated"]["value"] > 0
    assert line["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    # the recorded trace holds neither kernel, and a prompt takes several
    # prefill calls here, which ``prefill_ms_p50`` (one call a prompt)
    # does not pair up: nothing to read, no error
    assert not {"latent_decode_roofline_pct", "moe_ffn_roofline_pct",
                "paged_decode_roofline_pct", "prefill_ms_p50"} & reported


@pytest.mark.parametrize("seed", [2 ** 31 + 31, 4100000001])
def test_int8_control_fails_the_toy_cells_limit(toy_root, seed):
    """The control (the reference with int8 operands, put in the program's
    place) comes out NOT correct by the percentile check; the widest gap
    alone would have passed it."""
    driver = harness.load_driver(harness.Cell(CELL))
    ctx, line = run_toy(toy_root, seed, trace=False)
    assert line["correct"] is True, line["checks"]
    control = ctx.cell.reference.serve_reference(
        ctx.cell.config, ctx.seed, ctx.facts["sample"], quant="int8")
    got = driver.control_gap(ctx.facts["reference"], control)
    limits = TOY_CELL["limits"]
    assert got["served_logit_gap_p99"] > limits["served_logit_gap_p99"], got
    assert got["served_logit_gap"] < limits["served_logit_gap"], got
    picks = [c.argmax(axis=-1) for c in control]
    checks = driver.serve_checks(
        [(p, list(k)) for (p, _), k in zip(ctx.facts["sample"], picks)],
        ctx.facts["reference"], limits)
    assert [c["ok"] for c in checks] == [True, False], checks


def test_percentile_checks_against_a_hand_count():
    """``serve_quantile``'s statistics on gaps made by hand: 100 positions
    over two requests, the served token 0.5 under the best at one of them
    and 0.01 under it at four."""
    driver = harness.load_driver(harness.Cell(CELL))
    under = np.zeros(100)
    under[[3, 40, 41, 77]] = 0.01
    under[60] = 0.5
    logits = np.zeros((100, 6), np.float32)
    logits[:, 2] = 1.0                        # the reference's best
    logits[np.arange(100), 4] = 1.0 - under   # the served token
    ref = [logits[:30], logits[30:]]
    sample = [([1, 2], [4] * 30), ([3], [4] * 70)]
    gaps = driver.token_gaps(ref, [t for _, t in sample])
    assert gaps.shape == (100,) and gaps.sum() == pytest.approx(0.54)
    limits = {"served_logit_gap": 1.5, "served_logit_gap_p95": 0.005,
              "served_logit_gap_p99": 0.005}
    got = {c["name"]: c for c in driver.serve_checks(sample, ref, limits)}
    assert got["served_logit_gap"]["value"] == pytest.approx(0.5)
    # the 95th percentile of 95 noughts and five gaps lies between the
    # last nought and the first 0.01 (numpy interpolates: 0.0005)
    assert got["served_logit_gap_p95"]["value"] == pytest.approx(
        np.percentile(under, 95)) and got["served_logit_gap_p95"]["ok"]
    assert got["served_logit_gap_p99"]["value"] == pytest.approx(
        np.percentile(under, 99), rel=1e-5)
    assert not got["served_logit_gap_p99"]["ok"]
    with pytest.raises(KeyError):
        driver.serve_checks(sample, ref, {"mean_gap": 1.0})
    # the control's readings: the token the lower precision puts first
    ctl = [np.where(np.arange(6) == 4, 2.0, logits[:30]),
           np.where(np.arange(6) == 2, 2.0, logits[30:])]
    read = driver.control_gap(ref, ctl)
    assert set(read) == {"served_logit_gap", "served_logit_gap_p90",
                         "served_logit_gap_p95", "served_logit_gap_p99"}
    assert read["served_logit_gap"] == pytest.approx(0.01)   # position 3


# -- the configuration file and the cost functions ----------------------------

def published():
    cell = harness.Cell(CELL)
    return cell, pangu_reference.dims(cell.config)


def test_configuration_keeps_the_catalog_rows_numbers():
    """Every number of the catalog row under its own key; the keys that
    count give what is held here, are listed in ``reduced`` and have their
    published values beside them; widths as published."""
    cell, z = published()
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    config = cell.config
    row = {"attention_bias": False, "first_k_dense_replace": 3,
           "hidden_act": "silu", "hidden_size": 7680,
           "intermediate_size": 18432, "kv_lora_rank": 512,
           "max_position_embeddings": 131072,
           "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048,
           "n_routed_experts": 256, "n_shared_experts": 1,
           "norm_topk_prob": True, "num_attention_heads": 128,
           "num_experts_per_tok": 8, "num_hidden_layers": 61,
           "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
           "q_lora_rank": 1536, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
           "rope_theta": 25600000, "routed_scaling_factor": 2.5,
           "sandwich_norm": True, "tie_word_embeddings": False,
           "v_head_dim": 128, "vocab_size": 153600}
    differ = {k for k, v in row.items() if config[k] != v}
    assert differ == set(entry["reduced"]) == set(config["reduced"])
    assert {k: config["published"][k] for k in differ} \
        == {k: row[k] for k in differ}
    assert entry["source"] == config["source"]
    assert (z.L, z.L_dense, z.held, z.E, z.K, z.V) == (5, 1, 16, 256, 8,
                                                       19200)
    assert z.V * 8 == row["vocab_size"] and z.held * 16 == z.E
    assert [config[k] for k in harness.ARCHITECTURE] == [
        "benchmarks/pangu_reference.py", "benchmarks/pangu_layout.py",
        "benchmarks/pangu_costs.py"]
    assert cell.file["serve"]["engine"] == {
        "prefill_chunk": 512, "max_blocks_per_seq": 320,
        "dtype": "bfloat16"}
    assert cell.traffic["clients"] == cell.file["serve"]["max_slots"] == 64
    assert cell.traffic["max_total"] == 320 * cell.file["serve"]["block_size"]


def test_parameters_held_here_against_a_hand_count():
    """ISSUE 29's arithmetic: 4,919 M parameters, 9.84 GB in bfloat16."""
    import jax
    cell, z = published()
    shapes = jax.eval_shape(
        lambda: cell.layout.seed_params(z, np.uint32(1)))
    leaves = jax.tree_util.tree_leaves(shapes)
    count = sum(int(np.prod(s.shape)) for s in leaves)
    attn = 7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 \
        + 512 * 128 * 256 + 128 * 128 * 7680 + 1536 + 512
    norms = 4 * 7680
    dense = attn + norms + 3 * 7680 * 18432
    moe = attn + norms + 7680 * 256 + (16 + 1) * 3 * 7680 * 2048
    assert count == dense + 4 * moe + 2 * 19200 * 7680 + 7680
    assert round(count / 1e6) == 4919
    # matrices bfloat16; norm scales and the router's matrix float32
    nbytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in leaves)
    assert nbytes == 2 * count + 2 * (4 * 7680 * 256 + 5 * (norms + 2048)
                                      + 7680)
    assert cell.file["sizing"]["weight_bytes"] == nbytes


def test_cost_functions_against_a_hand_count():
    _, z = published()
    per_layer_attn = 7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 \
        + 128 * 128 * 7680
    moe = 7680 * 256 + 3 * 7680 * 2048 * (1 + 8 * 16 / 256)
    want = 5 * per_layer_attn + 3 * 7680 * 18432 + 4 * moe
    assert pangu_costs.layer_params(z) == want
    assert pangu_costs.absorbed_pair_flops(z) == 278528
    assert pangu_costs.expanded_pair_flops(z) == 81920
    head = 2 * 7680 * 19200
    absorb = 2 * 128 * 512 * 256                     # into and out of c_kv
    assert pangu_costs.decode_flops(z, 1000) == pytest.approx(
        2 * want + head + 5 * (absorb + 278528 * 1000))
    p = 2048
    pairs = p * (p + 1) / 2
    assert pangu_costs.prefill_flops(z, p) == pytest.approx(
        2 * want * p + head + 5 * (absorb * p + 81920 * pairs))
    assert pangu_costs.serve_flops(z, [p], [1000, 1000]) == pytest.approx(
        pangu_costs.prefill_flops(z, p)
        + 2 * pangu_costs.decode_flops(z, 1000))
    assert pangu_costs.attention_shape(z) == (5, 128, 192)
    # a live row: 1,152 bytes read once, 2 x (576 + 512) x 128 FLOPs
    c = pangu_costs.latent_decode_cost(1000, 64, 128, 576, 512, 2)
    assert c["flops"] == 1000 * 2 * (576 + 512) * 128
    assert c["bytes"] == 1000 * 1152 + 64 * 128 * (576 + 512) * 2
    m = pangu_costs.moe_ffn_cost(z, pairs=32, hits=14)
    assert m["flops"] == 32 * 2 * 3 * 7680 * 2048
    assert m["bytes"] == 14 * 3 * 7680 * 2048 * 2 \
        + 32 * (2 * 7680 * 2 + 3 * 2048 * 4 + 7680 * 4)


# -- the new readers, on made-up contexts --------------------------------------

def fake_ctx(kernel_seconds, ticks, facts, spans=()):
    cell, z = published()
    rec = types.SimpleNamespace(
        traced=lambda name: ticks if name == "tick" else [],
        spans={"window": [(0.0, 10.0, {})]})
    return types.SimpleNamespace(
        cell=cell, dims=z, costs=costs, peaks=PEAKS, rec=rec, facts=facts,
        trace={"kernel_seconds": kernel_seconds}), spans


def test_latent_decode_roofline_reader_against_a_hand_count():
    read = harness.load_reader("latent_decode_roofline_pct")
    facts = {"slots": 64, "latent_width": 576, "pool_bytes": 2}
    ticks = [(0, 1, {"live_tokens": 100_000}), (1, 2, {"live_tokens": 50_000})]
    ctx, _ = fake_ctx({"latent_decode": 0.004}, ticks, facts)
    least = 0.0
    for live in (100_000, 50_000):
        nbytes = live * 1152 + 64 * 128 * 1088 * 2
        flops = live * 2 * 1088 * 128
        least += 5 * max(nbytes / 819e9, flops / 197e12)
    assert read(ctx) == pytest.approx(100 * least / 0.004)
    # nothing to read: no kernel time, no such cost function, no trace
    ctx, _ = fake_ctx({"latent_decode": 0.0}, ticks, facts)
    assert read(ctx) is None
    ctx, _ = fake_ctx({"latent_decode": 0.004}, ticks, {"slots": 64})
    assert read(ctx) is None
    ctx.trace = None
    assert read(ctx) is None
    gpt2 = harness.Cell("serve-1p3b-closed8")
    ctx.cell, ctx.trace = gpt2, {"kernel_seconds": {}}
    assert read(ctx) is None


def test_expert_readers_read_the_engines_span_facts(monkeypatch):
    from paddle_tpu.obs import trace as obs_trace
    events = [
        {"name": "engine_tick", "ts": 1, "dur": 1,
         "args": {"expert_pairs": 32, "expert_hits": 20, "expert_max": 4}},
        {"name": "engine_tick", "ts": 2, "dur": 1,
         "args": {"expert_pairs": 64, "expert_hits": 40, "expert_max": 3}},
        {"name": "engine_tick", "ts": 3, "dur": 1, "args": {"tokens": 8}},
        {"name": "prefill_drain", "ts": 4, "dur": 1,
         "args": {"expert_pairs": 1000, "expert_hits": 64,
                  "expert_max": 40}},
        {"name": "tick_stage", "ts": 5, "dur": 1}]
    monkeypatch.setattr(
        obs_trace, "session_tracer",
        lambda: types.SimpleNamespace(between=lambda lo, hi: events))
    ctx, _ = fake_ctx({"ragged-dot-none": 0.01}, [], {"expert_slots": 64})
    load = harness.load_reader("expert_load_max_over_mean")
    # medians of 4 / (32 / 64) = 8 and 3 / (64 / 64) = 3
    assert load(ctx) == pytest.approx(5.5)
    ctx.facts = {}
    assert load(ctx) is None
    ctx.facts = {"expert_slots": 64}
    moe = harness.load_reader("moe_ffn_roofline_pct")
    least = 0.0
    for pairs, hits in ((32, 20), (64, 40), (1000, 64)):
        c = pangu_costs.moe_ffn_cost(ctx.dims, pairs, hits)
        least += max(c["bytes"] / 819e9, c["flops"] / 197e12)
    assert moe(ctx) == pytest.approx(100 * least / 0.01)
    ctx.trace = {"kernel_seconds": {"ragged-dot-none": 0.0}}
    assert moe(ctx) is None
