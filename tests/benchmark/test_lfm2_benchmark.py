"""The benchmark's files of the LFM2-24B-A2B configuration
(``benchmarks/lfm2_*.py``, its configuration file, its cell) and of the
shared-prefix cell that came with it: a toy cell of the architecture, a
small configuration file of its own in a temporary root, through the
harness's own steps on the CPU reading ``correct``; the int8 control and
the planted faults against the toy cell's limits; the cost functions and
the parameters held against hand counts; the configuration file against
the source's published numbers; both new cells' files against the numbers
they were written with.
"""

import copy
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

from benchmarks import costs, harness, traffic               # noqa: E402
from benchmarks import lfm2_costs, lfm2_reference            # noqa: E402
from benchmarks import pangu_costs                           # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "serve-lfm2-24b-closed128"
CONFIG = "lfm2-24b-a2b-pp5"
PREFIX = "serve-1p3b-shared-prefix"
PEAKS = harness.load_peaks("TPU v5 lite")
# every mechanism at a small size: five layers [conv, full, conv, conv,
# conv], 4 query heads on 2 KV heads of 64 (one 128-lane pool row), one
# dense layer, 8 experts (all held), 3 a token, a selection bias
TOY_CONFIG = {
    "source": "a toy of the convolution-and-attention expert decoder for "
              "the CPU tests",
    "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 3, "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "max_position_embeddings": 64,
    "vocab_size": 384,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "deployment": {"experts_held": [0, 8]},
    # a bias wide enough for ``bias_in_gates`` to move the gates past the
    # bfloat16 program's noise at this width
    "assumed": {"norm_scale_jitter": 0.1, "router_eps": 1e-6,
                "expert_bias_std": 0.3},
    "reference": "benchmarks/lfm2_reference.py",
    "layout": "benchmarks/lfm2_layout.py",
    "costs": "benchmarks/lfm2_costs.py"}
TOY_CHUNK = 8
# bfloat16 weights, pools and state, the real cell's driver. Readings on
# seeds 11 and 12 over all the requests a run finished (CPU runs, 26 to
# 34 requests): the program's widest gap 0.14 and 0.12, its 95th
# percentile 0.0 on both; the int8 control's 95th 0.062 and 0.053; each
# planted fault's 95th 0.13 and more, its widest 0.47 and more. The limits
# lie between (the published widths are the chip's to judge, PERF.md)
TOY_CELL = {
    "driver": "serve_quantile", "trace_seconds": 0.2,
    "kernels": ["paged_decode", "ragged-dot-none"],
    "serve": {"max_slots": 3, "block_size": 4, "sample_requests": 40,
              "engine": {"prefill_chunk": TOY_CHUNK, "max_blocks_per_seq": 15,
                         "dtype": "bfloat16"}},
    "limits": {"served_logit_gap": 1.0, "served_logit_gap_p95": 0.03}}
TOY_TRAFFIC = {"kind": "serve", "loop": "closed", "clients": 3, "pool": 24,
               "prompt_len": [4, 30], "max_new": [8, 24], "sigma": 0.6,
               "max_total": 60, "balance": 3}


def make_toy_root(root):
    """A checkout-shaped directory holding ONLY new files: the toy
    configuration, its cell and its mix beside a copy of the per-layer
    readers; the architecture's three modules are found in the harness's
    own checkout."""
    bdir = os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(bdir, sub))
    shutil.copytree(os.path.join(ROOT, "benchmarks", "layer_metrics"),
                    os.path.join(bdir, "layer_metrics"))
    for path, data in (("configs/toy-conv.json", TOY_CONFIG),
                       ("traffic/toy-closed3.json", TOY_TRAFFIC),
                       ("workloads/toy-conv-serve.json", TOY_CELL)):
        with open(os.path.join(bdir, path), "w") as f:
            json.dump(data, f)
    bench = copy.deepcopy(BENCH)
    bench["configs"] = [{"name": "toy-conv", "source": "none",
                         "reduced": [], "why": "toy",
                         "file": "benchmarks/configs/toy-conv.json"}]
    bench["workloads"] = [{"name": "toy-conv-serve", "chips": 1,
                           "config": "toy-conv", "why": "toy",
                           "traffic": "toy-closed3"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["toy-conv-serve"] * (CELL in m["workloads"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    import jax
    root = make_toy_root(str(tmp_path_factory.mktemp("toy_conv")))
    cell = harness.Cell("toy-conv-serve", root=root)
    ctx = harness.make_context(cell, 11, 1.0, False, time.perf_counter(),
                               jax.devices()[:1], PEAKS)
    out = harness.load_driver(cell).run(ctx)
    return ctx, harness.finish(ctx, out)


def test_toy_cell_of_this_architecture_reads_correct(toy_run):
    """Through the serve driver as it builds any engine: chunked prefill,
    bfloat16 weights, pools and state, the scheduler's closed loop, then
    the float32 reference over the sampled requests."""
    ctx, line = toy_run
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert ctx.facts["compile_counts"] == {"prefill": 1, "tick": 1}
    assert ctx.facts["pool_dtype"] == "bfloat16"
    # the paged group alone where the accepted readers look; the state
    # under a key of its own
    assert set(ctx.facts["pool_groups"]) == {"full"}
    assert ctx.facts["pool_groups"]["full"]["layers"] == 1
    assert ctx.facts["state_groups"] == {"conv": {
        "layers": 4, "slot_bytes": 4 * 2 * 256 * 2,
        "bytes": 3 * 4 * 2 * 256 * 2}}
    assert ctx.facts["query_heads"] == {"full": 4}
    assert (ctx.facts["kv_heads"], ctx.facts["head_dim"]) == (2, 64)
    assert ctx.facts["expert_slots"] == 4 * 8
    assert len(ctx.facts["sample"]) >= 10
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def toy_readings(ctx, **kw):
    driver = harness.load_driver(harness.Cell(CELL))
    other = ctx.cell.reference.serve_reference(
        ctx.cell.config, ctx.seed, ctx.facts["sample"], chunk=TOY_CHUNK, **kw)
    return driver, driver.control_gap(ctx.facts["reference"], other)


def test_int8_control_fails_the_toy_cells_limit(toy_run):
    """The control (the reference with int8 operands, put in the program's
    place) comes out NOT correct by the percentile check."""
    ctx, _ = toy_run
    driver, got = toy_readings(ctx, quant="int8")
    limits = TOY_CELL["limits"]
    assert got["served_logit_gap_p95"] > limits["served_logit_gap_p95"], got


@pytest.mark.parametrize("fault", lfm2_reference.FAULTS)
def test_a_wrong_layer_fails_the_toy_cells_limits(toy_run, fault):
    """The planted faults (no B gate, no QK norm, the bias in the gates,
    query heads on the other KV head of their pair, no state carried
    between prefill chunks), at full precision, put in the program's
    place: not correct."""
    ctx, _ = toy_run
    _, wrong = toy_readings(ctx, fault=fault)
    limits = TOY_CELL["limits"]
    assert any(wrong[k] > limits[k] for k in limits), wrong


# -- the configuration file and the cost functions ----------------------------

def published():
    cell = harness.Cell(CELL)
    return cell, lfm2_reference.dims(cell.config)


# the source's config.json as published (the numbers this configuration
# was cut from); the keys the cut changes are listed in ``reduced``
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv", "full_attention"]
    + ["conv", "conv", "conv", "full_attention"] * 9 + ["conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"


def test_configuration_keeps_the_published_numbers():
    """Every key of the source's config under its own name, lists and
    nested groups whole; the keys changed are the depth's, listed in
    ``reduced`` with their published values beside them; every width as
    published and all 64 experts held."""
    cell, z = published()
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    config = cell.config
    differ = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert differ == set(entry["reduced"]) == set(config["reduced"]) \
        == {"num_hidden_layers", "num_dense_layers", "layer_types"}
    assert config["published"] == {k: PUBLISHED[k] for k in differ}
    assert entry["source"] == config["source"] == SOURCE
    assert config["layer_types"] == (["conv"] + ["full_attention", "conv",
                                                 "conv", "conv"] * 2)
    # this stage's layers are the published period in its order
    assert config["layer_types"][1:] == PUBLISHED["layer_types"][2:10]
    assert (z.L, z.D, z.H, z.H_kv, z.hd, z.V) == (9, 2048, 32, 8, 64, 65536)
    assert (z.F, z.F_e, z.E, z.held, z.K) == (11776, 1536, 64, 64, 4)
    assert z.kinds == ("conv", "full", "conv", "conv", "conv") * 1 + (
        "full", "conv", "conv", "conv")
    assert z.dense == (True,) + (False,) * 8
    assert (z.conv_k, z.theta, z.eps, z.router_eps) == (3, 1e6, 1e-5, 1e-6)
    assert config["deployment"]["experts_held"] == [0, 64]
    assert config["deployment"]["pipeline_stages"] == 5
    assert {"tie_word_embeddings", "head_dim", "router_eps",
            "expert_bias_std", "initializer"} <= set(config["assumed"])
    assert [config[k] for k in harness.ARCHITECTURE] == [
        "benchmarks/lfm2_reference.py", "benchmarks/lfm2_layout.py",
        "benchmarks/lfm2_costs.py"]


def test_cells_are_as_written():
    """Both new cells' files against the numbers they were written with,
    and no pair of
    configuration and traffic twice."""
    cell, z = published()
    serve, engine = cell.file["serve"], cell.file["serve"]["engine"]
    assert cell.chips == 1 and cell.file["driver"] == "serve_quantile"
    assert cell.entry["traffic"] == "closed128"
    assert engine == {"prefill_chunk": 512, "max_blocks_per_seq": 192,
                      "dtype": "bfloat16"}
    assert (serve["max_slots"], serve["block_size"], serve["num_blocks"],
            serve["attention"], serve["sample_requests"]) == (
        128, 16, 24577, "paged", 16)
    assert serve["num_blocks"] == 128 * 192 + 1
    assert cell.file["kernels"] == ["paged_decode", "ragged-dot-none"]
    assert set(cell.file["limits"]) == {"served_logit_gap",
                                        "served_logit_gap_p85"}
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "sched_overhead_ms", "serve_step_mfu_pct", "device_idle_pct.serve",
        "sched_self_ms_p50", "tick_host_ms_p50", "setup_compile_s",
        "expert_load_max_over_mean", "moe_ffn_roofline_pct",
        "moe_rows_per_pair", "grouped_decode_roofline_pct",
        "kv_pool_bytes_per_live_token", "tick_ms_p50.saturated",
        "itl_p95_ms.saturated", "ttft_p95_ms.saturated",
        "host_starved_pct.serve", "starved_feed_ms", "starved_fetch_ms",
        "starved_retire_ms"}
    # 8 tokens an expert a tick: 128 slots x 4 choices over 64 experts
    assert serve["max_slots"] * z.K / z.E == 8
    prefix = harness.Cell(PREFIX)
    closed8 = harness.Cell("serve-1p3b-closed8")
    assert prefix.config == closed8.config and prefix.chips == 1
    assert prefix.file["driver"] == "serve"
    assert prefix.file["serve"] == closed8.file["serve"]
    assert prefix.file["limits"] == closed8.file["limits"] \
        == {"served_logit_gap": 0.05}
    assert prefix.traffic == dict(
        closed8.traffic, n_sessions=4, session_prefix_len=1024,
        p_session=0.8, why=prefix.traffic["why"])
    assert {m["name"] for m in prefix.end_to_end} == {"serve_tokens_per_s",
                                                      "setup_s"}
    # closed8's per-layer metrics of the end-to-end metrics both report
    assert {m["name"] for m in prefix.per_layer} == {
        "sched_overhead_ms", "serve_step_mfu_pct", "device_idle_pct.serve",
        "sched_self_ms_p50", "tick_host_ms_p50", "setup_compile_s",
        "paged_decode_roofline_pct", "host_starved_pct.serve",
        "starved_feed_ms", "starved_fetch_ms", "starved_retire_ms"}
    assert {m["name"] for m in prefix.per_layer} == {
        m["name"] for m in closed8.per_layer
        if m["moves"] in ("serve_tokens_per_s", "setup_s")}
    # four in five requests open with one of the four prefixes
    reqs = traffic.serve_requests(prefix.traffic, 50257, 2 ** 31 + 41)
    shared = [r for r in reqs if r["session"] is not None]
    assert len(shared) == round(0.8 * 32)
    assert len({tuple(r["prompt"][:1024]) for r in shared}) <= 4
    assert max(len(r["prompt"]) + r["max_new"] for r in reqs) <= 2048
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_rows_per_pair_is_declared_for_every_cell_with_held_experts():
    """Each expert cell reports the held experts' counters, this one
    among them."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in ("moe_rows_per_pair", "expert_load_max_over_mean",
                 "moe_ffn_roofline_pct"):
        assert by_name[name]["workloads"][-1] == CELL
        assert by_name[name] in harness.Cell(CELL).per_layer


def test_parameters_held_here_against_a_hand_count():
    """The cut's arithmetic: a conv mixer 16.78 M, an attention mixer
    10.49 M, 64 experts 604.0 M, the dense feed-forward 72.35 M, the
    embedding and the head 134.2 M each; 10.62 GB as the program holds
    it, to the byte the cell's sizing states."""
    import jax
    cell, z = published()
    shapes = jax.eval_shape(
        lambda: cell.layout.seed_params(z, np.uint32(1)))
    leaves = jax.tree_util.tree_leaves(shapes)
    count = sum(int(np.prod(s.shape)) for s in leaves)
    D = 2048
    conv_mats, taps = 3 * D * D + D * D, 3 * D
    attn = 2 * D * D + 2 * D * 512
    experts, dense, router = 64 * 3 * D * 1536, 3 * D * 11776, D * 64
    assert round((conv_mats + taps) / 1e6, 2) == 16.78
    assert round(attn / 1e6, 2) == 10.49
    assert round(experts / 1e6, 1) == 604.0
    assert round(dense / 1e6, 2) == 72.35
    assert round(65536 * D / 1e6, 1) == 134.2
    matrices = (conv_mats + dense + 6 * (conv_mats + router + experts)
                + 2 * (attn + router + experts) + 2 * 65536 * D)
    small = (7 * taps + 8 * 64 + 9 * 2 * D + D + 2 * 2 * 64)
    assert count == matrices + small
    # matrices bfloat16; taps, norm scales, router and bias float32
    nbytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in leaves)
    assert nbytes == 2 * matrices + 4 * (small + 8 * router) - 2 * 8 * router
    assert nbytes == cell.file["sizing"]["weight_bytes"] == 10_626_599_936
    assert f"{nbytes:,} bytes" in cell.config["parameters"]
    # the paged pool: 2 attention layers, a row of two KV heads of 64
    block = 2 * 4 * 16 * 128 * 2
    state = 7 * 128 * 2 * D * 2
    assert cell.file["sizing"]["pool_bytes"] == 2 * 24577 * block + state
    sizing = cell.file["sizing"]
    assert sizing["prefill_peak_bytes"] == (sizing["prefill_argument_bytes"]
                                            + sizing["prefill_temp_bytes"])
    assert sizing["prefill_peak_bytes"] > 0.25 * 16 * 2 ** 30


def test_initializer_gives_unit_variance_at_the_published_widths():
    """The ``initializer`` assumption: with N(0, 1 / fan_in) matrices on a
    normalised input and taps N(0, 1/3), the convolution's u and output,
    and the router's logits have unit variance at the published width."""
    import jax
    import jax.numpy as jnp
    cell, z = published()
    w = {k: v.astype(jnp.float32) for k, v in
         lfm2_reference.layer_weights(z, np.uint32(5), 2).items()}
    x = jax.random.normal(jax.random.PRNGKey(0), (256, z.D), jnp.float32)
    b, c, xt = jnp.split(x @ w["w_in"], 3, -1)
    assert float((b * xt).std()) == pytest.approx(1.0, rel=0.1)
    y = lfm2_reference._short_conv(x, w, z, jnp.zeros((256,), jnp.int32),
                                   None)
    assert float(y.std()) == pytest.approx(1.0, rel=0.15)
    assert float((x @ w["router"]).std()) == pytest.approx(1.0, rel=0.1)
    assert 0.002 < float(w["bias"].std()) < 0.008


def test_cost_functions_against_a_hand_count():
    _, z = published()
    D, hd = 2048, 64
    conv, attn = 4 * D * D, D * (2 * 32 * hd + 2 * 8 * hd)
    moe = D * 64 + 3 * D * 1536 * 4
    want = conv + 3 * D * 11776 + 2 * (attn + moe) + 6 * (conv + moe)
    assert lfm2_costs.layer_params(z) == pytest.approx(want)
    assert lfm2_costs.attention_shape(z) == (2, 8, 64)
    head, taps = 2 * D * 65536, 7 * 2 * 3 * D
    assert lfm2_costs.decode_flops(z, 1500) == pytest.approx(
        2 * want + taps + head + 2 * 4 * 32 * hd * 1500)
    p = 2048
    assert lfm2_costs.prefill_flops(z, p) == pytest.approx(
        (2 * want + taps) * p + head + 2 * 4 * 32 * hd * p * (p + 1) / 2)
    assert lfm2_costs.serve_flops(z, [p], [1000, 1000]) == pytest.approx(
        lfm2_costs.prefill_flops(z, p)
        + 2 * lfm2_costs.decode_flops(z, 1000))
    # the decode kernel: K and V of 8 heads of 64 read once for the 32
    # query heads; q in and out at the packed row's 128 lanes
    c = lfm2_costs.grouped_decode_cost(1000, 128, 32, 8, 64, 2)
    assert c["flops"] == 4 * 32 * 64 * 1000
    assert c["bytes"] == 2 * 1000 * 8 * 64 * 2 + 128 * 32 * 128 * (2 + 4)
    assert lfm2_costs.moe_ffn_cost is pangu_costs.moe_ffn_cost
    m = lfm2_costs.moe_ffn_cost(z, pairs=512, hits=400)
    assert m["flops"] == 512 * 2 * 3 * D * 1536


def test_grouped_decode_reader_reads_the_paged_group_alone(monkeypatch):
    """The accepted reader on this cell's facts: the paged group's two
    layers of 32 query heads at head size 64; the state group, under a key
    of its own, is not read as attention."""
    from paddle_tpu.obs import trace as obs_trace
    cell, z = published()
    events = [{"name": "engine_tick", "ts": 1, "dur": 1,
               "args": {"active": 128, "live_tokens": 190000}},
              {"name": "engine_tick", "ts": 2, "dur": 1,
               "args": {"active": 120, "live_tokens": 170000}}]
    monkeypatch.setattr(
        obs_trace, "session_tracer",
        lambda: types.SimpleNamespace(between=lambda lo, hi: events))
    ctx = types.SimpleNamespace(
        cell=cell, dims=z, costs=costs, peaks=PEAKS,
        rec=types.SimpleNamespace(spans={"window": [(0.0, 10.0, {})]}),
        facts={"pool_groups": {"full": {"layers": 2, "window": 0}},
               "state_groups": {"conv": {"layers": 7, "bytes": 7340032}},
               "pool_bytes": 2, "kv_heads": 8, "head_dim": 64,
               "query_heads": {"full": 32}},
        trace={"kernel_seconds": {"paged_decode": 0.01}})
    read = harness.load_reader("grouped_decode_roofline_pct")
    hbm = PEAKS["hbm_bytes_per_s"]
    least = sum(2 * (2 * rows * 8 * 64 * 2 + active * 32 * 128 * 6) / hbm
                for active, rows in ((128, 190000), (120, 170000)))
    assert read(ctx) == pytest.approx(100 * least / 0.01)
    pools = harness.load_reader("kv_pool_bytes_per_live_token")
    ctx.facts["pool_groups"]["full"].update(
        block_bytes=2 * 2 * 4 * 16 * 128 * 2, blocks_live=12000,
        live_tokens=190000)
    assert pools(ctx) == pytest.approx(12000 * 65536 / 190000)
