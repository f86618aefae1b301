"""The benchmark's files of the Laguna-S-2.1 configuration
(``benchmarks/laguna_*.py``, the two readers it adds, its configuration
file) and of the long-prompt cell that came with it: a toy cell of the
architecture, a small configuration file of its own in a temporary root,
through ``harness.run_cell``'s own steps on the CPU reading ``correct``;
the int8 control and the planted faults against the cell's limits; the cost
functions and the new readers against hand counts; the configuration file
against the catalog row it was written from; both cells' files against
ISSUE 35's numbers; every rule of form.
"""

import copy
import glob
import json
import os
import re
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
from benchmarks import laguna_costs, laguna_reference        # noqa: E402
from benchmarks import pangu_costs                           # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "serve-laguna118b-closed64"
CONFIG = "laguna-s-2.1-118b-ep16"
LONG = "serve-1p3b-longprompt"
XPLANE = glob.glob(os.path.join(
    ROOT, "experiments", "trace_resnet50", "**", "*.xplane.pb"),
    recursive=True)[0]
PEAKS = harness.load_peaks("TPU v5 lite")
# every mechanism at a small size: five layers [full, sliding x 3, full]
# with 4 and 6 query heads on 2 KV heads, a window of 8 (a ring of 3
# blocks of 4), YaRN on the full layers' half rotation, one dense layer,
# a softmax router of 16 outputs with 4 experts held and 3 choices
TOY_CONFIG = {
    "source": "a toy of the two-kinds-of-attention expert decoder for the "
              "CPU tests",
    "hidden_size": 32, "head_dim": 16, "num_key_value_heads": 2,
    "num_attention_heads": 4, "intermediate_size": 64,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "num_experts": 4, "num_experts_per_tok": 3, "num_hidden_layers": 5,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "gating": "per-head", "sliding_window": 8, "rms_norm_eps": 1e-6,
    "moe_apply_router_weight_on_input": False,
    "moe_router_logit_softcapping": 0, "moe_routed_scaling_factor": 2.5,
    "max_position_embeddings": 64, "vocab_size": 384,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.2079441541679836,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention"] * 2,
    "num_attention_heads_per_layer": [4, 6, 6, 6] * 2,
    "published": {"num_hidden_layers": 8, "num_experts": 16},
    "deployment": {"experts_held": [4, 4]},
    "assumed": {"norm_scale_jitter": 0.1},
    "reference": "benchmarks/laguna_reference.py",
    "layout": "benchmarks/laguna_layout.py",
    "costs": "benchmarks/laguna_costs.py"}
# bfloat16 weights and pools, the real cell's driver. Readings on five
# seeds over ALL the requests a run finished (my CPU runs, PR 35; 52 to 59
# requests, 750 to 860 tokens): the program's widest gap 0.14 to 0.52
# (where bfloat16 noise swaps a token's third and fourth choice of 16, and
# normalised gates of 2.5 in all make a swap wide), its 99th percentile 0
# to 0.022; the int8 control's 99th percentile 0.15 to 0.64, its 95th 0 to
# 0.058: at 32 wide the 95th percentile does not part them, the 99th does.
# On the seed below the program reads 0.18 and 0.0, the control 1.27 and
# 0.64, each planted fault 2.3 and more at the widest: the limits lie
# between. The published widths are the chip's to judge (PERF.md).
TOY_CELL = {
    "driver": "serve_quantile", "trace_seconds": 0.2,
    "kernels": ["paged_decode", "ragged-dot-none"],
    "serve": {"max_slots": 3, "block_size": 4, "sample_requests": 40,
              "engine": {"prefill_chunk": 8, "max_blocks_per_seq": 15,
                         "dtype": "bfloat16"}},
    "limits": {"served_logit_gap": 1.0, "served_logit_gap_p99": 0.1}}
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
    for path, data in (("configs/toy-window.json", TOY_CONFIG),
                       ("traffic/toy-closed3.json", TOY_TRAFFIC),
                       ("workloads/toy-window-serve.json", TOY_CELL)):
        with open(os.path.join(bdir, path), "w") as f:
            json.dump(data, f)
    bench = copy.deepcopy(BENCH)
    bench["configs"] = [{"name": "toy-window", "source": "none",
                         "reduced": [], "why": "toy",
                         "file": "benchmarks/configs/toy-window.json"}]
    bench["workloads"] = [{"name": "toy-window-serve", "chips": 1,
                           "config": "toy-window", "why": "toy",
                           "traffic": "toy-closed3"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["toy-window-serve"] * (CELL in m["workloads"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_toy_root(str(tmp_path_factory.mktemp("toy_window")))


def run_toy(root, seed, trace):
    import jax
    cell = harness.Cell("toy-window-serve", root=root)
    ctx = harness.make_context(cell, seed, 1.0, trace, time.perf_counter(),
                               jax.devices()[:1], PEAKS)
    out = harness.load_driver(cell).run(ctx)
    return ctx, harness.finish(ctx, out, xplane=XPLANE if trace else None)


@pytest.fixture(scope="module")
def toy_run(toy_root):
    return run_toy(toy_root, 11, trace=True)


def test_toy_cell_of_this_architecture_reads_correct(toy_run):
    """Through the serve driver as it builds any engine: chunked prefill,
    bfloat16 weights and pools in two groups, the scheduler's closed loop,
    then the float32 reference over the sampled requests."""
    ctx, line = toy_run
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert ctx.facts["compile_counts"] == {"prefill": 1, "tick": 1}
    assert ctx.facts["pool_dtype"] == "bfloat16"
    groups = ctx.facts["pool_groups"]
    assert {g: (f["layers"], f["window"]) for g, f in groups.items()} \
        == {"full": (2, 0), "window": (3, 8)}
    assert groups["window"]["num_blocks"] == 3 * 3 + 1
    assert ctx.facts["query_heads"] == {"full": 4, "window": 6}
    assert (ctx.facts["kv_heads"], ctx.facts["head_dim"]) == (2, 16)
    assert ctx.facts["expert_slots"] == 4 * 4
    reported = set(line["metrics"])
    assert {"sched_overhead_ms", "serve_step_mfu_pct", "sched_self_ms_p50",
            "tick_host_ms_p50", "device_idle_pct.serve",
            "expert_load_max_over_mean", "moe_rows_per_pair",
            "tick_ms_p50.saturated", "itl_p95_ms.saturated",
            "ttft_p95_ms.saturated",
            "kv_pool_bytes_per_live_token"} <= reported
    e2e = {m["name"] for m in harness.Cell(CELL).end_to_end}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    # a position of a full layer is 2 x 2 x 16 x 2 B = 128 B, of two 256;
    # three window layers hold at most 12 positions of theirs a slot; a
    # slot of one position holds a block of four in all five layers
    per_token = line["metrics"]["kv_pool_bytes_per_live_token"]["value"]
    assert 256 <= per_token <= 4 * 5 * 128
    held = sum(g["blocks_live"] * g["block_bytes"] for g in groups.values())
    assert per_token == pytest.approx(held / groups["full"]["live_tokens"])
    # the recorded trace holds no kernel of these: nothing to read, no error
    assert not {"grouped_decode_roofline_pct", "moe_ffn_roofline_pct",
                "paged_decode_roofline_pct", "prefill_ms_p50"} & reported


def test_int8_control_fails_the_toy_cells_limit(toy_run):
    """The control (the reference with int8 operands, put in the program's
    place) comes out NOT correct by the percentile check."""
    driver = harness.load_driver(harness.Cell(CELL))
    ctx, line = toy_run
    control = ctx.cell.reference.serve_reference(
        ctx.cell.config, ctx.seed, ctx.facts["sample"], quant="int8")
    got = driver.control_gap(ctx.facts["reference"], control)
    limits = TOY_CELL["limits"]
    assert got["served_logit_gap_p99"] > limits["served_logit_gap_p99"], got
    picks = [c.argmax(axis=-1) for c in control]
    checks = driver.serve_checks(
        [(p, list(k)) for (p, _), k in zip(ctx.facts["sample"], picks)],
        ctx.facts["reference"], limits)
    assert not all(c["ok"] for c in checks), checks


@pytest.mark.parametrize("fault", laguna_reference.FAULTS)
def test_a_wrong_layer_fails_the_toy_cells_limits(toy_run, fault):
    """The planted faults (window layers that attend to everything, gates
    of 1, query heads on the wrong KV head), at full precision, put in
    the program's place: not correct."""
    driver = harness.load_driver(harness.Cell(CELL))
    ctx, _ = toy_run
    wrong = driver.control_gap(
        ctx.facts["reference"], ctx.cell.reference.serve_reference(
            ctx.cell.config, ctx.seed, ctx.facts["sample"], fault=fault))
    limits = TOY_CELL["limits"]
    assert any(wrong[k] > limits[k] for k in limits), wrong


# -- the configuration file and the cost functions ----------------------------

def published():
    cell = harness.Cell(CELL)
    return cell, laguna_reference.dims(cell.config)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Laguna-S-2.1")


def test_configuration_keeps_the_catalog_rows_numbers():
    """Every key of the catalog row under its own name, lists and nested
    groups whole; the keys that count give what is held here, are listed
    in ``reduced`` and have their published values beside them."""
    cell, z = published()
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    config, row = cell.config, catalog_row()
    differ = {k for k, v in row["config"].items() if config[k] != v}
    assert differ == set(entry["reduced"]) == set(config["reduced"]) \
        == {"num_hidden_layers", "num_experts"}
    assert config["published"] == {k: row["config"][k] for k in differ}
    assert entry["source"] == config["source"] == row["source_url"]
    assert (z.L, z.held, z.E, z.K, z.V, z.D) == (8, 16, 256, 10, 100352,
                                                 3072)
    assert z.heads == (48, 72, 72, 72, 48, 72, 72, 72)
    assert z.windows == (None, 512, 512, 512) * 2
    assert z.dense == (True,) + (False,) * 7
    assert (z.H_kv, z.hd, z.F, z.F_e, z.F_s) == (8, 128, 12288, 1024, 1024)
    assert z.rope_full == laguna_reference.Rotary(
        500000.0, 64, 128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    assert z.rope_window == laguna_reference.Rotary(
        10000.0, 128, 1.0, 0, 32.0, 1.0, 1.0)
    assert z.held * config["deployment"]["expert_parallel"] == z.E
    assert {"head_gate", "qk_norm", "shared_expert", "hidden_act",
            "rotary_pairing", "initializer"} <= set(config["assumed"])
    assert [config[k] for k in harness.ARCHITECTURE] == [
        "benchmarks/laguna_reference.py", "benchmarks/laguna_layout.py",
        "benchmarks/laguna_costs.py"]


def test_cells_are_as_issue_35_gives_them():
    """Both new cells' files against the issue's numbers, and no pair of
    configuration and traffic twice."""
    cell, z = published()
    serve, engine = cell.file["serve"], cell.file["serve"]["engine"]
    assert cell.chips == 1 and cell.file["driver"] == "serve_quantile"
    assert engine == {"prefill_chunk": 512, "max_blocks_per_seq": 896,
                      "dtype": "bfloat16"}
    assert (serve["max_slots"], serve["block_size"], serve["attention"],
            serve["sample_requests"]) == (64, 16, "paged", 8)
    assert serve["num_blocks"] == 64 * 896 + 1 == 57345
    mix = cell.traffic
    assert {k: mix[k] for k in ("loop", "clients", "pool", "prompt_len",
                                "max_new", "sigma", "max_total", "balance",
                                "n_sessions", "greedy", "eos")} == {
        "loop": "closed", "clients": 64, "pool": 256,
        "prompt_len": [2048, 12288], "max_new": [512, 2048], "sigma": 0.6,
        "max_total": 14336, "balance": 8, "n_sessions": 0, "greedy": True,
        "eos": None}
    assert mix["max_total"] == 896 * 16
    assert set(cell.file["limits"]) == {"served_logit_gap",
                                        "served_logit_gap_p95"}
    # the longest prompt is over 12k tokens, 23 windows long
    longest = max(len(r["prompt"]) for r in
                  traffic.serve_requests(mix, z.V, 7))
    assert longest > 12000 and longest // 512 >= 23
    long = harness.Cell(LONG)
    closed8 = harness.Cell("serve-1p3b-closed8")
    assert long.config == closed8.config and long.chips == 1
    assert long.file["driver"] == "serve"
    assert long.file["serve"] == dict(closed8.file["serve"], num_blocks=1025)
    assert long.file["limits"] == closed8.file["limits"] \
        == {"served_logit_gap": 0.05}
    assert {k: long.traffic[k] for k in (
        "loop", "clients", "pool", "prompt_len", "max_new", "sigma",
        "max_total", "balance")} == {
        "loop": "closed", "clients": 8, "pool": 32,
        "prompt_len": [1024, 1900], "max_new": [8, 32], "sigma": 0.6,
        "max_total": 2048, "balance": 8}
    # prefill_ms_p50 and prefill_host_ms_p50 move itl_p95_ms, which this
    # cell does not report: a metric lists only cells that report what it
    # moves, so they stay closed8's (PERF.md section 7)
    assert {m["name"] for m in long.per_layer} == {
        "serve_step_mfu_pct", "device_idle_pct.serve", "sched_overhead_ms",
        "sched_self_ms_p50", "tick_host_ms_p50", "setup_compile_s"}
    assert {m["name"] for m in long.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    mine = {m["name"] for m in cell.per_layer}
    assert {"grouped_decode_roofline_pct", "kv_pool_bytes_per_live_token",
            "moe_ffn_roofline_pct", "moe_rows_per_pair",
            "expert_load_max_over_mean", "serve_step_mfu_pct",
            "device_idle_pct.serve", "setup_compile_s"} <= mine
    assert "paged_decode_roofline_pct" not in mine


def test_rows_per_pair_is_declared_for_every_cell_with_held_experts():
    """What ``test_moe_rows_benchmark.py`` held to the LAST entry of the
    list (untrue once anything is appended), by name: the metric as PR 34
    declared it, its cells the two latent ones and, appended, this one;
    every one of them reports it."""
    entry = {m["name"]: m for m in BENCH["per_layer"]}["moe_rows_per_pair"]
    assert entry == {
        "name": "moe_rows_per_pair", "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "model step",
        "moves": "serve_tokens_per_s",
        "workloads": ["serve-pangu718b-closed64",
                      "serve-longcat560b-closed128", CELL]}
    for name in entry["workloads"]:
        assert entry in harness.Cell(name).per_layer
    assert [m["name"] for m in BENCH["per_layer"][-2:]] == [
        "grouped_decode_roofline_pct", "kv_pool_bytes_per_live_token"]


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_file_keeps_every_rule_of_form():
    """Names, units, lengths and keys of ``BENCHMARK.json`` as the
    contract states them, for every entry (this PR's among them)."""
    assert len(open(os.path.join(ROOT, "BENCHMARK.json")).read()) < 65536
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["per_layer"]) <= 128
    line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s
    names = lambda entries: [e["name"] for e in entries]
    for entries in (BENCH["configs"], BENCH["workloads"],
                    BENCH["end_to_end"] + BENCH["per_layer"]):
        assert len(set(names(entries))) == len(entries)
        assert all(NAME.match(n) for n in names(entries))
    cells = set(names(BENCH["workloads"]))
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line(c["source"]) and line(c["why"])
        assert len(c["reduced"]) <= 16 and all(
            NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names(BENCH["configs"]) and line(w["why"])
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    assert {w["config"] for w in BENCH["workloads"]} \
        == set(names(BENCH["configs"]))
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert line(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", ())) <= set(moved), m["name"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", ())) <= cells
    for w in BENCH["workloads"]:
        cell = harness.Cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer


def test_parameters_held_here_against_a_hand_count():
    """ISSUE 35's arithmetic: 2,325.5 M parameters in matrices, plus norm
    scales; 4.66 GB as the program holds it, to the byte the file
    states."""
    import jax
    cell, z = published()
    shapes = jax.eval_shape(
        lambda: cell.layout.seed_params(z, np.uint32(1)))
    leaves = jax.tree_util.tree_leaves(shapes)
    count = sum(int(np.prod(s.shape)) for s in leaves)
    D, hd, F_e = 3072, 128, 1024
    attn = lambda H: 2 * D * H * hd + 2 * D * 8 * hd + D * H
    assert (attn(48), attn(72)) == (44_187_648, 63_135_744)
    router, expert, dense = D * 256, 3 * D * F_e, 3 * D * 12288
    moe = router + expert + 16 * expert
    layers = attn(48) + dense + attn(48) + moe + 6 * (attn(72) + moe)
    matrices = layers + 2 * 100352 * D
    assert round(matrices / 1e6, 1) == 2325.5
    small = 8 * 2 * D + D
    assert count == matrices + small == 2_325_580_800
    # matrices bfloat16; norm scales and the router float32
    nbytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in leaves)
    assert nbytes == 2 * count + 2 * (7 * router + small) == 4_662_276_096
    assert cell.file["sizing"]["weight_bytes"] == nbytes
    assert f"{nbytes:,} bytes" in cell.config["parameters"]
    # the pools: 2 full layers of 57,345 blocks, 6 window layers of a ring
    # of 33 blocks a slot; a block 8 heads x 16 x 128 bfloat16, K and V
    block = 2 * 8 * 16 * 128 * 2
    assert 2 * block == 131_072 and 6 * block * 33 == 12_976_128
    assert cell.file["sizing"]["pool_bytes"] == (
        2 * 57345 * block + 6 * (64 * 33 + 1) * block)


def test_initializer_gives_unit_variance_at_the_published_widths():
    """Assumption (f), checked as PR 33 checked its own: with N(0, 1 /
    fan_in) matrices on a normalised input, queries, keys, gate logits and
    router logits have unit variance at the published widths; a sliding
    layer's scores a spread of 1, a full layer's about 1.7 (YaRN's
    attention factor on the rotated half of q and of k)."""
    import jax
    import jax.numpy as jnp
    _, z = published()
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, z.D), jnp.float32)
    ks = jax.random.split(key, 4)
    mat = lambda k, n: laguna_reference._matrix(k, (z.D, n), z.D).astype(
        jnp.float32)
    pos = jnp.arange(64) * 97
    for r, want in ((z.rope_window, 1.0), (z.rope_full, 1.7)):
        q = laguna_reference._rope((x @ mat(ks[0], 8 * z.hd)).reshape(
            64, 8, z.hd), pos, r)
        k = laguna_reference._rope((x @ mat(ks[1], 8 * z.hd)).reshape(
            64, 8, z.hd), pos, r)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(z.hd)
        assert float(scores.std()) == pytest.approx(want, rel=0.1)
    assert float((x @ mat(ks[2], 72)).std()) == pytest.approx(1.0, rel=0.1)
    assert float((x @ mat(ks[3], 256)).std()) == pytest.approx(1.0, rel=0.1)


def test_cost_functions_against_a_hand_count():
    _, z = published()
    D, hd = 3072, 128
    attn = lambda H: D * (2 * H * hd + 2 * 8 * hd + H)
    moe = D * 256 + 3 * D * 1024 + 3 * D * 1024 * (10 * 16 / 256)
    want = attn(48) + 3 * D * 12288 + attn(48) + moe + 6 * (attn(72) + moe)
    assert laguna_costs.layer_params(z) == pytest.approx(want)
    assert laguna_costs.attention_shape(z) == (8, 8, 128)
    head = 2 * D * 100352
    keys = lambda c: 2 * 4 * 48 * hd * c + 6 * 4 * 72 * hd * min(c, 512)
    assert laguna_costs.decode_flops(z, 6500) == pytest.approx(
        2 * want + head + keys(6500))
    assert laguna_costs.decode_flops(z, 100) == pytest.approx(
        2 * want + head + keys(100))
    p = 2048
    band = 512 * 513 / 2 + (p - 512) * 512
    assert laguna_costs.pairs_seen(512, p) == band
    assert laguna_costs.pairs_seen(512, 300) == 300 * 301 / 2
    assert laguna_costs.prefill_flops(z, p) == pytest.approx(
        2 * want * p + head + 2 * 4 * 48 * hd * p * (p + 1) / 2
        + 6 * 4 * 72 * hd * band)
    assert laguna_costs.serve_flops(z, [p], [1000, 1000]) == pytest.approx(
        laguna_costs.prefill_flops(z, p)
        + 2 * laguna_costs.decode_flops(z, 1000))
    # the decode kernel: K and V rows of 8 heads read once for 48 or 72
    c = laguna_costs.grouped_decode_cost(1000, 64, 72, 8, 128, 2)
    assert c["flops"] == 4 * 72 * 128 * 1000
    assert c["bytes"] == 2 * 1000 * 8 * 128 * 2 + 64 * 72 * 128 * (2 + 4)
    assert laguna_costs.moe_ffn_cost is pangu_costs.moe_ffn_cost
    m = laguna_costs.moe_ffn_cost(z, pairs=40, hits=14)
    assert m["flops"] == 40 * 2 * 3 * D * 1024


# -- the new readers, on made-up contexts --------------------------------------

def test_grouped_decode_reader_against_a_hand_count(monkeypatch):
    from paddle_tpu.obs import trace as obs_trace
    cell, z = published()
    events = [
        {"name": "engine_tick", "ts": 1, "dur": 1,
         "args": {"active": 64, "live_tokens": 400000,
                  "live_tokens_window": 32768}},
        {"name": "engine_tick", "ts": 2, "dur": 1,
         "args": {"active": 60, "live_tokens": 380000,
                  "live_tokens_window": 30720}},
        {"name": "prefill_drain", "ts": 3, "dur": 1, "args": {}},
        {"name": "tick_stage", "ts": 4, "dur": 1}]
    monkeypatch.setattr(
        obs_trace, "session_tracer",
        lambda: types.SimpleNamespace(between=lambda lo, hi: events))
    rec = types.SimpleNamespace(spans={"window": [(0.0, 10.0, {})]})
    groups = {"full": {"layers": 2, "window": 0},
              "window": {"layers": 6, "window": 512}}
    ctx = types.SimpleNamespace(
        cell=cell, dims=z, costs=costs, peaks=PEAKS, rec=rec,
        facts={"pool_groups": groups, "pool_bytes": 2, "kv_heads": 8,
               "head_dim": 128, "query_heads": {"full": 48, "window": 72}},
        trace={"kernel_seconds": {"paged_decode": 0.02}})
    read = harness.load_reader("grouped_decode_roofline_pct")
    hbm = PEAKS["hbm_bytes_per_s"]
    least = 0.0
    for active, full, win in ((64, 400000, 32768), (60, 380000, 30720)):
        for layers, rows, heads in ((2, full, 48), (6, win, 72)):
            nbytes = 2 * rows * 8 * 128 * 2 + active * heads * 128 * 6
            flops = 4 * heads * 128 * rows
            least += layers * max(nbytes / hbm,
                                  flops / PEAKS["bf16_flops_per_s"])
    assert read(ctx) == pytest.approx(100 * least / 0.02)
    # a program without pool groups, a trace without the kernel, a tick
    # without the window's fact: nothing to read, no error
    for change in ({"facts": {}}, {"trace": {"kernel_seconds": {}}},
                   {"trace": None}):
        assert read(types.SimpleNamespace(**{**vars(ctx), **change})) is None
    del events[0]["args"]["live_tokens_window"]
    assert read(ctx) is None
    rec.spans = {}
    assert read(ctx) is None


def test_pool_bytes_reader_against_a_hand_count():
    read = harness.load_reader("kv_pool_bytes_per_live_token")
    groups = {
        "full": {"block_bytes": 131072, "blocks_live": 26000,
                 "live_tokens": 415000},
        "window": {"block_bytes": 393216, "blocks_live": 64 * 33,
                   "live_tokens": 415000}}
    ctx = types.SimpleNamespace(facts={"pool_groups": groups})
    want = (26000 * 131072 + 64 * 33 * 393216) / 415000
    assert read(ctx) == pytest.approx(want) and 10000 < want < 12000
    # a window layer that kept everything would hold 8 layers' rows
    assert 8 * 2 * 8 * 128 * 2 == 32768
    assert read(types.SimpleNamespace(facts={})) is None
    groups["full"]["live_tokens"] = groups["window"]["live_tokens"] = 0
    assert read(ctx) is None
