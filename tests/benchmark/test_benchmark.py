"""The benchmark's own tests (``BENCHMARK.json`` lists this directory
under ``paths``): both drivers at a toy size on the CPU through the same
cell runner the chip runs use, the contract's character rules, discovery
of every file by name (a configuration's three architecture modules
among them, and a toy configuration of ANOTHER architecture that comes as
new files only), the trace reduction on a recorded trace, the cost
functions against hand counts, the float32 reference against
``TransformerLM``, and the planted faults that ``correct`` has to catch.
Nothing here touches a TPU library or describes a topology.
"""

import copy
import glob
import json
import os
import re
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import costs, gpt2_costs, harness, layout, reference  # noqa: E402
from benchmarks import trace_reduce, traffic  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
XPLANE = glob.glob(os.path.join(
    ROOT, "experiments", "trace_resnet50", "**", "*.xplane.pb"),
    recursive=True)[0]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

TOY_CONFIG = {
    "source": "a toy for the CPU tests", "n_layer": 2, "n_embd": 32,
    "n_head": 2, "n_inner": 64, "n_positions": 32, "vocab_size": 128,
    "activation_function": "gelu", "layer_norm_epsilon": 1e-5,
    "initializer_range": 0.02,
    "reference": "benchmarks/reference.py", "layout": "benchmarks/layout.py",
    "costs": "benchmarks/gpt2_costs.py",
    "departures": {"activation_function": "gelu_tanh",
                   "layer_norm_epsilon": 1e-6}}
# ANOTHER architecture, as a later PR would bring it: a file with none of
# the GPT-2 keys and three modules of its own, whose Dims shares one
# attribute name with the GPT-2 one: V. (The program has one model today,
# so the toy's modules translate and delegate to the GPT-2 ones.)
ALT_CONFIG = {
    "source": "a toy of another architecture for the CPU tests",
    "num_hidden_layers": 2, "hidden_size": 32, "num_attention_heads": 2,
    "intermediate_size": 64, "max_position_embeddings": 32,
    "padded_vocab_size": 128, "layer_norm_eps": 1e-6, "init_std": 0.02,
    "reference": "benchmarks/alt/reference.py",
    "layout": "benchmarks/alt/layout.py", "costs": "benchmarks/alt/costs.py"}
ALT_MODULES = {
    "reference.py": '''
from typing import NamedTuple
from benchmarks import reference as gpt2


class AltDims(NamedTuple):
    depth: int
    width: int
    heads: int
    inner: int
    positions: int
    V: int
    ln_eps: float
    std: float

    def gpt2(self):
        return gpt2.Dims(L=self.depth, D=self.width, H=self.heads,
                         F=self.inner, P=self.positions, V=self.V,
                         eps=self.ln_eps, act="gelu_tanh", init_std=self.std)


def dims(cfg):
    return AltDims(cfg["num_hidden_layers"], cfg["hidden_size"],
                   cfg["num_attention_heads"], cfg["intermediate_size"],
                   cfg["max_position_embeddings"], cfg["padded_vocab_size"],
                   cfg["layer_norm_eps"], cfg["init_std"])


def _gpt2_config(cfg):
    z = dims(cfg).gpt2()
    return {"n_layer": z.L, "n_embd": z.D, "n_head": z.H, "n_inner": z.F,
            "n_positions": z.P, "vocab_size": z.V,
            "layer_norm_epsilon": z.eps, "activation_function": z.act,
            "initializer_range": z.init_std}


def serve_reference(cfg, seed, sequences, quant=None):
    return gpt2.serve_reference(_gpt2_config(cfg), seed, sequences, quant)


def train_reference(cfg, seed, batches, opt, quant=None, fault=None):
    return gpt2.train_reference(_gpt2_config(cfg), seed, batches, opt,
                                quant, fault)
''',
    "layout.py": '''
from benchmarks import layout as gpt2
from benchmarks.layout import engine_facts, flatten_program, flatten_reference


def build_model(z):
    return gpt2.build_model(z.gpt2())


def loss_fn(z):
    return gpt2.loss_fn(z.gpt2())


def program_params(z, seed):
    return gpt2.program_params(z.gpt2(), seed)


def seed_params(z, seed):
    return gpt2.seed_params(z.gpt2(), seed)
''',
    "costs.py": '''
from benchmarks import gpt2_costs as gpt2


def train_flops_per_token(z, seq_len):
    return gpt2.train_flops_per_token(z.gpt2(), seq_len)


def serve_flops(z, prompts, contexts):
    return gpt2.serve_flops(z.gpt2(), prompts, contexts)


def attention_shape(z):
    return gpt2.attention_shape(z.gpt2())
'''}
TOY_TRAIN = {
    "driver": "train", "trace_seconds": 0.2,
    "kernels": ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"],
    "train": {"batch": 4, "warm_steps": 4,
              "optimizer": {"name": "adam", "lr": 1e-3, "b1": 0.9,
                            "b2": 0.999, "eps": 1e-8}},
    "limits": {"loss2_gap": 2e-4, "loss3_gap": 2e-4,
               "grad_norm_gap": 0.0015, "change_norm_gap": 0.01}}
TOY_SERVE = {
    "driver": "serve", "trace_seconds": 0.2, "kernels": ["paged_decode"],
    "serve": {"max_slots": 2, "block_size": 8, "sample_requests": 3},
    "limits": {"served_logit_gap": 1e-4}}
TOY_TRAFFIC = {
    "toy-seq": {"kind": "train", "seq_len": 32,
                "tokens": {"dist": "zipf", "exponent": 1.0}},
    "toy-closed": {"kind": "serve", "loop": "closed", "clients": 2,
                   "pool": 6, "prompt_len": [4, 20], "max_new": [3, 8],
                   "sigma": 0.6, "max_total": 32},
    "toy-open": {"kind": "serve", "loop": "open", "pool": 6,
                 "rate_rps": 20.0, "arrival": "poisson",
                 "prompt_len": [4, 20], "max_new": [3, 8], "sigma": 0.6,
                 "max_total": 32}}
TOY_CONFIGS = {"toy": TOY_CONFIG, "alt": ALT_CONFIG}
TOY_CELLS = {"toy-train": ("toy", "toy-seq", TOY_TRAIN),
             "toy-serve": ("toy", "toy-closed", TOY_SERVE),
             "toy-serve-open": ("toy", "toy-open", TOY_SERVE),
             "alt-train": ("alt", "toy-seq", TOY_TRAIN),
             "alt-serve": ("alt", "toy-closed", TOY_SERVE)}


def make_toy_root(root):
    """A checkout-shaped directory holding ONLY new files: two toy
    configurations (the second of another architecture, with its three
    modules), five toy cells and their mixes, added beside a copy of the
    per-layer readers. The harness finds each by name."""
    bdir = os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic", "workloads", "alt"):
        os.makedirs(os.path.join(bdir, sub))
    shutil.copytree(os.path.join(ROOT, "benchmarks", "layer_metrics"),
                    os.path.join(bdir, "layer_metrics"))
    for name, config in TOY_CONFIGS.items():
        with open(os.path.join(bdir, "configs", name + ".json"), "w") as f:
            json.dump(config, f)
    for name, text in ALT_MODULES.items():
        with open(os.path.join(bdir, "alt", name), "w") as f:
            f.write(text)
    for name, mix in TOY_TRAFFIC.items():
        with open(os.path.join(bdir, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    bench = copy.deepcopy(BENCH)
    bench["configs"] = [{"name": name, "source": "none", "reduced": [],
                         "file": f"benchmarks/configs/{name}.json",
                         "why": "toy"} for name in TOY_CONFIGS]
    bench["workloads"] = []
    for name, (config, mix, spec) in TOY_CELLS.items():
        with open(os.path.join(bdir, "workloads", name + ".json"),
                  "w") as f:
            json.dump(spec, f)
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": 1,
                                   "why": "toy"})
    real = {w["name"]: harness.Cell(w["name"]).driver
            for w in BENCH["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            drivers = {real[w] for w in m["workloads"]}
            m["workloads"] = [n for n, (_, _, s) in TOY_CELLS.items()
                              if s["driver"] in drivers]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_toy_root(str(tmp_path_factory.mktemp("toy_checkout")))


def run_toy(root, name, seed=3, seconds=0.5, trace=False):
    """One toy run through the cell runner's own steps. With ``trace`` the
    run is a traced one (the recorded trace stands in for the CPU's, which
    has no device plane) and BOTH result lines are built from it: the
    per-layer line, then the end-to-end line of the same measurements."""
    import time
    import jax
    cell = harness.Cell(name, root=root)
    ctx = harness.make_context(cell, seed, seconds, trace,
                               time.perf_counter(), jax.devices()[:1],
                               harness.load_peaks("TPU v5 lite"))
    out = harness.load_driver(cell).run(ctx)
    if not trace:
        return harness.finish(ctx, out)
    layers = harness.finish(ctx, out, xplane=XPLANE)
    ctx.trace_on = False
    return harness.finish(ctx, out), layers


def assert_contract_line(result, cell_metrics):
    line = json.loads(json.dumps(result))          # what emit() prints
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert set(line["metrics"]) <= set(cell_metrics)
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and UNIT.match(m["unit"])
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    return line


# -- the two drivers, through the cell runner --------------------------------

def test_train_driver_toy(toy_root, capsys):
    cell = harness.Cell("toy-train", root=toy_root)
    result, traced = run_toy(toy_root, "toy-train", trace=True)
    line = assert_contract_line(result, [m["name"] for m in cell.end_to_end])
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    layers = assert_contract_line(traced,
                                  [m["name"] for m in cell.per_layer])
    assert {"train_step_ms_p50", "train_step_mfu_pct",
            "device_idle_pct.train"} <= set(layers["metrics"])
    assert "flash_roofline_pct" not in layers["metrics"]   # nothing to read
    assert layers["device"]["busy_s"] > 0
    assert len(layers["breakdown"]["device_ops"]) <= 10
    assert list(layers)[-1] == "checks"
    harness.emit(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1])["correct"] is True
    assert "check loss2_gap" in err and err.strip().endswith("correct: True")


@pytest.mark.parametrize("cell_name", ["toy-serve", "toy-serve-open"])
def test_serve_driver_toy(toy_root, cell_name):
    cell = harness.Cell(cell_name, root=toy_root)
    result, traced = run_toy(toy_root, cell_name, seconds=1.0, trace=True)
    line = assert_contract_line(result, [m["name"] for m in cell.end_to_end])
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    layers = assert_contract_line(traced,
                                  [m["name"] for m in cell.per_layer])
    assert {"tick_ms_p50", "prefill_ms_p50", "sched_overhead_ms",
            "serve_step_mfu_pct", "ttft_p95_ms",
            "device_idle_pct.serve"} <= set(layers["metrics"])
    assert "paged_decode_roofline_pct" not in layers["metrics"]


@pytest.mark.parametrize("cell_name, mfu", [
    ("alt-train", "train_step_mfu_pct"), ("alt-serve", "serve_step_mfu_pct")])
def test_other_architecture_comes_as_new_files_only(toy_root, cell_name, mfu):
    """A configuration that is not GPT-2-shaped (other keys, a Dims of its
    own, its three modules in the toy root) runs through the same harness,
    drivers and readers, reads ``correct`` and reports the step's MFU."""
    cell = harness.Cell(cell_name, root=toy_root)
    assert not {"n_layer", "n_embd", "n_head", "n_inner", "n_positions",
                "vocab_size"} & set(cell.config)
    z = cell.reference.dims(cell.config)
    assert set(z._fields) & set(reference.Dims._fields) == {"V"}
    assert os.path.dirname(cell.layout.__file__) == os.path.join(
        toy_root, "benchmarks", "alt")
    result, traced = run_toy(toy_root, cell_name, seconds=1.0, trace=True)
    line = assert_contract_line(result, [m["name"] for m in cell.end_to_end])
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["checks"]) == set(cell.file["limits"])
    layers = assert_contract_line(traced,
                                  [m["name"] for m in cell.per_layer])
    assert layers["metrics"][mfu]["value"] > 0
    # the same arithmetic as the GPT-2 toy's cell of the same sizes
    twin = harness.Cell(cell_name.replace("alt", "toy"), root=toy_root)
    assert cell.costs.attention_shape(z) == twin.costs.attention_shape(
        twin.reference.dims(twin.config)) == (2, 2, 16)


def test_same_seed_same_inputs_and_large_seeds():
    mix = TOY_TRAFFIC["toy-closed"]
    big = 2 ** 31 + 12345
    a, b = (traffic.serve_requests(mix, 128, big) for _ in range(2))
    assert a == b != traffic.serve_requests(mix, 128, big + 1)
    sizes = lambda reqs: sorted(len(r["prompt"]) for r in reqs)
    assert sizes(a) == sizes(traffic.serve_requests(mix, 128, 7))
    x1, _ = next(traffic.train_batches(TOY_TRAFFIC["toy-seq"], 128, 2, big))
    x2, _ = next(traffic.train_batches(TOY_TRAFFIC["toy-seq"], 128, 2, big))
    assert (x1 == x2).all() and len({tuple(r) for r in x1}) == 2
    z = reference.dims(TOY_CONFIG)
    w1, w2 = reference.init_weights(z, big), reference.init_weights(z, big)
    assert (np.asarray(w1["wte"]) == np.asarray(w2["wte"])).all()
    assert not (np.asarray(w1["wte"]) == np.asarray(
        reference.init_weights(z, big - 2 ** 31)["wte"])).all()


# -- planted faults: correct has to come out false ---------------------------

def test_fault_state_unchanged(toy_root, monkeypatch):
    from paddle_tpu.train import trainer as trainer_mod
    monkeypatch.setattr(trainer_mod, "apply_updates", lambda p, u: p)
    result = run_toy(toy_root, "toy-train")
    assert result["correct"] is False
    assert result["checks"]["change_norm_gap"]["value"] == pytest.approx(
        1.0, abs=1e-3)


def test_fault_half_batch_left_out(toy_root, monkeypatch):
    from paddle_tpu.nn import costs as nn_costs
    whole = nn_costs.softmax_cross_entropy
    monkeypatch.setattr(
        nn_costs, "softmax_cross_entropy",
        lambda logits, labels: whole(logits[:logits.shape[0] // 2],
                                     labels[:labels.shape[0] // 2]))
    result = run_toy(toy_root, "toy-train")
    assert result["correct"] is False
    over = [n for n, c in result["checks"].items()
            if c["value"] > c["limit"]]
    assert "grad_norm_gap" in over, result["checks"]


def test_fault_token_altered(toy_root, monkeypatch):
    from paddle_tpu.serve.engine import DecodeEngine
    tick = DecodeEngine.decode_tick

    def altered(self):
        front = tick(self)
        if self.ticks % 3 == 0:
            for slot, toks in self.last_accepted.items():
                toks[-1] = (toks[-1] + 1) % self.model.emb.vocab
                self.tokens[slot] = front[slot] = toks[-1]
        return front
    monkeypatch.setattr(DecodeEngine, "decode_tick", altered)
    result = run_toy(toy_root, "toy-serve", seconds=1.0)
    assert result["correct"] is False
    c = result["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]


def test_control_int8_fails_the_comparison(toy_root):
    """The reference in the next precision down, put in the program's
    place, has to read over the limits (the chip-sized readings that set
    the limits are in PERF.md; this keeps the comparison honest at a size
    a test can hold)."""
    from benchmarks.drivers import serve, train
    z = reference.dims(TOY_CONFIG)
    batches = [b for b, _ in zip(traffic.train_batches(
        TOY_TRAFFIC["toy-seq"], z.V, 4, 5), range(3))]
    opt = TOY_TRAIN["train"]["optimizer"]
    ref = reference.train_reference(TOY_CONFIG, 5, batches, opt)
    ctl = reference.train_reference(TOY_CONFIG, 5, batches, opt,
                                    quant="int8")
    checks = train.compare(layout, train.as_program(layout, ctl), ref,
                           TOY_TRAIN["limits"])
    assert not all(c["ok"] for c in checks), checks
    same = train.compare(layout, train.as_program(layout, ref), ref,
                         TOY_TRAIN["limits"])
    assert all(c["value"] == 0 for c in same)
    # serving, on a vocabulary wide enough for int8 to change a choice
    wide = dict(TOY_CONFIG, vocab_size=8192)
    rng = np.random.RandomState(0)
    seqs = [(list(rng.randint(1, 8192, 12)), list(rng.randint(1, 8192, 20)))
            for _ in range(6)]
    ref_logits = reference.serve_reference(wide, 7, seqs)
    ctl_logits = reference.serve_reference(wide, 7, seqs, quant="int8")
    assert serve.control_gap(ref_logits, ref_logits) == 0.0
    assert serve.control_gap(ref_logits, ctl_logits) \
        > TOY_SERVE["limits"]["served_logit_gap"]


# -- BENCHMARK.json and discovery --------------------------------------------

def test_benchmark_json_names_units_and_arrows():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for n in names + [m["name"] for m in metrics]:
        assert NAME.match(n), n
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1
                                    for m in e2e.values())
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in moved.get("workloads", cells), (m["name"], w)
        assert ("mfu" in m["name"].split("_")) == ("mfu" in m["name"])
    for w in BENCH["workloads"]:
        cell = harness.Cell(w["name"])
        assert {m["name"] for m in cell.end_to_end} > {"setup_s"}
        assert cell.per_layer and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200


def test_every_file_is_found_by_name():
    bdir = os.path.join(ROOT, "benchmarks")
    listed = lambda sub, ext: {os.path.splitext(f)[0] for f in os.listdir(
        os.path.join(bdir, sub)) if f.endswith(ext)}
    cells = [harness.Cell(w["name"]) for w in BENCH["workloads"]]
    assert listed("workloads", ".json") == {c.name for c in cells}
    assert listed("traffic", ".json") == {c.entry["traffic"] for c in cells}
    assert {os.path.join("benchmarks", "configs", f) for f in os.listdir(
        os.path.join(bdir, "configs"))} == {c["file"]
                                            for c in BENCH["configs"]}
    assert {c["name"] for c in BENCH["configs"]} \
        == {c.entry["config"] for c in cells}
    per_layer = {m["name"] for m in BENCH["per_layer"]}
    assert {f[:-3] for f in os.listdir(os.path.join(bdir, "layer_metrics"))
            if f.endswith(".py")} == per_layer
    for name in per_layer:
        assert callable(harness.load_reader(name))
    for c in cells:
        assert os.path.exists(os.path.join(bdir, "drivers",
                                           c.driver + ".py"))
        # its three architecture modules load from the paths its file
        # names, and its own dims reads its own file
        for key in harness.ARCHITECTURE:
            assert getattr(c, key).__file__ == os.path.join(
                ROOT, c.config[key])
        z = c.reference.dims(c.config)
        assert isinstance(z.V, int) and z.V > 0 and hash(z) == hash(
            c.reference.dims(c.config))
        assert all(n > 0 for n in c.costs.attention_shape(z))
        assert set(c.file["limits"]) and c.file["sizing"]
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("name", ["cerebras-gpt-590m", "cerebras-gpt-1.3b"])
def test_cerebras_configurations_keep_their_published_shape(name):
    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    config = harness.load_json(os.path.join(ROOT, entry["file"]))
    for key in ("n_layer", "n_embd", "n_head", "n_inner", "n_positions",
                "vocab_size"):
        assert isinstance(config[key], int)
    assert config["n_embd"] // config["n_head"] == 128
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    assert [config[k] for k in harness.ARCHITECTURE] == [
        "benchmarks/reference.py", "benchmarks/layout.py",
        "benchmarks/gpt2_costs.py"]
    z = reference.dims(config)
    assert gpt2_costs.attention_shape(z) == (config["n_layer"],
                                             config["n_head"], 128)


def test_configuration_naming_a_missing_module_fails_with_its_path(tmp_path):
    root = make_toy_root(str(tmp_path))
    path = os.path.join(root, "benchmarks", "configs", "alt.json")
    for key, named in (("costs", "benchmarks/alt/no_such_costs.py"),
                       ("layout", None)):
        config = dict(ALT_CONFIG)
        if named:
            config[key] = named
        else:
            del config[key]
        with open(path, "w") as f:
            json.dump(config, f)
        with pytest.raises((FileNotFoundError, KeyError),
                           match=named or key):
            harness.Cell("alt-serve", root=root)
        assert harness.Cell("toy-serve", root=root).costs is not None
    # a module of the harness's own checkout is found from a toy root
    assert harness.Cell("toy-serve", root=root).reference.__file__ \
        == os.path.join(ROOT, "benchmarks", "reference.py")


def test_harness_drivers_and_readers_spell_no_architecture():
    """Of a configuration they know its name and the modules its file
    names: no GPT-2 key, model class or leaf name outside those modules."""
    bdir = os.path.join(ROOT, "benchmarks")
    files = [os.path.join(bdir, f) for f in (
        "harness.py", "run.py", "traffic.py", "trace_reduce.py",
        "control.py")]
    files += glob.glob(os.path.join(bdir, "drivers", "*.py"))
    files += glob.glob(os.path.join(bdir, "layer_metrics", "*.py"))
    assert len(files) > 20
    word = re.compile(r"n_embd|n_head|n_layer|TransformerLM|wte")
    found = [(os.path.relpath(f, ROOT), m) for f in files
             for m in word.findall(open(f).read())]
    assert not found, found
    imports = re.compile(r"^\s*(?:from|import)\s+(benchmarks[\w.]*)"
                         r"(?:\s+import\s+(.*))?", re.M)
    for f in glob.glob(os.path.join(bdir, "drivers", "*.py")) \
            + [os.path.join(bdir, "control.py")]:
        for module, names in imports.findall(open(f).read()):
            assert module == "benchmarks" and set(
                n.strip() for n in names.split(",")) <= {"harness", "traffic"}, (
                    os.path.relpath(f, ROOT), module, names)


# -- the yardstick ------------------------------------------------------------

def test_trace_reduction_on_a_recorded_trace():
    r = trace_reduce.reduce_trace(XPLANE, kernels=["select_and_scatter"])
    assert r["n_devices"] == 1 and r["window_s"] > 0
    assert 0.0 < r["busy_s"] / r["window_s"] <= 1.0
    ops = dict(r["device_ops"])
    assert len(ops) == 10 and "fusion" in ops
    assert all(0 < s <= r["busy_s"] for s in ops.values())
    assert r["kernel_seconds"]["select_and_scatter"] \
        >= r["op_seconds"]["select_and_scatter"] > 0
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace_reduce.op_kind(trace_reduce.op_name(
        "%fusion.12 = f32[8]{0} fusion(...)")) == "fusion"


def test_cost_functions_against_a_hand_count():
    z = reference.Dims(L=2, D=8, H=2, F=16, P=4, V=10, eps=1e-5,
                       act="gelu", init_std=0.02)
    # per layer 4*8*8 + 2*8*16 = 512; readout 10*8 = 80
    assert gpt2_costs.matmul_params(z) == 2 * 512 + 80 == 1104
    # forward per token at T=4: 2*1104 + 4*D*L*(T+1)/2 = 2208 + 160
    assert gpt2_costs.train_flops_per_token(z, 4) == 3 * (2208 + 160)
    assert gpt2_costs.decode_flops(z, 3) == 2208 + 4 * 8 * 2 * 3
    # prompt of 3: body 2*1024*3, one readout row 160, pairs 6 -> 4*8*2*6
    assert gpt2_costs.prefill_flops(z, 3) == 6144 + 160 + 384
    assert gpt2_costs.serve_flops(z, [3], [3]) == 6688 + 2400
    assert gpt2_costs.attention_shape(z) == (2, 2, 4)
    f = costs.flash_cost(batch=1, heads=2, seq_len=4, head_dim=4)
    assert f["flops"] == 3 * 4 * 4 * (2 * 10)       # 10 pairs a head
    assert f["bytes"] == 12 * (2 * 4 * 4 * 2) + 2 * 2 * 4 * 4
    p = costs.paged_decode_cost(live_tokens=5, slots=2, heads=2,
                                head_dim=4, pool_bytes=4)
    assert p == {"flops": 4 * 2 * 4 * 5,
                 "bytes": 2 * 5 * 2 * 4 * 4 + 2 * 2 * 2 * 4 * 4}
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.roofline_seconds(50, 20, peaks) == (2.0, "bytes")
    assert costs.roofline_seconds(500, 20, peaks) == (5.0, "flops")
    # the 590M configuration: ISSUE 24's 3.86 GFLOP a token
    big = reference.dims(harness.Cell("train-590m-seq2048").config)
    assert gpt2_costs.train_flops_per_token(big, 2048) == pytest.approx(
        3.86e9, rel=0.01)


def test_reference_agrees_with_transformer_lm():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import TransformerLM
    z = reference.dims(TOY_CONFIG)
    model = TransformerLM(vocab=z.V, dim=z.D, num_layers=z.L,
                          num_heads=z.H, ffn_hidden=z.F, max_len=z.P)
    built = layout.build_model(z)        # what the drivers build, with flash
    assert type(built) is TransformerLM
    assert (built.emb.vocab, len(built.blocks), built.max_len) == (
        z.V, z.L, z.P)
    w = reference.init_weights(z, 11)
    variables = {"params": layout.to_program_tree(w, z.L), "state": {}}
    ids = jnp.asarray(np.random.RandomState(0).randint(0, z.V, (2, 24)))
    with jax.default_matmul_precision("highest"):
        got = model.apply(variables, ids)
        h = reference.hidden(w, ids, z)
        want = jnp.dot(h, w["wte"].T, precision="highest")
    assert got.shape == want.shape == (2, 24, z.V)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)
    # the program's own init draws the same tree of leaves
    drawn = model.init(jax.random.PRNGKey(0), ids, train=True)["params"]
    assert jax.tree_util.tree_structure(drawn) \
        == jax.tree_util.tree_structure(variables["params"])
    assert jax.tree_util.tree_map(jnp.shape, drawn) \
        == jax.tree_util.tree_map(jnp.shape, variables["params"])
