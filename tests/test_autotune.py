"""Autotuner + warmup tests (ISSUE 16): the persistent per-shape kernel
autotuner's cache contract (round-trip, corrupt/stale degrade silently,
atomic concurrent writers), the zero-overhead/bypass pins (disabled →
untimed default; explicit blocks → bit-identical, tuner never consulted),
the shared ``time_kernel`` util's compile-discard semantics, the fused
LN+matmul kernel as the first autotuned citizen, and the engine/trainer
warmup entry points (token/params-invisible, compile counts pinned)."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.nn import autotune
from paddle_tpu.nn.fused_ln import fused_ln_matmul, ln_matmul_reference
from paddle_tpu.nn.pallas_attention import flash_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_tuner_state(monkeypatch):
    """Every test starts with the tuner off and zeroed counters, and
    never inherits a cache dir from the environment."""
    monkeypatch.delenv(autotune.ENV_VAR, raising=False)
    autotune.reset()
    autotune.reset_stats()
    yield
    autotune.reset()
    autotune.reset_stats()


def _runner_factory(costs, calls):
    """A fake kernel runner: cand ``{"b": i}`` sleeps ``costs[i]``."""
    def runner(b):
        calls.append(b)
        time.sleep(costs[b])
        return b
    return runner


# ---------------------------------------------------------------------------
# choose(): gating, round-trip, failure semantics
# ---------------------------------------------------------------------------

def test_disabled_returns_default_untimed(tmp_path):
    calls = []
    got = autotune.choose(
        "k", key="k|8|f32|cpu", candidates=[{"b": 0}, {"b": 1}],
        runner=_runner_factory([0, 0], calls), default={"b": 7})
    assert got == {"b": 7}
    assert calls == []                       # zero trials
    assert autotune.stats() == {"trials": 0, "hits": 0, "misses": 0}
    assert autotune.cache_file() is None     # zero disk I/O possible


def test_cache_round_trip(tmp_path):
    autotune.enable(str(tmp_path))
    calls = []
    key = autotune.make_key("k", shape=(4, 8), dtype="float32",
                            platform="cpu")
    kw = dict(key=key, candidates=[{"b": 0}, {"b": 1}],
              runner=_runner_factory([0.03, 0.0], calls), default={"b": 0})
    got = autotune.choose("k", **kw)
    assert got == {"b": 1}                   # the faster candidate wins
    # each candidate ran twice: one discarded compile iter + one timed
    assert sorted(set(calls)) == [0, 1]
    assert autotune.stats()["misses"] == 1
    assert autotune.stats()["trials"] == 2
    # second selection: zero trials, straight from disk
    calls.clear()
    got2 = autotune.choose("k", **kw)
    assert got2 == {"b": 1} and calls == []
    assert autotune.stats()["hits"] == 1
    # the file is a complete schema-versioned document
    with open(autotune.cache_file()) as f:
        doc = json.load(f)
    assert doc["schema"] == autotune.SCHEMA_VERSION
    assert doc["entries"][key]["config"] == {"b": 1}
    assert doc["entries"][key]["trials"] == 2


@pytest.mark.parametrize("corruption", [
    b"{not json at all",                                   # unparseable
    b'{"schema": 1, "entries": ',                          # truncated
    b'[1, 2, 3]',                                          # wrong shape
])
def test_corrupt_cache_silently_retunes(tmp_path, corruption):
    autotune.enable(str(tmp_path))
    with open(autotune.cache_file(), "wb") as f:
        f.write(corruption)
    calls = []
    got = autotune.choose(
        "k", key="kk", candidates=[{"b": 0}],
        runner=_runner_factory([0.0], calls), default={"b": 9})
    assert got == {"b": 0} and calls        # re-tuned, no exception
    with open(autotune.cache_file()) as f:  # and the file healed
        assert json.load(f)["entries"]["kk"]["config"] == {"b": 0}


def test_schema_bump_ignores_stale_entries(tmp_path):
    autotune.enable(str(tmp_path))
    stale = {"schema": autotune.SCHEMA_VERSION + 1,
             "entries": {"kk": {"config": {"b": 5}}}}
    with open(autotune.cache_file(), "w") as f:
        json.dump(stale, f)
    calls = []
    got = autotune.choose(
        "k", key="kk", candidates=[{"b": 0}],
        runner=_runner_factory([0.0], calls), default={"b": 9})
    assert got == {"b": 0}                  # NOT the stale {"b": 5}
    assert autotune.stats()["misses"] == 1
    with open(autotune.cache_file()) as f:
        doc = json.load(f)
    assert doc["schema"] == autotune.SCHEMA_VERSION
    assert "kk" in doc["entries"]


def test_all_candidates_fail_raises_stores_nothing(tmp_path):
    """One failing candidate is skipped; when every candidate fails the
    error propagates (a kernel that cannot compile must be seen) and the
    cache is not poisoned."""
    autotune.enable(str(tmp_path))

    def boom(**kw):
        raise ValueError("mis-tiled")

    with pytest.raises(ValueError, match="mis-tiled"):
        autotune.choose("k", key="kk", candidates=[{"b": 0}, {"b": 1}],
                        runner=boom, default={"b": 7})
    assert not os.path.exists(autotune.cache_file())   # cache not poisoned

    def one_bad(b):
        if b == 0:
            raise ValueError("mis-tiled")

    got = autotune.choose("k", key="kk", candidates=[{"b": 0}, {"b": 1}],
                          runner=one_bad, default={"b": 7})
    assert got == {"b": 1}


# ---------------------------------------------------------------------------
# concurrent writers: atomic rename keeps the file a complete document
# ---------------------------------------------------------------------------

_WRITER = """
import sys
sys.path.insert(0, {repo!r})
from paddle_tpu.nn import autotune
path, key = sys.argv[1], sys.argv[2]
for i in range(120):
    autotune._store(path, key, {{"config": {{"i": i}}, "best_s": 0.0,
                                 "trials": 1, "kernel": "k"}})
print("done")
"""


def test_concurrent_writers_never_tear_the_file(tmp_path):
    path = str(tmp_path / autotune.CACHE_BASENAME)
    code = _WRITER.format(repo=REPO)
    procs = [subprocess.Popen([sys.executable, "-c", code, path, key],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for key in ("ka", "kb")]
    # hammer reads while both writers race: every observation must be
    # either no-file-yet or a COMPLETE parseable document (os.replace is
    # atomic — a torn read is the failure this test exists to catch)
    deadline = time.time() + 60
    observations = 0
    while any(p.poll() is None for p in procs) and time.time() < deadline:
        entries = autotune._load(path)      # raises on a torn read? no —
        assert isinstance(entries, dict)    # _load never raises; but a
        if os.path.exists(path):            # direct parse must succeed too
            with open(path) as f:
                json.load(f)
            observations += 1
    for p in procs:
        out, err = p.communicate(timeout=60)
        assert p.returncode == 0, err[-500:]
        assert "done" in out
    assert observations > 0
    # merge-with-disk: with 120 interleaved writes each, both keys survive
    final = autotune._load(path)
    assert set(final) == {"ka", "kb"}
    with open(path) as f:
        assert json.load(f)["schema"] == autotune.SCHEMA_VERSION


# ---------------------------------------------------------------------------
# time_kernel: the compile iteration is discarded
# ---------------------------------------------------------------------------

def test_time_kernel_discards_first_iteration():
    calls = []

    def fn():
        calls.append(len(calls))
        if len(calls) == 1:
            time.sleep(0.15)            # the "compile" hit
        return np.float32(1.0)

    wall, out = autotune.time_kernel(fn, warmup=1, iters=2, fence=None)
    assert calls == [0, 1, 2]           # 1 discarded + 2 timed
    assert wall < 0.15                  # the sleep did NOT leak into timing
    assert out == np.float32(1.0)


def test_time_kernel_fences_jax_result():
    x = jnp.ones((64, 64))
    wall, out = autotune.time_kernel(jnp.dot, x, x, warmup=1, iters=1)
    assert wall > 0
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ x))


# ---------------------------------------------------------------------------
# kernel integration: bypass + bit-identity pins
# ---------------------------------------------------------------------------

def _qkv(shape=(1, 2, 128, 16)):
    rng = np.random.RandomState(0)
    return tuple(jnp.asarray(rng.normal(size=shape).astype(np.float32))
                 for _ in range(3))


def test_explicit_blocks_bypass_tuner(tmp_path):
    q, k, v = _qkv()
    want = np.asarray(flash_attention(q, k, v, None, True, None, 64, 64,
                                      True))
    autotune.enable(str(tmp_path))
    got = np.asarray(flash_attention(q, k, v, None, True, None, 64, 64,
                                     True))
    # bit-identical AND the tuner was never consulted: no trials, no file
    assert (got == want).all()
    assert autotune.stats() == {"trials": 0, "hits": 0, "misses": 0}
    assert not os.path.exists(autotune.cache_file())


def test_tuned_flash_is_bit_identical_and_caches(tmp_path):
    q, k, v = _qkv()
    baseline = np.asarray(flash_attention(q, k, v))      # heuristic path
    autotune.enable(str(tmp_path))
    tuned = np.asarray(flash_attention(q, k, v))         # tuning path
    assert (tuned == baseline).all()    # block sizes never change math
    s = autotune.stats()
    assert s["misses"] == 1 and s["trials"] >= 1
    # warm process: same call is a pure cache hit
    autotune.reset_stats()
    tuned2 = np.asarray(flash_attention(q, k, v))
    assert (tuned2 == baseline).all()
    assert autotune.stats() == {"trials": 0, "hits": 1, "misses": 0}
    entries = autotune._load(autotune.cache_file())
    assert any(k_.startswith("flash_fwd|") for k_ in entries)


def test_fused_ln_matmul_matches_reference(tmp_path):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.normal(size=(128, 32)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(32, 256)).astype(np.float32))
    scale = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))
    for sc, bi in ((None, None), (scale, None), (scale, bias)):
        got = fused_ln_matmul(x, w, sc, bi)
        want = ln_matmul_reference(x, w, sc, bi)
        # f32-roundoff match, not bit-identity: the fused kernel body is
        # one XLA computation, whose FMA contraction can differ by 1 ulp
        # from the op-at-a-time eager reference
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    # the autotuned path selects a dividing config and persists it
    autotune.enable(str(tmp_path))
    got = fused_ln_matmul(x, w, scale, bias)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ln_matmul_reference(x, w, scale, bias)),
        rtol=1e-5, atol=1e-5)
    entries = autotune._load(autotune.cache_file())
    assert any(k_.startswith("ln_matmul|") for k_ in entries)
    cfg = next(v["config"] for k_, v in entries.items()
               if k_.startswith("ln_matmul|"))
    assert 128 % cfg["block_m"] == 0 and 256 % cfg["block_n"] == 0


# ---------------------------------------------------------------------------
# warmup entry points (engine + trainer)
# ---------------------------------------------------------------------------

def test_engine_warmup_invisible_and_counts_pinned():
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.serve import DecodeEngine

    V, W = 64, 24
    model = TransformerLM(vocab=V, dim=32, num_layers=2, num_heads=4,
                          ffn_hidden=64, max_len=W)
    vs = model.init(jax.random.PRNGKey(0), jnp.zeros((1, W), jnp.int32))

    def run(warm):
        eng = DecodeEngine(model, vs, max_slots=2, block_size=4)
        if warm:
            rep = eng.warmup()
            assert rep["compile_counts"] == {"prefill": 1, "tick": 1}
            assert rep["wall_s"] > 0
            # no cache dirs configured → tri-state Nones, zero trials
            assert rep["autotune_trials"] == 0
            assert rep["autotune_cache_hit"] is None
            assert rep["xla_cache_hit"] is None
        eng.admit(0, [3, 1, 4, 1], reserve_len=12)
        toks = [int(eng.decode_tick()[0]) for _ in range(6)]
        assert eng.compile_counts() == {"prefill": 1, "tick": 1}
        return toks

    assert run(warm=True) == run(warm=False)   # warmup is token-invisible


def test_trainer_warmup_aot_reports():
    from paddle_tpu import optim
    from paddle_tpu.models import MnistMLP
    from paddle_tpu.nn import costs
    from paddle_tpu.train import Trainer

    rng = np.random.RandomState(0)
    batch = {"x": rng.normal(size=(8, 784)).astype(np.float32),
             "label": rng.randint(0, 10, (8,)).astype(np.int32)}
    tr = Trainer(model=MnistMLP(),
                 loss_fn=lambda out, b: costs.softmax_cross_entropy(
                     out, b["label"]),
                 optimizer=optim.sgd(0.1))
    tr.init(jax.random.PRNGKey(0), batch)
    before = jax.tree_util.tree_map(np.asarray, tr.train_state.params)
    rep = tr.warmup([batch])
    assert rep["wall_s"] > 0 and rep["fingerprint"]
    assert rep["cache_hit"] is None            # no XLA cache configured
    assert rep["autotune_trials"] == 0
    # AOT-only: warmup must not step the optimizer
    after = jax.tree_util.tree_map(np.asarray, tr.train_state.params)
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        assert (a == b).all()
