"""Bucketed gradient-sync overlap equivalence suite (ISSUE 8 tentpole).

The contract under test: ``Trainer(grad_sync="bucketed")`` — explicit
per-bucket dp grad all-reduces anchored inside the backward — reproduces
``grad_sync="fused"`` (one flat post-backward all-reduce) bit-for-bit in
f32 on a 2-device dp mesh: params and per-step losses, composing with
``grad_accum > 1``, ``steps_per_call > 1``, ``param_sharding``, the
remat'd scan-over-layers stack (per-layer in-scan sync), and the
pipelined host loop. Plus: the count of all-reduces each mode asks of
the compiler (one a bucket where fused asks exactly one, the per-layer
one inside the layer scan), the bucket partitioner's invariants, and the
graceful no-dp fallback.
"""

import logging

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
from paddle_tpu import optim, parallel
from paddle_tpu.core.module import Module
from paddle_tpu.nn import costs
from paddle_tpu.parallel import overlap
from paddle_tpu.train import Trainer, events as ev

from hlo_counts import compiled_all_reduces, lowered_all_reduces


class MLP(Module):
    def __init__(self, hidden=32, classes=8):
        super().__init__()
        self.hidden = nn.Linear(hidden, act="relu", name="hidden")
        self.out = nn.Linear(classes, name="out")

    def forward(self, x, train=False):
        return self.out(self.hidden(x))


MLP_RULES = parallel.ShardingRules([
    ("*/hidden/w", P(None, "model")),
    ("*/hidden/b", P("model")),
    ("*/out/w", P("model", None)),
])


def _batches(n=8, bs=32, d=16, classes=8, seed=0, weighted=False):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        b = {"x": rng.normal(size=(bs, d)).astype(np.float32),
             "label": rng.randint(0, classes, bs).astype(np.int32)}
        if weighted:
            b["weight"] = rng.randint(0, 3, bs).astype(np.float32)
        out.append(b)
    return out


def _dp_mesh(n=2):
    return pt.make_mesh({"data": n}, devices=jax.devices()[:n])


def _make_trainer(batches, grad_sync, K=2, M=1, bucket_mb=0.0005,
                  mesh=None, param_sharding=None, pipeline_depth=1):
    tr = Trainer(
        model=MLP(),
        loss_fn=lambda out, b: costs.softmax_cross_entropy(out, b["label"]),
        optimizer=optim.adam(1e-3),
        mesh=mesh if mesh is not None else _dp_mesh(),
        param_sharding=param_sharding, steps_per_call=K, grad_accum=M,
        grad_sync=grad_sync, bucket_mb=bucket_mb,
        pipeline_depth=pipeline_depth)
    tr.init(jax.random.PRNGKey(0), batches[0])
    return tr


def _run(tr, batches, num_passes=1):
    losses = []

    def handler(e):
        if isinstance(e, ev.EndIteration):
            losses.append(e.cost)

    tr.train(lambda: iter(batches), num_passes=num_passes,
             event_handler=handler, log_period=0)
    return jax.device_get(tr.train_state.params), losses


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _step_all_reduces(tr, batches):
    """``(whiles, tag)`` of every all-reduce the trainer's step ASKS for:
    read in the lowered StableHLO, because what a compiler's combiner
    makes of them (the CPU's folds the buckets into one variadic
    instruction) is that compiler's business, not the program's."""
    return lowered_all_reduces(
        tr.lower_step(batches)[0].as_text(debug_info=True))


# ---------------------------------------------------------------------------
# bucket partition invariants
# ---------------------------------------------------------------------------

def test_partition_buckets_reverse_order_and_budget():
    params = {"a": {"w": jnp.zeros((256, 256)),       # 256 KiB
                    "b": jnp.zeros((256,))},
              "z": {"w": jnp.zeros((256, 256)),
                    "b": jnp.zeros((256,))}}
    buckets = overlap.partition_buckets(params, bucket_mb=0.3)
    # reverse flatten order: z's leaves close first
    assert buckets[0].paths[0].startswith("z/")
    all_paths = [p for b in buckets for p in b.paths]
    assert all_paths == ["z/w", "z/b", "a/w", "a/b"]
    # 0.3 MiB budget cannot hold two 256 KiB weights in one bucket
    assert len(buckets) >= 2
    for b in buckets:
        assert b.bytes > 0 and b.dtype == "float32"
    # a huge budget collapses to a single bucket
    assert len(overlap.partition_buckets(params, bucket_mb=1e9)) == 1


def test_partition_buckets_dtype_split_and_exclude():
    params = {"f32": jnp.zeros((8,), jnp.float32),
              "bf16": jnp.zeros((8,), jnp.bfloat16),
              "ids": jnp.zeros((8,), jnp.int32),          # non-inexact
              "block0": {"w": jnp.zeros((8,))}}
    buckets = overlap.partition_buckets(params, bucket_mb=1e9,
                                        exclude=("*block*",))
    dtypes = {b.dtype for b in buckets}
    assert dtypes == {"float32", "bfloat16"}
    all_paths = [p for b in buckets for p in b.paths]
    assert "ids" not in all_paths                          # no cotangent
    assert not any("block0" in p for p in all_paths)       # excluded
    assert overlap.partition_buckets({}, bucket_mb=1.0) == []
    with pytest.raises(ValueError):
        overlap.partition_buckets(params, bucket_mb=0)


# ---------------------------------------------------------------------------
# bucketed == fused, bit-exact in f32 (2-device dp mesh)
# ---------------------------------------------------------------------------

def test_bucketed_equals_fused_bitexact():
    batches = _batches(8)
    pb, lb = _run(_make_trainer(batches, "bucketed"), batches)
    pf, lf = _run(_make_trainer(batches, "fused"), batches)
    assert lb == lf
    _assert_trees_equal(pb, pf)
    # sanity vs the implicit partitioner sync: same math, different
    # reduction anchoring — allclose, not bit-exact
    pn, ln_ = _run(_make_trainer(batches, None), batches)
    assert np.allclose(lb, ln_, rtol=1e-4, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(pb),
                    jax.tree_util.tree_leaves(pn)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)


def test_composes_with_grad_accum_and_weighted_batches():
    """grad_accum > 1: local grads accumulate across microbatches and the
    bucketed/fused sync fires once per optimizer step — bit-exact across
    the two modes, with weighted (zero-weight-included) batches."""
    batches = _batches(8, weighted=True)
    pb, lb = _run(_make_trainer(batches, "bucketed", K=2, M=2), batches)
    pf, lf = _run(_make_trainer(batches, "fused", K=2, M=2), batches)
    assert lb == lf and len(lb) == 4
    _assert_trees_equal(pb, pf)


def test_composes_with_param_sharding():
    """Tensor-parallel param_sharding (model axis) stays GSPMD-auto
    inside the manual-dp region: bucketed and fused agree to last-ulp
    tolerance and the committed layout survives training. (Bit-exactness
    is the PURE-DP contract: under auto tp the partitioner may pick
    different intermediate shardings for the two programs, re-associating
    feature-axis reductions — observed delta ~1e-8.)"""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    mesh = pt.make_mesh({"data": 2, "model": 2},
                        devices=jax.devices()[:4])
    batches = _batches(8)
    tr_b = _make_trainer(batches, "bucketed", mesh=mesh,
                         param_sharding=MLP_RULES)
    tr_f = _make_trainer(batches, "fused", mesh=mesh,
                         param_sharding=MLP_RULES)
    pb, lb = _run(tr_b, batches)
    pf, lf = _run(tr_f, batches)
    np.testing.assert_allclose(lb, lf, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(pb),
                    jax.tree_util.tree_leaves(pf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    root = next(iter(tr_b.train_state.params))
    w = tr_b.train_state.params[root]["hidden"]["w"]
    assert tuple(w.sharding.spec) == (None, "model")


def test_composes_with_pipelined_host_loop():
    """The async host pipeline defers host bookkeeping, not device math:
    a pipelined bucketed run reproduces the serial bucketed run (and the
    fused one) bit-exact."""
    batches = _batches(8)
    ps, ls = _run(_make_trainer(batches, "bucketed"), batches)
    pp, lp = _run(_make_trainer(batches, "bucketed", pipeline_depth=3),
                  batches)
    assert ls == lp
    _assert_trees_equal(ps, pp)


# ---------------------------------------------------------------------------
# HLO gate: all-reduce counts + backward anchoring
# ---------------------------------------------------------------------------

def test_hlo_bucketed_vs_fused_allreduce_counts():
    batches = _batches(4)
    tr_b = _make_trainer(batches, "bucketed")
    tr_f = _make_trainer(batches, "fused")
    ars_b = _step_all_reduces(tr_b, batches[:2])
    ars_f = _step_all_reduces(tr_f, batches[:2])
    buckets = overlap.partition_buckets(tr_b.train_state.params,
                                        tr_b.bucket_mb)
    assert len(buckets) >= 2
    # one scoped all-reduce a bucket
    assert sorted(tag for _, tag in ars_b if tag) == \
        sorted(b.tag for b in buckets)
    assert [tag for _, tag in ars_f if tag] == ["bucket0"]
    # beside them both modes ask the same unscoped one (the loss's mean)
    assert len(ars_b) - len(buckets) == len(ars_f) - 1 == 1
    # and the default mode asks none: its reductions are the partitioner's
    assert _step_all_reduces(_make_trainer(batches, None), batches[:2]) == []


def test_hlo_default_mode_has_no_grad_sync_scopes():
    """grad_sync=None is the pre-overlap program: no ``grad_sync/`` scope
    anywhere in the scoped transformer's lowered step."""
    batches = _lm_batches()
    tr = _make_lm_trainer(batches, None)
    text = tr.lower_step(batches[:2])[0].as_text(debug_info=True)
    assert overlap.GRAD_SYNC_SCOPE + "/" not in text
    assert lowered_all_reduces(text) == []


def test_compiled_all_reduces_reads_variadic_and_async_forms():
    """The compiled-text counter on the forms XLA prints: a plain result,
    a tuple result long enough to carry ``/*index=5*/`` marks (the CPU
    combiner's variadic all-reduce), an async start (its done is not a
    second one), and an operand that merely NAMES an all-reduce."""
    hlo = """
  %psum.80 = f32[8416]{0} all-reduce(%concatenate.210), channel_id=1
  %all-reduce.1 = (f32[4]{0}, f32[4]{0}, f32[4]{0}, f32[4]{0}, f32[4]{0}, /*index=5*/f32[]) all-reduce(%a, %b, %c, %d, %e, %f), channel_id=2
  %ars = f32[8]{0} all-reduce-start(%x), channel_id=3
  ROOT %ard = f32[8]{0} all-reduce-done(%ars)
  %gte = f32[4]{0} get-tuple-element(%all-reduce.1), index=0
"""
    assert compiled_all_reduces(hlo) == 3


# ---------------------------------------------------------------------------
# the remat'd transformer: per-layer in-scan sync
# ---------------------------------------------------------------------------

def _make_lm_trainer(batches, grad_sync, V=64, T=16, K=2):
    from paddle_tpu.models import TransformerLM
    tr = Trainer(
        model=TransformerLM(vocab=V, dim=32, num_layers=2, num_heads=4,
                            ffn_hidden=64, max_len=T, remat="dots"),
        loss_fn=lambda out, b: costs.softmax_cross_entropy(
            out.reshape(-1, V), b["y"].reshape(-1)),
        optimizer=optim.adam(1e-3), mesh=_dp_mesh(), steps_per_call=K,
        grad_sync=grad_sync, bucket_mb=0.0005)
    tr.init(jax.random.PRNGKey(0), batches[0])
    return tr


def _lm_batches(n=4, V=64, T=16, bs=8):
    rng = np.random.RandomState(0)
    return [{"x": rng.randint(0, V, (bs, T)).astype(np.int32),
             "y": rng.randint(0, V, (bs, T)).astype(np.int32)}
            for _ in range(n)]


def test_transformer_in_scan_sync_bitexact_and_in_loop():
    batches = _lm_batches()
    tr_b = _make_lm_trainer(batches, "bucketed")
    tr_f = _make_lm_trainer(batches, "fused")
    pb, lb = _run(tr_b, batches)
    pf, lf = _run(tr_f, batches)
    assert lb == lf
    _assert_trees_equal(pb, pf)
    whiles = {tag: n for n, tag in _step_all_reduces(tr_b, batches[:2])}
    # embed/pos/head leaves still sync via top-level buckets, inside the
    # K-step scan alone; the per-layer sync sits one while region deeper,
    # INSIDE the backward layer scan and not after it
    assert whiles["bucket0"] == 1
    assert whiles["scan_layer"] == 2
    assert all(n == 1 for n, tag in _step_all_reduces(tr_f, batches[:2]))


def test_transformer_scan_claim_protocol():
    from paddle_tpu.models import TransformerLM
    lm = TransformerLM(vocab=32, dim=16, num_layers=2, num_heads=2,
                       ffn_hidden=32, max_len=8, remat="dots")
    assert lm.grad_sync_scan_paths() == ("*/block*/*",)
    # without remat the stack is a plain loop: nothing to claim, block
    # leaves stay in the top-level buckets
    lm_plain = TransformerLM(vocab=32, dim=16, num_layers=2, num_heads=2,
                             ffn_hidden=32, max_len=8)
    assert lm_plain.grad_sync_scan_paths() == ()
    # the hook is a no-op outside an active sync scope
    tree = {"w": jnp.ones((2, 2))}
    assert overlap.sync_scan_slice(tree) is tree


def test_sync_scan_slice_mixed_dtypes():
    """The in-scan hook groups a mixed-precision layer slice by dtype
    (flat psum buffers cannot mix — concatenate would promote and the
    cotangents would come back wrong-typed) and passes non-inexact
    leaves through unmarked."""
    from jax import lax
    mesh = _dp_mesh()
    tree = {"w": jnp.ones((4, 4), jnp.bfloat16),
            "scale": jnp.ones((4,), jnp.float32),
            "ids": jnp.arange(4, dtype=jnp.int32)}

    def per_device(t):
        ids = t["ids"]

        def local(sub):
            with overlap.scan_sync_scope("data"):
                marked = overlap.sync_scan_slice({**sub, "ids": ids},
                                                 tag="mixed")
            return (jnp.sum(marked["w"].astype(jnp.float32))
                    + jnp.sum(marked["scale"])
                    + jnp.sum(marked["ids"]).astype(jnp.float32) * 0.0)

        sub = {"w": t["w"], "scale": t["scale"]}
        s, g = jax.value_and_grad(local)(sub)
        return lax.psum(s, "data"), g

    gspec = {"w": P(), "scale": P()}
    sm = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(), tree),),
        out_specs=(P(), gspec), check_vma=False)
    s, g = jax.jit(sm)(tree)
    assert g["w"].dtype == jnp.bfloat16
    assert g["scale"].dtype == jnp.float32
    # both devices contributed: cotangent 1 psum'd over dp=2
    np.testing.assert_array_equal(np.asarray(g["scale"]),
                                  np.full((4,), 2.0, np.float32))


# ---------------------------------------------------------------------------
# graceful fallback
# ---------------------------------------------------------------------------

def test_fallback_single_device_dp_warns_once(caplog):
    batches = _batches(4)
    mesh = pt.make_mesh({"data": 1}, devices=jax.devices()[:1])
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.trainer"):
        tr = _make_trainer(batches, "bucketed", mesh=mesh)
        pb, lb = _run(tr, batches)
        # same mesh, implicit sync: the degraded program IS the default
        tr_n = _make_trainer(batches, None, mesh=mesh)
        pn, ln_ = _run(tr_n, batches)
    assert lb == ln_
    _assert_trees_equal(pb, pn)
    warns = [r for r in caplog.records
             if "cannot engage" in r.getMessage()]
    assert len(warns) == 1                      # one-shot per trainer


def test_fallback_fsdp_style_param_sharding_warns(caplog):
    """param_sharding over the dp axis itself (FSDP-style): the explicit
    sync must decline (shards are not replicas) and degrade."""
    batches = _batches(4)
    rules = parallel.ShardingRules([("*/hidden/w", P(None, "data"))])
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.trainer"):
        tr = _make_trainer(batches, "bucketed", mesh=_dp_mesh(),
                           param_sharding=rules)
        _, lb = _run(tr, batches)
    assert all(np.isfinite(l) for l in lb)
    assert any("cannot engage" in r.getMessage() for r in caplog.records)


def test_invalid_mode_and_bucket_mb_raise():
    with pytest.raises(ValueError):
        Trainer(model=MLP(), loss_fn=lambda o, b: o, optimizer=optim.sgd(0.1),
                grad_sync="nope")
    with pytest.raises(ValueError):
        Trainer(model=MLP(), loss_fn=lambda o, b: o, optimizer=optim.sgd(0.1),
                grad_sync="bucketed", bucket_mb=0.0)


# ---------------------------------------------------------------------------
# xla_flags helper (ISSUE 8 satellite)
# ---------------------------------------------------------------------------

def test_xla_flags_assembly_and_merge():
    from paddle_tpu.obs import xla_flags
    core = xla_flags.overlap_flags()
    assert all(f.startswith("--xla_") and "=" in f for f in core)
    assert len(xla_flags.overlap_flags(strict=True)) > len(core)
    # operator-set values win; order is existing-first
    merged = xla_flags.merge_xla_flags(
        ["--xla_tpu_enable_async_collective_fusion=true", "--b=2"],
        existing="--xla_tpu_enable_async_collective_fusion=false")
    assert merged.split() == [
        "--xla_tpu_enable_async_collective_fusion=false", "--b=2"]
    # no TPU hints, no force: environment untouched
    env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--a=1"}
    assert xla_flags.apply_overlap_flags(env=env) == "--a=1"
    assert env["XLA_FLAGS"] == "--a=1"
    # forced: merged in, operator flags first and preserved
    out = xla_flags.apply_overlap_flags(env=env, force=True)
    assert out.startswith("--a=1") and env["XLA_FLAGS"] == out
    assert "--xla_tpu_enable_async_collective_fusion=true" in out.split()
