"""Parallelism tests on the virtual 8-device CPU mesh — the analog of the
reference's in-process cluster tests (``test_CompareSparse.cpp:64``,
``ParallelNeuralNetwork.h:36``): tensor-parallel training must match
replicated training; ring attention must match dense attention."""

import os

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
from paddle_tpu.train import Trainer


class MLP(Module):
    def __init__(self, hidden=32, classes=8):
        super().__init__()
        self.hidden = nn.Linear(hidden, act="relu", name="hidden")
        self.out = nn.Linear(classes, name="out")

    def forward(self, x, train=False):
        return self.out(self.hidden(x))


def _batch(nprng, n=32, d=16, classes=8):
    return {
        "x": nprng.normal(size=(n, d)).astype(np.float32),
        "label": nprng.randint(0, classes, size=n).astype(np.int32),
    }


MLP_RULES = parallel.ShardingRules([
    ("*/hidden/w", P(None, "model")),     # column parallel
    ("*/hidden/b", P("model")),
    ("*/out/w", P("model", None)),        # row parallel
])


def _train_losses(mesh, param_sharding, batches, rng):
    trainer = Trainer(
        model=MLP(),
        loss_fn=lambda out, b: costs.softmax_cross_entropy(out, b["label"]),
        optimizer=optim.momentum(0.1, 0.9),
        mesh=mesh, param_sharding=param_sharding, donate=False)
    trainer.init(rng, batches[0])
    trainer._build_train_step()
    ts = trainer.train_state
    p, s, o, st = ts.params, ts.state, ts.opt_state, ts.step
    losses = []
    for hb in batches:
        b = trainer._shard(hb)
        p, s, o, st, loss, stats = trainer._train_step(
            p, s, o, st, b, jax.random.PRNGKey(7))
        losses.append(float(loss))
    return losses, p


def test_tensor_parallel_matches_replicated(nprng, rng):
    """data x model mesh with sharded params == pure-DP replicated params
    (same global batches, same rng) — the ParallelNeuralNetwork equivalence."""
    batches = [_batch(nprng) for _ in range(5)]
    mesh_dp = pt.make_mesh({"data": 8})
    mesh_tp = pt.make_mesh({"data": 2, "model": 4})
    losses_dp, p_dp = _train_losses(mesh_dp, None, batches, rng)
    losses_tp, p_tp = _train_losses(mesh_tp, MLP_RULES, batches, rng)
    np.testing.assert_allclose(losses_dp, losses_tp, rtol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p_dp),
                    jax.tree_util.tree_leaves(p_tp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_param_sharding_actually_shards(nprng, rng):
    mesh = pt.make_mesh({"data": 2, "model": 4})
    trainer = Trainer(
        model=MLP(),
        loss_fn=lambda out, b: costs.softmax_cross_entropy(out, b["label"]),
        optimizer=optim.adam(1e-3),
        mesh=mesh, param_sharding=MLP_RULES, donate=False)
    trainer.init(rng, _batch(nprng))
    root = next(iter(trainer.train_state.params))
    w = trainer.train_state.params[root]["hidden"]["w"]
    spec = w.sharding.spec
    assert tuple(spec) == (None, "model")
    # optimizer state inherited the layout by SPMD propagation
    m_leaves = [x for x in jax.tree_util.tree_leaves(
        trainer.train_state.opt_state) if getattr(x, "ndim", 0) == 2
        and x.shape == w.shape]
    assert m_leaves, "adam should carry param-shaped slots"
    for leaf in m_leaves:
        assert tuple(leaf.sharding.spec) == (None, "model")


def test_sharded_restore_recommits_layout(nprng, rng, tmp_path):
    """save -> restore with param_sharding keeps the tensor-parallel layout
    (params, state, and optimizer slots)."""
    mesh = pt.make_mesh({"data": 2, "model": 4})
    def make():
        return Trainer(
            model=MLP(),
            loss_fn=lambda out, b: costs.softmax_cross_entropy(
                out, b["label"]),
            optimizer=optim.adam(1e-3),
            mesh=mesh, param_sharding=MLP_RULES, donate=False)
    t1 = make()
    t1.init(rng, _batch(nprng))
    t1.save(str(tmp_path), 0)
    t2 = make()
    t2.init(rng, _batch(nprng))          # builds _param_specs
    t2.restore(str(tmp_path), 0)
    root = next(iter(t2.train_state.params))
    w = t2.train_state.params[root]["hidden"]["w"]
    assert tuple(w.sharding.spec) == (None, "model")
    for leaf in jax.tree_util.tree_leaves(t2.train_state.opt_state):
        if getattr(leaf, "shape", None) == w.shape:
            assert tuple(leaf.sharding.spec) == (None, "model")


def test_sharded_init_layout(nprng, rng):
    mesh = pt.make_mesh({"data": 2, "model": 4})
    model = MLP(hidden=64)
    x = jnp.asarray(nprng.normal(size=(8, 16)).astype(np.float32))
    variables, specs = parallel.sharded_init(model, rng, x, mesh=mesh,
                                             rules=MLP_RULES)
    root = next(iter(variables["params"]))
    w = variables["params"][root]["hidden"]["w"]
    assert tuple(w.sharding.spec) == (None, "model")
    assert specs[root]["hidden"]["w"] == P(None, "model")
    # replicated leaf
    b = variables["params"][root]["out"]["b"]
    assert tuple(b.sharding.spec) == ()


# ---------------------------------------------------------------- ring attn

def _dense_attention(q, k, v, causal):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        t = q.shape[1]
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(nprng, causal):
    mesh = pt.make_mesh({"data": 2, "seq": 4})
    B, T, H, D = 2, 16, 2, 4
    q = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    k = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    v = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    ring = parallel.make_ring_attention(mesh, seq_axis="seq", causal=causal)
    out = jax.jit(ring)(q, k, v)
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grads_match_dense(nprng):
    mesh = pt.make_mesh({"seq": 8})
    B, T, H, D = 1, 16, 1, 4
    q = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    k = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    v = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    ring = parallel.make_ring_attention(mesh, seq_axis="seq", causal=True)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, True) ** 2)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------- multi-host

def test_multihost_initialize_noop_single_process(monkeypatch):
    """initialize() must be a safe no-op without a coordinator (the common
    single-host path) so programs call it unconditionally."""
    from paddle_tpu.parallel import multihost
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    multihost.initialize()
    assert not multihost.is_initialized()


def test_host_sharded_reader_partitions_disjointly(monkeypatch):
    """Each simulated host gets a disjoint slice; the union is the stream
    (the Go master task-queue property, go/master/service.go:368)."""
    from paddle_tpu.core import mesh as mesh_lib
    from paddle_tpu.parallel import multihost
    items = list(range(23))
    got = {}
    for hid in range(4):
        monkeypatch.setattr(mesh_lib, "host_count", lambda: 4)
        monkeypatch.setattr(mesh_lib, "host_id", lambda h=hid: h)
        r = multihost.host_sharded_reader(lambda: iter(items))
        got[hid] = list(r())
    allitems = sorted(x for v in got.values() for x in v)
    assert allitems == items
    for a in range(4):
        for b in range(a + 1, 4):
            assert not set(got[a]) & set(got[b])


def test_checkpoint_single_writer(tmp_path, monkeypatch):
    """Non-zero processes must not write checkpoints (single-controller
    write guard); everyone loads the same files."""
    from paddle_tpu.train import checkpoint as ckpt
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    d = ckpt.save_checkpoint(str(tmp_path), 0, {"params": {"w": np.ones(2)}})
    assert not os.path.exists(d)      # nothing written by process 1
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    d = ckpt.save_checkpoint(str(tmp_path), 0, {"params": {"w": np.ones(2)}})
    assert os.path.exists(d)
    out = ckpt.load_checkpoint(str(tmp_path))
    np.testing.assert_allclose(out["params"]["w"], np.ones(2))


def test_multihost_mesh_and_trainer_end_to_end():
    """A multihost-style run on the 8-device harness: global mesh + host
    sharded reader + trainer step — the composition the docstring promises."""
    from paddle_tpu import optim
    from paddle_tpu.models import MnistMLP
    from paddle_tpu.nn import costs
    from paddle_tpu.parallel import multihost
    from paddle_tpu.train import Trainer

    mesh = multihost.multihost_mesh()
    assert mesh.devices.size == len(jax.devices())
    rng = np.random.RandomState(0)
    batches = [{"x": rng.normal(size=(16, 28, 28, 1)).astype(np.float32),
                "label": rng.randint(0, 10, 16).astype(np.int32)}
               for _ in range(6)]
    reader = multihost.host_sharded_reader(lambda: iter(batches))
    tr = Trainer(MnistMLP(),
                 lambda o, b: costs.softmax_cross_entropy(o, b["label"]),
                 optim.sgd(0.1), mesh=mesh)
    tr.init(jax.random.PRNGKey(0), batches[0])
    tr.train(reader, num_passes=1, log_period=0)
    assert int(tr.train_state.step) == 6   # single host consumed everything


# ------------------------------------------------------------- ulysses attn

@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_dense(nprng, causal):
    mesh = pt.make_mesh({"data": 2, "seq": 4})
    B, T, H, D = 2, 16, 4, 4           # H=4 divides seq axis size 4
    q = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    k = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    v = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    uly = parallel.make_ulysses_attention(mesh, seq_axis="seq", causal=causal)
    out = jax.jit(uly)(q, k, v)
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_matches_ring(nprng):
    """The two sequence-parallel strategies must agree (same math, different
    collectives) — models can switch by config."""
    mesh = pt.make_mesh({"seq": 8})
    B, T, H, D = 1, 32, 8, 4
    q = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    k = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    v = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    ring = parallel.make_ring_attention(mesh, seq_axis="seq", causal=True)
    uly = parallel.make_ulysses_attention(mesh, seq_axis="seq", causal=True)
    np.testing.assert_allclose(np.asarray(jax.jit(ring)(q, k, v)),
                               np.asarray(jax.jit(uly)(q, k, v)),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_grads_match_dense(nprng):
    mesh = pt.make_mesh({"seq": 8})
    B, T, H, D = 1, 16, 8, 4
    q = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    k = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    v = jnp.asarray(nprng.normal(size=(B, T, H, D)).astype(np.float32))
    uly = parallel.make_ulysses_attention(mesh, seq_axis="seq", causal=True)

    def loss_u(q, k, v):
        return jnp.sum(uly(q, k, v) ** 2)

    def loss_d(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, True) ** 2)

    gu = jax.jit(jax.grad(loss_u, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gu, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------- pipeline (pp)

def test_pipeline_matches_sequential(nprng):
    """GPipe wavefront over the pipe axis == applying the stages in
    sequence on one device."""
    mesh = pt.make_mesh({"data": 2, "pipe": 4})
    S, M, mb, D = 4, 6, 2, 8
    w = jnp.asarray(nprng.normal(size=(S, D, D)).astype(np.float32) * 0.3)
    b = jnp.asarray(nprng.normal(size=(S, D)).astype(np.float32) * 0.1)
    x = jnp.asarray(nprng.normal(size=(M, mb, D)).astype(np.float32))

    def stage_fn(params, act):
        return jnp.tanh(act @ params["w"] + params["b"])

    pipe = parallel.make_pipeline(mesh, stage_fn)
    got = jax.jit(pipe)({"w": w, "b": b}, x)

    want = x
    for s in range(S):
        want = jnp.tanh(want @ w[s] + b[s])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_pipeline_gradients_match_sequential(nprng):
    mesh = pt.make_mesh({"data": 2, "pipe": 4})
    S, M, mb, D = 4, 5, 2, 6
    w = jnp.asarray(nprng.normal(size=(S, D, D)).astype(np.float32) * 0.3)
    b = jnp.asarray(nprng.normal(size=(S, D)).astype(np.float32) * 0.1)
    x = jnp.asarray(nprng.normal(size=(M, mb, D)).astype(np.float32))

    def stage_fn(params, act):
        return jnp.tanh(act @ params["w"] + params["b"])

    pipe = parallel.make_pipeline(mesh, stage_fn)

    def loss_pipe(params):
        return jnp.sum(pipe(params, x) ** 2)

    def loss_seq(params):
        h = x
        for s in range(S):
            h = jnp.tanh(h @ params["w"][s] + params["b"][s])
        return jnp.sum(h ** 2)

    gp = jax.jit(jax.grad(loss_pipe))({"w": w, "b": b})
    gs = jax.grad(loss_seq)({"w": w, "b": b})
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gs[k]),
                                   rtol=2e-4, atol=2e-5)


def test_pipeline_1f1b_matches_sequential(nprng):
    """1F1B interleaved schedule: loss and stage-param grads must equal the
    sequential (single-device) oracle — and GPipe+jax.grad."""
    mesh = pt.make_mesh({"data": 2, "pipe": 4})
    S, M, mb, D = 4, 6, 2, 8
    w = jnp.asarray(nprng.normal(size=(S, D, D)).astype(np.float32) * 0.3)
    b = jnp.asarray(nprng.normal(size=(S, D)).astype(np.float32) * 0.1)
    x = jnp.asarray(nprng.normal(size=(M, mb, D)).astype(np.float32))

    def stage_fn(params, act):
        return jnp.tanh(act @ params["w"] + params["b"])

    def loss_fn(out):
        return jnp.sum(out ** 2)

    f1b = parallel.make_pipeline_1f1b(mesh, stage_fn, loss_fn)
    loss, grads = jax.jit(f1b)({"w": w, "b": b}, x)

    def seq_loss(params):
        total = 0.0
        for m in range(M):
            h = x[m]
            for s in range(S):
                h = jnp.tanh(h @ params["w"][s] + params["b"][s])
            total = total + loss_fn(h)
        return total

    want_loss = seq_loss({"w": w, "b": b})
    want_grads = jax.grad(seq_loss)({"w": w, "b": b})
    np.testing.assert_allclose(float(loss), float(want_loss),
                               rtol=2e-5, atol=2e-6)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(grads[k]),
                                   np.asarray(want_grads[k]),
                                   rtol=2e-4, atol=2e-5, err_msg=k)


def test_pipeline_1f1b_many_microbatches(nprng):
    """M >> S (the gradient-accumulation regime 1F1B exists for) stays
    correct: the S-slot activation ring never collides."""
    mesh = pt.make_mesh({"pipe": 4}, devices=jax.devices()[:4])
    S, M, mb, D = 4, 13, 2, 4
    w = jnp.asarray(nprng.normal(size=(S, D, D)).astype(np.float32) * 0.3)
    x = jnp.asarray(nprng.normal(size=(M, mb, D)).astype(np.float32))

    def stage_fn(params, act):
        return jnp.tanh(act @ params["w"])

    def loss_fn(out):
        return jnp.mean(out ** 2)

    f1b = parallel.make_pipeline_1f1b(mesh, stage_fn, loss_fn)
    loss, grads = jax.jit(f1b)({"w": w}, x)

    def seq_loss(params):
        h = x
        for s in range(S):
            h = jnp.tanh(h @ params["w"][s])
        return sum(loss_fn(h[m]) for m in range(M))

    np.testing.assert_allclose(float(loss), float(seq_loss({"w": w})),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(grads["w"]),
                               np.asarray(jax.grad(seq_loss)({"w": w})["w"]),
                               rtol=2e-4, atol=2e-5)


def test_pipeline_1f1b_gates_compute_with_conditionals(nprng):
    """Off-tick events must SKIP stage compute, not run-and-mask it: the
    lowered schedule carries one HLO conditional per event class (forward,
    backward) inside the tick loop, so a device idles on its bubble ticks —
    the ideal M-fwd + M-recompute-vjp 1F1B budget, not 2M+2S-2 of each."""
    mesh = pt.make_mesh({"pipe": 4}, devices=jax.devices()[:4])
    S, M, mb, D = 4, 6, 2, 8
    w = jnp.asarray(nprng.normal(size=(S, D, D)).astype(np.float32) * 0.3)
    x = jnp.asarray(nprng.normal(size=(M, mb, D)).astype(np.float32))

    f1b = parallel.make_pipeline_1f1b(
        mesh, lambda p, a: jnp.tanh(a @ p["w"]), lambda o: jnp.sum(o ** 2))
    txt = jax.jit(f1b).lower({"w": w}, x).as_text()
    n_cond = txt.count("stablehlo.case") + txt.count("stablehlo.if")
    # >= 2 (fwd + bwd gates) rather than == 2: unrelated ops may also lower
    # to conditionals across XLA versions; the numeric 1F1B oracle test is
    # the budget/correctness check
    assert n_cond >= 2, f"expected fwd+bwd conditionals in the tick loop, " \
                        f"found {n_cond}"


def test_seq_parallel_residuals_match_and_use_reduce_scatter(nprng, rng):
    """Megatron tensor parallel with SEQUENCE-PARALLEL residuals
    (``TransformerLM(residual_sharding=...)``): constraining the residual
    stream to a seq-sharded spec must (a) leave the logits numerically
    identical to the unsharded model and (b) make XLA lower the tp
    activation sync as reduce-scatter/all-gather pairs instead of
    all-reduces — the halved-wire-bytes recipe."""
    from jax.sharding import NamedSharding

    from paddle_tpu.models import TransformerLM

    mesh = pt.make_mesh({"data": 2, "model": 4})
    V, D, T, B = 64, 32, 16, 4
    kw = dict(vocab=V, dim=D, num_layers=2, num_heads=4, ffn_hidden=64,
              max_len=T)
    base = TransformerLM(**kw)
    ids = jnp.asarray(nprng.randint(0, V, (B, T)), jnp.int32)
    variables = base.init(jax.random.PRNGKey(0), ids)
    ref = base.apply(variables, ids)

    rules = parallel.ShardingRules([
        ("*/attn/wq", P(None, "model")), ("*/attn/wk", P(None, "model")),
        ("*/attn/wv", P(None, "model")), ("*/attn/wo", P("model", None)),
        ("*/ffn1/w", P(None, "model")), ("*/ffn1/b", P("model")),
        ("*/ffn2/w", P("model", None)),
    ])
    params = parallel.shard_tree(mesh, variables["params"],
                                 rules(variables["params"]))

    def seq_sharded(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("data", "model", None)))

    sp = TransformerLM(**kw, residual_sharding=seq_sharded)
    inp = jax.device_put(ids, NamedSharding(mesh, P("data", None)))
    f = jax.jit(lambda p, i: sp.apply({"params": p}, i))
    np.testing.assert_allclose(np.asarray(f(params, inp)), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    # The constraint must change the lowering: the tp-only forward syncs its
    # partial sums with per-sublayer all-reduces; seq-sharding the residuals
    # re-expresses those syncs in scattered form (reduce-scatter, or
    # all-gather pairs — the exact mix is XLA's cost-model choice).
    def n_allreduce(fn):
        hlo = fn.lower(params, inp).compile().as_text()
        return hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")

    f_tp = jax.jit(lambda p, i: base.apply({"params": p}, i))
    assert n_allreduce(f) < n_allreduce(f_tp), \
        "seq-sharded residuals should eliminate tp activation all-reduces"


def test_megatron_sp_matches_unsharded_lm(nprng, rng):
    """Explicit Megatron tp + sequence-parallel residuals
    (``parallel.make_megatron_sp_lm_apply``): logits, loss, AND grads must
    equal the standard unsharded TransformerLM on the same variables tree,
    and the lowering must carry the hand-written AG/RS pairs with NO
    activation all-reduces. (AG+RS moves the same wire as the all-reduce
    it replaces — the recipe's win is T/tp-sharded residuals/LayerNorms/
    activation memory, which pjit's partitioner does not produce.)"""
    from jax.sharding import NamedSharding

    from paddle_tpu.models import TransformerLM
    from paddle_tpu.nn import costs

    mesh = pt.make_mesh({"data": 2, "model": 4})
    V, D, T, B, H = 64, 32, 16, 4, 4
    model = TransformerLM(vocab=V, dim=D, num_layers=2, num_heads=H,
                          ffn_hidden=64, max_len=T)
    ids = jnp.asarray(nprng.randint(0, V, (B, T)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    ref = model.apply(variables, ids)

    params = parallel.shard_tree(mesh, variables["params"],
                                 parallel.megatron_sp_rules()(
                                     variables["params"]))
    inp = jax.device_put(ids, NamedSharding(mesh, P("data", None)))
    apply_fn = parallel.make_megatron_sp_lm_apply(model, mesh)
    f = jax.jit(lambda p, i: apply_fn({"params": p}, i))
    np.testing.assert_allclose(np.asarray(f(params, inp)), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)

    # grads through the shard_map (AG/RS transpose pair) == plain grads
    tgt = jnp.asarray(nprng.randint(0, V, (B, T)), jnp.int32)

    loss_fn_sp = parallel.make_megatron_sp_lm_apply(model, mesh,
                                                    with_loss=True)

    def loss_sp(p, i):
        return loss_fn_sp({"params": p}, i, tgt)

    def loss_ref(p):
        lg = model.apply({"params": p}, ids)
        return jnp.mean(costs.softmax_cross_entropy(
            lg.reshape(-1, V), tgt.reshape(-1)))

    g_sp = jax.jit(jax.grad(loss_sp))(params, inp)
    g_ref = jax.grad(loss_ref)(variables["params"])
    for a, b in zip(jax.tree_util.tree_leaves(g_sp),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)

    def count_ar(hlo):
        return hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")

    # The TRAINING path's only all-reduces are the loss/count psums — the
    # activation syncs are hand-written AG/RS. Compare RELATIVELY against
    # the tp-only pjit lowering of the same loss on the same sharded
    # params, which pays per-sublayer activation all-reduces (the sibling
    # residual-sharding test uses the same relative form): an absolute
    # budget pins XLA's exact op count and rots across versions.
    hlo = jax.jit(loss_sp).lower(params, inp).compile().as_text()
    assert "reduce-scatter" in hlo, \
        "explicit Megatron-SP training must carry reduce-scatter syncs"

    def loss_tp(p, i):
        lg = model.apply({"params": p}, i)
        return jnp.mean(costs.softmax_cross_entropy(
            lg.reshape(-1, V), tgt.reshape(-1)))

    n_sp = count_ar(hlo)
    n_tp = count_ar(jax.jit(loss_tp).lower(params, inp).compile().as_text())
    assert n_sp < n_tp, \
        f"explicit SP loss path should carry fewer all-reduces than the " \
        f"tp-only pjit lowering (activation ARs reintroduced?): " \
        f"{n_sp} vs {n_tp}"
    fwd_hlo = jax.jit(lambda p, i: apply_fn({"params": p}, i)).lower(
        params, inp).compile().as_text()
    assert "all-gather" in fwd_hlo and "reduce-scatter" in fwd_hlo, \
        "explicit Megatron-SP must lower to all-gather + reduce-scatter"
    assert " all-reduce(" not in fwd_hlo, \
        "forward should carry no activation all-reduce"


def test_pipeline_loss_form_matches_sequential(nprng):
    """``make_pipeline_loss``: the GPipe wavefront closing the loss on the
    LAST stage (scalar psum) must reproduce the sequential loss AND the
    grads of stage params, final (head) params, and the input stack — and
    its lowering must NOT broadcast the [M, mb, D] output stack over the
    pipe axis (1.07 GB/step at the d1024 shape; the scalar psum is the
    point of the loss form)."""
    mesh = pt.make_mesh({"data": 2, "pipe": 4})
    S, M, mbg, Din = 4, 6, 4, 8
    w = jnp.asarray(nprng.normal(size=(S, Din, Din)).astype(np.float32) * .3)
    wh = jnp.asarray(nprng.normal(size=(Din, 3)).astype(np.float32) * .5)
    x = jnp.asarray(nprng.normal(size=(M, mbg, Din)).astype(np.float32))
    y = jnp.asarray(nprng.normal(size=(M, mbg, 3)).astype(np.float32))

    def stage_fn(p, a):
        return jnp.tanh(a @ p["w"])

    def final_fn(fp, outbuf, tgt):
        return jnp.sum((outbuf @ fp["wh"] - tgt) ** 2)

    pipe_loss = parallel.make_pipeline_loss(
        mesh, stage_fn, final_fn,
        x_spec=P(None, "data", None), extra_specs=(P(None, "data", None),),
        reduce_axes=("data",))

    def loss_sp(sp_, fp, x, y):
        return pipe_loss(sp_, fp, x, y)

    def loss_seq(sp_, fp, x, y):
        h = x
        for s in range(S):
            h = jnp.tanh(h @ sp_["w"][s])
        return jnp.sum((h @ fp["wh"] - y) ** 2)

    args = ({"w": w}, {"wh": wh}, x, y)
    got = jax.jit(loss_sp)(*args)
    want = loss_seq(*args)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)

    g_sp = jax.jit(jax.grad(loss_sp, argnums=(0, 1, 2)))(*args)
    g_seq = jax.grad(loss_seq, argnums=(0, 1, 2))(*args)
    for a, b in zip(jax.tree_util.tree_leaves(g_sp),
                    jax.tree_util.tree_leaves(g_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)

    # no [M, mb, D]-sized all-reduce: every all-reduce buffer in the loss
    # HLO must be orders below the output stack's element count
    import re as _re
    hlo = jax.jit(loss_sp).lower(*args).compile().as_text()
    stack_elems = M * mbg * Din
    for line in hlo.splitlines():
        m = _re.search(r"f32\[([\d,]*)\]\{[^}]*\}? all-reduce", line)
        if m:
            n = 1
            for d in m.group(1).split(","):
                if d:
                    n *= int(d)
            assert n < stack_elems, \
                f"loss form should not broadcast the output stack: {line}"


def test_megatron_sp_bf16_comm_close_to_exact(nprng, rng):
    """comm_dtype=bfloat16 (the Megatron-standard wire compression —
    halves tp activation bytes vs the policy's f32 Linear outputs) must
    stay within bf16 tolerance of the exact unsharded loss."""
    from jax.sharding import NamedSharding

    from paddle_tpu.models import TransformerLM
    from paddle_tpu.nn import costs

    mesh = pt.make_mesh({"data": 2, "model": 4})
    V, D, T, B, H = 64, 32, 16, 4, 4
    model = TransformerLM(vocab=V, dim=D, num_layers=2, num_heads=H,
                          ffn_hidden=64, max_len=T)
    ids = jnp.asarray(nprng.randint(0, V, (B, T)), jnp.int32)
    tgt = jnp.asarray(nprng.randint(0, V, (B, T)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    ref_loss = jnp.mean(costs.softmax_cross_entropy(
        model.apply(variables, ids).reshape(-1, V), tgt.reshape(-1)))

    params = parallel.shard_tree(mesh, variables["params"],
                                 parallel.megatron_sp_rules()(
                                     variables["params"]))
    inp = jax.device_put(ids, NamedSharding(mesh, P("data", None)))
    loss_fn = parallel.make_megatron_sp_lm_apply(
        model, mesh, with_loss=True, comm_dtype=jnp.bfloat16)
    got = jax.jit(lambda p, i: loss_fn({"params": p}, i, tgt))(params, inp)
    np.testing.assert_allclose(float(got), float(ref_loss), rtol=2e-2)


def test_pipeline_loss_bf16_comm_close_to_exact(nprng):
    """comm_dtype=bfloat16 on the inter-stage hops stays within bf16
    tolerance of the exact pipeline loss."""
    mesh = pt.make_mesh({"pipe": 4}, devices=jax.devices()[:4])
    S, M, mbg, Din = 4, 6, 4, 8
    w = jnp.asarray(nprng.normal(size=(S, Din, Din)).astype(np.float32) * .3)
    wh = jnp.asarray(nprng.normal(size=(Din, 3)).astype(np.float32) * .5)
    x = jnp.asarray(nprng.normal(size=(M, mbg, Din)).astype(np.float32))
    y = jnp.asarray(nprng.normal(size=(M, mbg, 3)).astype(np.float32))

    def stage_fn(p, a):
        return jnp.tanh(a.astype(jnp.float32) @ p["w"])

    def final_fn(fp, outbuf, tgt):
        return jnp.sum((outbuf @ fp["wh"] - tgt) ** 2)

    exact = parallel.make_pipeline_loss(
        mesh, stage_fn, final_fn, extra_specs=(P(),))
    comp = parallel.make_pipeline_loss(
        mesh, stage_fn, final_fn, extra_specs=(P(),),
        comm_dtype=jnp.bfloat16)
    le = jax.jit(exact)({"w": w}, {"wh": wh}, x, y)
    lc = jax.jit(comp)({"w": w}, {"wh": wh}, x, y)
    np.testing.assert_allclose(float(lc), float(le), rtol=3e-2)


def test_megatron_sp_flash_matches_unsharded_lm(nprng, rng):
    """The megatron-SP kernel's use_flash=True path (per-device Pallas
    flash attention on the local head group, interpreter mode off-TPU)
    must match the unsharded model like the einsum path does."""
    from jax.sharding import NamedSharding

    from paddle_tpu.models import TransformerLM

    mesh = pt.make_mesh({"data": 2, "model": 4})
    V, D, T, B, H = 64, 32, 16, 4, 4
    model = TransformerLM(vocab=V, dim=D, num_layers=2, num_heads=H,
                          ffn_hidden=64, max_len=T)
    ids = jnp.asarray(nprng.randint(0, V, (B, T)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    ref = model.apply(variables, ids)

    params = parallel.shard_tree(mesh, variables["params"],
                                 parallel.megatron_sp_rules()(
                                     variables["params"]))
    inp = jax.device_put(ids, NamedSharding(mesh, P("data", None)))
    apply_fn = parallel.make_megatron_sp_lm_apply(model, mesh,
                                                  use_flash=True)
    got = jax.jit(lambda p, i: apply_fn({"params": p}, i))(params, inp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_megatron_sp_bf16_policy_matches_pjit(nprng, rng):
    """Mixed-precision parity (ISSUE 1 satellite 1): under
    ``use_policy(bfloat16_compute)`` the explicit Megatron-SP path must
    apply the SAME policy casts as the pjit path's Linears (cast_compute
    operands, accumulate in accum_dtype) — the two lowerings of one model
    must agree to bf16 tolerance, not silently diverge because the explicit
    kernel ran f32."""
    from jax.sharding import NamedSharding

    from paddle_tpu.core.dtypes import bfloat16_compute, use_policy
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.nn import costs

    mesh = pt.make_mesh({"data": 2, "model": 4})
    V, D, T, B, H = 64, 32, 16, 4, 4
    model = TransformerLM(vocab=V, dim=D, num_layers=2, num_heads=H,
                          ffn_hidden=64, max_len=T)
    ids = jnp.asarray(nprng.randint(0, V, (B, T)), jnp.int32)
    tgt = jnp.asarray(nprng.randint(0, V, (B, T)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    params = parallel.shard_tree(mesh, variables["params"],
                                 parallel.megatron_sp_rules()(
                                     variables["params"]))
    inp = jax.device_put(ids, NamedSharding(mesh, P("data", None)))
    tgt_s = jax.device_put(tgt, NamedSharding(mesh, P("data", None)))

    # build the factory OUTSIDE the policy context, trace INSIDE: the
    # policy must be read at trace time (as nn.layers.Linear reads it),
    # not captured when the factory ran
    loss_fn = parallel.make_megatron_sp_lm_apply(model, mesh,
                                                 with_loss=True)
    with use_policy(bfloat16_compute):
        got = float(jax.jit(loss_fn)({"params": params}, inp, tgt_s))

        def pjit_loss(p):
            lg = model.apply({"params": p}, ids)
            return jnp.mean(costs.softmax_cross_entropy(
                lg.reshape(-1, V).astype(jnp.float32), tgt.reshape(-1)))

        want = float(jax.jit(pjit_loss)(variables["params"]))
    # both paths multiply bf16 operands with f32 accumulation; residual
    # collectives reorder sums, so policy tolerance, not bit equality
    np.testing.assert_allclose(got, want, rtol=5e-3)
    # sanity: the bf16-policy loss must differ from an f32 trace by MORE
    # than f32 roundoff (i.e. the casts actually happened)
    f32_loss = parallel.make_megatron_sp_lm_apply(model, mesh,
                                                  with_loss=True)
    exact = float(jax.jit(f32_loss)({"params": params}, inp, tgt_s))
    assert got != exact, "bf16 policy had no effect on the explicit path"


def test_megatron_sp_remat_matches(nprng, rng):
    """remat="dots" on the explicit Megatron-SP path (layer loop as a
    jax.checkpoint'd lax.scan over stacked shard params) reproduces the
    unrolled loop's loss and grads."""
    from jax.sharding import NamedSharding

    from paddle_tpu.models import TransformerLM
    from paddle_tpu.nn import costs

    mesh = pt.make_mesh({"data": 2, "model": 4})
    V, D, T, B, H = 64, 32, 16, 4, 4
    model = TransformerLM(vocab=V, dim=D, num_layers=3, num_heads=H,
                          ffn_hidden=64, max_len=T)
    ids = jnp.asarray(nprng.randint(0, V, (B, T)), jnp.int32)
    tgt = jnp.asarray(nprng.randint(0, V, (B, T)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    params = parallel.shard_tree(mesh, variables["params"],
                                 parallel.megatron_sp_rules()(
                                     variables["params"]))
    inp = jax.device_put(ids, NamedSharding(mesh, P("data", None)))
    tgt_s = jax.device_put(tgt, NamedSharding(mesh, P("data", None)))

    plain = parallel.make_megatron_sp_lm_apply(model, mesh, with_loss=True)
    remat = parallel.make_megatron_sp_lm_apply(model, mesh, with_loss=True,
                                               remat="dots")
    lp = jax.jit(plain)({"params": params}, inp, tgt_s)
    lr = jax.jit(remat)({"params": params}, inp, tgt_s)
    np.testing.assert_allclose(float(lr), float(lp), rtol=1e-6)
    gp = jax.jit(jax.grad(lambda p: plain({"params": p}, inp, tgt_s)))(
        params)
    gr = jax.jit(jax.grad(lambda p: remat({"params": p}, inp, tgt_s)))(
        params)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_pipeline_loss_bubble_nonfinite_safe(nprng):
    """Bubble devices run final_fn on a zero output buffer; a non-finite
    value there (0/0 normalisation, log 0, ...) must NOT poison the psum —
    regression for the ``val * mask`` NaN*0 masking (ISSUE 1 satellite 2:
    now jnp.where-selected)."""
    mesh = pt.make_mesh({"pipe": 4}, devices=jax.devices()[:4])
    S, M, mbg, Din = 4, 6, 4, 8
    w = jnp.asarray(nprng.normal(size=(S, Din, Din)).astype(np.float32) * .3)
    x = jnp.asarray(nprng.normal(size=(M, mbg, Din)).astype(np.float32))

    def stage_fn(p, a):
        return jnp.tanh(a @ p["w"])

    def final_fn(fp, outbuf):
        # 0/0 on bubble devices (their outbuf is all zeros): mean over the
        # buffer's nonzero entries — NaN on every stage but the last
        nz = jnp.sum(jnp.abs(outbuf) > 0)
        return jnp.sum(outbuf * fp["v"]) / nz

    fp = {"v": jnp.asarray(nprng.normal(size=(Din,)).astype(np.float32))}
    loss_sp = parallel.make_pipeline_loss(mesh, stage_fn, final_fn)
    got = float(jax.jit(loss_sp)({"w": w}, fp, x))
    assert np.isfinite(got), "bubble-device NaN poisoned the psum"
    # the BACKWARD must survive too: an outer where alone still multiplies
    # the zeroed cotangent into final_fn's inf partials (0 * inf = NaN) —
    # the double-where (safe bubble input) keeps stage grads finite
    grads = jax.jit(jax.grad(lambda sp: loss_sp(sp, fp, x)))({"w": w})
    for g in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(g)).all(), \
            "bubble-device NaN poisoned the backward"

    # sequential oracle
    def seq(w, fp, x):
        outs = []
        for m in range(M):
            a = x[m]
            for s in range(S):
                a = jnp.tanh(a @ w[s])
            outs.append(a)
        ob = jnp.stack(outs)
        return jnp.sum(ob * fp["v"]) / jnp.sum(jnp.abs(ob) > 0)

    want = float(seq(w, fp, x))
    np.testing.assert_allclose(got, want, rtol=2e-5)
