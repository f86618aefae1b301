"""LongCat-Flash's shortcut-connected double layer
(``models/latent_moe.py:ShortcutMoEBlock`` inside ``LatentMoELM``) against
its plain float32 reference (``benchmarks/longcat_reference.py``) at a
small size with every mechanism present: two double layers (four latent
attentions and cache layers, four dense feed-forwards, two expert layers),
a softmax router of 32 real + 16 identity outputs of which 4 real experts
are held (ids 8 to 11) and 4 picked a token, a selection bias, both
latent factors (``sqrt(32 / 16)`` and ``sqrt(32 / 8)``), 4 heads, rotary
positions, a sliced vocabulary, seeded weights.

Tolerances. Program and reference compute the same float32 arithmetic in
another order (absorbed against expanded attention, a grouped product
against a loop over experts, an online softmax against a whole one, the
identity gates summed before or after they weigh the input). Nothing
renormalises a sublayer's output here, so the residual stream reaches an
RMS of 3 to 4 after two double layers and the float32 roundings of its
values are that much larger than under sandwich norms: logits of order one
agree to ``ATOL`` 5e-4 (measured: 1e-5 and under). The reference with
int8 operands (the control) misses by 0.05 and more, and has to.
"""

import copy
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import longcat_layout as layout          # noqa: E402
from benchmarks import longcat_reference as reference    # noqa: E402
from paddle_tpu.models import (LatentMoEBlock, LatentMoELM,  # noqa: E402
                               ShortcutMoEBlock)
from paddle_tpu.nn.attention import LatentAttention      # noqa: E402
from paddle_tpu.nn.moe import HeldExpertsFFN             # noqa: E402
from paddle_tpu.obs import InMemorySink, Telemetry       # noqa: E402
from paddle_tpu.obs.trace import Tracer                  # noqa: E402
from paddle_tpu.serve import (ContinuousBatchingScheduler,  # noqa: E402
                              DecodeEngine)
from paddle_tpu.serve.kv_cache import PagedKVCache       # noqa: E402
from steered_router import CASES, M, TOKENS, steered     # noqa: E402

ATOL = 5e-4
TOY = {
    "hidden_size": 32, "num_attention_heads": 4, "q_lora_rank": 16,
    "kv_lora_rank": 8, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "ffn_hidden_size": 64, "expert_ffn_hidden_size": 16,
    "n_routed_experts": 4, "zero_expert_num": 16,
    "zero_expert_type": "identity", "moe_topk": 4,
    "routed_scaling_factor": 6, "num_layers": 2, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "max_position_embeddings": 256, "vocab_size": 96,
    "published": {"n_routed_experts": 32, "vocab_size": 768},
    "deployment": {"experts_held": [8, 4]},
    "assumed": {"norm_scale_jitter": 0.1, "select_bias_std": 0.004}}
SEED = 2 ** 31 + 13
Z = reference.dims(TOY)
BS = 4


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def program(z=Z, seed=SEED):
    """The program's model and its variables, float32 copies of the
    bfloat16 values the benchmark would hand it."""
    return layout.build_model(z), {
        "params": f32(layout.program_params(z, seed)), "state": {}}


def ref_logits(ids, rows=None, quant=None, cfg=TOY, routing=None):
    ids = np.asarray(ids, np.int32)
    rows = np.arange(len(ids)) if rows is None else rows
    return reference.forward(cfg, SEED, [(ids, np.asarray(rows, np.int32))],
                             quant, routing)[0]


@pytest.fixture(scope="module")
def prog():
    return program()


# -- (a) forward against the reference ----------------------------------------

def test_forward_agrees_with_reference(prog):
    model, vs = prog
    assert (Z.q_scale, Z.kv_scale) == (2 ** 0.5, 2.0)
    ids = np.random.RandomState(0).randint(0, Z.V, (2, 24))
    logits, aux = model.apply(vs, jnp.asarray(ids), return_aux=True)
    routing = []
    for b in range(2):
        want = ref_logits(ids[b], routing=routing)
        assert np.abs(np.asarray(logits[b]) - want).max() < ATOL
    # the counters count what the reference routed: routing is a list a
    # call, a layer
    picked = [np.concatenate([routing[l][0], routing[Z.L + l][0]])
              for l in range(Z.L)]
    held = [[(picked[l] == Z.held_first + e).sum() for e in range(Z.held)]
            for l in range(Z.L)]
    assert np.array_equal(np.asarray(aux["expert_tokens"]), held)
    assert np.array_equal(np.asarray(aux["zero_pairs"]),
                          [(p >= Z.E).sum() for p in picked])
    # a third of the router's outputs are identities, and about a third
    # of the choices go to them
    share = np.asarray(aux["zero_pairs"]).sum() / (48 * Z.K * Z.L)
    assert 0.2 < share < 0.5
    assert 0.5 < float(np.asarray(logits).std()) < 2.0


def test_int8_control_misses_the_tolerance():
    ids = np.random.RandomState(1).randint(0, Z.V, 32)
    gap = np.abs(ref_logits(ids) - ref_logits(ids, quant="int8")).max()
    assert gap > 100 * ATOL, gap


# -- (b) prefill by chunks, then decode, through the paged cache --------------

def serve_logits(model, vs, prompts, new, chunk, attn_impl="xla",
                 dtype=jnp.float32):
    """Every prompt prefilled by chunks of ``chunk`` (``decode_span``, one
    slot a call as the engine does), then ``new`` decode steps over all
    slots. Returns each prompt's logits at its last prompt row and the
    ``new`` decoded rows, the tokens fed, and the last step's counters."""
    S = len(prompts)
    spec = model.cache_spec()
    MB = 16
    cache = PagedKVCache(spec["layers"], None, None, S * MB + 1, BS,
                         max_slots=S, max_blocks_per_seq=MB, dtype=dtype,
                         row_shapes=spec["pools"])
    for s, p in enumerate(prompts):
        assert cache.ensure_capacity(s, len(p) + new + 1)
    pool = cache.pools["latent"]
    assert pool.shape[0] == 2 * Z.L
    span = jax.jit(lambda *a, **k: model.apply(vs, *a, method="decode_span",
                                               attn_impl=attn_impl, **k))
    step = jax.jit(lambda *a: model.apply(vs, *a, method="decode_step",
                                          attn_impl=attn_impl))
    out = [[] for _ in prompts]
    counters = None
    for s, p in enumerate(prompts):
        table = jnp.asarray(cache.tables[s:s + 1])
        cur = 0
        while cur < len(p):
            n = min(chunk, len(p) - cur)
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :n] = p[cur:cur + n]
            logits, (pool, _), counters = span(
                jnp.asarray(ids), (pool, table),
                jnp.asarray([cur], jnp.int32), jnp.asarray([n], jnp.int32))
            cur += n
        out[s].append(np.asarray(logits[0, n - 1]))
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    fed = [list(p) for p in prompts]
    tables = jnp.asarray(cache.tables)
    for _ in range(new):
        tok = np.asarray([int(np.argmax(o[-1])) for o in out], np.int32)
        for s in range(S):
            fed[s].append(int(tok[s]))
        logits, (pool, _), counters = step(
            jnp.array(tok), (pool, tables), jnp.array(lengths),
            jnp.ones((S,), bool))
        lengths += 1
        for s in range(S):
            out[s].append(np.asarray(logits[s]))
    return [np.stack(o) for o in out], fed, counters


def check_served(out, fed, prompts, atol=ATOL):
    for logits, seq, p in zip(out, fed, prompts):
        want = ref_logits(seq, rows=np.arange(len(p) - 1, len(seq)))
        assert np.abs(logits - want).max() < atol, \
            np.abs(logits - want).max()


def test_chunked_prefill_then_decode_agrees_with_reference(prog):
    """Ragged prompts that cross block (4) and chunk (8) edges; both
    cache rows of every double layer are written and read back."""
    model, vs = prog
    rng = np.random.RandomState(2)
    prompts = [list(rng.randint(0, Z.V, n)) for n in (5, 16, 19)]
    out, fed, counters = serve_logits(model, vs, prompts, new=6, chunk=8)
    check_served(out, fed, prompts)
    assert counters["expert_tokens"].shape == (Z.L, Z.held)
    assert counters["expert_rows"].shape == (Z.L,)
    assert counters["zero_pairs"].shape == (Z.L,)
    # three live tokens, four choices each, an expert layer
    z = np.asarray(counters["zero_pairs"])
    assert ((0 <= z) & (z <= 3 * Z.K)).all()
    # a padded chunk's tail rows count for nothing: 5 live rows of 8
    _, _, c = serve_logits(model, vs, prompts[:1], new=0, chunk=8)
    assert (np.asarray(c["zero_pairs"]) <= 5 * Z.K).all()


def test_int8_control_misses_the_served_tolerance(prog):
    model, vs = prog
    rng = np.random.RandomState(12)
    prompts = [list(rng.randint(0, Z.V, n)) for n in (9, 14)]
    out, fed, _ = serve_logits(model, vs, prompts, new=6, chunk=8)
    for logits, seq, p in zip(out, fed, prompts):
        ctl = ref_logits(seq, rows=np.arange(len(p) - 1, len(seq)),
                         quant="int8")
        assert np.abs(logits - ctl).max() > 20 * ATOL


# -- (c) absorbed against expanded, with the latent factors -------------------

def test_absorbed_decode_agrees_with_expanded_span(prog):
    """The same tokens once through ``decode_step`` (absorbed, one a call)
    and once through ``decode_span`` (expanded, all in one call): one
    arithmetic in two forms, the factor on ``c_kv`` in the cached row."""
    model, vs = prog
    p = list(np.random.RandomState(4).randint(0, Z.V, 11))
    whole, _, _ = serve_logits(model, vs, [p], new=0, chunk=16)
    stepwise, _, _ = serve_logits(model, vs, [p], new=0, chunk=1)
    assert np.abs(whole[0] - stepwise[0]).max() < ATOL
    spec = model.cache_spec()
    cache = PagedKVCache(spec["layers"], None, None, 9, BS, max_slots=1,
                         max_blocks_per_seq=8, row_shapes=spec["pools"])
    cache.ensure_capacity(0, len(p))
    pool, tables = cache.pools["latent"], jnp.asarray(cache.tables)
    for t, tok in enumerate(p):
        logits, (pool, _), _ = model.apply(
            vs, jnp.asarray([tok]), (pool, tables), jnp.asarray([t]),
            method="decode_step")
    assert np.abs(np.asarray(logits[0]) - whole[0][0]).max() < ATOL


def test_cached_row_holds_the_scaled_latent():
    """``kv_scale`` multiplies the normalised ``c_kv`` BEFORE the row is
    written (the rotary key is not scaled); 1.0 multiplies nothing, so
    the other latent configuration's rows are what they were."""
    kw = dict(num_heads=2, q_rank=8, kv_rank=8, nope_dim=4, rope_dim=4,
              v_dim=4)
    x = jnp.asarray(np.random.RandomState(5).randn(1, 1, 16), jnp.float32)
    rows = {}
    for scale in (1.0, 2.0):
        attn = LatentAttention(16, kv_scale=scale, q_scale=3.0 * scale - 2.0,
                               name="attn", **kw)
        vs = attn.init(jax.random.PRNGKey(0), x)
        pool = jnp.zeros((1, 3, BS, attn.row_width), jnp.float32)
        _, pool = attn.apply(vs, x, pool, 0, jnp.asarray([[1, 2]]),
                             jnp.asarray([0]), jnp.asarray([True]),
                             method="decode")
        rows[scale] = np.asarray(pool[0, 1, 0])
    assert np.allclose(rows[2.0][:8], 2.0 * rows[1.0][:8], rtol=1e-6)
    assert np.array_equal(rows[2.0][8:12], rows[1.0][8:12])
    assert np.abs(rows[1.0][:8]).max() > 0.1 and (rows[1.0][12:] == 0).all()


def test_decode_through_the_interpreted_kernel(prog):
    model, vs = prog
    rng = np.random.RandomState(6)
    prompts = [list(rng.randint(0, Z.V, n)) for n in (7, 13)]
    out, fed, _ = serve_logits(model, vs, prompts, new=3, chunk=8,
                               attn_impl="paged")
    check_served(out, fed, prompts)


# -- (d) the expert layer: shares, identities, the bias -----------------------

def full_layer(z=Z):
    """An UNCUT double layer of the toy's widths: all 32 real experts."""
    cfg = copy.deepcopy(TOY)
    cfg["n_routed_experts"] = z.E
    cfg["deployment"]["experts_held"] = [0, z.E]
    zf = reference.dims(cfg)
    return zf, f32(reference.layer_weights(zf, reference.seed32(SEED), 0))


def layer_of(zf, first, count, **kw):
    return HeldExpertsFFN(zf.D, zf.F_e, zf.E, zf.K, (first, count),
                          scaling=zf.scaling, scoring="softmax",
                          select_bias=True, num_zero=zf.Z, name="experts",
                          **kw)


def share_params(w, first, count):
    return {"experts": {"router": w["router"], "select_bias": w["bias"],
                        "gate": w["e_gate"][first:first + count],
                        "up": w["e_up"][first:first + count],
                        "down": w["e_down"][first:first + count]}}


def share(zf, w, first, count, x, live=None):
    """``(y, the held experts' rows, all the layer's counters)``."""
    y, counters = layer_of(zf, first, count).apply(
        {"params": share_params(w, first, count), "state": {}}, x, live)
    return y, counters["expert_tokens"], counters


def test_all_thirty_two_shares_add_up_to_the_uncut_expert_layer():
    """The guide's share test on the expert layer: the real parts that
    every share of ``experts_held`` computes (32 shares of one expert),
    with the identity terms (which every chip computes alike for its own
    tokens) counted once, add up to the uncut reference's whole layer."""
    zf, w = full_layer()
    x = jnp.asarray(np.random.RandomState(7).randn(40, zf.D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, idx = reference._experts(x, w, zf, None)
        ids, gates = reference.route(x, w, zf, None)
    ident = jnp.sum(jnp.where(ids >= zf.E, gates, 0.0), -1)[:, None] * x
    parts = [share(zf, w, e, 1, x) for e in range(zf.E)]
    total = ident + sum(y - ident for y, _, _ in parts)
    assert np.abs(np.asarray(total - whole)).max() < 1e-5
    counts = np.asarray([int(c[0]) for _, c, _ in parts])
    n_zero = int((np.asarray(idx) >= zf.E).sum())
    assert counts.sum() + n_zero == 40 * zf.K           # every pair, once
    assert np.array_equal(counts, np.bincount(
        np.asarray(idx).ravel(), minlength=zf.E + zf.Z)[:zf.E])
    assert {int(s["zero_pairs"]) for _, _, s in parts} == {n_zero}
    # eight shares of four, as the toy configuration cuts it
    eight = sum(share(zf, w, f, 4, x)[0] - ident for f in range(0, 32, 4))
    assert np.abs(np.asarray(ident + eight - whole)).max() < 1e-5


def test_all_thirty_two_shares_add_up_to_the_uncut_double_layer():
    """The same on the whole double layer through the program's block:
    both attentions, both dense feed-forwards and the identity terms,
    which every share computes alike, counted once."""
    zf, w = full_layer()
    x = jnp.asarray(np.random.RandomState(11).randn(1, 12, zf.D),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = reference.block(x[0], w, zf)
        # what every chip computes alike, from the reference's own parts
        rms, att, dense = reference._rms, reference._attention, \
            reference._dense
        h = x[0] + att(rms(x[0], w["n_a0"], zf.eps), w["attn0"], zf, None)
        u = rms(h, w["n_f0"], zf.eps)
        ids, gates = reference.route(u, w, zf, None)
        ident = jnp.sum(jnp.where(ids >= zf.E, gates, 0.0), -1)[:, None] * u
        h = h + dense(u, w["ffn0"], None)
        h = h + att(rms(h, w["n_a1"], zf.eps), w["attn1"], zf, None)
        alike = h + dense(rms(h, w["n_f1"], zf.eps), w["ffn1"], None) + ident
    attn = dict(num_heads=zf.H, q_rank=zf.q_rank, kv_rank=zf.kv_rank,
                nope_dim=zf.nope, rope_dim=zf.rope, v_dim=zf.v,
                rope_base=zf.theta, q_scale=zf.q_scale,
                kv_scale=zf.kv_scale)
    tree = layout.block_tree(zf, w)
    total = alike
    for e in range(zf.E):
        moe = dict(hidden=zf.F_e, num_experts=zf.E, top_k=zf.K,
                   experts_held=(e, 1), scaling=zf.scaling,
                   scoring="softmax", select_bias=True, num_zero=zf.Z)
        blk = ShortcutMoEBlock(zf.D, attn, zf.F, moe, zf.eps, None,
                               name="block")
        params = dict(tree, experts=share_params(w, e, 1)["experts"])
        y, _ = blk.apply({"params": {"block": params}, "state": {}}, x)
        total = total + (y[0] - alike)
    assert np.abs(np.asarray(total - whole)).max() < ATOL
    assert float(jnp.abs(whole - alike).max()) > 0.05   # the experts count


def forced(zf, w, x, lifted):
    """Router and bias under which EVERY token's choices are ``lifted``
    (``K`` outputs): all inputs positive, the router's column ``j`` a
    constant ``c_j``, so the logits' order is the ``c_j``'s for any
    token; the trained-like bias is left out of the choice."""
    c = jnp.zeros((zf.E + zf.Z,)).at[jnp.asarray(lifted)].set(
        jnp.linspace(3.0, 2.0, len(lifted)))
    w = dict(w, router=jnp.ones((zf.D, 1)) * c[None] / zf.D,
             bias=jnp.zeros_like(w["bias"]))
    return w, jnp.abs(x) + 0.1


def test_a_token_of_identity_choices_only_gets_the_weighted_input():
    zf, w = full_layer()
    x = jnp.asarray(np.random.RandomState(8).randn(9, zf.D), jnp.float32)
    w, x = forced(zf, w, x, [zf.E + 1, zf.E + 7, zf.E + 2, zf.E + 15])
    y, counts, stats = share(zf, w, 8, 4, x)
    ids, gates = layer_of(zf, 8, 4).apply(
        {"params": share_params(w, 8, 4), "state": {}}, x, method="route")
    assert (np.asarray(ids) >= zf.E).all()
    # exactly u * the sum of its gates: no expert's product is in it
    assert np.array_equal(np.asarray(y),
                          np.asarray(jnp.sum(gates, -1)[:, None] * x))
    assert int(counts.sum()) == 0
    assert int(stats["zero_pairs"]) == 9 * zf.K
    # gates are the raw softmax scores times 6, not renormalised
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(x @ w["router"], axis=-1)
    assert np.allclose(np.asarray(gates), 6.0 * np.take_along_axis(
        np.asarray(p), np.asarray(ids), -1), rtol=1e-6)
    assert not np.allclose(np.asarray(gates.sum(-1)), 6.0, rtol=0.05)
    # padding rows keep no pair and count for none
    live = jnp.arange(9) < 5
    y2, _, stats2 = share(zf, w, 8, 4, x, live)
    assert int(stats2["zero_pairs"]) == 5 * zf.K
    assert float(jnp.abs(y2[5:]).max()) == 0.0
    assert np.array_equal(np.asarray(y2[:5]), np.asarray(y[:5]))


def test_a_token_of_real_choices_only_counts_all_of_them():
    zf, w = full_layer()
    x = jnp.asarray(np.random.RandomState(9).randn(9, zf.D), jnp.float32)
    w, x = forced(zf, w, x, [9, 30, 10, 2])         # two held in (8, 4)
    y, counts, stats = share(zf, w, 8, 4, x)
    assert int(stats["zero_pairs"]) == 0
    assert np.asarray(counts).tolist() == [0, 9, 9, 0]
    ids, gates = layer_of(zf, 8, 4).apply(
        {"params": share_params(w, 8, 4), "state": {}}, x, method="route")
    want = jnp.zeros_like(x)
    with jax.default_matmul_precision("highest"):
        for e in (9, 10):
            g = jnp.sum(jnp.where(ids == e, gates, 0.0), -1)
            want += g[:, None] * reference._gated(
                x, w["e_gate"][e], w["e_up"][e], w["e_down"][e], None)
    assert np.abs(np.asarray(y - want)).max() < 1e-5


@pytest.mark.parametrize("case", list(CASES))
def test_grouped_product_runs_over_the_kept_pairs_windows(case):
    """``test_latent_moe.py``'s cases on this router: 200 tokens, 4
    choices each of 32 real and 16 identity outputs, 4 held (ids 8 to
    11); a token's other choices are absent experts (0, 30) and
    identities, whose pairs never enter the sort. Each case agrees with
    every token through every held expert plus its identity terms, and
    the product is handed whole windows of the kept pairs' rows only."""
    zf, w = full_layer()
    x = np.random.RandomState(12).randn(TOKENS, zf.D)
    router, x, live, kept = steered(
        case, x, zf.E + zf.Z, yes=(8, 9, 10, 11),
        no=(0, zf.E + 1, 30, zf.E + 7))
    w = dict(w, router=jnp.asarray(router), bias=jnp.zeros_like(w["bias"]))
    x = jnp.asarray(x)
    y, counts, stats = share(zf, w, 8, 4, x,
                             None if live is None else jnp.asarray(live))
    ids, gates = layer_of(zf, 8, 4).apply(
        {"params": share_params(w, 8, 4), "state": {}}, x, method="route")
    if live is not None:
        gates = jnp.where(jnp.asarray(live)[:, None], gates, 0.0)
    want = jnp.sum(jnp.where(ids >= zf.E, gates, 0.0), -1)[:, None] * x
    received = []
    with jax.default_matmul_precision("highest"):
        for e in range(8, 12):
            g = jnp.sum(jnp.where(ids == e, gates, 0.0), -1)
            want += g[:, None] * reference._gated(
                x, w["e_gate"][e], w["e_up"][e], w["e_down"][e], None)
            received.append(int(((ids == e) & (gates > 0)).sum()))
    assert np.abs(np.asarray(y - want)).max() < 1e-5
    assert sum(received) == kept
    assert set(stats) == {"expert_tokens", "expert_rows", "zero_pairs"}
    assert np.asarray(counts).tolist() == received
    assert int(stats["expert_rows"]) == M * -(-kept // M)
    assert int(stats["zero_pairs"]) == int(
        ((ids >= zf.E) & (gates > 0)).sum())


def test_selection_bias_moves_a_choice_and_never_a_gate():
    zf, w = full_layer()
    x = jnp.asarray(np.random.RandomState(10).randn(50, zf.D), jnp.float32)
    route = lambda w: layer_of(zf, 8, 4).apply(
        {"params": share_params(w, 8, 4), "state": {}}, x, method="route")
    plain = dict(w, bias=jnp.zeros_like(w["bias"]))
    ids0, gates0 = (np.asarray(a) for a in route(plain))
    assert (ids0 == 5).any(axis=1).mean() < 0.5
    lifted = dict(plain, bias=plain["bias"].at[5].set(1.0))
    ids1, gates1 = (np.asarray(a) for a in route(lifted))
    assert (ids1 == 5).any(axis=1).all()              # the choice moved
    with jax.default_matmul_precision("highest"):
        p = np.asarray(jax.nn.softmax(x @ w["router"], axis=-1))
    # expert 5's gate is its raw score times 6: the bias is not in it;
    # every expert chosen either way has the gate it had
    for t in range(50):
        g1 = dict(zip(ids1[t], gates1[t]))
        assert g1[5] == pytest.approx(6.0 * p[t, 5], rel=1e-6)
        g0 = dict(zip(ids0[t], gates0[t]))
        assert all(g1[e] == g0[e] for e in set(g0) & set(g1))
    # the configuration's own small bias moves a few choices in a hundred
    ids2, _ = (np.asarray(a) for a in route(w))
    moved = np.mean([len(set(a) - set(b)) for a, b in zip(ids0, ids2)])
    assert 0 < moved / zf.K < 0.25


def test_sigmoid_scoring_is_what_it_was():
    """The other latent configuration's router: no bias, no identity
    experts, sigmoid scores normalised over the k; its two
    counters."""
    layer = HeldExpertsFFN(16, 8, 8, 2, (2, 2), scaling=2.5, name="experts")
    x = jnp.asarray(np.random.RandomState(3).randn(6, 16), jnp.float32)
    vs = layer.init(jax.random.PRNGKey(1), x)
    assert set(vs["params"]["experts"]) == {"router", "gate", "up", "down"}
    _, counters = layer.apply(vs, x)
    assert set(counters) == {"expert_tokens", "expert_rows"}
    _, gates = layer.apply(vs, x, method="route")
    assert np.allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-6)


# -- (e) the cache the model declares, the engine and the scheduler -----------

def test_cache_spec_declares_two_rows_a_block(prog):
    model, vs = prog
    spec = model.cache_spec()
    assert [type(b) for b in model.blocks] == [ShortcutMoEBlock] * Z.L
    assert [b.cache_rows for b in model.blocks] == [2] * Z.L
    assert model.first_row == [0, 2]
    assert spec["layers"] == 2 * Z.L and spec["pools"] == {"latent": (128,)}
    assert spec["counters"] == {"expert_tokens": (Z.L, Z.held),
                                "expert_rows": (Z.L,),
                                "zero_pairs": (Z.L,)}
    engine = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                          num_blocks=16, prefill_chunk=8,
                          max_blocks_per_seq=4)
    assert engine.cache.pools["latent"].shape == (2 * Z.L, 16, BS, 128)
    assert engine.counter_names == ("expert_tokens", "expert_rows",
                                    "zero_pairs")
    # the sandwich-norm stack: one row a block, the counters it had
    other = LatentMoELM(vocab=32, dim=16, num_layers=3, num_dense_layers=1,
                        num_heads=2, q_rank=8, kv_rank=8, nope_dim=4,
                        rope_dim=4, v_dim=4, dense_hidden=32,
                        expert_hidden=8, num_experts=8, top_k=2,
                        experts_held=(0, 4))
    assert [type(b) for b in other.blocks] == [LatentMoEBlock] * 3
    assert other.first_row == [0, 1, 2]
    assert other.cache_spec() == {"layers": 3, "pools": {"latent": (128,)},
                                  "counters": {"expert_tokens": (2, 4),
                                               "expert_rows": (2,)}}


def test_engine_serves_tokens_the_reference_ranks_first(prog):
    """``DecodeEngine`` + ``ContinuousBatchingScheduler``, chunked prefill
    with prefix sharing on: every served token is the reference's first
    choice at its position, or within ``ATOL`` of it; the identity pairs
    are on the engine's spans and in its telemetry records."""
    model, vs = prog
    records = InMemorySink()
    engine = DecodeEngine(model, vs, max_slots=3, block_size=BS,
                          num_blocks=64, prefill_chunk=8,
                          max_blocks_per_seq=16, dtype="float32",
                          telemetry=Telemetry(sinks=[records]))
    assert engine.warmup()["compile_counts"] == {"prefill": 1, "tick": 1}
    engine.tracer = Tracer()
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.RandomState(9)
    prefix = list(rng.randint(0, Z.V, 12))
    prompts = [prefix + list(rng.randint(0, Z.V, n)) for n in (2, 9, 5, 17)]
    reqs = [sched.submit(p, 6) for p in prompts]
    sched.run()
    assert engine.compile_counts() == {"prefill": 1, "tick": 1}
    assert engine.cache.prefix_hit_blocks > 0          # the prefix was shared
    assert engine.expert_pairs > 0 and engine.expert_hits > 0
    for p, r in zip(prompts, reqs):
        assert len(r.tokens) == 6
        seq = p + list(r.tokens[:-1])
        want = ref_logits(seq, rows=np.arange(len(p) - 1, len(seq)))
        served = want[np.arange(6), np.asarray(r.tokens)]
        assert (want.max(-1) - served).max() < ATOL
    spans = {}
    for e in engine.tracer.events():
        if e.get("ph") == "X":
            spans.setdefault(e["name"], []).append(e.get("args", {}))
    ticks = [a for a in spans["engine_tick"] if "zero_pairs" in a]
    assert ticks and len(ticks) == len(spans["engine_tick"])
    for a in ticks:
        choices = a["tokens"] * Z.K * Z.L
        assert 0 <= a["zero_pairs"] <= choices
        assert a["expert_pairs"] <= choices - a["zero_pairs"]
        # whole windows: a tick's is its 3 slots' 12 sorted rows
        assert a["expert_pairs"] <= a["expert_rows"] <= 3 * Z.K * Z.L
        assert a["expert_rows"] % (3 * Z.K) == 0
    drains = [a for a in spans["prefill_drain"] if "zero_pairs" in a]
    assert len(drains) == len(prompts)
    assert sum(a["zero_pairs"] for a in drains) > 0
    tick_records = records.by_kind("decode_tick")
    assert tick_records and all(
        {"zero_pairs", "expert_pairs", "expert_rows"} <= set(r)
        for r in tick_records)
    assert sum(r["zero_pairs"] for r in tick_records) \
        == sum(a["zero_pairs"] for a in ticks)


def test_named_scopes_name_the_double_layers_parts(prog):
    model, vs = prog
    spec = model.cache_spec()
    cache = PagedKVCache(spec["layers"], None, None, 9, BS, max_slots=1,
                         max_blocks_per_seq=8, row_shapes=spec["pools"])
    text = jax.jit(lambda *a: model.apply(vs, *a, method="decode_step")
                   ).lower(jnp.asarray([1]),
                           (cache.pools["latent"], jnp.asarray(cache.tables)),
                           jnp.asarray([0])).as_text(debug_info=True)
    for scope in ("attn0", "ffn0", "attn1", "ffn1", "moe_shortcut",
                  "moe_route", "moe_experts", "moe_zero", "latent_attn"):
        assert f"/{scope}/" in text or f"/{scope}\"" in text, scope
