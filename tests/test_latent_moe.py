"""The latent-attention expert model (``models/latent_moe.py``) against its
plain float32 reference (``benchmarks/pangu_reference.py``) at a small
size with every mechanism present: a leading dense layer and two expert
layers, a router of 16 outputs of which 4 experts are held (ids 4 to 7)
and 2 picked a token, one shared expert, 4 heads, both latents, rotary
positions, sandwich norms, a sliced vocabulary, seeded weights.

Tolerances. Program and reference compute the same float32 arithmetic in
another order (absorbed against expanded attention, a grouped product
against a loop over experts, an online softmax against a whole one), so
logits of order one agree to a few float32 roundings accumulated over
three layers: ``ATOL`` 2e-4 (measured: 2e-5 and under). The reference
with int8 operands (the control) misses by 0.05 and more, and has to.
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

from benchmarks import pangu_layout as layout          # noqa: E402
from benchmarks import pangu_reference as reference    # noqa: E402
from paddle_tpu.nn.moe import HeldExpertsFFN           # noqa: E402
from paddle_tpu.nn import pallas_attention as pa       # noqa: E402
from paddle_tpu.serve import (ContinuousBatchingScheduler,  # noqa: E402
                              DecodeEngine)
from paddle_tpu.serve.kv_cache import PagedKVCache     # noqa: E402
from steered_router import CASES, M, TOKENS, steered   # noqa: E402

ATOL = 2e-4
TOY = {
    "hidden_size": 32, "num_attention_heads": 4, "q_lora_rank": 16,
    "kv_lora_rank": 24, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "intermediate_size": 64, "moe_intermediate_size": 16,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.5, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "max_position_embeddings": 256, "vocab_size": 96,
    "published": {"n_routed_experts": 16, "vocab_size": 768},
    "deployment": {"experts_held": [4, 4]},
    "assumed": {"norm_scale_jitter": 0.1}}
SEED = 2 ** 31 + 11
Z = reference.dims(TOY)
BS = 4


def program(z=Z, seed=SEED):
    """The program's model and its variables, float32 copies of the
    bfloat16 values the benchmark would hand it."""
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), layout.program_params(z, seed))
    return layout.build_model(z), {"params": params, "state": {}}


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
    ids = np.random.RandomState(0).randint(0, Z.V, (2, 24))
    logits, aux = model.apply(vs, jnp.asarray(ids), return_aux=True)
    routing = []
    for b in range(2):
        want = ref_logits(ids[b], routing=routing)
        assert np.abs(np.asarray(logits[b]) - want).max() < ATOL
    # the counters count what the reference routed to the held experts
    n_moe = Z.L - Z.L_dense          # routing: a list a call, a layer
    picked = [np.concatenate([routing[l][0], routing[n_moe + l][0]])
              for l in range(n_moe)]
    want = [[(picked[l] == Z.held_first + e).sum() for e in range(Z.held)]
            for l in range(Z.L - Z.L_dense)]
    assert np.array_equal(np.asarray(aux["expert_tokens"]), want)
    # every mechanism of the block moves the logits: order-one residual,
    # unit-variance scores (the initialiser's claim)
    assert 0.5 < float(np.asarray(logits).std()) < 2.0


def test_int8_control_misses_the_tolerance():
    ids = np.random.RandomState(1).randint(0, Z.V, 32)
    gap = np.abs(ref_logits(ids) - ref_logits(ids, quant="int8")).max()
    assert gap > 100 * ATOL, gap


# -- (b) prefill by chunks, then decode, through the paged cache --------------

def serve_logits(model, vs, prompts, new, chunk, attn_impl="xla",
                 shared=0, dtype=jnp.float32):
    """Every prompt prefilled by chunks of ``chunk`` (``decode_span``,
    one slot a call as the engine does), then ``new`` decode steps over
    all slots; ``shared`` leading tokens of every prompt after the first
    are NOT rewritten (``write_from``: the rows the first prompt wrote are
    mapped into the others' tables). Returns each prompt's logits at its
    last prompt row and the ``new`` decoded rows, and the tokens fed."""
    S = len(prompts)
    spec = model.cache_spec()
    MB = 16
    cache = PagedKVCache(spec["layers"], None, None, S * MB + 1, BS,
                         max_slots=S, max_blocks_per_seq=MB, dtype=dtype,
                         row_shapes=spec["pools"])
    for s, p in enumerate(prompts):
        if s and shared:
            cache.tables[s, :shared // BS] = cache.tables[0, :shared // BS]
            cache._owned[s] = list(cache.tables[s, :shared // BS])
        assert cache.ensure_capacity(s, len(p) + new + 1)
    pool = cache.pools["latent"]
    span = jax.jit(lambda *a, **k: model.apply(vs, *a, method="decode_span",
                                               attn_impl=attn_impl, **k))
    step = jax.jit(lambda *a: model.apply(vs, *a, method="decode_step",
                                          attn_impl=attn_impl))
    out = [[] for _ in prompts]
    counters = None
    for s, p in enumerate(prompts):
        floor = shared if s else 0
        table = jnp.asarray(cache.tables[s:s + 1])
        cur = floor if s else 0
        while cur < len(p):
            n = min(chunk, len(p) - cur)
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :n] = p[cur:cur + n]
            logits, (pool, _), _ = span(
                jnp.asarray(ids), (pool, table),
                jnp.asarray([cur], jnp.int32), jnp.asarray([n], jnp.int32),
                write_from=jnp.asarray([floor], jnp.int32))
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
    """Ragged prompts that cross block (4) and chunk (8) edges."""
    model, vs = prog
    rng = np.random.RandomState(2)
    prompts = [list(rng.randint(0, Z.V, n)) for n in (5, 16, 19)]
    out, fed, counters = serve_logits(model, vs, prompts, new=6, chunk=8)
    check_served(out, fed, prompts)
    assert counters["expert_tokens"].shape == (Z.L - Z.L_dense, Z.held)
    assert counters["expert_rows"].shape == (Z.L - Z.L_dense,)


def test_shared_prefix_is_read_and_not_rewritten(prog):
    model, vs = prog
    rng = np.random.RandomState(3)
    prefix = list(rng.randint(0, Z.V, 8))
    prompts = [prefix + list(rng.randint(0, Z.V, n)) for n in (3, 9)]
    out, fed, _ = serve_logits(model, vs, prompts, new=3, chunk=8, shared=8)
    check_served(out, fed, prompts)


# -- (c), (d) absorbed against expanded; the kernel against the XLA path ------

def test_absorbed_decode_agrees_with_expanded_span(prog):
    """The same tokens once through ``decode_step`` (absorbed, one a
    call) and once through ``decode_span`` (expanded, all in one call):
    one arithmetic in two forms."""
    model, vs = prog
    p = list(np.random.RandomState(4).randint(0, Z.V, 11))
    whole, _, _ = serve_logits(model, vs, [p], new=0, chunk=16)
    stepwise, _, _ = serve_logits(model, vs, [p], new=0, chunk=1)
    assert np.abs(whole[0] - stepwise[0]).max() < ATOL
    by_step, fed, _ = serve_logits(model, vs, [p[:4]], new=0, chunk=4)
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


@pytest.mark.parametrize("dtype, atol", [(jnp.float32, 1e-5),
                                         (jnp.bfloat16, 2e-2)])
def test_latent_kernel_interpreted_agrees_with_xla_path(dtype, atol):
    """float32: the same products in another order. bfloat16: the kernel
    rounds the probabilities of a group of pages to bfloat16 before the
    weighted sum, the XLA path those of the whole context: 2 ** -8 of
    values of order one."""
    rng = np.random.RandomState(5)
    S, H, W, C, MB, N, L = 3, 8, 128, 96, 6, 20, 2
    q = jnp.asarray(rng.randn(S, H, W), dtype)
    pool = jnp.asarray(rng.randn(L, N, BS, W), dtype)
    tables = jnp.asarray(rng.randint(1, N, (S, MB)), jnp.int32)
    lengths = jnp.asarray([0, 5, 23], jnp.int32)
    for group in (1, 2, 4):
        got = pa.latent_paged_decode(q, pool, tables, lengths, jnp.int32(1),
                                     value_width=C, scale=0.1, group=group,
                                     interpret=True)
        want = pa.latent_paged_reference(q, pool, tables, lengths, 1, C, 0.1)
        assert got.shape == (S, H, C) and got.dtype == dtype
        assert float(jnp.abs(got[0]).max()) == 0.0          # empty slot
        assert float(jnp.abs(got.astype(jnp.float32)
                             - want.astype(jnp.float32)).max()) < atol


def test_decode_through_the_interpreted_kernel(prog):
    model, vs = prog
    rng = np.random.RandomState(6)
    prompts = [list(rng.randint(0, Z.V, n)) for n in (7, 13)]
    out, fed, _ = serve_logits(model, vs, prompts, new=3, chunk=8,
                               attn_impl="paged")
    check_served(out, fed, prompts)


# -- (e), (f) the expert layer ------------------------------------------------

def full_layer_weights(z):
    """An UNCUT expert layer of the toy's widths: all 16 experts."""
    cfg = copy.deepcopy(TOY)
    cfg["n_routed_experts"] = z.E
    cfg["deployment"]["experts_held"] = [0, z.E]
    zf = reference.dims(cfg)
    return zf, jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        reference.layer_weights(zf, reference.seed32(SEED), zf.L_dense))


def share(zf, w, first, count, x, live=None):
    layer = HeldExpertsFFN(zf.D, zf.F_e, zf.E, zf.K, (first, count),
                           scaling=zf.scaling, name="experts")
    params = {"experts": {"router": w["router"],
                          "gate": w["e_gate"][first:first + count],
                          "up": w["e_up"][first:first + count],
                          "down": w["e_down"][first:first + count]}}
    y, counters = layer.apply({"params": params, "state": {}}, x, live)
    return y, counters["expert_tokens"]


def test_all_sixteen_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the parts that every share of
    ``experts_held`` computes (16 shares of one expert), with the shared
    expert counted once, add up to the uncut reference's whole layer."""
    zf, w = full_layer_weights(Z)
    x = jnp.asarray(np.random.RandomState(7).randn(40, zf.D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, idx = reference._experts(x, w, zf, None)
        shared = reference._gated(x, w["s_gate"], w["s_up"], w["s_down"],
                                  None)
    parts = [share(zf, w, e, 1, x) for e in range(zf.E)]
    total = shared + sum(y for y, _ in parts)
    assert np.abs(np.asarray(total - whole)).max() < 1e-5
    counts = np.asarray([int(c[0]) for _, c in parts])
    assert counts.sum() == 40 * zf.K                  # every pair, once
    assert np.array_equal(counts, np.bincount(np.asarray(idx).ravel(),
                                              minlength=zf.E))
    # four shares of four, as the toy configuration cuts it
    four = sum(share(zf, w, f, 4, x)[0] for f in (0, 4, 8, 12))
    assert np.abs(np.asarray(shared + four - whole)).max() < 1e-5


def test_no_pair_is_dropped_when_every_token_picks_one_expert():
    zf, w = full_layer_weights(Z)
    w = dict(w)
    x = jnp.asarray(np.random.RandomState(8).randn(33, zf.D), jnp.float32)
    # expert 5's score is the largest for every token
    w["router"] = w["router"].at[:, 5].set(0.0) \
        + 50.0 * jnp.zeros((zf.D, zf.E)).at[:, 5].set(jnp.sign(x).mean(0))
    x = jnp.abs(x) * jnp.sign(jnp.sign(x).mean(0) + 1e-9)
    y, counts = share(zf, w, 5, 1, x)
    assert int(counts[0]) == 33
    idx, gates = HeldExpertsFFN(zf.D, zf.F_e, zf.E, zf.K, (5, 1),
                                scaling=zf.scaling, name="experts").apply(
        {"params": {"experts": {"router": w["router"]}}, "state": {}}, x,
        method="route")
    assert (np.asarray(idx) == 5).sum(1).tolist() == [1] * 33
    g = jnp.sum(jnp.where(idx == 5, gates, 0.0), -1)
    with jax.default_matmul_precision("highest"):
        want = g[:, None] * reference._gated(x, w["e_gate"][5], w["e_up"][5],
                                             w["e_down"][5], None)
    assert np.abs(np.asarray(y - want)).max() < 1e-5
    # padding rows keep no pair
    live = jnp.arange(33) < 20
    y2, counts2 = share(zf, w, 5, 1, x, live)
    assert int(counts2[0]) == 20
    assert float(jnp.abs(y2[20:]).max()) == 0.0
    assert np.abs(np.asarray(y2[:20] - want[:20])).max() < 1e-5


@pytest.mark.parametrize("case", ["as_the_router_falls", *CASES])
def test_grouped_product_keeps_few_pairs_or_all_of_them(case):
    """200 tokens, 2 experts each, 4 of 16 held (ids 4 to 7): the kept
    pairs against the grouped product's window of ``M`` sorted rows. As
    the seeded router falls about 100 of the 400 pairs are kept; steered
    (``steered_router.py``), none, fewer than a window, exactly one, one
    more, three windows with experts 5 and 6 across their edges, every
    pair, and a ``live`` mask on top. No case drops a pair: each agrees
    with every token through every held expert, counts the rows each
    expert received, and hands the product whole windows and no more of
    them than the kept pairs need."""
    zf, w = full_layer_weights(Z)
    w = dict(w)
    x = np.random.RandomState(10).randn(TOKENS, zf.D)
    live = kept = None
    if case in CASES:
        w["router"], x, live, kept = steered(
            case, x, zf.E, yes=(5, 6), no=(0, 1))
    x = jnp.asarray(x, jnp.float32)
    layer = HeldExpertsFFN(zf.D, zf.F_e, zf.E, zf.K, (4, 4),
                           scaling=zf.scaling, name="experts")
    params = {"experts": {"router": w["router"], "gate": w["e_gate"][4:8],
                          "up": w["e_up"][4:8], "down": w["e_down"][4:8]}}
    y, counters = layer.apply({"params": params, "state": {}}, x,
                              None if live is None else jnp.asarray(live))
    idx, gates = layer.apply({"params": params, "state": {}}, x,
                             method="route")
    if live is not None:
        gates = jnp.where(jnp.asarray(live)[:, None], gates, 0.0)
    want, received = jnp.zeros_like(x), []
    with jax.default_matmul_precision("highest"):
        for e in range(4, 8):
            g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)
            want += g[:, None] * reference._gated(
                x, w["e_gate"][e], w["e_up"][e], w["e_down"][e], None)
            received.append(int(((idx == e) & (gates > 0)).sum()))
    assert np.abs(np.asarray(y - want)).max() < 1e-5
    assert (sum(received) == kept) if case in CASES \
        else (0 < sum(received) <= 256)
    assert set(counters) == {"expert_tokens", "expert_rows"}
    assert np.asarray(counters["expert_tokens"]).tolist() == received
    assert int(counters["expert_rows"]) == M * -(-sum(received) // M)


# -- the engine and the scheduler, as the serve driver builds them ------------

def test_engine_serves_tokens_the_reference_ranks_first(prog):
    """``DecodeEngine`` + ``ContinuousBatchingScheduler``, chunked prefill
    with prefix sharing on: every served token is the reference's first
    choice at its position, or within ``ATOL`` of it."""
    model, vs = prog
    engine = DecodeEngine(model, vs, max_slots=3, block_size=BS,
                          num_blocks=64, prefill_chunk=8,
                          max_blocks_per_seq=16, dtype="float32")
    assert engine.warmup()["compile_counts"] == {"prefill": 1, "tick": 1}
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


def test_engine_refuses_what_the_latent_pool_does_not_carry(prog):
    model, vs = prog
    kw = dict(max_slots=2, block_size=BS, num_blocks=16, prefill_chunk=8,
              max_blocks_per_seq=4)
    with pytest.raises(NotImplementedError, match="int8"):
        DecodeEngine(model, vs, kv_dtype="int8", **kw)
    with pytest.raises(NotImplementedError, match="one-shot prefill"):
        DecodeEngine(model, vs, **{**kw, "prefill_chunk": None})
    engine = DecodeEngine(model, vs, **kw)
    engine.admit(0, [1, 2, 3])
    with pytest.raises(NotImplementedError, match="export_slot"):
        engine.export_slot(0)
    with pytest.raises(NotImplementedError, match="adopt_slot"):
        engine.adopt_slot(1, [1, 2], 3, None, None)
