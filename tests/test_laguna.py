"""Laguna-S-2.1's decoder (``models/window_moe.py:WindowMoELM``: full and
windowed layers over grouped KV heads, per-head gates, YaRN and plain
rotary, a softmax router renormalised over its choices beside a shared
expert) against its plain float32 reference
(``benchmarks/laguna_reference.py``) at a small size with every mechanism
present: five layers ``[full, sliding, sliding, sliding, full]`` with 4 and
6 query heads on 2 KV heads of 16, a window of 8, one dense layer and four
expert layers of 16 experts with 4 held (ids 4 to 7) and 3 picked a token,
seeded weights; through the paged cache in two pool GROUPS, the window
group a ring of 3 blocks of 4 a slot.

Tolerances. Program and reference compute the same float32 arithmetic in
another order (an online softmax over tiles read back from the pool
against a whole one, a grouped product against a loop over experts):
logits of order one agree to ``ATOL`` 5e-4 (measured: 3e-5 and under). The
reference with int8 operands (the control) misses by a hundred times that,
and has to; so does each planted fault.
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

from benchmarks import laguna_layout as layout          # noqa: E402
from benchmarks import laguna_reference as reference    # noqa: E402
from paddle_tpu.models import WindowMoEBlock            # noqa: E402
from paddle_tpu.nn.moe import HeldExpertsFFN            # noqa: E402
from paddle_tpu.nn.pallas_attention import (            # noqa: E402
    paged_decode_attention, paged_reference_attention,
    paged_span_attention, paged_span_reference_attention)
from paddle_tpu.nn.rotary import (apply_rotary, rotary_angles,  # noqa: E402
                                  yarn_frequencies)
from paddle_tpu.obs.trace import Tracer                 # noqa: E402
from paddle_tpu.serve import (ContinuousBatchingScheduler,  # noqa: E402
                              DecodeEngine)
from paddle_tpu.serve.kv_cache import (PagedKVCache,     # noqa: E402
                                       scatter_span, write_span)

ATOL = 5e-4
WINDOW, BS = 8, 4
RING = -(-WINDOW // BS) + 1
TOY = {
    "hidden_size": 32, "head_dim": 16, "num_key_value_heads": 2,
    "num_attention_heads": 4, "intermediate_size": 64,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "num_experts": 4, "num_experts_per_tok": 3, "num_hidden_layers": 5,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "gating": "per-head", "sliding_window": WINDOW, "rms_norm_eps": 1e-6,
    "moe_apply_router_weight_on_input": False,
    "moe_router_logit_softcapping": 0, "moe_routed_scaling_factor": 2.5,
    "max_position_embeddings": 256, "vocab_size": 96,
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
    "assumed": {"norm_scale_jitter": 0.1}}
SEED = 2 ** 31 + 35
Z = reference.dims(TOY)


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def program(z=Z, seed=SEED):
    """The program's model and its variables, float32 copies of the
    bfloat16 values the benchmark would hand it."""
    return layout.build_model(z), {
        "params": f32(layout.program_params(z, seed)), "state": {}}


def ref_logits(ids, rows=None, cfg=TOY, **kw):
    ids = np.asarray(ids, np.int32)
    rows = np.arange(len(ids)) if rows is None else rows
    return reference.forward(cfg, SEED, [(ids, np.asarray(rows, np.int32))],
                             **kw)[0]


@pytest.fixture(scope="module")
def prog():
    return program()


# -- (i) forward against the reference ----------------------------------------

@pytest.mark.parametrize("length", [40, 256])
def test_forward_agrees_with_reference(prog, length):
    """At 256 positions the reference takes its queries 128 at a time and
    hands a sliding layer's chunk only the keys its window can reach."""
    model, vs = prog
    assert Z.heads == (4, 6, 6, 6, 4) and Z.dense == (True,) + (False,) * 4
    assert Z.windows == (None, 8, 8, 8, None)
    ids = np.random.RandomState(0).randint(0, Z.V, (2, length))
    logits, aux = model.apply(vs, jnp.asarray(ids), return_aux=True)
    routing = []
    for b in range(2):
        want = ref_logits(ids[b], routing=routing)
        assert np.abs(np.asarray(logits[b]) - want).max() < ATOL
    # the counters count what the reference routed: a list a call, a layer
    moe = sum(not d for d in Z.dense)
    picked = [np.concatenate([routing[l][0], routing[moe + l][0]])
              for l in range(moe)]
    held = [[(picked[l] == Z.held_first + e).sum() for e in range(Z.held)]
            for l in range(moe)]
    assert np.array_equal(np.asarray(aux["expert_tokens"]), held)
    assert 0.5 < float(np.asarray(logits).std()) < 3.0
    if length == 256:       # the near-keys path is the whole one, masked
        ids = ids[0]
        assert np.abs(ref_logits(ids) - ref_logits(
            np.concatenate([ids, ids[:1]]))[:256]).max() < 1e-5


@pytest.mark.parametrize("kw", [{"quant": "int8"}] + [
    {"fault": f} for f in reference.FAULTS], ids=lambda kw: str(*kw.values()))
def test_control_and_faults_miss_the_tolerance(kw):
    """What the limits of ``correct`` are set between: the int8 control
    and each wrong layer move a logit a hundred tolerances and more, on a
    sequence five windows long."""
    ids = np.random.RandomState(1).randint(0, Z.V, 40)
    gap = np.abs(ref_logits(ids) - ref_logits(ids, **kw)).max()
    assert gap > 100 * ATOL, gap


def test_yarn_frequencies_and_partial_rotation():
    """The program's YaRN frequencies against the reference's own and a
    hand count at the published settings (correction dimensions 9 and 18
    of 32: unscaled below, over 128 above); a partial rotation turns
    the first values of a head and passes the others."""
    pub = reference.Rotary(500000.0, 64, 128.0, 8192, 32.0, 1.0, 1.485)
    got = yarn_frequencies(64, 500000.0, 128.0, 8192)
    assert np.allclose(got, reference.yarn_inv_freq(pub), rtol=1e-6)
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    assert np.allclose(got[:10], plain[:10], rtol=1e-6)
    assert np.allclose(got[18:], plain[18:] / 128, rtol=1e-6)
    assert plain[14] / 128 < got[14] < plain[14]
    x = jnp.asarray(np.random.RandomState(2).randn(3, 16), jnp.float32)
    cos, sin = rotary_angles(jnp.arange(3), 8, 10000.0, factor=1.5)
    y = apply_rotary(x, cos, sin)
    assert np.array_equal(np.asarray(y[:, 8:]), np.asarray(x[:, 8:]))
    assert np.allclose(np.asarray(y[0, :8]), 1.5 * np.asarray(x[0, :8]))
    assert not np.allclose(np.asarray(y[2, :8]), 1.5 * np.asarray(x[2, :8]))


# -- (ii) prefill by chunks, then decode, through both cache groups -----------

def make_cache(model, slots, blocks_per_seq=16, dtype=jnp.float32):
    spec = model.cache_spec()
    return PagedKVCache(None, None, None, slots * blocks_per_seq + 1, BS,
                        max_slots=slots, max_blocks_per_seq=blocks_per_seq,
                        dtype=dtype, groups=spec["groups"])


def serve_logits(model, vs, prompts, new, chunk, attn_impl="xla"):
    """Every prompt prefilled by chunks of ``chunk`` (``decode_span``, one
    slot a call as the engine does), then ``new`` decode steps over all
    slots. Returns each prompt's logits at its last prompt row and the
    ``new`` decoded rows, the tokens fed, the cache and its pools."""
    S = len(prompts)
    cache = make_cache(model, S)
    for s, p in enumerate(prompts):
        assert cache.ensure_capacity(s, len(p) + new + 1)
    pools = tuple(cache.pools.values())
    span = jax.jit(lambda *a: model.apply(vs, *a, method="decode_span"))
    step = jax.jit(lambda *a: model.apply(vs, *a, method="decode_step",
                                          attn_impl=attn_impl))
    out = [[] for _ in prompts]
    for s, p in enumerate(prompts):
        cur = 0
        while cur < len(p):
            n = min(chunk, len(p) - cur)
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :n] = p[cur:cur + n]
            logits, (*pools, _), _ = span(
                jnp.asarray(ids), (*pools, cache.slot_tables(s)),
                jnp.asarray([cur], jnp.int32), jnp.asarray([n], jnp.int32))
            cur += n
        out[s].append(np.asarray(logits[0, n - 1]))
        cache.lengths[s] = len(p)
    fed = [list(p) for p in prompts]
    for _ in range(new):
        tok = np.asarray([int(np.argmax(o[-1])) for o in out], np.int32)
        for s in range(S):
            fed[s].append(int(tok[s]))
        tables, lengths = cache.device_tables()
        logits, (*pools, _), counters = step(
            jnp.array(tok), (*pools, tables), lengths, jnp.ones((S,), bool))
        cache.lengths += 1
        for s in range(S):
            out[s].append(np.asarray(logits[s]))
    return [np.stack(o) for o in out], fed, cache, pools


def check_served(out, fed, prompts, atol=ATOL):
    for logits, seq, p in zip(out, fed, prompts):
        want = ref_logits(seq, rows=np.arange(len(p) - 1, len(seq)))
        assert np.abs(logits - want).max() < atol, \
            np.abs(logits - want).max()


@pytest.mark.parametrize("attn_impl", ["xla", "paged"])
def test_chunked_prefill_then_decode_agrees_with_reference(prog, attn_impl):
    """Ragged prompts up to five windows long whose lengths cross block
    (4), chunk (8) and ring (12 rows) edges, decoded on to 48: the window
    layers' rings wrap three times, in the chunks and in the ticks. With
    ``paged`` the ticks go through the interpreted grouped kernel, whose
    walk starts at the window's first page."""
    model, vs = prog
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(0, Z.V, n)) for n in (5, 23, 38)]
    out, fed, cache, pools = serve_logits(model, vs, prompts, new=10,
                                          chunk=8, attn_impl=attn_impl)
    assert [len(s) for s in fed] == [15, 33, 48]
    check_served(out, fed, prompts)
    # a window layer's pool is the slots' rings and the null block
    shapes = {n: p.shape for n, p in zip(cache.pools, pools)}
    assert shapes["window/k"] == (3, 3 * RING + 1, Z.H_kv, BS, Z.hd)
    assert shapes["full/k"] == (2, 3 * 16 + 1, Z.H_kv, BS, Z.hd)


def test_span_of_one_agrees_with_a_chunk(prog):
    """The same prompt a token a call and eight a call: one arithmetic."""
    model, vs = prog
    p = list(np.random.RandomState(4).randint(0, Z.V, 27))
    whole, _, _, _ = serve_logits(model, vs, [p], new=0, chunk=8)
    stepwise, _, _, _ = serve_logits(model, vs, [p], new=0, chunk=1)
    assert np.abs(whole[0] - stepwise[0]).max() < ATOL


def test_engine_serves_what_the_reference_ranks_first(prog):
    """Through ``DecodeEngine`` under the scheduler (chunked prefill, both
    groups, slots reused): every served token is the float32 reference's
    first choice at its position, or within the tolerance of it."""
    model, vs = prog
    engine = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                          prefill_chunk=8, max_blocks_per_seq=16)
    assert engine.cache.share_prefix is False     # the default, for a ring
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.RandomState(5)
    reqs = [sched.submit(list(rng.randint(0, Z.V, n)), m)
            for n, m in ((30, 12), (9, 20), (21, 16), (13, 8))]
    while not all(r.done for r in reqs):
        sched.step()
    assert engine.compile_counts() == {"prefill": 1, "tick": 1}
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        want = ref_logits(seq[:-1], rows=np.arange(len(r.prompt) - 1,
                                                   len(seq) - 1))
        served = want[np.arange(len(r.tokens)), np.asarray(r.tokens)]
        assert (want.max(-1) - served).max() < ATOL


@pytest.mark.parametrize("start,n,write_from", [
    ([3, 6], [9, 5], None), ([4, 8], [9, 3], None), ([3, 6], [9, 5], [5, 6]),
    ([3, 6], [9, 0], [0, 20]), ([0, 7], [9, 9], [0, 16])],
    ids=["ragged", "page-aligned", "write-from", "nothing-live", "mixed"])
def test_fresh_page_writes_agree_with_the_scatter(start, n, write_from):
    """``write_span(by_page=True)`` on ``k`` / ``v`` rows reads no page:
    every position below the span's end holds what the scatter oracle
    writes (positions below ``write_from`` what they held), the rows past
    the end in the span's last page what they held or zeros, and no other
    layer is touched."""
    rng = np.random.RandomState(13)
    pool = jnp.asarray(rng.randn(2, 14, 3, BS, 8), jnp.float32)
    kv = jnp.asarray(rng.randn(2, 9, 3, 8), jnp.float32)
    table = jnp.asarray([[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]],
                        jnp.int32)
    args = (table, jnp.asarray(start, jnp.int32), jnp.asarray(n, jnp.int32),
            None if write_from is None else jnp.asarray(write_from,
                                                        jnp.int32))
    got = np.asarray(write_span(pool, 1, kv, *args, by_page=True))
    want = np.asarray(scatter_span(pool[1], kv, *args))
    assert np.array_equal(got[0], np.asarray(pool[0]))
    for s in range(2):
        for p in range(6 * BS):
            row = got[1, int(table[s, p // BS]), :, p % BS]
            same = np.array_equal(
                row, want[int(table[s, p // BS]), :, p % BS])
            assert same or (p >= start[s] + n[s] and not row.any()), (s, p)


# -- (iii) the kernels, interpreted, against the XLA oracle --------------------

@pytest.mark.parametrize("window", [None, 6])
@pytest.mark.parametrize("groups", [1, 6, 9])
def test_paged_kernels_with_grouped_heads_and_a_window(groups, window):
    """``paged_decode`` and ``paged_span`` for ``G`` query heads a KV head
    and a window, lengths on and off page edges (13, 16, 32 of pages of
    4), an empty slot, against the gather-and-softmax oracle."""
    rng = np.random.RandomState(6)
    S, Hk, D, MB, L = 4, 2, 128, 8, 2
    H, N = Hk * groups, S * MB + 1
    pages_k, pages_v = (jnp.asarray(rng.randn(L, N, Hk, BS, D), jnp.float32)
                        for _ in range(2))
    tables = jnp.asarray(1 + rng.permutation(S * MB).reshape(S, MB),
                         jnp.int32)
    lengths = jnp.asarray([0, 13, 16, 32], jnp.int32)
    q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
    got = paged_decode_attention(q, pages_k, pages_v, tables, lengths, 1,
                                 window=window, interpret=True)
    want = paged_reference_attention(q, pages_k[1], pages_v[1], tables,
                                     lengths, window=window)
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(got[0]).max()) == 0.0
    Q = 5
    qs = jnp.asarray(rng.randn(S, Q, H, D), jnp.float32)
    start = jnp.asarray([3, 9, 12, 27], jnp.int32)
    n = jnp.asarray([0, 5, 4, 5], jnp.int32)
    got = paged_span_attention(qs, pages_k, pages_v, tables, start, n, 1,
                               window=window, interpret=True)
    want = paged_span_reference_attention(qs, pages_k[1], pages_v[1], tables,
                                          start, n, window=window)
    live = (np.arange(Q)[None] < np.asarray(n)[:, None])[:, :, None, None]
    assert float(jnp.abs(jnp.where(live, got - want, 0.0)).max()) < 2e-5
    if window is not None:      # the window moves the result
        full = paged_reference_attention(q, pages_k[1], pages_v[1], tables,
                                         lengths)
        assert float(jnp.abs(full[3] - paged_reference_attention(
            q, pages_k[1], pages_v[1], tables, lengths,
            window=window)[3]).max()) > 1e-3


# -- (iv) the expert layer's shares --------------------------------------------

def full_layer(z=Z):
    """An UNCUT expert layer of the toy's widths: all 16 experts."""
    cfg = copy.deepcopy(TOY)
    cfg["num_experts"] = z.E
    cfg["deployment"]["experts_held"] = [0, z.E]
    zf = reference.dims(cfg)
    return zf, f32(reference.layer_weights(zf, reference.seed32(SEED), 1))


def share(zf, w, first, count, x, live=None):
    layer = HeldExpertsFFN(zf.D, zf.F_e, zf.E, zf.K, (first, count),
                           scaling=zf.scaling, scoring="softmax",
                           normalise=True, name="experts")
    params = {"experts": {"router": w["router"],
                          "gate": w["e_gate"][first:first + count],
                          "up": w["e_up"][first:first + count],
                          "down": w["e_down"][first:first + count]}}
    return layer.apply({"params": params, "state": {}}, x, live), layer, \
        params


@pytest.mark.parametrize("held", [1, 4])
def test_all_shares_add_up_to_the_uncut_expert_layer(held):
    """The guide's share test: the parts that every share of
    ``experts_held`` computes (16 shares of one expert, 4 of four), with
    the shared expert (which every chip computes alike) counted once, add
    up to the uncut reference's whole layer."""
    zf, w = full_layer()
    x = jnp.asarray(np.random.RandomState(7).randn(40, zf.D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, idx = reference._experts(x, w, zf, None)
        alike = reference._gated(x, w["s_gate"], w["s_up"], w["s_down"],
                                 None)
    parts = [share(zf, w, first, held, x)[0]
             for first in range(0, zf.E, held)]
    total = alike + sum(y for y, _ in parts)
    assert np.abs(np.asarray(total - whole)).max() < 1e-5
    counts = np.concatenate([np.asarray(c["expert_tokens"])
                             for _, c in parts])
    assert counts.sum() == 40 * zf.K                     # every pair, once
    assert np.array_equal(counts, np.bincount(np.asarray(idx).ravel(),
                                              minlength=zf.E))
    assert float(jnp.abs(whole - alike).max()) > 0.05    # the experts count


def test_gates_are_the_softmax_renormalised_over_the_choices():
    """``scoring="softmax"`` with ``normalise``: a token's k gates add up
    to the scaling; LongCat's rule (raw scores) is what it was."""
    zf, w = full_layer()
    x = jnp.asarray(np.random.RandomState(8).randn(9, zf.D), jnp.float32)
    _, layer, params = share(zf, w, 4, 4, x)
    ids, gates = layer.apply({"params": params, "state": {}}, x,
                             method="route")
    with jax.default_matmul_precision("highest"):
        want_ids, want = reference.route(x, w, zf, None)
    assert np.array_equal(np.asarray(ids), np.asarray(want_ids))
    assert np.allclose(np.asarray(gates), np.asarray(want), rtol=1e-6)
    assert np.allclose(np.asarray(gates.sum(-1)), zf.scaling, rtol=1e-6)
    raw = HeldExpertsFFN(zf.D, zf.F_e, zf.E, zf.K, (4, 4),
                         scaling=zf.scaling, scoring="softmax",
                         name="experts")
    assert raw.normalise is False
    _, raw_gates = raw.apply({"params": params, "state": {}}, x,
                             method="route")
    assert float(raw_gates.sum(-1).max()) < 0.9 * zf.scaling


def test_all_shares_add_up_to_the_uncut_layer_through_the_block():
    """The same on a whole sliding expert layer through the program's
    block: attention with its gate and the shared expert, which every
    share computes alike, counted once."""
    zf, w = full_layer()
    x = jnp.asarray(np.random.RandomState(9).randn(1, 20, zf.D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = reference.block(x[0], w, zf, 1)
        h = x[0] + reference._attention(
            reference._rms(x[0], w["n_attn"], zf.eps), w, zf, 1, None)
        u = reference._rms(h, w["n_mlp"], zf.eps)
        alike = h + reference._gated(u, w["s_gate"], w["s_up"], w["s_down"],
                                     None)
    tree = layout.block_tree(zf, 1, w)
    attn = dict(num_heads=zf.heads[1], num_kv_heads=zf.H_kv,
                head_dim=zf.hd, window=zf.windows[1], head_gate=True,
                **layout._rotary_args(zf.rope_window))
    total = alike
    for first in range(0, zf.E, 4):
        moe = dict(hidden=zf.F_e, num_experts=zf.E, top_k=zf.K,
                   experts_held=(first, 4), scaling=zf.scaling,
                   scoring="softmax", normalise=True, shared_hidden=zf.F_s)
        blk = WindowMoEBlock(zf.D, attn, None, moe, zf.eps, None,
                             name="block")
        experts = {"router": w["router"],
                   **{k: w["e_" + k][first:first + 4]
                      for k in ("gate", "up", "down")}}
        y, _ = blk.apply({"params": {"block": dict(tree, experts=experts)},
                          "state": {}}, x)
        total = total + (y[0] - alike)
    assert np.abs(np.asarray(total - whole)).max() < ATOL


# -- (v) a window group holds its window and no more ---------------------------

def test_window_group_holds_a_ring_a_slot_whatever_the_context(prog):
    """Over a long run under the scheduler (contexts to 60, five rings
    long) the window group's pool is ``slots * ring + 1`` blocks, its
    blocks in use never pass ``ring`` a slot, the table never changes,
    and ``free_slot`` returns them; the full group's account grows with
    the context and comes back whole."""
    model, vs = prog
    engine = DecodeEngine(model, vs, max_slots=3, block_size=BS,
                          prefill_chunk=8, max_blocks_per_seq=16)
    engine.tracer = Tracer()
    cache = engine.cache
    assert cache.groups["window"].ring == RING == 3
    assert cache.pools["window/k"].shape[1] == 3 * RING + 1
    ring_table = np.asarray(cache.device_tables()[0]["window"])
    assert np.array_equal(ring_table[1, :7], [4, 5, 6, 4, 5, 6, 4])
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.RandomState(10)
    reqs = [sched.submit(list(rng.randint(0, Z.V, n)), m)
            for n, m in ((40, 20), (6, 30), (25, 12), (33, 9), (12, 40))]
    peak = {"window": 0, "full": 0}
    while not all(r.done for r in reqs):
        sched.step()
        facts = cache.group_facts()
        busy = int((cache.lengths > 0).sum())
        assert facts["window"]["blocks_in_use"] <= RING * busy
        assert facts["window"]["blocks_live"] <= facts["full"]["blocks_live"]
        for g in peak:
            peak[g] = max(peak[g], facts[g]["blocks_in_use"])
    assert peak["window"] == 3 * RING and peak["full"] > 4 * RING
    assert np.array_equal(np.asarray(cache.device_tables()[0]["window"]),
                          ring_table)
    facts = cache.group_facts()
    assert facts["window"]["blocks_in_use"] == 0
    assert facts["full"]["blocks_in_use"] == 0
    assert cache.free_blocks == cache.num_blocks - 1
    assert facts["full"]["block_bytes"] == 2 * 2 * Z.H_kv * BS * Z.hd * 4
    assert facts["window"]["block_bytes"] == 3 * 2 * Z.H_kv * BS * Z.hd * 4
    assert cache.kv_bytes_per_token * BS == facts["full"]["block_bytes"]
    # the tick's span says how many keys each kind of layer read
    ticks = [e["args"] for e in engine.tracer.events()
             if e["name"] == "engine_tick"]
    assert ticks and all(
        0 < t["live_tokens_window"] <= min(t["live_tokens"],
                                           WINDOW * t["active"])
        for t in ticks)
    assert any(t["live_tokens_window"] < t["live_tokens"] for t in ticks)


# -- (vi) what a window group does not carry refuses at engine build ----------

@pytest.mark.parametrize("kw,what", [
    ({"share_prefix": True}, "share_prefix"),
    ({"kv_dtype": "int8"}, "int8"),
    ({"mesh": "a mesh"}, "mesh="),
    ({"prefill_chunk": None}, "one-shot prefill"),
    ({"speculative": 2}, "speculative"),
    ({"prefill_chunk": 16}, "longer than the window"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_engine_refuses_what_a_window_group_does_not_carry(prog, kw, what):
    model, vs = prog
    kw = {"prefill_chunk": 8, **kw}
    with pytest.raises(NotImplementedError, match=what):
        DecodeEngine(model, vs, max_slots=2, block_size=BS,
                     max_blocks_per_seq=16, **kw)


def test_export_and_adopt_refuse_when_called(prog):
    model, vs = prog
    engine = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                          prefill_chunk=8, max_blocks_per_seq=16)
    engine.admit(0, [1, 2, 3], reserve_len=8)
    with pytest.raises(NotImplementedError, match="export_slot"):
        engine.export_slot(0)
    with pytest.raises(NotImplementedError, match="adopt_slot"):
        engine.adopt_slot(1, [1, 2, 3], 4, None, None)
    with pytest.raises(ValueError, match="prefix sharing"):
        PagedKVCache(None, None, None, 9, BS, max_slots=2,
                     max_blocks_per_seq=8, share_prefix=True,
                     groups=model.cache_spec()["groups"])
