"""Serving runtime tests (ISSUE 9): paged KV cache correctness, the
decode-shaped Pallas kernel vs its oracle, engine/scheduler behavior, and
the two acceptance contracts —

- **KV correctness**: prefill + N x decode_step logits BIT-EQUAL (f32,
  CPU) to the full-sequence forward, for ragged lengths crossing block
  boundaries; and block free/reuse reproduces identical tokens after
  eviction churn (stale pool contents must be fully masked).
- **The no-retrace invariant**: one compiled program per entry point
  across arbitrary admission/eviction churn.
"""

import functools
import logging
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models import TransformerLM
from paddle_tpu.serve import (BlockAllocator, ContinuousBatchingScheduler,
                              DecodeEngine, PagedKVCache)
from paddle_tpu.serve import kv_cache as kvc

V, W, DIM, LAYERS, HEADS, FFN = 64, 24, 32, 2, 4, 64
BS, MB = 4, 6                        # block_size x max_blocks = W


@pytest.fixture(scope="module")
def model_and_vars():
    model = TransformerLM(vocab=V, dim=DIM, num_layers=LAYERS,
                          num_heads=HEADS, ffn_hidden=FFN, max_len=W)
    vs = model.init(jax.random.PRNGKey(0), jnp.zeros((1, W), jnp.int32))
    return model, vs


def _greedy_oracle(model, vs, prompt, n_new):
    """Token-by-token greedy decode through the full training forward."""
    fwd = jax.jit(lambda v, i: model.apply(v, i))
    seq, out = list(prompt), []
    for _ in range(n_new):
        pad = np.zeros((1, W), np.int32)
        pad[0, :len(seq)] = seq
        logits = fwd(vs, jnp.asarray(pad))
        tok = int(np.argmax(np.asarray(logits[0, len(seq) - 1])))
        out.append(tok)
        seq.append(tok)
    return out


# ---------------------------------------------------------------------------
# kv_cache: allocator + pure gather/scatter
# ---------------------------------------------------------------------------

def test_block_allocator_invariants():
    a = BlockAllocator(6)                    # blocks 1..5 usable
    assert a.num_free == 5
    got = a.alloc(3)
    assert got == [1, 2, 3] and a.num_free == 2
    assert a.alloc(3) is None and a.num_free == 2   # refuse, no change
    a.free([2])
    assert a.alloc(3) == [4, 5, 2]           # FIFO reuse
    with pytest.raises(AssertionError):
        a.free([kvc.NULL_BLOCK])


def test_cache_capacity_and_free(nprng):
    c = PagedKVCache(num_layers=1, num_heads=2, head_dim=4, num_blocks=5,
                     block_size=BS, max_slots=2, max_blocks_per_seq=MB)
    assert c.context_width == MB * BS
    assert c.ensure_capacity(0, 9)           # 3 blocks
    assert c.free_blocks == 1
    assert not c.ensure_capacity(1, 9)       # needs 3, 1 free: refuse
    assert c.free_blocks == 1                # refusal changed nothing
    assert c.ensure_capacity(1, 3)           # 1 block fits
    c.free_slot(0)
    assert c.free_blocks == 3
    assert (c.tables[0] == kvc.NULL_BLOCK).all() and c.lengths[0] == 0


def test_gather_scatter_roundtrip(nprng):
    H, hd = 2, 4
    pages = jnp.zeros((8, H, BS, hd), jnp.float32)
    table = jnp.asarray([[3, 1, 5, 0, 0, 0]], jnp.int32)
    kv = jnp.asarray(nprng.randn(1, MB * BS, H, hd).astype(np.float32))
    length = jnp.asarray([9], jnp.int32)
    pages = kvc.scatter_prefill(pages, kv, table, length)
    got = kvc.gather_pages(pages, table)
    np.testing.assert_array_equal(np.asarray(got[0, :9]),
                                  np.asarray(kv[0, :9]))
    # rows >= length went to the null block, not the sequence's pages:
    # row 8 is block 5 offset 0, so block 5's tail stays untouched
    assert not np.any(np.asarray(pages[5][:, 1:]))

    tok = jnp.asarray(nprng.randn(1, H, hd).astype(np.float32))
    pages = kvc.scatter_token(pages, tok, table, jnp.asarray([9]),
                              jnp.asarray([True]))
    got = kvc.gather_pages(pages, table)
    np.testing.assert_array_equal(np.asarray(got[0, 9]), np.asarray(tok[0]))
    # inactive slots scatter to the null block only
    before = np.asarray(pages)
    pages2 = kvc.scatter_token(pages, tok * 7, table, jnp.asarray([9]),
                               jnp.asarray([False]))
    after = np.asarray(pages2)
    np.testing.assert_array_equal(before[1:], after[1:])


def _stacked_pool(nprng, kind, L, N, H, hd):
    raw = jnp.asarray(nprng.randn(L, N, H, BS, hd).astype(np.float32))
    return kvc.quantize_rows(raw) if kind == "int8" else raw


@functools.partial(jax.jit, static_argnums=0)
def _reference_write(scatter, pool, kv, *route):
    """The XLA scatter on one layer's pool, quantizing as the in-place
    writes do (compiled, as they are: XLA's division by a constant
    differs from the eager one in the last bit of a scale)."""
    if isinstance(pool, tuple):
        q, sc = kvc.quantize_rows(kv)
        return (scatter(pool[0], q, *route), scatter(pool[1], sc, *route))
    return scatter(pool, kv, *route)


def _assert_only_layer_written(got, pool, want, layer):
    """``got[layer] == want`` outside the null block (masked rows land
    there in an order the scatter does not fix), every other layer
    untouched."""
    for g, p, w in zip(*(jax.tree_util.tree_leaves(t)
                         for t in (got, pool, want))):
        g, p, w = np.asarray(g), np.asarray(p), np.asarray(w)
        assert g.dtype == p.dtype
        np.testing.assert_array_equal(g[layer, 1:], w[1:])
        others = [l for l in range(g.shape[0]) if l != layer]
        np.testing.assert_array_equal(g[others], p[others])


@pytest.mark.parametrize("slots", [3, 11])
@pytest.mark.parametrize("kind", ["float32", "int8"])
def test_write_token_equals_scatter_token_on_every_layer(nprng, kind,
                                                         slots):
    """The tick's in-place row writes into the stacked pools against the
    reference scatter on each layer's slice: inactive slots (null block
    only), plain and quantized pools, the layer traced."""
    L, N, H, hd = 3, 16, 2, 8
    pool = _stacked_pool(nprng, kind, L, N, H, hd)
    blocks = 1 + nprng.permutation(N - 1)[:slots]
    table = np.zeros((slots, MB), np.int32)
    table[:, 1] = blocks                       # position 4..7 -> block
    table = jnp.asarray(table)
    position = jnp.asarray(nprng.randint(BS, 2 * BS, slots), jnp.int32)
    active = jnp.asarray(np.arange(slots) % 3 != 1)
    write = jax.jit(kvc.write_token)
    for layer in range(L):
        kv = jnp.asarray(nprng.randn(slots, H, hd).astype(np.float32))
        got = write(pool, jnp.int32(layer), kv, table, position, active)
        want = _reference_write(kvc.scatter_token, layer_of(pool, layer),
                                kv, table, position, active)
        _assert_only_layer_written(got, pool, want, layer)
        inactive = np.asarray(blocks)[~np.asarray(active)]
        for g, p in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(pool)):
            np.testing.assert_array_equal(np.asarray(g)[:, inactive],
                                          np.asarray(p)[:, inactive])


@pytest.mark.parametrize("kind", ["float32", "int8"])
def test_write_span_equals_scatter_span_on_every_layer(nprng, kind):
    """Span writes (speculation's verify tick, a prefill chunk): rows
    past ``n``, an inactive slot and positions under ``write_from`` go
    to the null block; a span that crosses a block boundary lands in
    both blocks."""
    L, N, H, hd, S, Q = 2, 16, 2, 8, 3, 5
    pool = _stacked_pool(nprng, kind, L, N, H, hd)
    table = jnp.asarray([[3, 1, 5, 0, 0, 0], [7, 2, 0, 0, 0, 0],
                         [9, 4, 6, 0, 0, 0]], jnp.int32)
    start = jnp.asarray([2, 1, 6], jnp.int32)
    n = jnp.asarray([Q, 0, 3], jnp.int32)
    write = jax.jit(kvc.write_span)
    for layer in range(L):
        for write_from in (None, jnp.asarray([4, 0, 0], jnp.int32)):
            kv = jnp.asarray(nprng.randn(S, Q, H, hd).astype(np.float32))
            got = write(pool, jnp.int32(layer), kv, table, start, n,
                        write_from)
            want = _reference_write(kvc.scatter_span,
                                    layer_of(pool, layer), kv, table,
                                    start, n, write_from)
            _assert_only_layer_written(got, pool, want, layer)


@pytest.mark.parametrize("kind", ["float32", "bfloat16"])
@pytest.mark.parametrize("start", [0, BS, BS + 2],
                         ids=["from_0", "from_a_block_edge",
                              "from_inside_a_block"])
@pytest.mark.parametrize("length",
                         [1, BS - 1, BS, BS + 1, 2 * BS + 3, W])
def test_write_prefill_equals_scatter_prefill(nprng, kind, start, length):
    """The one-shot prefill's in-place write against its oracle, the
    scatter a layer: every position in ``[start, length)`` reads back
    through ``gather_pages`` what ``scatter_prefill`` put there; rows
    below ``start`` (a shared prefix, which may end INSIDE a block: an
    exact duplicate's partial boundary), the table's blocks wholly past
    ``length`` and every block the table does not name are byte for byte
    what they were. ``start >= length`` writes nothing a reader sees."""
    L, N, H, hd = 2, 16, 2, 8
    pool = _stacked_pool(nprng, kind, L, N, H, hd).astype(kind)
    named = [3, 1, 5, 7, 2, 6]
    table = jnp.asarray([named], jnp.int32)
    kv = jnp.asarray(nprng.randn(L, 1, W, H, hd).astype(np.float32))
    args = (table, jnp.asarray([length], jnp.int32),
            jnp.asarray([start], jnp.int32))
    got = jax.jit(kvc.write_prefill)(pool, kv, *args)
    assert got.dtype == pool.dtype and got.shape == pool.shape
    before, after = np.asarray(pool), np.asarray(got)
    for layer in range(L):
        want = kvc.scatter_prefill(pool[layer], kv[layer].astype(pool.dtype),
                                   *args)
        rows, oracle, old = (np.asarray(kvc.gather_pages(p, table))[0]
                             for p in (got[layer], want, pool[layer]))
        np.testing.assert_array_equal(rows[start:length],
                                      oracle[start:length])
        np.testing.assert_array_equal(rows[:start], old[:start])
    reached = -(-length // BS)
    untouched = [b for b in range(1, N) if b not in named[:reached]]
    np.testing.assert_array_equal(after[:, untouched], before[:, untouched])


def test_write_prefill_scatters_into_a_quantized_pool(nprng):
    """A ``(values, scales)`` pool takes the scatter it took: the write
    adapts to the pool's type, with no option."""
    L, N, H, hd = 2, 16, 2, 8
    pool = _stacked_pool(nprng, "int8", L, N, H, hd)
    table = jnp.asarray([[3, 1, 5, 7, 2, 6]], jnp.int32)
    kv = jnp.asarray(nprng.randn(L, 1, W, H, hd).astype(np.float32))
    args = (table, jnp.asarray([2 * BS + 3], jnp.int32),
            jnp.asarray([BS], jnp.int32))
    got = jax.jit(kvc.write_prefill)(pool, kv, *args)
    for layer in range(L):
        want = kvc.scatter_prefill_pages(layer_of(pool, layer), kv[layer],
                                         *args)
        for g, w in zip(layer_of(got, layer), want):
            np.testing.assert_array_equal(np.asarray(g)[1:],
                                          np.asarray(w)[1:])


# ---------------------------------------------------------------------------
# the decode-shaped Pallas kernel vs its oracle
# ---------------------------------------------------------------------------

def layer_of(pool, layer):
    """One layer's pool out of the stacked pools (plain or quantized):
    what the kernels' oracles and the reference scatters take."""
    return jax.tree_util.tree_map(lambda leaf: leaf[layer], pool)


def _paged_case(nprng, case):
    """``(q, K, V, tables, lengths)`` of one decode-kernel case, float32
    pools of three layers. Head size 128 runs the kernel that walks a
    slot's page groups (8 pages, 128 rows, a group at these shapes); a
    narrower head and an int8 pool run the (slot, head, page) grid."""
    from paddle_tpu.nn.pallas_attention import _pages_per_group
    S, N = 4, 32
    H, D, bs, mb = (2, 16, BS, MB) if case == "narrow_head" \
        else (8, 128, 16, 20)
    rows = _pages_per_group(H * bs * D * 4, mb) * bs
    tables = nprng.randint(0, N, (S, mb))
    lengths = {
        # mid-block, inactive, full capacity, block boundary
        "ragged": [5, 0, mb * bs, 3 * bs],
        "narrow_head": [5, 0, mb * bs, 3 * bs],
        # one under, on and one over a group's last row; two groups and
        # a table that ends inside the third
        "group_edges": [rows - 1, rows, rows + 1, mb * bs],
        "repeated_block": [rows + 7, mb * bs, 2 * bs, 1],
        "all_inactive": [0, 0, 0, 0],
    }[case]
    if case == "group_edges":
        assert mb * bs % rows and mb * bs > 2 * rows
    if case == "repeated_block":
        tables[0, :] = 7                     # one block, every page
        tables[1, 1::2] = tables[1, 0]       # every other page the same
    q = jnp.asarray(nprng.randn(S, H, D).astype(np.float32))
    pk = jnp.asarray(nprng.randn(3, N, H, bs, D).astype(np.float32))
    pv = jnp.asarray(nprng.randn(3, N, H, bs, D).astype(np.float32))
    return (q, pk, pv, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32))


PAGED_CASES = ["ragged", "narrow_head", "group_edges", "repeated_block",
               "all_inactive"]


# a wrong index map reads layer 0 and passes a one-layer test: the pools
# hold three layers of different rows
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_decode_attention_matches_reference(nprng, case, layer):
    from paddle_tpu.nn.pallas_attention import (paged_decode_attention,
                                                paged_reference_attention)
    q, pk, pv, tables, lengths = _paged_case(nprng, case)
    # the layer arrives traced, as the tick's scan hands it over
    out = jax.jit(paged_decode_attention)(q, pk, pv, tables, lengths,
                                          jnp.int32(layer))
    ref = paged_reference_attention(q, pk[layer], pv[layer], tables,
                                    lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)
    # inactive slots: zeros
    assert not np.any(np.asarray(out)[np.asarray(lengths) == 0])


@pytest.mark.parametrize("case", ["ragged", "group_edges"])
def test_paged_decode_attention_bfloat16_pool(nprng, case):
    """A bfloat16 pool runs the same kernel: its pages are widened in
    VMEM, so it agrees with the oracle on the same rounded rows."""
    from paddle_tpu.nn.pallas_attention import (paged_decode_attention,
                                                paged_reference_attention)
    q, pk, pv, tables, lengths = _paged_case(nprng, case)
    pk, pv = pk.astype(jnp.bfloat16), pv.astype(jnp.bfloat16)
    out = paged_decode_attention(q, pk, pv, tables, lengths, 1)
    ref = paged_reference_attention(q, pk[1].astype(jnp.float32),
                                    pv[1].astype(jnp.float32), tables,
                                    lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


def test_model_decode_step_paged_impl_matches_xla(model_and_vars, nprng):
    """The Pallas paged path and the bit-exact XLA gather path agree
    (allclose — different softmax algebra) on the same cache state."""
    model, vs = model_and_vars
    hd = DIM // HEADS
    cache = PagedKVCache(LAYERS, HEADS, hd, 16, BS, max_slots=2,
                         max_blocks_per_seq=MB)
    ids = nprng.randint(0, V, (2, W)).astype(np.int32)
    _, (ks, vsv) = jax.jit(
        lambda v, i: model.apply(v, i, method="prefill"))(
            vs, jnp.asarray(ids))
    for b in range(2):
        assert cache.ensure_capacity(b, 10)
    tbl = jnp.asarray(cache.tables)
    plen = jnp.asarray([9, 6], jnp.int32)
    scat = jax.vmap(kvc.scatter_prefill, in_axes=(0, 0, None, None))
    cache.k = scat(cache.k, ks, tbl, plen)
    cache.v = scat(cache.v, vsv, tbl, plen)
    tok = jnp.asarray([3, 7], jnp.int32)
    act = jnp.asarray([True, False])      # one inactive lane
    outs = {}
    for impl in ("xla", "paged"):
        logits, _ = model.apply(vs, tok, (cache.k, cache.v, tbl), plen,
                                act, attn_impl=impl, method="decode_step")
        outs[impl] = np.asarray(logits)
    # both impls agree on the active lane AND on the inactive lane's
    # zero-context convention (the whole [S] front, not just active rows)
    np.testing.assert_allclose(outs["paged"], outs["xla"],
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# acceptance: prefill + N x decode_step match the full forward
# ---------------------------------------------------------------------------

# Equality to the bit is not owed between two programs of different shapes
# (one query row against W of them): XLA's CPU backend may block their
# products differently, and does: the worst miss over every position of the
# two tests below is 1.2e-6 absolute on logits of order 1 (five units in the
# last place). A wrong mask, page or position changes the attention's inputs,
# not its last bits, so four times that miss is the bound.
LOGITS_ATOL = 5e-6


def test_prefill_decode_matches_full_forward(model_and_vars, nprng):
    """f32 CPU: for ragged lengths crossing block boundaries, every
    decoded position's logits match the full-sequence forward at the
    fixed padded width to ``LOGITS_ATOL`` (why not to the bit: above) —
    the serving path reads the same keys, values and positions as the
    training forward."""
    model, vs = model_and_vars
    B = 3
    lens = [13, W, 7]                 # mid-block, full, block-boundary+3
    P = 3                             # prefill length (rest decoded)
    ids = nprng.randint(0, V, (B, W)).astype(np.int32)
    oracle = np.asarray(jax.jit(lambda v, i: model.apply(v, i))(
        vs, jnp.asarray(ids)))

    hd = DIM // HEADS
    cache = PagedKVCache(LAYERS, HEADS, hd, B * MB + 1, BS, max_slots=B,
                         max_blocks_per_seq=MB)
    logits_pre, (ks, vsv) = jax.jit(
        lambda v, i: model.apply(v, i, method="prefill"))(
            vs, jnp.asarray(ids))
    # the prefill's own logits: the same shapes, but its blocks run as a scan
    np.testing.assert_allclose(np.asarray(logits_pre), oracle,
                               rtol=0, atol=LOGITS_ATOL)

    for b in range(B):
        assert cache.ensure_capacity(b, lens[b])
    tbl = jnp.asarray(cache.tables)
    plen = jnp.full((B,), P, jnp.int32)
    scat = jax.vmap(kvc.scatter_prefill, in_axes=(0, 0, None, None))
    cache.k = scat(cache.k, ks, tbl, plen)
    cache.v = scat(cache.v, vsv, tbl, plen)

    decode = jax.jit(lambda v, t, kv, pos, a: model.apply(
        v, t, kv, pos, a, method="decode_step"))
    for t in range(P, max(lens)):
        active = jnp.asarray([t < lens[b] for b in range(B)])
        pos = jnp.full((B,), t, jnp.int32)
        logits, (cache.k, cache.v, _) = decode(
            vs, jnp.asarray(ids[:, t]), (cache.k, cache.v, tbl), pos,
            active)
        for b in range(B):
            if t < lens[b]:
                np.testing.assert_allclose(
                    np.asarray(logits[b]), oracle[b, t],
                    rtol=0, atol=LOGITS_ATOL,
                    err_msg=f"slot {b} position {t}")


def test_block_free_reuse_identical_after_churn(model_and_vars, nprng):
    """Evicting sequences and re-admitting onto RECYCLED blocks (stale
    pool contents) reproduces the exact same generation — proof the
    length mask fully owns the block-content boundary."""
    model, vs = model_and_vars
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                       num_blocks=2 * MB + 1)
    prompt = list(nprng.randint(0, V, 5))
    sched = ContinuousBatchingScheduler(eng)
    first = sched.submit(prompt, 6)
    sched.run()
    assert first.done

    # churn: fill and free the pool with other sequences several times
    for i in range(3):
        s2 = ContinuousBatchingScheduler(eng)
        for j in range(3):
            s2.submit(list(nprng.randint(0, V, 4 + i + j)), 5 + j)
        s2.run()
    assert eng.cache.free_blocks == 2 * MB   # all returned

    again = ContinuousBatchingScheduler(eng)
    rerun = again.submit(prompt, 6)
    again.run()
    assert rerun.tokens == first.tokens      # bit-identical generation
    # and the whole time, nothing ever retraced
    assert eng.compile_counts() == {"prefill": 1, "tick": 1}


# ---------------------------------------------------------------------------
# engine + scheduler
# ---------------------------------------------------------------------------

def test_continuous_batching_completes_and_matches_oracle(model_and_vars,
                                                          nprng):
    from paddle_tpu.obs import InMemorySink, Telemetry
    model, vs = model_and_vars
    mem = InMemorySink()
    eng = DecodeEngine(model, vs, max_slots=4, block_size=BS,
                       telemetry=Telemetry(sinks=[mem]))
    sched = ContinuousBatchingScheduler(eng)
    prompts = [list(nprng.randint(0, V, nprng.randint(2, 8)))
               for _ in range(8)]
    maxnew = [3, 9, 5, 12, 7, 4, 10, 6]
    reqs = [sched.submit(p, m) for p, m in zip(prompts, maxnew)]
    done = sched.run()
    assert len(done) == 8 and all(r.done for r in reqs)
    assert eng.compile_counts() == {"prefill": 1, "tick": 1}
    # per-request telemetry: one record each, with the SLO fields
    recs = mem.by_kind("request")
    assert len(recs) == 8
    for r in recs:
        assert r["ttft_ms"] is not None and r["ttft_ms"] >= 0
        assert r["new_tokens"] >= 1
        if r["new_tokens"] >= 2:
            assert r["tpot_ms"] is not None and r["tpot_ms"] >= 0
    assert len(mem.by_kind("decode_tick")) == eng.ticks
    # generated tokens match the naive greedy full-forward oracle
    for req, p, m in list(zip(reqs, prompts, maxnew))[:3]:
        assert req.tokens == _greedy_oracle(model, vs, p, m)


def test_static_policy_gangs_and_is_slower(model_and_vars, nprng):
    """The gang baseline completes but burns idle-lane ticks on ragged
    lengths — the differential continuous batching exists to win."""
    model, vs = model_and_vars
    prompts = [list(nprng.randint(0, V, 4)) for _ in range(8)]
    maxnew = [2, 12, 2, 2, 12, 2, 2, 2]      # stragglers pin their gang
    ticks = {}
    for policy in ("continuous", "static"):
        eng = DecodeEngine(model, vs, max_slots=4, block_size=BS)
        sched = ContinuousBatchingScheduler(eng, policy=policy)
        reqs = [sched.submit(p, m) for p, m in zip(prompts, maxnew)]
        sched.run()
        assert all(r.done for r in reqs)
        ticks[policy] = eng.ticks
    assert ticks["static"] > ticks["continuous"]


def test_pool_backpressure_defers_admission(model_and_vars, nprng):
    """A pool sized for ~2 concurrent sequences serves 4 requests by
    deferring admissions until eviction frees blocks."""
    model, vs = model_and_vars
    # 2 sequences x 3 blocks each fit; the third admission must wait
    eng = DecodeEngine(model, vs, max_slots=4, block_size=BS,
                       num_blocks=2 * 3 + 1)
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(list(nprng.randint(0, V, 5)), 6)
            for _ in range(4)]
    done = sched.run()
    assert len(done) == 4 and all(r.done for r in reqs)
    assert eng.cache.free_blocks == 6
    assert eng.compile_counts() == {"prefill": 1, "tick": 1}


class _FakeClock:
    """Deterministic scheduler clock: the test advances it between
    ticks, so deadline expiry is exact, not wall-time-flaky."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_deadline_evicts_running_slot_and_frees_blocks(model_and_vars,
                                                       nprng):
    """ISSUE 10: a slot that exceeds its deadline_s is evicted between
    ticks with finish_reason="timeout" and its blocks freed — a stuck/
    long request can no longer hold a slot + reservation forever."""
    from paddle_tpu.obs import InMemorySink, Telemetry
    model, vs = model_and_vars
    mem = InMemorySink()
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                       telemetry=Telemetry(sinks=[mem]))
    clock = _FakeClock()
    sched = ContinuousBatchingScheduler(eng, clock=clock)
    free0 = eng.cache.free_blocks
    stuck = sched.submit(list(nprng.randint(0, V, 4)), 18, deadline_s=2.5)
    quick = sched.submit(list(nprng.randint(0, V, 4)), 3)
    while sched.step():
        clock.t += 1.0                        # one "second" per tick
    assert quick.done and quick.finish_reason == "length"
    assert stuck.done and stuck.finish_reason == "timeout"
    # evicted mid-decode: partial tokens, well short of max_new
    assert 1 <= len(stuck.tokens) < 18
    # the whole reservation came back to the pool
    assert eng.cache.free_blocks == free0
    assert not eng.active.any()
    # surfaced in the request telemetry records
    recs = {r["rid"]: r for r in mem.by_kind("request")}
    assert recs[stuck.rid]["finish_reason"] == "timeout"
    assert recs[stuck.rid]["deadline_s"] == 2.5
    assert recs[quick.rid]["finish_reason"] == "length"
    assert recs[quick.rid]["deadline_s"] is None


def test_deadline_drops_expired_queued_request(model_and_vars, nprng):
    """A request whose deadline expires while still QUEUED (pool/slot
    backpressure) is dropped before ever taking a slot."""
    model, vs = model_and_vars
    eng = DecodeEngine(model, vs, max_slots=1, block_size=BS)
    clock = _FakeClock()
    sched = ContinuousBatchingScheduler(eng, clock=clock)
    free0 = eng.cache.free_blocks
    long_req = sched.submit(list(nprng.randint(0, V, 4)), 10)
    starved = sched.submit(list(nprng.randint(0, V, 4)), 4, deadline_s=3.0)
    while sched.step():
        clock.t += 1.0
    assert long_req.finish_reason == "length"
    assert starved.finish_reason == "timeout"
    assert starved.slot is None and starved.tokens == []
    # the timed-out request never took a slot or any blocks
    assert eng.cache.free_blocks == free0


def test_deadline_evictions_emit_records_and_return_blocks(model_and_vars,
                                                           nprng):
    """ISSUE 11 satellite: BOTH deadline-eviction paths are visible in
    telemetry — the queued drop emits a kind="evict" record (previously
    only slot evictions were distinguishable), and the running slot's
    exact block ids land back on the BlockAllocator free list (leak
    regression)."""
    from paddle_tpu.obs import InMemorySink, Telemetry
    model, vs = model_and_vars
    mem = InMemorySink()
    eng = DecodeEngine(model, vs, max_slots=1, block_size=BS,
                       telemetry=Telemetry(sinks=[mem]))
    clock = _FakeClock()
    sched = ContinuousBatchingScheduler(eng, clock=clock)
    running = sched.submit(list(nprng.randint(0, V, 4)), 18,
                           deadline_s=2.5)
    starved = sched.submit(list(nprng.randint(0, V, 4)), 4,
                           deadline_s=2.0)   # expires before a slot frees
    sched.step()                      # admit `running`; blocks reserved
    owned = list(eng.cache._owned[running.slot])
    assert owned, "admission reserved no blocks"
    while sched.step():
        clock.t += 1.0
    assert running.finish_reason == "timeout"
    assert starved.finish_reason == "timeout" and starved.slot is None
    # the evicted slot's block ids are reclaimable — ON the free list or
    # parked in the retained LRU (ISSUE 14: a registered prefix block
    # outlives its owner there), never leaked in the refcount table
    reclaimable = (set(eng.cache.allocator._free)
                   | set(eng.cache.allocator._retained))
    assert set(owned) <= reclaimable
    assert eng.cache.free_blocks == eng.cache.num_blocks - 1
    evicts = {r["rid"]: r for r in mem.by_kind("evict")}
    assert evicts[running.rid]["where"] == "running"
    assert evicts[running.rid]["blocks_freed"] == len(owned)
    assert evicts[starved.rid]["where"] == "queued"
    assert evicts[starved.rid]["blocks_freed"] == 0


def test_scheduler_surfaces_structured_backpressure(model_and_vars,
                                                    nprng):
    """ISSUE 11 satellite: when admission stalls on the pool, the
    scheduler records WHY (blocks vs slots) so a router doesn't guess."""
    model, vs = model_and_vars
    eng = DecodeEngine(model, vs, max_slots=4, block_size=BS,
                       num_blocks=2 * 3 + 1)
    sched = ContinuousBatchingScheduler(eng)
    for _ in range(4):
        sched.submit(list(nprng.randint(0, V, 5)), 6)
    sched.step()
    assert sched.last_backpressure == "blocks"    # pool, not slots
    sched.run()
    assert sched.last_backpressure is None        # cleared when flowing
    # the static gang-wait path clears it too (no stale reason while
    # the gang runs)
    eng2 = DecodeEngine(model, vs, max_slots=2, block_size=BS)
    s2 = ContinuousBatchingScheduler(eng2, policy="static")
    for _ in range(2):
        s2.submit(list(nprng.randint(0, V, 5)), 4)
    s2.step()
    s2.last_backpressure = "blocks"               # simulate a stale read
    s2.step()                                     # gang still running
    assert s2.last_backpressure is None


def test_deadline_none_is_unchanged_and_validation(model_and_vars, nprng):
    model, vs = model_and_vars
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS)
    sched = ContinuousBatchingScheduler(eng)
    with pytest.raises(ValueError, match="deadline_s"):
        sched.submit([1, 2], 2, deadline_s=-1.0)
    req = sched.submit(list(nprng.randint(0, V, 3)), 4)
    sched.run()
    assert req.finish_reason == "length" and len(req.tokens) == 4


def test_decode_past_reservation_raises(model_and_vars):
    """Out-decoding the admission reservation must fail loud, not scatter
    new-token KV into the null block (silent wrong logits)."""
    model, vs = model_and_vars
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS)
    eng.admit(0, [1, 2, 3])                  # reserves 1 block (3 tokens)
    eng.decode_tick()                        # position 3 fills block 0
    with pytest.raises(RuntimeError, match="past its reservation"):
        eng.decode_tick()                    # position 4 needs block 2


def test_prompt_capacity_validation(model_and_vars):
    model, vs = model_and_vars
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS)
    sched = ContinuousBatchingScheduler(eng)
    with pytest.raises(ValueError, match="exceeds slot capacity"):
        sched.submit(list(range(W)), 2)      # W + 2 > capacity W
    with pytest.raises(ValueError, match="max_new_tokens"):
        sched.submit([1, 2], 0)
    with pytest.raises(ValueError, match="attention"):
        DecodeEngine(model, vs, attention="nope")


# ---------------------------------------------------------------------------
# ISSUE 12: copy-on-write prefix sharing
# ---------------------------------------------------------------------------

def test_allocator_refcounts():
    a = BlockAllocator(6)
    got = a.alloc(2)
    assert got == [1, 2] and a.total_allocs == 2
    a.incref(1)                              # a second owner
    assert a.ref_count(1) == 2
    assert a.decref(1) is False              # co-owner holds on
    assert a.num_free == 3                   # nothing freed yet
    assert a.decref(1) is True               # last owner: freed
    assert a.num_free == 4 and a.ref_count(1) == 0
    with pytest.raises(AssertionError, match="double free"):
        a.decref(1)
    with pytest.raises(AssertionError):
        a.incref(5)                          # never allocated


def test_prefix_cache_chain_and_partial():
    from paddle_tpu.serve import PrefixCache
    pc = PrefixCache(block_size=4)
    prompt = list(range(10))                 # 2 full blocks + tail [8, 9]
    pc.register(prompt, [7, 8, 9])
    # full-chain walk + exact-tail partial hit
    m = pc.match(prompt)
    assert m.blocks == [7, 8, 9] and m.length == 10 and m.partial
    # a divergent tail keeps only the full-block chain
    m = pc.match(list(range(8)) + [99, 98, 97])
    assert m.blocks == [7, 8] and m.length == 8 and not m.partial
    # diverging INSIDE a block shares nothing of that block
    m = pc.match([0, 1, 2, 3, 99, 5, 6, 7])
    assert m.blocks == [7] and m.length == 4
    m = pc.match([99, 1, 2, 3])
    assert m.blocks == [] and m.length == 0
    # cumulative hashing: a matching second block under a different
    # first block is NOT a hit (the chain key encodes the whole prefix)
    m = pc.match([9, 9, 9, 9] + list(range(4, 8)))
    assert m.blocks == []
    # invalidation drops every entry for the freed block
    pc.invalidate_block(8)
    assert pc.match(prompt).blocks == [7]


def test_shared_prefix_fewer_allocs_and_leak_free(model_and_vars, nprng):
    """Concurrent requests sharing a prompt prefix map the SAME physical
    full blocks (fewer fresh allocations), generate bit-identical
    tokens, and every shared block returns to the free list exactly once
    after all sharers evict — the ISSUE 12 leak regression."""
    model, vs = model_and_vars
    pre = list(nprng.randint(0, V, 2 * BS))          # 2 full blocks
    prompts = [pre + list(nprng.randint(0, V, 3)) for _ in range(4)]

    def run(share):
        eng = DecodeEngine(model, vs, max_slots=4, block_size=BS,
                           share_prefix=share)
        sched = ContinuousBatchingScheduler(eng)
        reqs = [sched.submit(list(p), 5) for p in prompts]
        sched.run()
        return eng, [r.tokens for r in reqs], reqs

    eng_on, toks_on, reqs_on = run(True)
    eng_off, toks_off, _ = run(False)
    assert toks_on == toks_off               # sharing never changes tokens
    assert (eng_on.cache.allocator.total_allocs
            < eng_off.cache.allocator.total_allocs)
    assert eng_on.cache.prefix_hit_blocks >= 2   # followers adopted
    # zero leaks: every block exactly once across the free list and the
    # retained LRU (ISSUE 14: registered blocks outlive their owners
    # there — reclaimable, not leaked)
    pool = (list(eng_on.cache.allocator._free)
            + list(eng_on.cache.allocator._retained))
    assert len(pool) == len(set(pool)) == eng_on.cache.num_blocks - 1
    assert eng_on.compile_counts() == {"prefill": 1, "tick": 1}
    # request records carry the sharing attribution
    follower = [r for r in reqs_on if (r.prefix_hit_blocks or 0) > 0]
    assert follower and all(r.blocks_reserved for r in reqs_on)


def test_cow_fork_on_duplicate_prompts(model_and_vars, nprng):
    """An exact-duplicate prompt shares EVERY block including the
    partial boundary; the first divergent decode write forks exactly
    that block (copy-on-write), generations stay bit-identical, and the
    fork leaks nothing after full churn."""
    model, vs = model_and_vars
    prompt = list(nprng.randint(0, V, 6))    # partial boundary (6 % 4)
    eng = DecodeEngine(model, vs, max_slots=4, block_size=BS)
    sched = ContinuousBatchingScheduler(eng)
    r1 = sched.submit(list(prompt), 5)
    r2 = sched.submit(list(prompt), 5)
    sched.run()
    assert r1.tokens == r2.tokens
    assert eng.cache.cow_forks >= 1
    assert (r2.cow_forks or 0) + (r1.cow_forks or 0) >= 1
    # solo oracle: the same prompt on a fresh engine, no sharing at all
    eng2 = DecodeEngine(model, vs, max_slots=4, block_size=BS,
                        share_prefix=False)
    s2 = ContinuousBatchingScheduler(eng2)
    solo = s2.submit(list(prompt), 5)
    s2.run()
    assert solo.tokens == r1.tokens
    pool = (list(eng.cache.allocator._free)
            + list(eng.cache.allocator._retained))
    assert len(pool) == len(set(pool)) == eng.cache.num_blocks - 1


def test_one_shot_prefill_serves_what_the_scatter_served(
        model_and_vars, nprng, monkeypatch):
    """A one-shot engine with sharing on, whose prefill writes its pages
    in place, against the same engine with the scatter under ``vmap`` put
    back in the write's place: an exact duplicate prompt (``start ==
    length``, the partial boundary block shared and forked), a prompt
    that extends a shared prefix (``start`` on a block edge) and one that
    shares nothing are served the same tokens, and the fresh blocks a
    prefill wrote gather to the same rows up to each slot's length."""
    from paddle_tpu.serve import engine as engine_mod
    model, vs = model_and_vars
    pre = list(nprng.randint(0, V, 2 * BS))
    first = pre + list(nprng.randint(0, V, 2))       # partial boundary
    prompts = [first, list(first), pre + list(nprng.randint(0, V, 3)),
               list(nprng.randint(0, V, BS + 1))]

    def serve():
        eng = DecodeEngine(model, vs, max_slots=4, block_size=BS,
                           share_prefix=True)
        assert eng.prefill_chunk is None
        sched = ContinuousBatchingScheduler(eng)
        reqs = [sched.submit(list(p), 5) for p in prompts]
        sched.step()                     # every prompt prefilled, one tick
        tables, lengths = eng.cache.device_tables()
        rows = [np.asarray(kvc.gather_pages(eng.cache.pools[name], tables,
                                            layer))
                for name in ("k", "v") for layer in range(LAYERS)]
        lengths = np.asarray(lengths)
        sched.run()
        return eng, [r.tokens for r in reqs], rows, lengths

    eng, tokens, rows, lengths = serve()
    scatter = jax.vmap(kvc.scatter_prefill_pages,
                       in_axes=(0, 0, None, None, None))
    monkeypatch.setattr(engine_mod, "write_prefill", scatter)
    oracle, want_tokens, want_rows, want_lengths = serve()
    assert tokens == want_tokens and tokens[0] == tokens[1]
    np.testing.assert_array_equal(lengths, want_lengths)
    assert lengths.min() > 0
    for got, want in zip(rows, want_rows):
        for slot, n in enumerate(lengths):
            np.testing.assert_array_equal(got[slot, :n], want[slot, :n])
    for e in (eng, oracle):
        assert e.cache.prefix_hit_blocks >= 4 and e.cache.cow_forks >= 1
        assert e.compile_counts() == {"prefill": 1, "tick": 1}


def test_sharing_eviction_churn_bit_identity(model_and_vars, nprng):
    """Sharing under admission/eviction churn (the PR-9 churn test with
    share_prefix on): recycled blocks + invalidated cache entries
    reproduce the exact same generation, and nothing ever retraces."""
    model, vs = model_and_vars
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                       num_blocks=2 * MB + 3)
    pre = list(nprng.randint(0, V, BS))
    prompt = pre + list(nprng.randint(0, V, 2))
    sched = ContinuousBatchingScheduler(eng)
    first = sched.submit(list(prompt), 6)
    sched.run()
    # churn: session-style prompts fill, share, and free the pool
    for i in range(3):
        s2 = ContinuousBatchingScheduler(eng)
        for j in range(3):
            s2.submit(pre + list(nprng.randint(0, V, 1 + i + j)), 4 + j)
        s2.run()
    assert eng.cache.free_blocks == eng.cache.num_blocks - 1
    again = ContinuousBatchingScheduler(eng)
    rerun = again.submit(list(prompt), 6)
    again.run()
    assert rerun.tokens == first.tokens
    assert eng.compile_counts() == {"prefill": 1, "tick": 1}


# ---------------------------------------------------------------------------
# ISSUE 12: lossless speculative decoding
# ---------------------------------------------------------------------------

def test_speculative_bit_identical_fewer_ticks(model_and_vars, nprng):
    """The acceptance contract: speculative greedy decode produces
    BIT-IDENTICAL tokens to the non-speculative engine on the ragged
    request set, with strictly fewer decode ticks, and the drafted
    width never retraces the pinned programs."""
    model, vs = model_and_vars
    prompts = [list(nprng.randint(0, V, nprng.randint(2, 8)))
               for _ in range(8)]
    maxnew = [3, 9, 5, 12, 7, 4, 10, 6]

    def run(k):
        eng = DecodeEngine(model, vs, max_slots=4, block_size=BS,
                           speculative=k)
        sched = ContinuousBatchingScheduler(eng)
        reqs = [sched.submit(list(p), m)
                for p, m in zip(prompts, maxnew)]
        sched.run()
        return eng, [r.tokens for r in reqs], reqs

    eng_b, toks_b, _ = run(0)
    eng_s, toks_s, reqs_s = run(3)
    assert toks_s == toks_b
    assert eng_s.ticks < eng_b.ticks
    assert eng_s.compile_counts() == {"prefill": 1, "tick": 1}
    assert eng_s.draft_proposed > 0
    # the per-request accept-rate attribution rides the records
    assert any(r.draft_accepted for r in reqs_s)
    # and the oracle: matches token-by-token greedy over the training
    # forward (transitively via toks_b, but pin one directly)
    assert toks_s[0] == _greedy_oracle(model, vs, prompts[0], maxnew[0])


def test_speculative_eos_and_deadline_semantics(model_and_vars, nprng):
    """A draft window crossing an EOS stops exactly where the
    sequential engine would (accepted tokens feed the finish rules one
    at a time), and speculation composes with deadline eviction."""
    model, vs = model_and_vars
    prompt = list(nprng.randint(0, V, 5))
    oracle = _greedy_oracle(model, vs, prompt, 12)
    eos = oracle[4]                          # stop at its FIRST occurrence
    expect = oracle[:oracle.index(eos) + 1]
    assert len(expect) < 12                  # genuinely mid-stream
    for k in (0, 3):
        eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                           speculative=k)
        sched = ContinuousBatchingScheduler(eng)
        req = sched.submit(list(prompt), 12, eos_id=eos)
        sched.run()
        assert req.finish_reason == "eos"
        assert req.tokens == expect, f"speculative={k}"


def test_speculative_capacity_clamp(model_and_vars, nprng):
    """A slot near its block reservation clamps the draft width instead
    of scattering past owned blocks — the guard that kept the plain
    tick honest keeps the fat tick honest too."""
    model, vs = model_and_vars
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                       speculative=4)
    sched = ContinuousBatchingScheduler(eng)
    req = sched.submit(list(nprng.randint(0, V, 3)), 4)
    sched.run()                              # reservation = 3 + 4 - 1
    assert req.finish_reason == "length" and len(req.tokens) == 4
    assert eng.cache.free_blocks == eng.cache.num_blocks - 1


def test_speculative_composes_with_sampling_rejection_rule(model_and_vars,
                                                           nprng):
    """ISSUE 14: the speculation×sampling guard is LIFTED — stochastic
    verification uses the [S3] rejection-sampling rule (accept draft d
    with prob p(d), resample rejections from the residual), which is
    (a) seeded-deterministic: a fixed seed replays the identical token
    stream, (b) distribution-preserving by construction — pinned here
    by the temperature→0 limit, where the rule degenerates to greedy
    acceptance and must match the greedy speculative engine EXACTLY."""
    from paddle_tpu.serve import SamplingConfig
    model, vs = model_and_vars
    prompts = [list(nprng.randint(0, V, 5)) for _ in range(3)]

    def run_sampled(seed, temp=1.0):
        eng = DecodeEngine(model, vs, max_slots=3, block_size=BS,
                           speculative=3,
                           sampling=SamplingConfig(temperature=temp,
                                                   seed=seed))
        sched = ContinuousBatchingScheduler(eng)
        reqs = [sched.submit(list(p), 8) for p in prompts]
        sched.run()
        assert eng.compile_counts() == {"prefill": 1, "tick": 1}
        return [r.tokens for r in reqs], eng

    a, eng_a = run_sampled(7)
    b, _ = run_sampled(7)
    c, _ = run_sampled(8)
    assert a == b                       # seeded-deterministic replay
    assert a != c                       # a different seed diverges
    assert all(len(t) == 8 for t in a)  # every request completed
    # temperature -> 0: p collapses onto the argmax, the accept coin
    # always lands under p(draft)==1 for agreeing drafts, and the
    # stream must equal the greedy speculative engine's token for token
    tiny, _ = run_sampled(7, temp=1e-4)
    eng_g = DecodeEngine(model, vs, max_slots=3, block_size=BS,
                         speculative=3)
    sg = ContinuousBatchingScheduler(eng_g)
    greedy = [sg.submit(list(p), 8) for p in prompts]
    sg.run()
    assert tiny == [r.tokens for r in greedy]


# ---------------------------------------------------------------------------
# ISSUE 12: chunked prefill
# ---------------------------------------------------------------------------

def test_every_admission_in_progress_runs_one_chunk_between_ticks(
        model_and_vars, nprng):
    """The scheduler's one interleaving policy: between two decode ticks
    every admission in progress runs ONE chunk, so a running slot's
    inter-token gap is a tick and as many chunks as admissions overlap
    (the benchmark's closed-loop cells read their tail from this), and
    never two chunks of one admission."""
    model, vs = model_and_vars
    prompts = [list(nprng.randint(0, V, n)) for n in (3, 17, 18, 15, 16)]
    eng = DecodeEngine(model, vs, max_slots=4, block_size=BS,
                       prefill_chunk=4)
    calls = []
    step, tick = eng.prefill_step, eng.decode_tick
    eng.prefill_step = lambda slot: (calls.append(slot), step(slot))[1]
    eng.decode_tick = lambda: (calls.append("t"), tick())[1]
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(list(p), 6) for p in prompts]
    sched.run()
    assert all(len(r.tokens) == 6 for r in reqs)
    runs, run = [], []
    for c in calls:
        if c == "t":
            runs.append(run)
            run = []
        else:
            run.append(c)
    assert max(len(r) for r in runs) > 1          # admissions overlap
    assert all(len(set(r)) == len(r) for r in runs)


def test_chunked_prefill_bit_equal_and_interleaves(model_and_vars, nprng):
    """Chunked prefill produces the same first token and generation as
    the monolithic prefill (bit-equal span rows), and a long admission
    interleaves with a running slot's decode ticks instead of stalling
    its token stream."""
    model, vs = model_and_vars
    short_prompt = list(nprng.randint(0, V, 3))
    long_prompt = list(nprng.randint(0, V, 18))

    def run(chunk):
        eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                           prefill_chunk=chunk)
        sched = ContinuousBatchingScheduler(eng)
        short = sched.submit(list(short_prompt), 18)
        for _ in range(2):
            sched.step()
        before = len(short.tokens)
        long_req = sched.submit(list(long_prompt), 3)
        while long_req.first_token_ts is None and sched.step():
            pass
        interleaved = len(short.tokens) - before
        sched.run()
        return eng, short.tokens, long_req, interleaved

    eng_c, short_c, long_c, il_c = run(4)
    eng_f, short_f, long_f, il_f = run(None)
    assert short_c == short_f and long_c.tokens == long_f.tokens
    assert il_c > il_f                       # decode kept flowing
    assert eng_c.prefill_chunks > eng_f.prefill_chunks
    assert (long_c.prefill_chunks or 0) >= 5     # ceil(18/4)
    assert eng_c.compile_counts() == {"prefill": 1, "tick": 1}
    assert eng_f.compile_counts() == {"prefill": 1, "tick": 1}


def test_chunked_prefill_composes_with_sharing(model_and_vars, nprng):
    """Chunked prefill skips fully-shared chunks (the prefix-cache
    compute win) and still reproduces identical generations — including
    the exact-duplicate case that re-attends only the final position
    with writes masked."""
    model, vs = model_and_vars
    pre = list(nprng.randint(0, V, 2 * BS))
    donor_prompt = pre + list(nprng.randint(0, V, 3))
    dup = pre + [7, 7]

    def run(chunk, share):
        eng = DecodeEngine(model, vs, max_slots=4, block_size=BS,
                           prefill_chunk=chunk, share_prefix=share)
        sched = ContinuousBatchingScheduler(eng)
        # the donor must be RESIDENT (registered) before the sharers
        # admit — sharing is between concurrently-live sequences
        donor = sched.submit(list(donor_prompt), 12)
        for _ in range(4):
            sched.step()
        sharers = [sched.submit(list(dup), 4) for _ in range(2)]
        sched.run()
        return eng, [r.tokens for r in [donor] + sharers], \
            [donor] + sharers

    eng_a, toks_a, reqs_a = run(4, True)
    _, toks_b, _ = run(4, False)
    _, toks_c, _ = run(None, False)
    assert toks_a == toks_b == toks_c
    # the sharers' chunk counts shrink: adopted blocks skip their chunks
    by_chunks = [r.prefill_chunks for r in reqs_a]
    assert max(by_chunks[1], by_chunks[2]) < by_chunks[0]
    assert eng_a.cache.prefix_hit_blocks >= 2
    # the second duplicate exact-matches the first: one COW fork each
    # at the first divergent decode write
    assert eng_a.cache.cow_forks >= 1
    pool = (list(eng_a.cache.allocator._free)
            + list(eng_a.cache.allocator._retained))
    assert len(pool) == len(set(pool)) == eng_a.cache.num_blocks - 1


def test_decode_span_logits_match_full_forward(model_and_vars, nprng):
    """The ISSUE 12 acceptance invariant at LOGITS level: the span
    program (chunked prefill + speculative verify's shared core)
    produces rows that match the full-sequence training forward to
    ``LOGITS_ATOL`` (f32 CPU; two differently shaped programs owe each
    other no more, see there) — prefill a stub, then cover the rest of
    the sequence in ragged multi-token spans."""
    model, vs = model_and_vars
    B, P = 2, 3
    lens = [W, 14]                       # full capacity + mid-block
    ids = nprng.randint(0, V, (B, W)).astype(np.int32)
    oracle = np.asarray(jax.jit(lambda v, i: model.apply(v, i))(
        vs, jnp.asarray(ids)))
    hd = DIM // HEADS
    cache = PagedKVCache(LAYERS, HEADS, hd, B * MB + 1, BS, max_slots=B,
                         max_blocks_per_seq=MB)
    _, (ks, vsv) = jax.jit(
        lambda v, i: model.apply(v, i, method="prefill"))(
            vs, jnp.asarray(ids))
    for b in range(B):
        assert cache.ensure_capacity(b, lens[b])
    tbl = jnp.asarray(cache.tables)
    plen = jnp.full((B,), P, jnp.int32)
    scat = jax.vmap(kvc.scatter_prefill, in_axes=(0, 0, None, None))
    cache.k = scat(cache.k, ks, tbl, plen)
    cache.v = scat(cache.v, vsv, tbl, plen)
    span = jax.jit(lambda v, t, kv, s, n, a: model.apply(
        v, t, kv, s, n, a, method="decode_span"))
    Q = 5
    t = P
    while t < max(lens):
        n = jnp.asarray([max(0, min(Q, lens[b] - t)) for b in range(B)],
                        jnp.int32)
        active = n > 0
        chunk = np.zeros((B, Q), np.int32)
        for b in range(B):
            take = int(n[b])
            chunk[b, :take] = ids[b, t:t + take]
        logits, (cache.k, cache.v, _) = span(
            vs, jnp.asarray(chunk), (cache.k, cache.v, tbl),
            jnp.full((B,), t, jnp.int32), n, active)
        for b in range(B):
            for j in range(int(n[b])):
                np.testing.assert_allclose(
                    np.asarray(logits[b, j]), oracle[b, t + j],
                    rtol=0, atol=LOGITS_ATOL,
                    err_msg=f"slot {b} position {t + j}")
        t += Q


# ---------------------------------------------------------------------------
# ISSUE 12: stochastic sampling
# ---------------------------------------------------------------------------

def test_sampling_seeded_deterministic(model_and_vars, nprng):
    """Temperature/top-k/top-p sampling with per-slot keys: the same
    seed replays the exact token stream, a different seed diverges, and
    greedy (sampling=None) stays the bit-pinned default."""
    from paddle_tpu.serve import SamplingConfig
    model, vs = model_and_vars
    prompts = [list(nprng.randint(0, V, 4)) for _ in range(3)]

    def run(cfg):
        eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                           sampling=cfg)
        sched = ContinuousBatchingScheduler(eng)
        reqs = [sched.submit(list(p), 8) for p in prompts]
        sched.run()
        return [r.tokens for r in reqs], eng

    cfg = SamplingConfig(temperature=1.2, top_k=16, top_p=0.9, seed=3)
    a, eng_a = run(cfg)
    b, _ = run(cfg)
    c, _ = run(SamplingConfig(temperature=1.2, top_k=16, top_p=0.9,
                              seed=4))
    assert a == b                            # seeded-deterministic
    assert a != c                            # the seed is load-bearing
    assert eng_a.compile_counts() == {"prefill": 1, "tick": 1}
    greedy, _ = run(None)
    assert greedy[0] == _greedy_oracle(model, vs, prompts[0], 8)


def test_sampling_validation(model_and_vars):
    from paddle_tpu.serve import SamplingConfig
    model, vs = model_and_vars
    for bad in (SamplingConfig(temperature=0.0),
                SamplingConfig(top_k=0),
                SamplingConfig(top_k=V + 1),
                SamplingConfig(top_p=0.0),
                SamplingConfig(top_p=1.5)):
        with pytest.raises(ValueError):
            DecodeEngine(model, vs, max_slots=2, block_size=BS,
                         sampling=bad)


def test_sampling_top_k_one_is_greedy(model_and_vars, nprng):
    """top_k=1 collapses the categorical to argmax whatever the seed —
    a cheap structural check on the filter chain."""
    from paddle_tpu.serve import SamplingConfig
    model, vs = model_and_vars
    prompt = list(nprng.randint(0, V, 4))
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                       sampling=SamplingConfig(top_k=1, seed=11))
    sched = ContinuousBatchingScheduler(eng)
    req = sched.submit(list(prompt), 6)
    sched.run()
    assert req.tokens == _greedy_oracle(model, vs, prompt, 6)


# ---------------------------------------------------------------------------
# ISSUE 12: telemetry fields
# ---------------------------------------------------------------------------

def test_tick_and_request_records_carry_throughput_fields(model_and_vars,
                                                          nprng):
    """Per-tick records carry prefix_hit_blocks / cow_forks /
    draft_accept_rate / prefill_chunks; request records carry the
    per-request attribution; summarize_requests aggregates accept rate
    and the block-sharing ratio (ISSUE 12 telemetry satellite)."""
    from paddle_tpu.obs import InMemorySink, Telemetry
    from paddle_tpu.obs.percentiles import summarize_requests
    model, vs = model_and_vars
    mem = InMemorySink()
    pre = list(nprng.randint(0, V, BS))
    eng = DecodeEngine(model, vs, max_slots=4, block_size=BS,
                       speculative=2, prefill_chunk=4,
                       telemetry=Telemetry(sinks=[mem]))
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(pre + list(nprng.randint(0, V, 2)), 8)
    for _ in range(3):
        sched.step()                 # the donor registers its prefix
    for i in range(3):
        sched.submit(pre + list(nprng.randint(0, V, 3 + i)), 6)
    sched.run()
    ticks = mem.by_kind("decode_tick")
    assert ticks
    for r in ticks:
        for key in ("prefix_hit_blocks", "cow_forks",
                    "draft_accept_rate", "prefill_chunks", "tokens"):
            assert key in r, key
    # counter fields are PER-TICK DELTAS: summing records == the
    # engine's cumulative truth (one aggregation rule per record)
    assert sum(r["prefix_hit_blocks"] for r in ticks) \
        == eng.cache.prefix_hit_blocks >= 1
    assert sum(r["prefill_chunks"] for r in ticks) <= eng.prefill_chunks
    reqs = mem.by_kind("request")
    assert len(reqs) == 4
    for r in reqs:
        for key in ("prefix_hit_blocks", "blocks_reserved", "cow_forks",
                    "prefill_chunks", "draft_accept_rate"):
            assert key in r, key
    summary = summarize_requests(reqs)
    assert summary["prefix_hit_blocks"] >= 1
    assert summary["block_sharing_ratio"] is not None
    assert summary["prefill_chunks"] >= 4
    assert summary["draft_accept_rate"] is None \
        or 0 <= summary["draft_accept_rate"] <= 1


# ---------------------------------------------------------------------------
# inference.py routing satellites
# ---------------------------------------------------------------------------

def test_inference_predict_routes_serving_methods(tmp_path, model_and_vars,
                                                  nprng):
    from paddle_tpu.inference import export, load_inference_model
    model, vs = model_and_vars
    path = os.path.join(str(tmp_path), "bundle")
    export(path, model, vs)
    im = load_inference_model(path)
    prompts = [[1, 2, 3], [5, 6, 7, 8]]
    first = im.predict(prompts, method="prefill", max_slots=2,
                       block_size=BS)
    assert first.shape == (2,)
    # decode well past the prompts' first block: the session reserves
    # full slot capacity at prefill, so crossing block boundaries keeps
    # matching the greedy full-forward oracle (regression: an
    # under-reserved session silently scattered KV to the null block)
    fronts = [im.predict(method="decode_step") for _ in range(6)]
    assert all(f.shape == (2,) for f in fronts)
    for b, p in enumerate(prompts):
        got = [int(first[b])] + [int(f[b]) for f in fronts]
        assert got == _greedy_oracle(im.model, im.variables, p, 7)
    # the engine-backed session ran the compiled fixed-shape programs
    assert im.engine().compile_counts() == {"prefill": 1, "tick": 1}
    # generate() sugar matches the greedy oracle on a fresh bundle
    im2 = load_inference_model(path)
    outs = im2.generate(prompts, max_new_tokens=4, block_size=BS)
    for p, got in zip(prompts, outs):
        assert got == _greedy_oracle(im2.model, im2.variables, p, 4)


def test_inference_unhashable_kwarg_warns_once_naming_it(
        tmp_path, model_and_vars, caplog):
    from paddle_tpu.inference import export, load_inference_model
    model, vs = model_and_vars
    path = os.path.join(str(tmp_path), "bundle")
    export(path, model, vs)
    im = load_inference_model(path)
    x = jnp.zeros((1, W), jnp.int32)
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.inference"):
        im.predict(x, segments=np.ones((1, W), np.int32))   # unhashable
        im.predict(x, segments=np.ones((1, W), np.int32))   # warned already
    warns = [r for r in caplog.records if "unhashable" in r.getMessage()]
    assert len(warns) == 1
    assert "segments" in warns[0].getMessage()


# ---------------------------------------------------------------------------
# ISSUE 14: int8 KV quantization
# ---------------------------------------------------------------------------

def test_quantize_rows_roundtrip_bound(nprng):
    """Symmetric per-row-per-head int8: reconstruction error is bounded
    by half a quantization step (amax/254) per element."""
    kv = jnp.asarray(nprng.randn(3, 5, 4, 16).astype(np.float32))
    q, s = kvc.quantize_rows(kv)
    assert q.dtype == jnp.int8 and s.shape == (3, 5, 4)
    deq = kvc.dequantize_rows(q, s)
    amax = np.max(np.abs(np.asarray(kv)), axis=-1, keepdims=True)
    err = np.abs(np.asarray(deq) - np.asarray(kv))
    assert np.all(err <= amax / 254.0 + 1e-7)


def test_quantized_pool_scatter_gather_dequantizes(nprng):
    """The (values, scales) tuple pool: scatter quantizes, gather
    returns dequantized f32 close to the original rows."""
    H, hd = 2, 8
    pages = (jnp.zeros((8, H, BS, hd), jnp.int8),
             jnp.zeros((8, H, BS), jnp.float32))
    table = jnp.asarray([[3, 1, 5, 0, 0, 0]], jnp.int32)
    kv = jnp.asarray(nprng.randn(1, MB * BS, H, hd).astype(np.float32))
    pages = kvc.scatter_prefill_pages(pages, kv, table,
                                      jnp.asarray([9], jnp.int32))
    got = kvc.gather_pages(pages, table)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got[0, :9]),
                               np.asarray(kv[0, :9]), atol=0.03)


def test_quantized_engine_drift_bound_and_token_agreement(model_and_vars,
                                                          nprng):
    """The ISSUE 14 acceptance contract on the gate set: an int8 KV pool
    generates with >= 99% greedy token agreement vs the f32 pool, and
    the decode-step logits drift stays within a small absolute bound —
    quantization is a capacity lever, not a quality cliff."""
    model, vs = model_and_vars
    prompts = [list(nprng.randint(0, V, nprng.randint(2, 8)))
               for _ in range(8)]
    maxnew = [3, 9, 5, 12, 7, 4, 10, 6]

    def run(kv_dtype):
        eng = DecodeEngine(model, vs, max_slots=4, block_size=BS,
                           kv_dtype=kv_dtype)
        sched = ContinuousBatchingScheduler(eng)
        reqs = [sched.submit(list(p), m)
                for p, m in zip(prompts, maxnew)]
        sched.run()
        assert eng.compile_counts() == {"prefill": 1, "tick": 1}
        return [r.tokens for r in reqs], eng

    toks_f, eng_f = run(None)
    toks_q, eng_q = run("int8")
    agree = sum(a == b for x, y in zip(toks_f, toks_q)
                for a, b in zip(x, y))
    total = sum(len(x) for x in toks_f)
    assert agree / total >= 0.99
    # capacity accounting: int8 + one f32 scale per head vs 4 bytes/elem
    assert eng_q.cache.kv_bytes_per_token < eng_f.cache.kv_bytes_per_token
    assert eng_q.cache.quant_dtype == "int8"
    # logit drift on a live decode step, both caches warm with the same
    # prompt: small absolute bound at this model's logit scale
    ef = DecodeEngine(model, vs, max_slots=1, block_size=BS)
    eq = DecodeEngine(model, vs, max_slots=1, block_size=BS,
                      kv_dtype="int8")
    p0 = prompts[1]
    for e in (ef, eq):
        e.admit(0, list(p0), reserve_len=len(p0) + 4)

    def step_logits(e):
        tables, lengths = e.cache.device_tables()
        logits, _ = model.apply(
            e.variables, jnp.asarray(e.tokens),
            (e.cache.k, e.cache.v, tables), lengths,
            jnp.asarray(e.active), attn_impl="xla", method="decode_step")
        return np.asarray(logits[0])

    lf, lq = step_logits(ef), step_logits(eq)
    assert np.max(np.abs(lf - lq)) < 0.05 * max(1.0, np.ptp(lf))


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("case", PAGED_CASES)
def test_quantized_paged_kernel_matches_reference(nprng, case, layer):
    """paged_decode_attention with an int8 (values, scales) pool matches
    the dequantizing oracle — dequant-in-kernel is numerically the same
    as dequant-then-attend."""
    from paddle_tpu.nn.pallas_attention import (paged_decode_attention,
                                                paged_reference_attention)
    q, raw_k, raw_v, tables, lengths = _paged_case(nprng, case)
    pk = kvc.quantize_rows(raw_k)
    pv = kvc.quantize_rows(raw_v)
    out = paged_decode_attention(q, pk, pv, tables, lengths, layer)
    ref = paged_reference_attention(q, layer_of(pk, layer),
                                    layer_of(pv, layer), tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)
    assert not np.any(np.asarray(out)[np.asarray(lengths) == 0])


# ---------------------------------------------------------------------------
# ISSUE 14: multi-query paged span kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("head", [(2, 16), (2, 128)])
def test_paged_span_kernel_matches_oracle_and_q1_decode_kernel(nprng, head):
    """The span kernel vs its oracle across ragged starts (mid-block,
    block-boundary, tail), span widths Q = 1+k for k in {0, 3}, partial
    spans (n < Q) and an inactive slot — and at Q=1 it agrees with the
    q_len=1 decode kernel as both agree with their oracles, to rounding:
    at head size 128 that kernel sums a group of pages at a time (the
    bit-equal lock-step of tick and span is the "xla" path's)."""
    from paddle_tpu.nn.pallas_attention import (
        paged_decode_attention, paged_span_attention,
        paged_span_reference_attention)
    S, (H, D), N, layer = 4, head, 32, 1
    pk = jnp.asarray(nprng.randn(3, N, H, BS, D).astype(np.float32))
    pv = jnp.asarray(nprng.randn(3, N, H, BS, D).astype(np.float32))
    tables = jnp.asarray(nprng.randint(0, N, (S, MB)), jnp.int32)
    for k in (0, 3):
        Q = 1 + k
        q = jnp.asarray(nprng.randn(S, Q, H, D).astype(np.float32))
        # mid-block, inactive WITH a stale start (must still be zeros),
        # block boundary, clamped tail
        start = jnp.asarray([3, 7, 8, MB * BS - Q], jnp.int32)
        n = jnp.asarray([Q, 0, max(1, Q - 1), Q], jnp.int32)
        out = paged_span_attention(q, pk, pv, tables, start, n, layer)
        ref = paged_span_reference_attention(q, pk[layer], pv[layer],
                                             tables, start, n)
        for s in range(S):
            live = int(n[s])
            if live == 0:
                assert not np.any(np.asarray(out[s]))
            else:
                np.testing.assert_allclose(
                    np.asarray(out[s, :live]), np.asarray(ref[s, :live]),
                    rtol=2e-6, atol=2e-6)
        if Q == 1:
            lengths = jnp.where(n > 0, start + 1, 0)
            single = paged_decode_attention(q[:, 0], pk, pv, tables,
                                            lengths, layer)
            np.testing.assert_allclose(np.asarray(out[:, 0]),
                                       np.asarray(single),
                                       rtol=2e-6, atol=2e-6)


def test_paged_span_kernel_quantized(nprng):
    """The span kernel's in-VMEM dequant path vs the dequantizing
    oracle (int8 pools)."""
    from paddle_tpu.nn.pallas_attention import (
        paged_span_attention, paged_span_reference_attention)
    S, Q, H, D, N, layer = 3, 4, 2, 16, 32, 2
    q = jnp.asarray(nprng.randn(S, Q, H, D).astype(np.float32))
    pk = kvc.quantize_rows(
        jnp.asarray(nprng.randn(3, N, H, BS, D).astype(np.float32)))
    pv = kvc.quantize_rows(
        jnp.asarray(nprng.randn(3, N, H, BS, D).astype(np.float32)))
    tables = jnp.asarray(nprng.randint(0, N, (S, MB)), jnp.int32)
    start = jnp.asarray([2, 0, 9], jnp.int32)
    n = jnp.asarray([Q, 0, Q], jnp.int32)
    out = paged_span_attention(q, pk, pv, tables, start, n, layer)
    ref = paged_span_reference_attention(
        q, layer_of(pk, layer), layer_of(pv, layer), tables, start, n)
    for s in range(S):
        live = int(n[s])
        if live:
            np.testing.assert_allclose(
                np.asarray(out[s, :live]), np.asarray(ref[s, :live]),
                rtol=2e-6, atol=2e-6)


def test_model_decode_span_paged_impl_matches_xla(model_and_vars, nprng):
    """End to end through the model: the span tick on the paged kernel
    path produces tokens identical to the XLA gather path on this CPU
    gate set (the kernel is tolerance-accurate; argmax agreement over
    the gate set is the behavioral check)."""
    model, vs = model_and_vars
    prompts = [list(nprng.randint(0, V, nprng.randint(2, 8)))
               for _ in range(4)]

    def run(attention):
        eng = DecodeEngine(model, vs, max_slots=4, block_size=BS,
                           speculative=3, attention=attention)
        sched = ContinuousBatchingScheduler(eng)
        reqs = [sched.submit(list(p), 8) for p in prompts]
        sched.run()
        assert eng.compile_counts() == {"prefill": 1, "tick": 1}
        return [r.tokens for r in reqs]

    assert run("paged") == run("xla")


# ---------------------------------------------------------------------------
# ISSUE 14: radix retention
# ---------------------------------------------------------------------------

def test_retention_sequential_prefix_hits(model_and_vars, nprng):
    """The RadixAttention win: a SECOND wave of same-prefix requests —
    no live sharer left — adopts retained blocks (fewer fresh allocs
    than a retention-off engine), generates identically, and the pool
    stays leak-free with retained counted as reclaimable."""
    model, vs = model_and_vars
    pre = list(nprng.randint(0, V, 2 * BS))
    tails = [list(nprng.randint(0, V, 3)) for _ in range(4)]

    def wave(eng, i):
        sched = ContinuousBatchingScheduler(eng)
        reqs = [sched.submit(pre + list(t), 4) for t in tails[2*i:2*i+2]]
        sched.run()
        return [r.tokens for r in reqs]

    eng_r = DecodeEngine(model, vs, max_slots=2, block_size=BS)
    eng_n = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                         retain_prefix=False)
    toks_r = wave(eng_r, 0)
    assert eng_r.cache.retained_blocks > 0        # wave 1 parked blocks
    toks_n = wave(eng_n, 0)
    a_r, a_n = (eng_r.cache.allocator.total_allocs,
                eng_n.cache.allocator.total_allocs)
    toks_r2 = wave(eng_r, 1)
    toks_n2 = wave(eng_n, 1)
    assert toks_r == toks_n and toks_r2 == toks_n2   # identical output
    assert eng_r.cache.retained_hits >= 2        # wave 2 hit the LRU
    # wave 2 allocated FEWER fresh blocks than the retention-off engine
    assert (eng_r.cache.allocator.total_allocs - a_r
            < eng_n.cache.allocator.total_allocs - a_n)
    # leak-free: free + retained covers the whole pool exactly once
    pool = (list(eng_r.cache.allocator._free)
            + list(eng_r.cache.allocator._retained))
    assert len(pool) == len(set(pool)) == eng_r.cache.num_blocks - 1
    assert eng_r.cache.free_blocks == eng_r.cache.num_blocks - 1


def test_retention_reclaim_under_pressure_leak_free(model_and_vars,
                                                    nprng):
    """The retention leak regression (ISSUE 14): under pool pressure
    retained blocks are lazily reclaimed (oldest first, prefix-cache
    entries invalidated at that moment) — churn through MANY distinct
    prompts on a small pool, then verify every block is on the free
    list or retained LRU exactly once and reclaims actually fired."""
    model, vs = model_and_vars
    # pool sized for ~2 resident sequences: wave churn forces reclaim
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                       num_blocks=2 * 3 + 1)
    for i in range(4):
        sched = ContinuousBatchingScheduler(eng)
        for j in range(3):
            sched.submit(list(nprng.randint(0, V, 4 + i + j)), 5)
        sched.run()
    assert eng.cache.allocator.retained_reclaims > 0
    pool = (list(eng.cache.allocator._free)
            + list(eng.cache.allocator._retained))
    assert len(pool) == len(set(pool)) == eng.cache.num_blocks - 1
    assert eng.cache.free_blocks == eng.cache.num_blocks - 1
    # the prefix cache holds no entry for any reclaimed (now-free) block
    for b in eng.cache.allocator._free:
        assert not eng.cache.prefix_cache.covers(b) or \
            b in eng.cache.allocator._retained
    assert eng.compile_counts() == {"prefill": 1, "tick": 1}


def test_retention_cow_fork_interaction(model_and_vars, nprng):
    """Retention x CoW (ISSUE 14 satellite): re-admitting an exact
    prompt whose blocks sit in the retained LRU increfs them OUT of the
    LRU (retained hit, rc back to 1), the partial boundary block is
    handled by the standard promote-or-fork discipline, and generation
    is identical to the first run."""
    model, vs = model_and_vars
    prompt = list(nprng.randint(0, V, 6))        # partial boundary
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS)
    s1 = ContinuousBatchingScheduler(eng)
    r1 = s1.submit(list(prompt), 5)
    s1.run()
    retained = list(eng.cache.allocator._retained)
    assert retained, "first run retained nothing"
    hits0 = eng.cache.retained_hits
    s2 = ContinuousBatchingScheduler(eng)
    r2 = s2.submit(list(prompt), 5)
    s2.run()
    assert r2.tokens == r1.tokens
    assert eng.cache.retained_hits > hits0
    # the adopted blocks left the LRU at adoption (incref-revive), and
    # after the second eviction they are retained or free again — once
    pool = (list(eng.cache.allocator._free)
            + list(eng.cache.allocator._retained))
    assert len(pool) == len(set(pool)) == eng.cache.num_blocks - 1


def test_admit_probe_counts_retained_as_reclaimable(model_and_vars,
                                                    nprng):
    """ISSUE 14 satellite: admit_probe threads the reclaimable count —
    a pool whose RAW free list is too small but whose retained LRU
    covers the need admits (no spurious "blocks" shed); the probe
    carries both numbers."""
    model, vs = model_and_vars
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                       num_blocks=2 * 3 + 1)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(list(nprng.randint(0, V, 2 * BS)), 4)
    sched.run()                        # evicted -> full blocks retained
    assert eng.cache.retained_blocks > 0
    raw_free = eng.cache.allocator.num_free
    need_len = (raw_free + 1) * BS     # needs more than raw free
    assert eng.cache.blocks_needed(need_len) <= eng.cache.free_blocks
    probe = eng.admit_probe(need_len, include_slots=False)
    assert probe.ok and probe.reason is None
    assert probe.raw_free_blocks == raw_free
    assert probe.retained_blocks == eng.cache.retained_blocks
    assert probe.free_blocks == raw_free + probe.retained_blocks
    # and the pool genuinely serves it: admission reclaims lazily
    s2 = ContinuousBatchingScheduler(eng)
    req = s2.submit(list(nprng.randint(0, V, need_len - 2)), 2)
    s2.run()
    assert req.finish_reason == "length"


def test_decode_tick_records_carry_retention_and_quant_fields(
        model_and_vars, nprng):
    """ISSUE 14 telemetry: decode_tick records carry kv_bytes_per_token,
    retained_blocks, retained_hits (per-tick delta) and quant_dtype;
    summarize_requests aggregates them into retention-hit-rate and
    KV-bytes rows; obs.report renders them."""
    from paddle_tpu.obs import InMemorySink, Telemetry
    from paddle_tpu.obs.percentiles import summarize_requests
    from paddle_tpu.obs.report import format_summary, summarize
    model, vs = model_and_vars
    mem = InMemorySink()
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                       kv_dtype="int8", telemetry=Telemetry(sinks=[mem]))
    pre = list(nprng.randint(0, V, BS))
    for tail in ([1, 2], [3, 4]):      # sequential same-prefix sessions
        sched = ContinuousBatchingScheduler(eng)
        sched.submit(pre + tail, 3)
        sched.run()
    recs = mem.by_kind("decode_tick")
    assert recs
    for r in recs:
        assert r["kv_bytes_per_token"] == eng.cache.kv_bytes_per_token
        assert r["quant_dtype"] == "int8"
        assert "retained_blocks" in r and "retained_hits" in r
    assert sum(r["retained_hits"] for r in recs) >= 1
    summary = summarize_requests(mem.records)
    assert summary["retained_hits"] >= 1
    assert summary["kv_bytes_per_token"] == eng.cache.kv_bytes_per_token
    assert summary["quant_dtype"] == "int8"
    assert summary["retention_hit_rate"] is not None
    text = format_summary(summarize(mem.records))
    assert "retained prefix hits" in text
    assert "KV bytes/token" in text


# ---------------------------------------------------------------------------
# tensor-parallel sharded decode tick (ISSUE 15)
# ---------------------------------------------------------------------------
#
# The tp=2 engine runs the SAME two compiled programs over a 2-device
# mesh (conftest forces 8 virtual CPU devices): params placed by the
# megatron rule, KV pools head-sharded, out/ffn2 all-reduced. The
# contract is the one every serving PR pinned — token-identical (greedy,
# f32) to the single-device engine across admit/evict/CoW/speculative
# churn, with compile_counts() == {prefill: 1, tick: 1} and the host
# side fully shard-oblivious.


def _tp_mesh():
    from jax.sharding import Mesh
    assert len(jax.devices()) >= 2, "conftest forces 8 CPU devices"
    return Mesh(np.asarray(jax.devices()[:2]), ("model",))


def _churn_run(model, vs, mesh, waves=1, **kw):
    """One engine, `waves` sequential scheduler waves of 8 ragged
    requests over 4 slots (admissions + evictions churn within and
    across waves). Returns (per-wave token lists, engine)."""
    eng = DecodeEngine(model, vs, max_slots=4, block_size=BS, mesh=mesh,
                       **kw)
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(0, V, rng.randint(2, 8)))
               for _ in range(8)]
    maxnew = [2, 12, 2, 12, 2, 12, 2, 2]
    out = []
    for _ in range(waves):
        sched = ContinuousBatchingScheduler(eng)
        reqs = [sched.submit(p, m) for p, m in zip(prompts, maxnew)]
        sched.run()
        out.append([r.tokens for r in reqs])
    return out, eng


def test_tp_engine_token_identical_greedy_churn(model_and_vars):
    """The tentpole pin: tp=2 greedy tokens == single-device greedy
    tokens across two full admit/evict waves on one engine, with zero
    retraces after warmup (wave 2 reuses wave 1's two programs) and the
    per-shard KV accounting halved."""
    model, vs = model_and_vars
    base, eng_b = _churn_run(model, vs, None, waves=2)
    tp, eng_t = _churn_run(model, vs, _tp_mesh(), waves=2)
    assert tp == base
    assert eng_t.tp_degree == 2 and eng_b.tp_degree == 1
    assert eng_t.compile_counts() == {"prefill": 1, "tick": 1}
    assert eng_b.compile_counts() == {"prefill": 1, "tick": 1}
    # head split halves the per-shard bytes; block math is unchanged
    assert eng_t.cache.kv_bytes_per_token * 2 \
        == eng_b.cache.kv_bytes_per_token
    assert eng_t.cache.blocks_needed(13) == eng_b.cache.blocks_needed(13)
    # leak-free after both waves: every block back (free or retained)
    assert eng_t.cache.free_blocks == eng_t.cache.num_blocks - 1


def test_tp_engine_stochastic_speculative_identical(model_and_vars):
    """Seeded stochastic sampling x speculation under tp: the [S3]
    accept/resample walk replays the exact single-device token stream
    (same seeds, same coins — the tp mesh only changes WHERE the matmuls
    run, never the sampled distribution)."""
    from paddle_tpu.serve import SamplingConfig
    model, vs = model_and_vars
    cfg = SamplingConfig(temperature=0.8, top_k=16, seed=11)
    base, _ = _churn_run(model, vs, None, speculative=3, sampling=cfg)
    tp, eng = _churn_run(model, vs, _tp_mesh(), speculative=3,
                         sampling=cfg)
    assert tp == base
    assert eng.compile_counts() == {"prefill": 1, "tick": 1}


def test_tp_engine_int8_pools_identical(model_and_vars):
    """Quantized pools under tp: int8 value pages AND f32 scale pages
    shard on the head axis; quantize-on-scatter/dequant-on-gather run
    per shard. Tokens match the single-device int8 engine exactly."""
    model, vs = model_and_vars
    base, eng_b = _churn_run(model, vs, None, kv_dtype="int8")
    tp, eng_t = _churn_run(model, vs, _tp_mesh(), kv_dtype="int8")
    assert tp == base
    assert eng_t.compile_counts() == {"prefill": 1, "tick": 1}
    # per-shard int8 accounting: half the heads' values+scales per token
    assert eng_t.cache.kv_bytes_per_token * 2 \
        == eng_b.cache.kv_bytes_per_token


def test_tp_cow_fork_and_retention_under_sharding(model_and_vars,
                                                  nprng):
    """Sharing composes with sharding: duplicate prompts adopt + COW-
    fork (the donated one-block device copy runs on the sharded pools),
    a second same-prefix wave revives retained blocks, and the pool
    stays leak-free — all through the ONE logical block table the host
    keeps (shard-obliviousness is the design's point)."""
    model, vs = model_and_vars
    pre = list(nprng.randint(0, V, 2 * BS))
    tails = [list(nprng.randint(0, V, 2)) for _ in range(4)]

    def run(mesh):
        eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                           mesh=mesh)
        toks = []
        # wave 1: a CONCURRENT exact-duplicate pair (both slots resident
        # at once) -> full-chain adoption + partial-boundary COW fork;
        # wave 2: fresh same-prefix tails with no live sharer ->
        # retained-LRU hits
        for wave in ([tails[0], tails[0]], tails[2:]):
            sched = ContinuousBatchingScheduler(eng)
            reqs = [sched.submit(pre + t, 4) for t in wave]
            sched.run()
            toks.append([r.tokens for r in reqs])
        return toks, eng

    base, eng_b = run(None)
    tp, eng_t = run(_tp_mesh())
    assert tp == base
    assert eng_t.cache.cow_forks >= 1           # forks actually fired
    assert eng_t.cache.retained_hits >= 1       # retention revived
    assert eng_t.cache.cow_forks == eng_b.cache.cow_forks
    assert eng_t.cache.retained_hits == eng_b.cache.retained_hits
    assert eng_t.cache.free_blocks == eng_t.cache.num_blocks - 1
    assert eng_t.compile_counts() == {"prefill": 1, "tick": 1}


def test_tp_paged_kernel_runs_per_shard(model_and_vars):
    """attention='paged' under a tp mesh: the Pallas q_len=1 and span
    kernels run PER SHARD over local heads via shard_map (the
    _tp_paged_kernel seam) and reproduce the xla path's greedy tokens."""
    model, vs = model_and_vars

    def run(attention, speculative=0):
        eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                           mesh=_tp_mesh(), attention=attention,
                           speculative=speculative)
        eng.admit(0, [1, 2, 3, 4, 5], reserve_len=eng.context_width)
        return [int(eng.decode_tick()[0]) for _ in range(4)], eng

    tx, _ = run("xla")
    tk, eng = run("paged")
    assert tk == tx
    assert eng.compile_counts() == {"prefill": 1, "tick": 1}
    # the span kernel (speculative tick) per shard
    sx, _ = run("xla", speculative=2)
    sk, _ = run("paged", speculative=2)
    assert sk == sx


def test_tp_kv_cache_accounting_and_validation():
    """PagedKVCache(tp_degree=): per-shard bytes divide by the head
    split, block math never changes, and a non-dividing head count
    fails loud (the kernel path needs whole head groups)."""
    mk = lambda tp: PagedKVCache(num_layers=2, num_heads=4, head_dim=8,
                                 num_blocks=9, block_size=BS,
                                 max_slots=2, max_blocks_per_seq=4,
                                 tp_degree=tp)
    c1, c2 = mk(1), mk(2)
    assert c2.kv_bytes_per_token * 2 == c1.kv_bytes_per_token
    assert c2.bytes_per_block * 2 == c1.bytes_per_block
    assert c2.blocks_needed(9) == c1.blocks_needed(9)
    with pytest.raises(ValueError, match="divide"):
        mk(3)
    with pytest.raises(ValueError, match="model"):
        # a mesh without the tp axis fails loud in the engine
        from jax.sharding import Mesh
        model = TransformerLM(vocab=V, dim=DIM, num_layers=1,
                              num_heads=HEADS, ffn_hidden=FFN, max_len=W)
        vs = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, W), jnp.int32))
        DecodeEngine(model, vs, mesh=Mesh(np.asarray(jax.devices()[:2]),
                                          ("data",)))


def test_tp_decode_tick_records_and_report(model_and_vars):
    """ISSUE 15 telemetry: decode_tick records carry tp_degree and the
    PER-SHARD kv_bytes_per_token; summarize_requests surfaces the mesh
    gauge; obs.report renders the tensor-parallel row."""
    from paddle_tpu.obs import InMemorySink, Telemetry
    from paddle_tpu.obs.percentiles import summarize_requests
    from paddle_tpu.obs.report import format_summary, summarize
    model, vs = model_and_vars
    mem = InMemorySink()
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                       mesh=_tp_mesh(), telemetry=Telemetry(sinks=[mem]))
    sched = ContinuousBatchingScheduler(eng)
    sched.submit([1, 2, 3], 4)
    sched.run()
    recs = mem.by_kind("decode_tick")
    assert recs
    for r in recs:
        assert r["tp_degree"] == 2
        assert r["kv_bytes_per_token"] == eng.cache.kv_bytes_per_token
    summary = summarize_requests(mem.records)
    assert summary["tp_degree"] == 2
    text = format_summary(summarize(mem.records))
    assert "tensor-parallel mesh" in text and "tp=2" in text


def test_tp_tick_has_decode_collectives(model_and_vars):
    """ISSUE 15 satellite: the sharded tick's tp reductions (the
    out-proj/ffn all-reduces) are the partitioner's, so they are counted
    in the COMPILED tick; the single-device tick has none."""
    from hlo_counts import compiled_all_reduces
    model, vs = model_and_vars
    eng = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                       mesh=_tp_mesh())
    assert eng.tp_degree == 2
    assert compiled_all_reduces(eng.lower_tick().compile().as_text()) >= 1
    eng1 = DecodeEngine(model, vs, max_slots=2, block_size=BS)
    assert compiled_all_reduces(eng1.lower_tick().compile().as_text()) == 0


def test_proc_spec_ships_mesh_and_single_device_roundtrip(
        model_and_vars, tmp_path):
    """ISSUE 15 satellite: build_proc_spec(mesh_axes=) ships the axis
    layout (a Mesh can't cross the JSON wire); a spec WITHOUT it is
    byte-identical to the pre-tp schema (old/new replicas agree on the
    frame bytes), and replica_proc._build raises the mesh into a real
    tensor-parallel engine."""
    import json
    from paddle_tpu.serve import build_proc_spec
    from paddle_tpu.serve import replica_proc
    model, vs = model_and_vars
    plain = build_proc_spec(model, vs, str(tmp_path))
    assert "mesh" not in plain
    assert json.loads(json.dumps(plain)) == plain       # round-trips
    meshy = build_proc_spec(model, vs, str(tmp_path),
                            mesh_axes={"model": 2})
    assert meshy["mesh"] == {"model": 2}
    assert {k: v for k, v in meshy.items() if k != "mesh"} == plain
    eng, sched, buf, clock, startup, metrics = replica_proc._build(
        dict(meshy, engine={"max_slots": 2, "block_size": BS}))
    assert metrics is None              # absent spec key = no registry
    assert eng.tp_degree == 2
    assert eng.cache.kv_bytes_per_token * 2 == 512      # per-shard
    # ISSUE 16: startup breakdown exists even with warmup off — the
    # hello/heartbeat payloads always carry the build wall
    assert startup["build"] > 0 and startup["warmup"] == 0.0
    # warmup/cache fields stay ABSENT from an unconfigured spec (the
    # PR-15 schema-stability rule extends to the ISSUE-16 fields)
    for k in ("warmup", "autotune_cache_dir"):
        assert k not in plain


# ---------------------------------------------------------------------------
# ISSUE 32: the engine prepares its weights once
# ---------------------------------------------------------------------------

def _policy(name):
    from paddle_tpu.core import dtypes
    return getattr(dtypes, name)


def _leaf_paths(tree):
    return {"/".join(str(k.key) for k in path): leaf for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("experts", [0, 4], ids=["dense", "moe"])
@pytest.mark.parametrize("policy", ["float32", "bfloat16_compute"])
def test_prepared_tree_stacks_blocks_and_casts_only_operands(policy,
                                                             experts):
    """``serving_variables``: the ``block<i>`` subtrees become ONE
    ``blocks`` subtree on a leading layer axis; exactly the leaves the
    block passes through ``cast_compute`` are in the policy's compute
    type, every other leaf in its stored type (an expert layer's leaves
    too: ``MoEFFN`` multiplies them as stored); embeddings, the final
    LayerNorm and the other collections are the objects they were."""
    from paddle_tpu.core import use_policy
    from paddle_tpu.models.transformer import TransformerBlock
    model = TransformerLM(vocab=V, dim=DIM, num_layers=3, num_heads=HEADS,
                          ffn_hidden=FFN, max_len=W, moe_experts=experts)
    vs = model.init(jax.random.PRNGKey(0), jnp.zeros((1, W), jnp.int32))
    pol = _policy(policy)
    with use_policy(pol):
        got = model.serving_variables(vs)
    own, was = got["params"]["transformer_lm"], vs["params"]["transformer_lm"]
    assert set(own) == {"emb", "pos", "ln_f", "blocks"}
    for name in ("emb", "pos", "ln_f"):
        for a, b in zip(jax.tree_util.tree_leaves(own[name]),
                        jax.tree_util.tree_leaves(was[name])):
            assert a is b
    assert got["state"] is vs["state"]
    stack = _leaf_paths(own["blocks"])
    assert set(stack) == set(_leaf_paths(was["block0"]))
    cast = [n for n in TransformerBlock.compute_operands if n in stack]
    assert len(cast) == (4 if experts else 6)
    for name, leaf in stack.items():
        per_block = [_leaf_paths(was[f"block{i}"])[name] for i in range(3)]
        assert leaf.shape == (3,) + per_block[0].shape, name
        want = pol.compute_dtype if name in cast else per_block[0].dtype
        assert leaf.dtype == want, (name, leaf.dtype)
        np.testing.assert_array_equal(
            np.asarray(leaf, np.float32),
            np.asarray(jnp.stack(per_block).astype(want), np.float32))


@pytest.fixture(scope="module")
def prepared_logits():
    """Logits of ``prefill``, ``decode_span`` and ``decode_step`` under
    ``bfloat16_compute`` on three trees of one jittered model: the
    training tree (stacked at trace time, every call), the prepared tree,
    and the prepared tree with ONE leaf too many cast (``ln1/scale`` in
    bfloat16): what a wrong list of cast leaves would cost."""
    from paddle_tpu.core import bfloat16_compute, use_policy
    model = TransformerLM(vocab=V, dim=DIM, num_layers=LAYERS,
                          num_heads=HEADS, ffn_hidden=FFN, max_len=W)
    vs = model.init(jax.random.PRNGKey(0), jnp.zeros((1, W), jnp.int32))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    # LayerNorm scales start at 1 and biases at 0, which every type holds
    vs = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape), vs)
    rng = np.random.RandomState(0)
    B, P, Q = 2, 7, 5
    ids = rng.randint(0, V, (B, W)).astype(np.int32)

    def run(tree):
        cache = PagedKVCache(LAYERS, HEADS, DIM // HEADS, B * MB + 1, BS,
                             max_slots=B, max_blocks_per_seq=MB)
        for b in range(B):
            assert cache.ensure_capacity(b, W)
        tbl = jnp.asarray(cache.tables)
        out = {}
        out["prefill"], (ks, vv) = jax.jit(lambda v, i: model.apply(
            v, i, method="prefill"))(tree, jnp.asarray(ids))
        scat = jax.vmap(kvc.scatter_prefill, in_axes=(0, 0, None, None))
        plen = jnp.full((B,), P, jnp.int32)
        k, v = scat(cache.k, ks, tbl, plen), scat(cache.v, vv, tbl, plen)
        every = jnp.ones((B,), bool)
        out["decode_span"], (k, v, _) = jax.jit(
            lambda t, c, kv: model.apply(
                t, c, kv, plen, jnp.full((B,), Q, jnp.int32), every,
                method="decode_span"))(
                    tree, jnp.asarray(ids[:, P:P + Q]), (k, v, tbl))
        out["decode_step"], _ = jax.jit(
            lambda t, c, kv: model.apply(
                t, c, kv, plen + Q, every, method="decode_step"))(
                    tree, jnp.asarray(ids[:, P + Q]), (k, v, tbl))
        return {name: np.asarray(x) for name, x in out.items()}

    with use_policy(bfloat16_compute):
        prepared = model.serving_variables(vs)
        own = prepared["params"]["transformer_lm"]
        ln1 = own["blocks"]["ln1"]
        wrong = {**prepared, "params": {"transformer_lm": {
            **own, "blocks": {**own["blocks"], "ln1": {
                **ln1, "scale": ln1["scale"].astype(jnp.bfloat16)}}}}}
        return {"training": run(vs), "prepared": run(prepared),
                "wrong": run(wrong)}


@pytest.mark.parametrize("method", ["prefill", "decode_span", "decode_step"])
def test_prepared_tree_logits_equal_training_tree(prepared_logits, method):
    """The same work, not less of it: on the prepared tree every entry
    point gives the logits it gives on the training tree, to the bit
    where the backend compiles the two products alike, and otherwise
    inside a hundredth of what ONE wrongly cast leaf moves them by."""
    training, prepared, wrong = (prepared_logits[k][method]
                                 for k in ("training", "prepared", "wrong"))
    assert training.shape == prepared.shape and training.dtype == np.float32
    miscast = float(np.max(np.abs(wrong - training)))
    assert miscast > 1e-3, miscast      # the control does show
    gap = float(np.max(np.abs(prepared - training)))
    assert gap <= miscast / 100, (gap, miscast)


@pytest.mark.parametrize("kw", [{}, {"speculative": 3},
                                {"prefill_chunk": 4}],
                         ids=["plain", "speculative3", "chunk4"])
def test_prepared_engine_tokens_equal_training_tree_engine(
        model_and_vars, monkeypatch, kw):
    """Greedy tokens over two waves of admit/evict churn under
    ``bfloat16_compute``: the engine on its prepared tree against an
    engine whose model hands the training tree back (the programs then
    stack and cast inside, every call, as they did before). Both trace
    each entry point once, the preparation not among them."""
    from paddle_tpu.core import bfloat16_compute, use_policy
    model, vs = model_and_vars
    with use_policy(bfloat16_compute):
        got, eng = _churn_run(model, vs, None, waves=2, **kw)
        assert "blocks" in eng.variables["params"]["transformer_lm"]
        monkeypatch.setattr(model, "serving_variables", lambda tree: tree)
        want, old = _churn_run(model, vs, None, waves=2, **kw)
        assert "block0" in old.variables["params"]["transformer_lm"]
    assert got == want
    assert eng.compile_counts() == {"prefill": 1, "tick": 1}
    assert old.compile_counts() == {"prefill": 1, "tick": 1}


def test_engine_holds_no_copy_of_the_blocks_float32_matrices(model_and_vars):
    """What the engine keeps is the prepared tree alone: under
    ``bfloat16_compute`` no float32 leaf of it has three axes (a stacked
    matrix), the caller's tree is untouched, and ``warmup()`` reports the
    bytes both programs take."""
    from paddle_tpu.core import bfloat16_compute, use_policy
    model, vs = model_and_vars
    before = jax.tree_util.tree_structure(vs)
    with use_policy(bfloat16_compute):
        eng = DecodeEngine(model, vs, max_slots=2, block_size=BS)
        report = eng.warmup()
    assert jax.tree_util.tree_structure(vs) == before
    leaves = jax.tree_util.tree_leaves(eng.variables)
    assert not [x.shape for x in leaves
                if x.ndim == 3 and x.dtype == jnp.float32]
    assert report["prepared_bytes"] == sum(x.nbytes for x in leaves)
    assert report["compile_counts"] == {"prefill": 1, "tick": 1}
    matrices = LAYERS * (4 * DIM * DIM + 2 * DIM * FFN)
    whole = sum(x.nbytes for x in jax.tree_util.tree_leaves(vs))
    assert report["prepared_bytes"] == whole - 2 * matrices


@pytest.mark.parametrize("build,trace", [("float32", "bfloat16_compute"),
                                         ("bfloat16_compute", "float32")])
def test_engine_traced_under_another_policy_raises(model_and_vars, build,
                                                   trace):
    """The prepared operands are right for the policy of the build only:
    a first call (the trace) under another policy fails loudly. Once
    traced, a program runs as traced whatever the caller's policy (a
    policy is no part of jit's cache key), so a warmed engine serves from
    any context."""
    from paddle_tpu.core import use_policy
    model, vs = model_and_vars
    with use_policy(_policy(build)):
        cold = DecodeEngine(model, vs, max_slots=2, block_size=BS)
        warm = DecodeEngine(model, vs, max_slots=2, block_size=BS)
        warm.warmup()
        want = warm.admit(0, [3, 1, 4], reserve_len=6)
        warm.evict(0)
    with use_policy(_policy(trace)):
        with pytest.raises(RuntimeError, match="prepared its weights"):
            cold.admit(0, [3, 1, 4], reserve_len=6)
        assert warm.admit(0, [3, 1, 4], reserve_len=6) == want
        warm.decode_tick()
    assert warm.compile_counts() == {"prefill": 1, "tick": 1}


@pytest.mark.parametrize("policy", ["float32", "bfloat16_compute"])
def test_tp_prepared_stack_keeps_the_rule_behind_the_layer_axis(
        model_and_vars, policy):
    """With ``mesh=``: the per-block leaves are placed by the rule, then
    stacked, and a stacked leaf carries its rule's spec behind an
    unsharded layer axis, ``P(None, *rule)``, pinned on the preparing
    program's outputs: the tick traces once over two waves of churn."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.core import use_policy
    from paddle_tpu.parallel.megatron import megatron_sp_rules
    model, vs = model_and_vars
    with use_policy(_policy(policy)):
        tp, eng = _churn_run(model, vs, _tp_mesh(), waves=2)
        base, _ = _churn_run(model, vs, None, waves=2)
    rules = megatron_sp_rules()
    stack = _leaf_paths(eng.variables["params"]["transformer_lm"]["blocks"])
    sharded = 0
    for name, leaf in stack.items():
        rule = rules.spec_for("transformer_lm/block0/" + name)
        assert isinstance(leaf.sharding, NamedSharding), name
        assert leaf.sharding.spec == P(None, *rule), (name, leaf.sharding)
        sharded += "model" in rule
    assert sharded == 7                 # the rule's seven sharded leaves
    assert eng.compile_counts() == {"prefill": 1, "tick": 1}
    if policy == "float32":
        assert tp == base
