"""LFM2-24B-A2B's decoder (``models/conv_moe.py:ConvMoELM``: gated short
convolutions beside QK-normed grouped attention at head size 64, a
sigmoid router whose choice takes an expert bias, renormalised gates with
an epsilon of 1e-6) against its plain float32 reference
(``benchmarks/lfm2_reference.py``) at a small size with every mechanism
present: six layers ``[conv, full, conv, conv, full, conv]`` (the first
with a dense feed-forward, the others 8 experts, 3 a token, all held),
8 query heads on 4 KV heads of 64 (two KV heads to a 128-lane pool row),
seeded weights; through the serving cache: a paged ``full`` group and the
convolutions' ``conv`` STATE group, a slot's last two inputs a layer.

Tolerances. Program and reference compute the same float32 arithmetic in
another order (an online softmax over tiles read back from the pool
against a whole one, a grouped product against a loop over experts, a
convolution carried across calls against one over the whole sequence):
logits of order one agree to ``ATOL`` 5e-4 (measured: under 1e-5).
The reference with int8 operands (the control) misses by a hundred times
that, and has to; so does each planted fault, ``no_state_carry`` at the
chunk of 8 the cache tests prefill with.
"""

import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import lfm2_layout as layout            # noqa: E402
from benchmarks import lfm2_reference as reference      # noqa: E402
from paddle_tpu.nn.attention import GroupedQueryAttention  # noqa: E402
from paddle_tpu.nn.moe import HeldExpertsFFN            # noqa: E402
from paddle_tpu.nn.pallas_attention import (            # noqa: E402
    lane_packed_decode_attention, paged_reference_attention)
from paddle_tpu.nn.recurrent import GatedShortConv      # noqa: E402
from paddle_tpu.serve import (ContinuousBatchingScheduler,  # noqa: E402
                              DecodeEngine)
from paddle_tpu.serve.kv_cache import PagedKVCache      # noqa: E402

ATOL = 5e-4
BS, CHUNK = 4, 8
TOY = {
    "hidden_size": 512, "num_attention_heads": 8, "num_key_value_heads": 4,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 3, "num_hidden_layers": 6, "num_dense_layers": 1,
    "layer_types": ["conv", "full_attention", "conv", "conv",
                    "full_attention", "conv"],
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "max_position_embeddings": 256,
    "vocab_size": 96,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "deployment": {"experts_held": [0, 8]},
    # a bias large enough to move choices among 8 experts at this width
    "assumed": {"norm_scale_jitter": 0.1, "router_eps": 1e-6,
                "expert_bias_std": 0.05}}
SEED = 2 ** 31 + 41
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
    """At 256 positions the reference takes its queries 128 at a time."""
    model, vs = prog
    assert Z.kinds == ("conv", "full", "conv", "conv", "full", "conv")
    assert Z.dense == (True,) + (False,) * 5 and Z.hd == 64
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
    held = [[(picked[l] == e).sum() for e in range(Z.held)]
            for l in range(moe)]
    assert np.array_equal(np.asarray(aux["expert_tokens"]), held)
    assert 0.5 < float(np.asarray(logits).std()) < 3.0


@pytest.mark.parametrize("kw", [{"quant": "int8"}] + [
    {"fault": f} for f in reference.FAULTS], ids=lambda kw: str(*kw.values()))
def test_control_and_faults_miss_the_tolerance(kw):
    """What the limits of ``correct`` are set between: the int8 control
    and each wrong layer move a logit a hundred tolerances and more on a
    prompt of 40 (``no_state_carry``: five chunks of 8)."""
    ids = np.random.RandomState(1).randint(0, Z.V, 40)
    gap = np.abs(ref_logits(ids) - ref_logits(
        ids, prompt_lens=[40], chunk=CHUNK, **kw)).max()
    assert gap > 100 * ATOL, gap


def test_no_state_carry_moves_only_what_follows_a_chunk_edge():
    """The fault cuts the convolution at each chunk's first two positions
    and nowhere else: the first chunk is the reference's own."""
    ids = np.random.RandomState(2).randint(0, Z.V, 20)
    gap = np.abs(ref_logits(ids) - ref_logits(
        ids, fault="no_state_carry", prompt_lens=[20], chunk=CHUNK)).max(-1)
    assert gap[:CHUNK].max() < 1e-6 and gap[CHUNK] > 100 * ATOL
    assert reference.segments(12, 10, 4).tolist() == [0] * 4 + [1] * 4 + [2] * 4


# -- (ii) prefill by chunks, then decode, through the paged and state groups --

def make_cache(model, slots, blocks_per_seq=24, dtype=jnp.float32):
    spec = model.cache_spec()
    return PagedKVCache(None, None, None, slots * blocks_per_seq + 1, BS,
                        max_slots=slots, max_blocks_per_seq=blocks_per_seq,
                        dtype=dtype, groups=spec["groups"])


class Served:
    """The model's two serving entry points over one cache, as the engine
    calls them: a prompt by chunks (``decode_span``, one slot a call),
    then ticks over every slot (``decode_step``)."""

    def __init__(self, model, vs, slots, attn_impl="xla"):
        self.cache = make_cache(model, slots)
        self.pools = tuple(self.cache.pools.values())
        self.span = jax.jit(lambda *a: model.apply(vs, *a,
                                                   method="decode_span"))
        self.step = jax.jit(lambda *a: model.apply(
            vs, *a, method="decode_step", attn_impl=attn_impl))
        self.slots = slots
        self.rows = {}          # slot -> logits at every position so far
        self.fed = {}           # slot -> the tokens fed

    def prefill(self, slot, prompt, chunk=CHUNK):
        cache = self.cache
        assert cache.ensure_capacity(slot, len(prompt) + 24)
        rows, cur = [], 0
        while cur < len(prompt):
            n = min(chunk, len(prompt) - cur)
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :n] = prompt[cur:cur + n]
            logits, (*pools, _), _ = self.span(
                jnp.asarray(ids), (*self.pools, cache.slot_tables(slot)),
                jnp.asarray([cur], jnp.int32), jnp.asarray([n], jnp.int32))
            self.pools = tuple(pools)
            rows.extend(np.asarray(logits[0, :n]))
            cur += n
        cache.lengths[slot] = len(prompt)
        self.rows[slot], self.fed[slot] = rows, list(prompt)

    def decode(self, steps):
        live = sorted(self.rows)
        active = np.zeros((self.slots,), bool)
        active[live] = True
        for _ in range(steps):
            tok = np.zeros((self.slots,), np.int32)
            for s in live:
                tok[s] = int(np.argmax(self.rows[s][-1]))
                self.fed[s].append(int(tok[s]))
            tables, lengths = self.cache.device_tables()
            logits, (*pools, _), _ = self.step(
                jnp.array(tok), (*self.pools, tables), lengths,
                jnp.asarray(active))
            self.pools = tuple(pools)
            for s in live:
                self.cache.lengths[s] += 1
                self.rows[s].append(np.asarray(logits[s]))

    def free(self, slot):
        self.cache.free_slot(slot)
        del self.rows[slot], self.fed[slot]

    def check(self, slot, atol=ATOL):
        """Every position's logits against the reference's whole pass."""
        got = np.stack(self.rows[slot])
        want = ref_logits(self.fed[slot][:len(got)])
        gap = np.abs(got - want).max()
        assert gap < atol, gap
        return gap


@pytest.mark.parametrize("attn_impl", ["xla", "paged"])
def test_chunked_prefill_then_decode_agrees_at_every_position(prog,
                                                              attn_impl):
    """Ragged prompts (5, 23, 38: a chunk of 8 divides none of them, so
    the convolutions' state crosses every chunk edge), decoded on 10
    ticks; every prompt and decoded position against the reference. With
    ``paged`` the ticks read the pool through the interpreted grouped
    kernel, two KV heads of 64 to a 128-lane row."""
    model, vs = prog
    rng = np.random.RandomState(3)
    served = Served(model, vs, 3, attn_impl)
    for s, n in enumerate((5, 23, 38)):
        served.prefill(s, list(rng.randint(0, Z.V, n)))
    served.decode(10)
    for s in range(3):
        served.check(s)
    shapes = {n: p.shape for n, p in zip(served.cache.pools, served.pools)}
    assert shapes["conv/state"] == (4, 3, 2 * Z.D)
    assert shapes["full/k"] == (2, 3 * 24 + 1, Z.H_kv // 2, BS, 2 * Z.hd)


def test_a_reused_slot_starts_from_a_zero_state(prog):
    """A slot freed after a longer request and given a shorter one: the
    new prompt's first chunk starts from zeros (the state the last
    request left is never read), and a slot that stays busy beside it is
    untouched by its prefill."""
    model, vs = prog
    rng = np.random.RandomState(4)
    served = Served(model, vs, 2)
    served.prefill(0, list(rng.randint(0, Z.V, 37)))
    served.prefill(1, list(rng.randint(0, Z.V, 9)))
    served.decode(6)
    served.free(0)
    served.prefill(0, list(rng.randint(0, Z.V, 11)))
    served.decode(5)
    served.check(0)
    served.check(1)


def test_prompt_cut_in_any_chunks_gives_the_same_logits(prog):
    """The same prompt a token a call, eight a call and whole: the state
    the convolution carries between calls is its own inputs, so one
    arithmetic."""
    model, vs = prog
    p = list(np.random.RandomState(5).randint(0, Z.V, 27))
    rows = []
    for chunk in (1, 8, 32):
        served = Served(model, vs, 1)
        served.prefill(0, p, chunk)
        rows.append(np.stack(served.rows[0]))
    assert np.abs(rows[0] - rows[1]).max() < ATOL
    assert np.abs(rows[2] - rows[1]).max() < ATOL


def test_engine_serves_what_the_reference_ranks_first(prog):
    """Through ``DecodeEngine`` under the scheduler (chunked prefill, both
    groups, slots reused): every served token is the float32 reference's
    first choice at its position, or within the tolerance of it."""
    model, vs = prog
    engine = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                          prefill_chunk=CHUNK, max_blocks_per_seq=16,
                          attention="paged")
    assert engine.cache.share_prefix is False     # the default, for a state
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.RandomState(6)
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


def test_state_group_is_a_slot_array_beside_the_paged_group(prog):
    """The ``conv`` group is one ``[layers, slots, 2D]`` array with no
    blocks; ``group_facts`` and ``kv_bytes_per_token`` count the paged
    group alone (what the accepted readers read); ``state_facts`` counts
    the state."""
    model, vs = prog
    engine = DecodeEngine(model, vs, max_slots=3, block_size=BS,
                          prefill_chunk=CHUNK, max_blocks_per_seq=16,
                          dtype="bfloat16")
    cache = engine.cache
    assert cache.pools["conv/state"].shape == (4, 3, 2 * Z.D)
    assert cache.pools["conv/state"].dtype == jnp.bfloat16
    assert set(cache.group_facts()) == {"full"}
    assert cache.state_facts() == {"conv": {
        "layers": 4, "slot_bytes": 4 * 2 * Z.D * 2,
        "bytes": 3 * 4 * 2 * Z.D * 2}}
    assert cache.kv_bytes_per_token == 2 * 2 * Z.H_kv * Z.hd * 2
    tables, _ = cache.device_tables()
    # the state's operand is the first of a call's consecutive slots
    assert np.asarray(tables["conv"]).shape == () and int(tables["conv"]) == 0
    conv_table = cache.slot_tables(2)["conv"]
    assert np.asarray(conv_table).shape == () and int(conv_table) == 2
    facts = layout.engine_facts(engine)
    assert facts["pool_groups"]["full"]["layers"] == 2
    assert facts["state_groups"]["conv"]["layers"] == 4
    assert (facts["query_heads"], facts["kv_heads"], facts["head_dim"]) \
        == ({"full": 8}, 4, 64)


# -- (iii) the short convolution's three forms ---------------------------------

def test_short_convolution_forms_agree():
    """``forward`` over a whole sequence, ``decode_span`` in ragged spans
    from a state (padding rows never advancing it; an inactive slot's
    state kept) and ``decode`` a token at a time give one output."""
    D, T = 16, 11
    conv = GatedShortConv(D, name="conv")
    x = jnp.asarray(np.random.RandomState(7).randn(2, T, D), jnp.float32)
    vs = conv.init(jax.random.PRNGKey(0), x)
    whole = np.asarray(conv.apply(vs, x))
    state = jnp.full((3, 2, 2 * D), 7.0)       # what a last request left
    outs, start = [], 0
    for n in (3, 5, 3):
        span = jnp.pad(x[:, start:start + n], ((0, 0), (0, 6 - n), (0, 0)))
        out, state = conv.apply(
            vs, span, state, 1, jnp.int32(0),
            jnp.asarray([start, start], jnp.int32),
            jnp.asarray([n, n], jnp.int32), jnp.asarray([True, True]),
            method="decode_span")
        outs.append(np.asarray(out[:, :n]))
        start += n
    assert np.abs(np.concatenate(outs, 1) - whole).max() < 1e-5
    assert float(jnp.abs(state[0] - 7.0).max()) == 0.0   # other layers
    kept = state
    out, state = conv.apply(
        vs, x[:, :1], state, 1, jnp.int32(0),
        jnp.asarray([True, False]), method="decode")
    assert np.array_equal(np.asarray(state[1, 1]), np.asarray(kept[1, 1]))
    x2 = jnp.concatenate([x, x[:, :1]], 1)
    assert np.abs(np.asarray(out[0, 0])
                  - np.asarray(conv.apply(vs, x2))[0, T]).max() < 1e-5


# -- (iv) head size 64 on the grouped kernel -----------------------------------

@pytest.mark.parametrize("groups", [1, 4])
def test_lane_packed_kernel_agrees_with_the_unpacked_oracle(groups):
    """Pools of two KV heads of 64 to a 128-lane row through the
    interpreted grouped kernel, lengths on and off page edges and an
    empty slot, against the gather-and-softmax oracle on the same pools
    unpacked."""
    rng = np.random.RandomState(8)
    S, Hk, d, MB, L = 4, 4, 64, 8, 2
    H, N = Hk * groups, S * MB + 1
    packed = [jnp.asarray(rng.randn(L, N, Hk // 2, BS, 2 * d), jnp.float32)
              for _ in range(2)]
    unpack = [p.reshape(L, N, Hk // 2, BS, 2, d).transpose(0, 1, 2, 4, 3, 5)
              .reshape(L, N, Hk, BS, d) for p in packed]
    tables = jnp.asarray(1 + rng.permutation(S * MB).reshape(S, MB),
                         jnp.int32)
    lengths = jnp.asarray([0, 13, 16, 32], jnp.int32)
    q = jnp.asarray(rng.randn(S, H, d), jnp.float32)
    got = lane_packed_decode_attention(q, *packed, tables, lengths, 1,
                                       pack=2, interpret=True)
    want = paged_reference_attention(q, unpack[0][1], unpack[1][1], tables,
                                     lengths, scale=d ** -0.5)
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(got[0]).max()) == 0.0


def test_attention_paged_and_xla_ticks_agree_at_head_64():
    """``GroupedQueryAttention`` with q and k normalised, two KV heads of
    64 to a pool row: a tick through the interpreted grouped kernel and
    through the XLA span path read the same pool to one result; a head of
    128 keeps one KV head a row."""
    attn = GroupedQueryAttention(256, 4, 2, 64, rope_base=1e6, qk_norm=True,
                                 name="attn")
    assert attn.pool_row == (1, 128) and attn.pack == 2
    assert GroupedQueryAttention(256, 4, 2, 128).pool_row == (2, 128)
    rng = np.random.RandomState(9)
    S, MB = 3, 6
    x = jnp.asarray(rng.randn(S, 1, 256), jnp.float32)
    vs = attn.init(jax.random.PRNGKey(1), x)
    assert vs["params"]["attn"]["q_norm"]["scale"].shape == (64,)
    pools = [jnp.asarray(rng.randn(1, S * MB + 1, 1, BS, 128), jnp.float32)
             for _ in range(2)]
    table = jnp.asarray(1 + np.arange(S * MB).reshape(S, MB), jnp.int32)
    pos = jnp.asarray([0, 9, 21], jnp.int32)
    active = jnp.asarray([True, True, False])
    outs = [attn.apply(vs, x, *pools, 0, table, pos, active, impl=impl,
                       method="decode") for impl in ("xla", "paged")]
    live = np.asarray(active)
    assert np.abs(np.asarray(outs[0][0] - outs[1][0])[live]).max() < 1e-4
    for a, b in zip(outs[0][1:], outs[1][1:]):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- (v) routing: the expert bias and the 1e-6 epsilon -------------------------

def test_routing_takes_the_bias_for_the_choice_and_eps_in_the_gates():
    """The program's router against the reference's: ids chosen by score
    plus bias, gates the scores alone over their sum plus 1e-6; the
    ``bias_in_gates`` fault moves the gates and not the choice; the
    default epsilon of every other configuration is 1e-20."""
    w = f32(reference.layer_weights(Z, reference.seed32(SEED), 1))
    x = jnp.asarray(np.random.RandomState(10).randn(64, Z.D), jnp.float32)
    layer = HeldExpertsFFN(Z.D, Z.F_e, Z.E, Z.K, scoring="sigmoid",
                           select_bias=True, normalise=True,
                           norm_eps=Z.router_eps, name="experts")
    assert HeldExpertsFFN(Z.D, Z.F_e, Z.E, Z.K).norm_eps == 1e-20
    params = {"experts": {"router": w["router"], "select_bias": w["bias"]}}
    ids, gates = layer.apply({"params": params, "state": {}}, x,
                             method="route")
    with jax.default_matmul_precision("highest"):
        want_ids, want = reference.route(x, w, Z, None)
        plain_ids = jax.lax.top_k(jax.nn.sigmoid(x @ w["router"]), Z.K)[1]
        fault_ids, fault = reference.route(x, w, Z, None, "bias_in_gates")
    assert np.array_equal(np.asarray(ids), np.asarray(want_ids))
    assert np.allclose(np.asarray(gates), np.asarray(want), rtol=1e-6)
    # the bias moves some choices; the fault moves gates and no choice
    assert not np.array_equal(np.asarray(ids), np.asarray(plain_ids))
    assert np.array_equal(np.asarray(fault_ids), np.asarray(want_ids))
    assert float(jnp.abs(fault - want).max()) > 1e-4
    # scores of 1e-7 (logits near -16): the epsilon is most of the sum
    # the gates are divided by, so they add to 0.25 and not to 1
    logits = -16.0 + 0.01 * np.arange(Z.E)
    low = {"experts": {"router": jnp.asarray(np.tile(logits / Z.D, (Z.D, 1)),
                                             jnp.float32),
                       "select_bias": jnp.zeros((Z.E,))}}
    _, gates = layer.apply({"params": low, "state": {}}, jnp.ones((2, Z.D)),
                           method="route")
    top = (1 / (1 + np.exp(-logits)))[-Z.K:].sum()
    assert np.allclose(np.asarray(gates.sum(-1)), top / (top + 1e-6),
                       rtol=1e-3)
    assert float(gates.sum(-1).max()) < 0.5


def test_all_shares_add_up_to_the_uncut_expert_layer():
    """The share test: the parts that four shares of two experts
    compute add up to the reference's whole layer (every expert held)."""
    w = f32(reference.layer_weights(Z, reference.seed32(SEED), 2))
    x = jnp.asarray(np.random.RandomState(11).randn(40, Z.D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, idx = reference._experts(x, w, Z, None)
    total, counts = 0.0, []
    for first in range(0, Z.E, 2):
        layer = HeldExpertsFFN(Z.D, Z.F_e, Z.E, Z.K, (first, 2),
                               scoring="sigmoid", select_bias=True,
                               normalise=True, norm_eps=Z.router_eps,
                               name="experts")
        y, c = layer.apply({"params": {"experts": {
            "router": w["router"], "select_bias": w["bias"],
            **{k: w["e_" + k][first:first + 2]
               for k in ("gate", "up", "down")}}}, "state": {}}, x)
        total = total + y
        counts.append(np.asarray(c["expert_tokens"]))
    assert np.abs(np.asarray(total - whole)).max() < 1e-5
    assert np.array_equal(np.concatenate(counts),
                          np.bincount(np.asarray(idx).ravel(),
                                      minlength=Z.E))


# -- (vi) what a state group does not carry refuses ----------------------------

@pytest.mark.parametrize("kw,what", [
    ({"share_prefix": True}, "share_prefix"),
    ({"kv_dtype": "int8"}, "int8"),
    ({"mesh": "a mesh"}, "mesh="),
    ({"prefill_chunk": None}, "one-shot prefill"),
    ({"speculative": 2}, "speculative"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_engine_refuses_what_a_state_group_does_not_carry(prog, kw, what):
    model, vs = prog
    kw = {"prefill_chunk": CHUNK, **kw}
    with pytest.raises(NotImplementedError, match=what):
        DecodeEngine(model, vs, max_slots=2, block_size=BS,
                     max_blocks_per_seq=16, **kw)


def test_export_and_adopt_refuse_when_called(prog):
    model, vs = prog
    engine = DecodeEngine(model, vs, max_slots=2, block_size=BS,
                          prefill_chunk=CHUNK, max_blocks_per_seq=16)
    engine.admit(0, [1, 2, 3], reserve_len=8)
    with pytest.raises(NotImplementedError, match="export_slot"):
        engine.export_slot(0)
    with pytest.raises(NotImplementedError, match="adopt_slot"):
        engine.adopt_slot(1, [1, 2, 3], 4, None, None)
    with pytest.raises(ValueError, match="state group"):
        PagedKVCache(None, None, None, 9, BS, max_slots=2,
                     max_blocks_per_seq=8, share_prefix=True,
                     groups=model.cache_spec()["groups"])
