"""Every Pallas kernel on the chip path lowers for the TPU from a CPU host,
and the serving tick compiles for it without moving its KV pools.

``jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))`` runs the
Pallas-to-Mosaic lowering without a TPU, at ``chip_smoke.py``'s shapes and
with ``interpret=False``. It is the cheap guard that keeps a kernel edit
from reaching the chip unlowerable: it catches a block shape the lowering
refuses (a squeezed second-to-last pool dimension did, before the pool
went heads-major) and a Mosaic kernel left to the partitioner
(``MultiHeadAttention``'s flash call did, before it ran per shard). What
it cannot see is Mosaic's own compile; ``chip_smoke.py`` proves that on
the chip.

``test_tick_leaves_the_pools_in_place`` goes one step further for the
decode tick: XLA's TPU compiler (libtpu is installed here) compiles a toy
engine's tick against a device-less ``v5e:2x2`` topology, and the compiled
text and ``memory_analysis()`` are held to the property PR 26 bought: the
layer scan carries the pools and nothing in the program copies one.
"""

import functools
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.core import bfloat16_compute, mesh as mesh_lib, use_policy
from paddle_tpu.models import (LatentMoELM, ShortcutMoEBlock,
                               TransformerLM, WindowMoELM)
from paddle_tpu.nn import pallas_attention
from paddle_tpu.nn import MultiHeadAttention, pallas_mode
from paddle_tpu.nn.moe import ROW_WINDOW, HeldExpertsFFN
from paddle_tpu.nn.pallas_attention import (flash_attention,
                                            latent_paged_decode,
                                            paged_decode_attention,
                                            paged_span_attention)
from paddle_tpu.parallel.sharding import tp_shard_scope
from paddle_tpu.serve import DecodeEngine

# chip_smoke.py's model: transformer_big width, dh = 128
HEADS, DH, T = 8, 128, 2048
SLOTS, BS, MB = 8, 16, T // 16
N = SLOTS * MB + 1


def lower_tpu(fn, *args):
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the lowering"
    return text


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def pool(kind, heads, dh, layers=2):
    if kind == "int8":
        return (sds((layers, N, heads, BS, dh), jnp.int8),
                sds((layers, N, heads, BS), jnp.float32))
    return sds((layers, N, heads, BS, dh), jnp.dtype(kind))


@pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("heads,dh", [(HEADS, DH), (HEADS // 4, DH),
                                      (HEADS, 64), (16, DH)])
def test_paged_kernels_lower(kind, heads, dh):
    """Decode (Q=1), speculative verify (Q=5) and prefill chunk (Q=256)
    over f32 / bf16 / int8 pools; ``heads // 4`` is one tp=4 shard, 16
    heads of 128 the ``serve-1p3b-closed8`` cell's own shape (8 slots of
    128 blocks, float32 there)."""
    pages = pool(kind, heads, dh)
    tables = sds((SLOTS, MB), jnp.int32)
    vec = sds((SLOTS,), jnp.int32)
    layer = sds((), jnp.int32)
    lower_tpu(functools.partial(paged_decode_attention, interpret=False),
              sds((SLOTS, heads, dh), jnp.float32), pages, pages, tables,
              vec, layer)
    for q_len in (5, 256):
        lower_tpu(functools.partial(paged_span_attention, interpret=False),
                  sds((SLOTS, q_len, heads, dh), jnp.float32), pages, pages,
                  tables, vec, vec, layer)


@pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("q_heads,window", [(48, None), (72, 512)])
def test_grouped_paged_kernels_lower(kind, q_heads, window):
    """The same kernels for GROUPED KV heads at the published shape of
    ``serve-laguna118b-closed64``'s two layer kinds: 48 query heads on 8
    KV heads over the whole context, 72 on 8 over a window of 512 (an
    int8 pool takes the grid, with a KV head's scale row)."""
    pages = pool(kind, 8, DH)
    tables = sds((SLOTS, MB), jnp.int32)
    vec = sds((SLOTS,), jnp.int32)
    layer = sds((), jnp.int32)
    lower_tpu(functools.partial(paged_decode_attention, interpret=False,
                                window=window),
              sds((SLOTS, q_heads, DH), jnp.float32), pages, pages, tables,
              vec, layer)
    lower_tpu(functools.partial(paged_span_attention, interpret=False,
                                window=window),
              sds((SLOTS, 5, q_heads, DH), jnp.float32), pages, pages,
              tables, vec, vec, layer)


@pytest.mark.parametrize("heads,layers", [(128, 5), (64, 8)])
@pytest.mark.parametrize("kind", ["float32", "bfloat16"])
def test_latent_decode_kernel_lowers(kind, heads, layers):
    """The latent decode kernel at the published widths: 128 heads (five
    cache layers) or 64 (eight: two a double layer) against pages of 16
    rows of 576 values stored in 640 columns, the first 512 of them the
    values, 16 and 32 pages a group."""
    row, values = 640, 512
    for group in (16, 32):
        lower_tpu(functools.partial(latent_paged_decode, value_width=values,
                                    scale=192 ** -0.5, group=group,
                                    interpret=False),
                  sds((SLOTS, heads, row), jnp.dtype(kind)),
                  sds((layers, N, BS, row), jnp.dtype(kind)),
                  sds((SLOTS, MB), jnp.int32), sds((SLOTS,), jnp.int32),
                  sds((), jnp.int32))


@pytest.mark.parametrize("segmented", [False, True])
@pytest.mark.parametrize("dh", [DH, 64])
def test_flash_forward_and_backward_lower(segmented, dh):
    q = sds((2, HEADS, T, dh), jnp.bfloat16)
    seg = sds((2, T), jnp.int32) if segmented else None

    def loss(q, k, v, seg):
        out = flash_attention(q, k, v, seg, True, None, None, None, False)
        return out.astype(jnp.float32).sum()

    text = lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q, seg)
    # forward, dq and dk/dv: three Mosaic kernels
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("axis", ["data", "model"])
def test_flash_through_attention_layer_lowers_on_four_devices(axis,
                                                              monkeypatch):
    """The flash path as the Trainer (batch over ``data``) and the tp
    engine (heads over ``model``) reach it: on four devices the Mosaic
    kernel must sit inside a ``shard_map``, never under the partitioner."""
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    mesh = mesh_lib.make_mesh({axis: 4}, devices=jax.devices()[:4])
    layer = MultiHeadAttention(num_heads=HEADS, use_flash=True)
    x = jnp.zeros((4, T, HEADS * DH), jnp.float32)
    variables = jax.eval_shape(
        lambda: MultiHeadAttention(num_heads=HEADS).init(
            jax.random.PRNGKey(0), x[:, :8]))

    def loss(variables, x):
        if axis == "data":
            with mesh_lib.use_mesh(mesh):
                out = layer.apply(variables, x, causal=True)
        else:
            with tp_shard_scope(mesh, axis):
                out = layer.apply(variables, x, causal=True)
        return out.sum()

    spec = P("data") if axis == "data" else P()
    xs = jax.ShapeDtypeStruct(x.shape, x.dtype,
                              sharding=NamedSharding(mesh, spec))
    text = lower_tpu(jax.grad(loss), variables, xs)
    assert text.count("tpu_custom_call") >= 3


# ---------------------------------------------------------------------------
# the compiled tick: the pools stay where they are
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    """One device of a described, not attached, v5e host. The topology is
    asked for only here, never at import: one process at a time may load
    libtpu, and every xdist worker imports this file."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("heads,dh", [(16, DH), (HEADS // 4, DH),
                                      (HEADS, 64)])
def test_paged_decode_compiles(one_chip, kind, heads, dh):
    """Mosaic's own compile of the decode kernel, which the lowering
    above does not run: the kernel copies whole pages out of a pool in
    HBM, and Mosaic refuses such a copy where the page is narrower than
    its 128 lanes (head size 64, an int8 pool's scale pages): those
    pools take the grid. One Mosaic kernel named ``paged_decode`` and no
    temporary the size of a layer's pool either way, head size 64 apart:
    XLA re-lays a pool of half-filled lanes for any Mosaic kernel."""
    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    pages = jax.tree_util.tree_map(on_chip, pool(kind, heads, dh))
    compiled = jax.jit(
        functools.partial(paged_decode_attention, interpret=False)).lower(
            on_chip(sds((SLOTS, heads, dh), jnp.float32)), pages, pages,
            on_chip(sds((SLOTS, MB), jnp.int32)),
            on_chip(sds((SLOTS,), jnp.int32)),
            on_chip(sds((), jnp.int32))).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and "paged_decode" in calls[0], calls
    if dh % 128 == 0:
        layer_bytes = N * heads * BS * dh * jnp.dtype(kind).itemsize
        assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


@pytest.mark.parametrize("kind", ["float32", "bfloat16"])
@pytest.mark.parametrize("q_heads,window,slots,blocks", [
    (48, None, 64, 896), (72, 512, 64, 896), (16, 128, SLOTS, MB)])
def test_grouped_paged_decode_compiles(one_chip, kind, q_heads, window,
                                       slots, blocks):
    """Mosaic's own compile of the GROUPED decode kernel at
    ``serve-laguna118b-closed64``'s shapes (64 slots of 896 pages; 48
    query heads on 8 KV heads over everything, 72 on 8 over a window of
    512, whose 33 pages walk as 3 groups of 11) and of one row a KV head
    under a window: one Mosaic kernel named ``paged_decode`` (the name the
    benchmark's readers look for) and no temporary the size of a layer's
    pool."""
    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    kv_heads = 8 if q_heads > 16 else 16
    pages = on_chip(sds((2, N, kv_heads, BS, DH), jnp.dtype(kind)))
    compiled = jax.jit(functools.partial(
        paged_decode_attention, interpret=False, window=window)).lower(
            on_chip(sds((slots, q_heads, DH), jnp.float32)), pages, pages,
            on_chip(sds((slots, blocks), jnp.int32)),
            on_chip(sds((slots,), jnp.int32)),
            on_chip(sds((), jnp.int32))).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and "paged_decode" in calls[0], calls
    layer_bytes = N * kv_heads * BS * DH * jnp.dtype(kind).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


# an instruction: its name, whether its result is a tuple, the (first)
# result shape; then the operation and its first operand
_RESULT = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(?)([a-z]+[0-9]*\[[0-9,]*\])")
_OPERATION = re.compile(r" ([a-z][a-z\-]*)\(%?([\w.\-]*)")
# what may have a pool-sized result: the carry's plumbing (``tuple`` and
# ``while`` results are tuples and never match) and the in-place row write
_MAY_HOLD_A_POOL = {"parameter", "get-tuple-element", "bitcast",
                    "dynamic-update-slice"}


def results(text):
    """``(operation, shape, dims)`` of every instruction of the compiled
    module whose result is one array, fused computations' insides left
    out. What the compiler prefetches into the chip's fast memory
    (``S(1)`` in a layout: a toy pool or weight fits there, a
    deployment's does not) is left out, with the ``copy-done`` that
    brings it back."""
    fused, prefetches = False, set()
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = "fused_computation" in line.split("(")[0]
        m = None if fused else _RESULT.match(line)
        if not m:
            continue
        name, is_tuple, shape = m.groups()
        op, operand = _OPERATION.search(line, m.end()).groups()
        if "S(1)" in line.split(f" {op}(")[0]:
            prefetches.add(name)
            continue
        dims = tuple(int(d) for d in
                     re.findall(r"[0-9]+", shape.split("[")[1]))
        if not is_tuple and not (op == "copy-done"
                                 and operand in prefetches):
            yield op, shape, dims


def pool_sized_results(text, sizes):
    """``[(operation, shape)]`` of the :func:`results` with as many
    elements as a pool or as one layer of one."""
    return [(op, shape) for op, shape, dims in results(text)
            if int(np.prod(dims)) in sizes]


def fusions_updating_in_place(text):
    """The result shapes of the compiled module's fusions that ARE an
    in-place write: the fused computation's root is a
    ``dynamic-update-slice`` of the computation's first parameter, and
    the result has that parameter's shape (XLA aliases the two: the
    update's rows are all that is written). A shape is in the set only if
    EVERY fusion with that result shape is such a write."""
    roots, body = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            body = line.split()[0].lstrip("%")
        elif body and "ROOT " in line:
            m = re.search(r" dynamic-update-slice\(%?(param_0[\w.\-]*)", line)
            roots[body] = bool(m)
    verdicts = {}
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m or " fusion(" not in line:
            continue
        called = re.search(r"calls=%?([\w.\-]+)", line).group(1)
        shape = m.group(3)
        verdicts[shape] = verdicts.get(shape, True) and roots.get(called,
                                                                  False)
    return {shape for shape, ok in verdicts.items() if ok}


def toy_engine(blocks, **kw):
    """A toy ``TransformerLM`` engine at the lane tile's widths: two
    layers of 4 heads of 128, float32 weights, the one-shot prefill."""
    model = TransformerLM(vocab=512, dim=512, num_layers=2, num_heads=4,
                          ffn_hidden=1024, max_len=256)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    return DecodeEngine(model, variables, max_slots=4, block_size=BS,
                        num_blocks=blocks, attention="paged", **kw)


def toy_latent_engine(blocks, shortcut=False, **kw):
    """A toy of the latent-attention expert model at the lane tile's
    widths: two layers (one dense, one of 8 experts with 4 held), a
    latent row of 128 + 64 values stored in 256 columns, bfloat16
    weights and pool, a chunked prefill. ``shortcut``: ONE
    shortcut-connected double layer in their place (two cache layers
    too), a softmax router with a bias and 4 identity experts, both
    latent factors."""
    sizes = dict(vocab=512, dim=256, num_heads=4, q_rank=128, kv_rank=128,
                 nope_dim=128, rope_dim=64, v_dim=128, dense_hidden=512,
                 expert_hidden=256, num_experts=8, top_k=2,
                 experts_held=(2, 4), max_len=256)
    if shortcut:
        model = LatentMoELM(num_layers=1, num_dense_layers=0, num_shared=0,
                            block=ShortcutMoEBlock, scoring="softmax",
                            select_bias=True, num_zero_experts=4,
                            q_scale=2 ** 0.5, kv_scale=2 ** 0.5, **sizes)
    else:
        model = LatentMoELM(num_layers=2, num_dense_layers=1, **sizes)
    variables = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    return DecodeEngine(model, variables, max_slots=4, block_size=BS,
                        num_blocks=blocks, attention="paged",
                        prefill_chunk=32, dtype="bfloat16", **kw)


# pools of 160 MB: one that fits the chip's 128 MiB of fast memory is
# prefetched there whole, and its writes with it
@pytest.mark.parametrize("kv_dtype,blocks,speculative",
                         [(None, 2449, 0), ("int8", 9793, 0),
                          (None, 2449, 4), ("latent", 19593, 0),
                          ("shortcut", 19593, 0)],
                         ids=["float32", "int8", "float32-speculative4",
                              "latent-bfloat16", "shortcut-bfloat16"])
def test_tick_leaves_the_pools_in_place(one_chip, monkeypatch, kv_dtype,
                                        blocks, speculative):
    """The decode tick (and speculation's verify tick) of a toy engine,
    compiled for the TPU: (a) nothing but the carry's plumbing and the
    in-place ``dynamic-update-slice`` row writes has a result the size
    of a pool or of one layer's pool, (b) the program's temporaries are
    smaller than one pool. The tick that scanned the pools as ``xs`` /
    ``ys`` sliced every layer out, copied it to the scatter's layout and
    back, collected it, and copied both pools whole at the end. The
    same holds for whatever pools a model declares: the latent case is a
    ``LatentMoELM``'s one pool of latent rows, written by its unrolled
    layers and read by ``latent_paged_decode``, the shortcut case one
    double layer's two cache layers."""
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    layers = 2
    if kv_dtype in ("latent", "shortcut"):
        engine = toy_latent_engine(blocks, shortcut=kv_dtype == "shortcut")
    else:
        engine = toy_engine(blocks, kv_dtype=kv_dtype,
                            speculative=speculative)
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        engine._tick_args())
    compiled = engine._tick_fn.lower(*args).compile()
    if kv_dtype in ("latent", "shortcut"):
        assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()

    # a quantized pool's values; its scale pages, a thirty-second of its
    # bytes, XLA re-lays once a tick at the program's entry
    leaves = jax.tree_util.tree_leaves(
        next(iter(engine.cache.pools.values())))
    values = leaves[0].size
    held = pool_sized_results(compiled.as_text(),
                              {values, values // layers})
    assert held, "the pools are not in the compiled text"
    moved = [h for h in held if h[0] not in _MAY_HOLD_A_POOL]
    assert not moved, f"pool-sized results besides the row writes: {moved}"
    writes = sum(op == "dynamic-update-slice" for op, _ in held)
    assert writes > 0, "no in-place row write in the compiled tick"
    pool_bytes = sum(leaf.nbytes for leaf in leaves)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes, \
        f"temporaries {temp} B, one pool {pool_bytes} B"


@pytest.mark.parametrize("kind,blocks", [("float32", 2449),
                                         ("bfloat16", 4897),
                                         ("int8", 9793)])
def test_one_shot_prefill_leaves_the_pools_in_place(one_chip, monkeypatch,
                                                    kind, blocks):
    """The one-shot prefill (``prefill_chunk=None``, prefix sharing on)
    of a toy engine, compiled for the TPU. A plain pool, float32 or
    bfloat16: nothing but the carry's plumbing and the in-place
    ``dynamic-update-slice`` row and page writes has a result the size of
    a pool or of one layer of one, at least one such write exists, both
    pools are aliased to their results and the temporaries are smaller
    than one pool: the scatter under ``vmap`` copied both pools whole to
    its layout and back (3.43 GB of temporaries at
    ``serve-1p3b-closed8``'s shapes). An int8 pool KEEPS that scatter (no
    cell runs one; ``DESIGN_DECISIONS.md`` PR-36): the write adapts to the
    pool's type, and its program still compiles and still scatters."""
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    layers, quantized = 2, kind == "int8"
    engine = toy_engine(blocks, share_prefix=True, **(
        {"kv_dtype": "int8"} if quantized else {"dtype": kind}))
    assert engine.prefill_chunk is None
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        engine._prefill_args())
    compiled = engine._prefill_fn.lower(*args).compile()
    text = compiled.as_text()

    leaves = jax.tree_util.tree_leaves(engine.cache.pools["k"])
    assert leaves[0].dtype == jnp.dtype(kind)
    values = leaves[0].size
    held = pool_sized_results(text, {values, values // layers})
    assert held, "the pools are not in the compiled text"
    if quantized:
        assert " scatter(" in text, "an int8 pool no longer scatters"
        return
    assert " scatter(" not in text
    # a page write fuses with its page's select into ONE in-place update:
    # a fusion whose root is the dynamic-update-slice of its first operand
    in_place = fusions_updating_in_place(text)
    moved = [h for h in held if h[0] not in _MAY_HOLD_A_POOL
             and not (h[0] == "fusion" and h[1] in in_place)]
    assert not moved, f"pool-sized results besides the writes: {moved}"
    writes = sum(op in ("fusion", "dynamic-update-slice") for op, _ in held)
    assert writes >= 2 * 2, "no in-place page write in the compiled prefill"
    pool_bytes = sum(leaf.nbytes for leaf in leaves)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 2 * pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes, \
        f"temporaries {memory.temp_size_in_bytes} B, one pool {pool_bytes} B"


def toy_window_engine(blocks):
    """A toy of the two-kinds-of-attention expert model at the lane
    tile's widths: ``[full, sliding, sliding]`` layers of 4 and 6 query
    heads on 2 KV heads of 128, a window of 64, one dense layer and two
    of 8 experts with 4 held, bfloat16 weights and pools, a chunked
    prefill."""
    model = WindowMoELM(
        vocab=512, dim=256, layer_windows=[None, 64, 64],
        layer_heads=[4, 6, 6], num_kv_heads=2, head_dim=128,
        rotary={"full": dict(rope_base=5e5, rope_dim=64, yarn=dict(
            factor=8.0, original_len=64, attention_factor=1.2)),
            "window": dict(rope_base=1e4)},
        dense_hidden=512, expert_hidden=256, shared_hidden=256,
        num_experts=8, top_k=2, experts_held=(2, 4), routed_scaling=2.5,
        max_len=1024)
    variables = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    return DecodeEngine(model, variables, max_slots=4, block_size=BS,
                        num_blocks=blocks, attention="paged",
                        max_blocks_per_seq=64, prefill_chunk=32,
                        dtype="bfloat16")


def test_window_groups_programs_leave_every_pool_in_place(one_chip,
                                                          monkeypatch):
    """The tick AND the prefill chunk of a toy engine with two pool
    groups, compiled for the TPU: every pool of both groups is an
    argument aliased to a result (written in place: the chunk's page
    writes too), nothing but the carry's plumbing and the in-place writes
    has a result the size of a pool or of one layer of one, and the
    temporaries are smaller than one pool that grows. The window group's
    pools are ``slots * ring + 1`` blocks whatever ``num_blocks`` is."""
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    engine = toy_window_engine(blocks=40001)
    pools = engine.cache.pools
    ring = engine.cache.groups["window"].ring
    assert ring == 64 // BS + 1
    assert pools["window/k"].shape == (2, 4 * ring + 1, 2, BS, 128)
    assert pools["full/k"].shape == (1, 40001, 2, BS, 128)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    sizes = {p.size // div for p in pools.values()
             for div in (1, p.shape[0])}
    pool_bytes = sum(p.nbytes for p in pools.values())
    for name, fn, args in (
            ("tick", engine._tick_fn, engine._tick_args()),
            ("prefill", engine._prefill_fn, engine._prefill_args())):
        compiled = fn.lower(*on_chip(args)).compile()
        text = compiled.as_text()
        assert 'custom_call_target="tpu_custom_call"' in text or \
            name == "prefill"
        held = pool_sized_results(text, sizes)
        moved = [h for h in held if h[0] not in _MAY_HOLD_A_POOL]
        assert not moved, f"{name}: pool-sized results: {moved}"
        # the chunk's page writes of the four pools fuse into in-place
        # updates with several results; the tick's row writes stand alone
        assert " dynamic-update-slice(" in text, name
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes == pool_bytes, name
        assert memory.temp_size_in_bytes < pools["full/k"].nbytes, name


@pytest.mark.parametrize("kind", ["transformer", "latent"])
def test_models_without_groups_keep_their_pools_and_their_kernel(
        kind, monkeypatch):
    """A model that declares no pool groups gets the pools, the tables and
    the decode kernel it got before groups existed: one pool a name with
    the one leading layer axis, one table an array, prefix sharing on by
    default, and (``TransformerLM``: as many KV heads as query heads, no
    window) the one-row ``paged_decode`` kernel, never the grouped one."""
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)

    def refuse(*a, **k):
        raise AssertionError("the grouped kernel on an ungrouped model")
    monkeypatch.setattr(pallas_attention, "_grouped_decode_call", refuse)
    if kind == "latent":
        engine = toy_latent_engine(blocks=257)
        want = {"latent": (2, 257, BS, 256)}
    else:
        model = TransformerLM(vocab=512, dim=512, num_layers=2, num_heads=4,
                              ffn_hidden=1024, max_len=256)
        engine = DecodeEngine(
            model, model.init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32)),
            max_slots=4, block_size=BS, num_blocks=257, attention="paged")
        want = {"k": (2, 257, 4, BS, 128), "v": (2, 257, 4, BS, 128)}
    cache = engine.cache
    assert {n: p.shape for n, p in cache.pools.items()} == want
    assert engine.pool_names == tuple(want) and cache.groups == {}
    assert cache.share_prefix is True and cache.group_facts() == {}
    tables, lengths = cache.device_tables()
    assert tables.shape == (4, 16) and lengths.shape == (4,)
    assert cache.slot_tables(1).shape == (1, 16)
    text = jax.jit(engine._tick_fn.__wrapped__).trace(
        *engine._tick_args()).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


# what may have a result the shape of a stacked block leaf: the program's
# arguments on their way into the layer scan
_MAY_HOLD_THE_STACK = {"parameter", "get-tuple-element", "bitcast"}


@pytest.mark.parametrize("kw", [{}, {"speculative": 4},
                                {"prefill_chunk": 32}],
                         ids=["plain", "speculative4", "chunk32"])
def test_programs_take_the_weights_as_prepared(one_chip, monkeypatch, kw):
    """Both programs of a toy engine built under ``bfloat16_compute``,
    compiled for the TPU: (a) no instruction but the arguments' plumbing
    has a result in the shape of a stacked block leaf (``[L, D, F]``,
    ``[L, F, D]``, ``[L, D, D]``), (b) the temporaries are smaller than
    one stacked MLP matrix. Engine build stacks the blocks and casts the
    products' operands once (``TransformerLM.serving_variables``); the
    programs that did it themselves, every call, held the whole stack
    as temporaries: a ``concatenate`` or an update loop a leaf, and a
    ``convert`` where a leaf was left out of the cast."""
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    L, heads, dh, F = 24, 4, 128, 2048
    D = heads * dh
    model = TransformerLM(vocab=512, dim=D, num_layers=L, num_heads=heads,
                          ffn_hidden=F, max_len=128)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    with use_policy(bfloat16_compute):
        engine = DecodeEngine(model, variables, max_slots=4, block_size=BS,
                              attention="paged", **kw)
        stack = engine.variables["params"]["transformer_lm"]["blocks"]
        assert stack["ffn1"]["w"].shape == (L, D, F)
        assert stack["ffn1"]["w"].dtype == jnp.bfloat16
        on_chip = lambda tree: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)
        programs = {
            "tick": engine._tick_fn.lower(*on_chip(engine._tick_args())),
            "prefill": engine._prefill_fn.lower(
                *on_chip(engine._prefill_args()))}
    for name, lowered in programs.items():
        compiled = lowered.compile()
        shaped = [(op, shape) for op, shape, dims in
                  results(compiled.as_text())
                  if dims in {(L, D, F), (L, F, D), (L, D, D)}]
        assert shaped, f"{name}: the stack is not in the compiled text"
        made = [r for r in shaped if r[0] not in _MAY_HOLD_THE_STACK]
        assert not made, f"{name} makes stack-shaped results: {made}"
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < stack["ffn1"]["w"].nbytes, \
            f"{name}: temporaries {temp} B, one stacked MLP matrix " \
            f"{stack['ffn1']['w'].nbytes} B"


def test_grouped_product_is_handed_a_window_of_rows(one_chip):
    """``HeldExpertsFFN`` at ``serve-pangu718b-closed64``'s tick shape
    (64 tokens, 8 of 256 experts each, 16 held, 7,680 x 2,048, bfloat16)
    compiled for the TPU: each of the three grouped products is XLA's
    ``ragged-dot-none`` custom call (the name the benchmark's
    ``moe_ffn_roofline_pct`` reads) over ``ROW_WINDOW`` rows, inside the
    loop over the kept pairs' windows, and none over the tick's 512
    sorted pairs: the compiler multiplies a whole tile of the rows it is
    handed for every expert with a row in it."""
    tokens, K, E, held, D, F = 64, 8, 256, 16, 7680, 2048
    layer = HeldExpertsFFN(D, F, E, K, (0, held), name="experts")

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"experts": {
        "router": on_chip((D, E)), "gate": on_chip((held, D, F)),
        "up": on_chip((held, D, F)), "down": on_chip((held, F, D))}}

    def share(params, x):
        with use_policy(bfloat16_compute):
            return layer.apply({"params": params, "state": {}}, x)

    text = jax.jit(share).lower(
        params, on_chip((tokens, D), jnp.float32)).compile().as_text()
    rows = [int(m.group(1)) for m in re.finditer(
        r"%ragged-dot-none[.\d]* = [a-z0-9]+\[(\d+),\d+\]", text)]
    assert len(rows) == 3 and set(rows) == {ROW_WINDOW}, rows
    assert ROW_WINDOW < tokens * K
    assert re.search(r" while\(", text), "no loop over the windows"
