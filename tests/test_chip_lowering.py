"""Every Pallas kernel on the chip path lowers for the TPU from a CPU host.

``jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))`` runs the
Pallas-to-Mosaic lowering without a TPU, at ``chip_smoke.py``'s shapes and
with ``interpret=False``. It is the cheap guard that keeps a kernel edit
from reaching the chip unlowerable: it catches a block shape the lowering
refuses (a squeezed second-to-last pool dimension did, before the pool
went heads-major) and a Mosaic kernel left to the partitioner
(``MultiHeadAttention``'s flash call did, before it ran per shard). What
it cannot see is Mosaic's own compile; ``chip_smoke.py`` proves that on
the chip.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.core import mesh as mesh_lib
from paddle_tpu.nn import MultiHeadAttention, pallas_mode
from paddle_tpu.nn.pallas_attention import (flash_attention,
                                            paged_decode_attention,
                                            paged_span_attention)
from paddle_tpu.parallel.sharding import tp_shard_scope

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


def pool(kind, heads, dh):
    if kind == "int8":
        return (sds((N, heads, BS, dh), jnp.int8),
                sds((N, heads, BS), jnp.float32))
    return sds((N, heads, BS, dh), jnp.dtype(kind))


@pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("heads,dh", [(HEADS, DH), (HEADS // 4, DH),
                                      (HEADS, 64)])
def test_paged_kernels_lower(kind, heads, dh):
    """Decode (Q=1), speculative verify (Q=5) and prefill chunk (Q=256)
    over f32 / bf16 / int8 pools; ``heads // 4`` is one tp=4 shard."""
    pages = pool(kind, heads, dh)
    tables = sds((SLOTS, MB), jnp.int32)
    vec = sds((SLOTS,), jnp.int32)
    lower_tpu(functools.partial(paged_decode_attention, interpret=False),
              sds((SLOTS, heads, dh), jnp.float32), pages, pages, tables,
              vec)
    for q_len in (5, 256):
        lower_tpu(functools.partial(paged_span_attention, interpret=False),
                  sds((SLOTS, q_len, heads, dh), jnp.float32), pages, pages,
                  tables, vec, vec)


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
