"""1x1 convolution as matmul + Pallas dW — the ResNet bottleneck hot path.

PERF.md (round 3) traced the ResNet-50 residual to XLA's conv kernels: the
dW convs for [1,1,Cin,Cout] kernels reduce a ~10^5-element contraction into
a tiny output and run at ~13% MXU efficiency; dx convs output-fused with
BN-backward reductions run at 5-11%. A 1x1 stride-1 conv IS a matmul
(``[B*H*W, Cin] @ [Cin, Cout]``), so this module provides:

- :func:`conv1x1` — the matmul form with a ``jax.custom_vjp``: forward and
  dx go through XLA's *matmul* path (tiled very differently from its conv
  path), and dW runs a dedicated Pallas reduction-matmul kernel that
  streams M-chunks of x/dy through VMEM and accumulates the [Cin, Cout]
  tile in f32 across the sequential TPU grid.
- :func:`conv1x1_strided` — the stride-s variant (the bottleneck shortcut):
  slice then matmul; the slice VJP is a scatter XLA handles well.

``nn.layers.Conv2D`` routes 1x1 convs here when ``set_conv1x1_impl``
selects it.

Reference lineage: the reference's 1x1 convs run as cuDNN GEMMs
(``gserver/layers/ExpandConvLayer.cpp`` im2col+GEMM path) — the GEMM view
is the original form; the TPU twist is owning the dW tiling.

Compiled on a TPU, interpreted elsewhere: :func:`pallas_mode.interpret`
decides, as for every kernel here.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode

__all__ = ["conv1x1", "conv1x1_strided", "dw_pallas"]


def _dw_kernel(x_ref, dy_ref, out_ref):
    """One M-chunk's contribution: out += x_chunk^T @ dy_chunk (f32)."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot_general(
        x_ref[...], dy_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _chunk_rows(m: int, cap: int = 2048) -> int:
    """Largest divisor of m that is a multiple of 16 (bf16 sublane tile)
    and <= cap; falls back to m itself (single chunk)."""
    best = m
    for mc in range(min(cap, m), 15, -16):
        if m % mc == 0 and mc % 16 == 0:
            best = mc
            break
    return best


@functools.partial(jax.jit, static_argnames=("interpret",))
def dw_pallas(x2d, dy2d, interpret: Optional[bool] = None):
    """dW = x2d^T @ dy2d with f32 accumulation. x2d [M, Cin], dy2d
    [M, Cout] -> [Cin, Cout] f32. Grid streams M-chunks; the output tile is
    revisited every step (sequential TPU grid) and accumulated in place."""
    m, cin = x2d.shape
    cout = dy2d.shape[1]
    mc = _chunk_rows(m)
    interp = pallas_mode.interpret() if interpret is None else interpret
    return pl.pallas_call(
        _dw_kernel,
        grid=(m // mc,),
        in_specs=[
            pl.BlockSpec((mc, cin), lambda i: (i, 0)),
            pl.BlockSpec((mc, cout), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((cin, cout), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((cin, cout), jnp.float32),
        interpret=interp,
    )(x2d, dy2d)


@jax.custom_vjp
def conv1x1(x, w):
    """y[b,h,w,:] = x[b,h,w,:] @ w. x [B,H,W,Cin], w [Cin,Cout]."""
    b, h, ww, cin = x.shape
    y = x.reshape(b * h * ww, cin) @ w
    return y.reshape(b, h, ww, w.shape[1])


def _conv1x1_fwd(x, w):
    return conv1x1(x, w), (x, w)


def _conv1x1_bwd(res, dy):
    x, w = res
    b, h, ww, cin = x.shape
    cout = w.shape[1]
    dy2 = dy.reshape(b * h * ww, cout)
    dx = (dy2 @ w.T).reshape(x.shape)
    dw = dw_pallas(x.reshape(b * h * ww, cin), dy2).astype(w.dtype)
    return dx, dw


conv1x1.defvjp(_conv1x1_fwd, _conv1x1_bwd)


def conv1x1_strided(x, w, stride=(1, 1)):
    """Stride-s 1x1 conv (the bottleneck/shortcut downsample): slicing
    commutes with a pointwise conv, and the slice VJP (zero-scatter) is
    cheap — so the strided case reuses the dense-matmul kernel."""
    sh, sw = stride
    if (sh, sw) != (1, 1):
        x = x[:, ::sh, ::sw, :]
    return conv1x1(x, w)
