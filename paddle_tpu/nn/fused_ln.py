"""Fused LayerNorm + matmul epilogue kernel (ROADMAP item, ISSUE 16).

The transformer block's pre-projection pattern ``Linear(LayerNorm(x))``
costs an extra HBM round trip when XLA materializes the normalized
activations between the two ops; this Pallas kernel computes the row
statistics in VMEM and feeds the normalized tile straight into the MXU
dot — the LN is an *epilogue of the matmul's operand load*, never a
stored tensor. Each ``(block_m, block_n)`` output tile loads its
``(block_m, K)`` x rows once, normalizes in f32 (the ``LayerNorm``
module's exact recipe: f32 mean/var, ``rsqrt(var + eps)``), applies the
optional scale/bias, casts back to the input dtype and runs one
``jnp.dot`` with ``preferred_element_type=jnp.float32`` — matching
:func:`ln_matmul_reference` to f32 roundoff (the kernel body compiles
as ONE fused computation, so its FMA-fused rounding can differ from the
op-at-a-time oracle in the last ulp; K is never split, so the dot's
accumulation order is identical).

The row statistics recompute once per N-tile — the standard epilogue
trade: recomputing a [bm, 1] mean/var in VMEM is cheaper than an HBM
round trip of the [M, K] normalized tensor for every realistic K.

This is the first *autotuned citizen* beyond the flash kernels: with
:mod:`~paddle_tpu.nn.autotune` enabled, ``(block_m, block_n)`` come from
timed trials persisted per ``(shape, dtype, platform)``; disabled, the
``_auto_block`` heuristic answers untimed, and explicit blocks bypass
selection entirely — the same three-tier contract as
``flash_attention``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import autotune, pallas_mode
from .pallas_attention import _auto_block

__all__ = ["fused_ln_matmul", "ln_matmul_reference"]


def ln_matmul_reference(x, w, scale=None, bias=None, eps: float = 1e-6):
    """Unfused oracle: ``LayerNorm(x) @ w`` with the ``LayerNorm``
    module's numerics (f32 statistics, cast back to ``x.dtype`` before
    the dot, f32 accumulation)."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    y = y.astype(x.dtype)
    return jnp.dot(y, w, preferred_element_type=jnp.float32
                   ).astype(x.dtype)


def _ln_matmul_kernel(x_ref, w_ref, *refs, eps, has_scale, has_bias):
    o_ref = refs[-1]
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    i = 0
    if has_scale:
        y = y * refs[i][...].astype(jnp.float32)
        i += 1
    if has_bias:
        y = y + refs[i][...].astype(jnp.float32)
    y = y.astype(x_ref.dtype)
    o_ref[...] = jnp.dot(y, w_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def _ln_candidates(M, N):
    """Candidate tile grid: MXU-friendly blocks dividing M/N, capped at
    6 trials (the flash kernels' budget rule)."""
    ms = [b for b in (256, 128, 64) if M % b == 0]
    ns = [b for b in (512, 256, 128) if N % b == 0]
    if not ms:
        ms = [_auto_block(M, 128)]
    if not ns:
        ns = [_auto_block(N, 512)]
    return [{"block_m": a, "block_n": b} for a in ms for b in ns][:6]


def fused_ln_matmul(x, w, scale=None, bias=None, *, eps: float = 1e-6,
                    block_m: Optional[int] = None,
                    block_n: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """``LayerNorm(x) @ w`` in one Pallas kernel.

    Args:
      x: ``[M, K]`` activations (leading dims: flatten upstream — the
        framework's static-shape packing already does).
      w: ``[K, N]`` projection weight.
      scale, bias: optional ``[K]`` LN affine params (the ``LayerNorm``
        module's ``scale``/``bias``).
      eps: LN epsilon (module default 1e-6).
      block_m, block_n: explicit tile sizes (must divide M/N); None =
        autotuned when the tuner is enabled, else the ``_auto_block``
        heuristic.
      interpret: Pallas interpreter toggle; defaults to True off-TPU
        (same auto-select rule as the flash kernels).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, f"x [{M},{K}] @ w [{K2},{N}]: contraction mismatch"
    if interpret is None:
        interpret = pallas_mode.interpret()
    explicit = block_m is not None or block_n is not None
    bm = _auto_block(M, 128) if block_m is None else min(block_m, M)
    bn = _auto_block(N, 512) if block_n is None else min(block_n, N)
    if not explicit and autotune.is_enabled():
        key = autotune.make_key("ln_matmul", shape=(M, K, N),
                                dtype=x.dtype,
                                extra=(int(scale is not None),
                                       int(bias is not None)))

        def runner(block_m, block_n):
            zx = jnp.zeros((M, K), x.dtype)
            zw = jnp.zeros((K, N), w.dtype)
            zs = jnp.zeros((K,), x.dtype) if scale is not None else None
            zb = jnp.zeros((K,), x.dtype) if bias is not None else None
            return fused_ln_matmul(zx, zw, zs, zb, eps=eps,
                                   block_m=block_m, block_n=block_n,
                                   interpret=interpret)

        cfg = autotune.choose("ln_matmul", key=key,
                              candidates=_ln_candidates(M, N),
                              runner=runner,
                              default={"block_m": bm, "block_n": bn})
        cm, cn = cfg.get("block_m", bm), cfg.get("block_n", bn)
        if M % cm == 0 and N % cn == 0:
            bm, bn = cm, cn
    assert M % bm == 0 and N % bn == 0, \
        f"[{M},{N}] must tile by blocks ({bm}, {bn})"
    in_specs = [
        pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
        pl.BlockSpec((K, bn), lambda i, j: (0, j)),
    ]
    operands = [x, w]
    for p in (scale, bias):
        if p is not None:
            # rank-2 block: TPU tiling rejects rank-1
            in_specs.append(pl.BlockSpec((1, K), lambda i, j: (0, 0)))
            operands.append(p.reshape(1, K))
    return pl.pallas_call(
        functools.partial(_ln_matmul_kernel, eps=eps,
                          has_scale=scale is not None,
                          has_bias=bias is not None),
        grid=(M // bm, N // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(*operands)
