"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

Long sequences are sharded along time; each device holds a [B, T/n, H, D]
slice of q/k/v. Attention against the full sequence is computed blockwise:
devices rotate their k/v shards around the ring with ``lax.ppermute`` (ICI
neighbor exchanges, overlapped with the block matmuls by XLA's async
collectives) while accumulating a streaming softmax (flash-attention style
log-sum-exp running max/sum), so the full [T, T] score matrix never
materializes and memory stays O(T/n * T/n) per step.

The 2017 reference has no sequence parallelism (SURVEY.md §5 records its
absence); this is the forward-looking capability row. Design follows the
public blockwise/ring-attention recipe (psum-free: only neighbor ppermute).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention", "make_ring_attention"]

_NEG = -1e30  # finite "-inf": keeps fully-masked rows NaN-free


def ring_attention(q, k, v, segments=None, *, axis_name: str, axis_size: int,
                   causal: bool = False, scale: Optional[float] = None):
    """Blockwise ring attention — call INSIDE shard_map.

    q, k, v: local shards [B, Tlocal, H, D], time sharded over ``axis_name``
    (axis static size ``axis_size``). ``segments``: optional local [B,
    Tlocal] packed-sequence ids (``core.sequence`` convention: 1-based,
    0 = padding); the k-side ids rotate around the ring with their k/v
    shard, confining attention within each packed sub-sequence. Returns the
    local output shard [B, Tlocal, H, D]. Softmax statistics accumulate in
    float32. Rows with no visible key (padding) return an unspecified
    finite value — mask downstream.
    """
    n = axis_size
    idx = lax.axis_index(axis_name)
    b, tl, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32) * scale
    q_pos = idx * tl + jnp.arange(tl)                       # global positions

    # Derive the accumulators from q (zeroed) so they carry q's exact
    # device-varying axes — plain constants would trip shard_map's
    # varying-axes check on the scan carry (constants are "unvarying", the
    # updated accumulators vary over the ring axis and any batch axes).
    zero_rows = jnp.swapaxes(jnp.sum(qf, axis=-1) * 0.0, 1, 2)  # [B, H, Tl]
    acc0 = qf * 0.0                                             # [B, Tl, H, D]
    m0 = zero_rows + _NEG                                       # running max
    l0 = zero_rows                                              # running sum
    perm = [(j, (j + 1) % n) for j in range(n)]
    carry0 = (k, v, acc0, m0, l0)
    if segments is not None:
        carry0 = carry0 + (segments,)

    def step(carry, i):
        if segments is not None:
            kb, vb, acc, m, l, seg_kb = carry
        else:
            kb, vb, acc, m, l = carry
        src = (idx - i) % n                 # ring owner of the block we hold
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32))
        if causal:
            k_pos = src * tl + jnp.arange(tl)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, _NEG)
        if segments is not None:
            sm = (segments[:, :, None] == seg_kb[:, None, :]) \
                & (segments[:, :, None] > 0) & (seg_kb[:, None, :] > 0)
            s = jnp.where(sm[:, None], s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = (acc * jnp.swapaxes(corr, 1, 2)[..., None]
                   + jnp.einsum("bhqk,bkhd->bqhd", p,
                                vb.astype(jnp.float32)))
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        out = (kb, vb, acc_new, m_new, l_new)
        if segments is not None:
            out = out + (lax.ppermute(seg_kb, axis_name, perm),)
        return out, None

    carry, _ = lax.scan(step, carry0, jnp.arange(n))
    acc, m, l = carry[2], carry[3], carry[4]
    out = acc / jnp.maximum(jnp.swapaxes(l, 1, 2)[..., None], 1e-30)
    return out.astype(q.dtype)


def wrap_seq_parallel(attn_fn, mesh: Mesh, seq_axis: str,
                      batch_axis: Optional[str], causal: bool,
                      with_segments: bool = False):
    """Shared shard_map wrapper for sequence-parallel attention kernels
    (ring and Ulysses expose the same surface): takes GLOBAL [B, T, H, D]
    arrays (time sharded over ``seq_axis``, optionally batch over
    ``batch_axis``) and returns the global output. With
    ``with_segments=True`` the wrapped fn takes a fourth global [B, T]
    packed-sequence id argument (sharded over time like q/k/v)."""
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[seq_axis]
    spec = P(batch_axis, seq_axis, None, None)
    seg_spec = P(batch_axis, seq_axis)
    fn = functools.partial(attn_fn, axis_name=seq_axis, axis_size=n,
                           causal=causal)
    in_specs = (spec, spec, spec) + ((seg_spec,) if with_segments else ())
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=spec)


def make_ring_attention(mesh: Mesh, seq_axis: str = "seq",
                        batch_axis: Optional[str] = None,
                        causal: bool = False, with_segments: bool = False):
    """:func:`ring_attention` over global arrays (see
    :func:`wrap_seq_parallel`)."""
    return wrap_seq_parallel(ring_attention, mesh, seq_axis, batch_axis,
                             causal, with_segments)
