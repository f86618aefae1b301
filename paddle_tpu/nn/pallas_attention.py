"""Fused blockwise (flash-style) attention in Pallas — the long-context hot
op where XLA's generic fusion loses: materialising the [T, T] score matrix in
HBM is O(T^2) bandwidth, while these kernels stream K/V blocks through VMEM
with an online softmax, keeping HBM traffic linear in T.

Reference-lineage note: the 2017 reference has no attention kernel at all
(SURVEY §5 long-context row — this is one of the deliberate "exceeds" items);
its closest machinery is the RNN-era ``ContextProjection``, and its
variable-length contract is ``Argument::sequenceStartPositions``
(``paddle/parameter/Argument.h:84-93``) — never-padded ragged batches. The
TPU-native successor of that contract is packing + segment ids
(``core/sequence.py``), and these kernels consume it natively: pass
``segments`` ([B, T] int32, 1-based, 0 = padding, the ``pack_sequences``
layout) and attention is confined within each packed sub-sequence. Blocks
whose segment-id ranges cannot intersect are skipped with ``pl.when``
(FLOPs and VPU work skipped; the DMA still runs since index maps cannot
depend on data), and intersecting blocks mask per-element. The algorithm is
the public flash-attention recipe; the kernels follow the Pallas TPU
playbook (`/opt/skills/guides/pallas_guide.md`).

Structure: 3-D grids ``(batch*heads, row blocks, streamed blocks)`` with the
online-softmax state carried in VMEM scratch across the innermost grid axis
(sequential on TPU) — so VMEM holds only one q/k/v BLOCK at a time and the
kernels scale to arbitrary T (a full-K/V-resident design caps out around
T=8k on a 16 MB-VMEM chip). Causal upper-triangle blocks are skipped with
``pl.when`` (no FLOPs; the grid step still retires).

Training is fully blockwise: the forward saves only O and the per-row
log-sum-exp L; the backward runs two Pallas kernels (dq over query blocks;
dk/dv over key blocks) that rebuild each probability tile as
``exp(s - L)`` — nothing [T, T]-shaped ever exists in HBM, forward or
backward.

Rows with no visible key (segment id 0 = padding) produce an unspecified
finite output (uniform average of the streamed v blocks) — identical to the
convention of other public TPU flash kernels; mask padding rows downstream.

Every ``pallas_call`` carries a stable ``name`` (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``, ``paged_decode``, ``paged_span``,
``latent_decode``; the last is not its wrapper's name, so that a trace's
events of the wrapper's own small operations do not read as the kernel's):
it names the Mosaic custom call in compiled HLO text and in a device
trace, which is how ``chip_smoke.py`` proves the kernels were compiled.

``interpret=None`` asks :func:`pallas_mode.interpret`: the Pallas
interpreter off-TPU, so the same tests run on the CPU harness; Mosaic on a
TPU, where a kernel that cannot compile raises.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune, pallas_mode

__all__ = ["flash_attention", "reference_attention",
           "paged_decode_attention", "paged_reference_attention",
           "lane_packed_decode_attention",
           "paged_span_attention", "paged_span_reference_attention",
           "latent_paged_decode", "latent_paged_reference"]

_NEG = -1e30


def reference_attention(q, k, v, causal: bool = False,
                        scale: Optional[float] = None, segments=None):
    """Plain softmax attention — the numeric oracle. [B, H, T, D] inputs;
    ``segments`` [B, T] confines attention within equal non-zero ids."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    T = q.shape[2]
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    if segments is not None:
        seg = (segments[:, :, None] == segments[:, None, :]) \
            & (segments[:, :, None] > 0) & (segments[:, None, :] > 0)
        s = jnp.where(seg[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if segments is not None:
        p = jnp.where(jnp.isnan(p), 0.0, p)     # fully-masked padding rows
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _causal_mask(qi, bq, kb, bk):
    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_idx = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return k_idx <= q_idx


def _block_needed(qi, bq, ki, bk, causal):
    """Whether key block ki intersects the causal cone of query block qi."""
    if not causal:
        return True
    return ki * bk <= (qi + 1) * bq - 1


def _seg_block_mask(sq, sk):
    """[bq,1], [bk,1] id blocks -> [bq, bk] visibility mask (0 = padding)."""
    return (sq == sk.reshape(1, -1)) & (sq > 0) & (sk.reshape(1, -1) > 0)


def _seg_block_relevant(sq, sk):
    """Sound skip test: packed ids in the two blocks can only match if
    their value ranges intersect (exact for any id layout) and neither
    block is all-padding."""
    return ((jnp.min(sq) <= jnp.max(sk)) & (jnp.max(sq) >= jnp.min(sk))
            & (jnp.max(sq) > 0) & (jnp.max(sk) > 0))


def _attn_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, segs):
    if segs:
        sq_ref, sk_ref, o_ref, lse_ref, m_s, l_s, acc_s = rest
    else:
        o_ref, lse_ref, m_s, l_s, acc_s = rest
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        m_s[:] = jnp.full((bq, 1), _NEG, jnp.float32)
        l_s[:] = jnp.zeros((bq, 1), jnp.float32)
        acc_s[:] = jnp.zeros((bq, d), jnp.float32)

    needed = _block_needed(qi, bq, ki, bk, causal)
    if segs:
        needed = needed & _seg_block_relevant(sq_ref[:], sk_ref[:])

    @pl.when(needed)
    def _():
        # Matmuls take the operands in their NATIVE dtype with an f32
        # accumulator: for bf16 inputs the MXU multiplies bf16 pairs into
        # f32 at full rate (upcasting first halves throughput and changes
        # nothing numerically — bf16 values are exact in f32). The scale is
        # applied to the f32 scores instead of the q operand for the same
        # reason. The probability tile is cast back to the value dtype
        # before the PV matmul (the standard flash recipe; softmax stats
        # m/l/LSE stay f32).
        s = jax.lax.dot_general(q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, bq, ki, bk), s, _NEG)
        if segs:
            s = jnp.where(_seg_block_mask(sq_ref[:], sk_ref[:]), s, _NEG)
        m = m_s[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_s[:] = l_s[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[:] = acc_s[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:] = m_new

    @pl.when(ki == nkb - 1)
    def _():
        l = jnp.maximum(l_s[:], 1e-30)
        o_ref[:] = (acc_s[:] / l).astype(o_ref.dtype)
        lse_ref[:] = m_s[:] + jnp.log(l)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               scale, causal, segs):
    if segs:
        sq_ref, sk_ref, dq_ref, dq_s = rest
    else:
        dq_ref, dq_s = rest
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_s[:] = jnp.zeros((bq, d), jnp.float32)

    needed = _block_needed(qi, bq, ki, bk, causal)
    if segs:
        needed = needed & _seg_block_relevant(sq_ref[:], sk_ref[:])

    @pl.when(needed)
    def _():
        # Native-dtype matmul operands + f32 accumulate (see _attn_kernel);
        # ds is cast to the k dtype before the dq matmul.
        lse = lse_ref[:]
        delta = delta_ref[:]
        s = jax.lax.dot_general(q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, bq, ki, bk), s, _NEG)
        if segs:
            s = jnp.where(_seg_block_mask(sq_ref[:], sk_ref[:]), s, _NEG)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_s[:] = dq_s[:] + jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nkb - 1)
    def _():
        dq_ref[:] = (dq_s[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, *rest,
                scale, causal, segs):
    if segs:
        sk_ref, sq_ref, dk_ref, dv_ref, dk_s, dv_s = rest
    else:
        dk_ref, dv_ref, dk_s, dv_s = rest
    bk, d = k_ref.shape
    bq = q_ref.shape[0]
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nqb = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_s[:] = jnp.zeros((bk, d), jnp.float32)
        dv_s[:] = jnp.zeros((bk, d), jnp.float32)

    needed = _block_needed(qi, bq, ki, bk, causal)
    if segs:
        needed = needed & _seg_block_relevant(sq_ref[:], sk_ref[:])

    @pl.when(needed)
    def _():
        # Native-dtype matmul operands + f32 accumulate (see _attn_kernel).
        # dk accumulates against the UNSCALED q; the scale lands once at
        # the final write.
        lse = lse_ref[:]
        delta = delta_ref[:]
        s = jax.lax.dot_general(q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, bq, ki, bk), s, _NEG)
        if segs:
            s = jnp.where(_seg_block_mask(sq_ref[:], sk_ref[:]), s, _NEG)
        p = jnp.exp(s - lse)
        dv_s[:] = dv_s[:] + jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_s[:] = dk_s[:] + jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nqb - 1)
    def _():
        dk_ref[:] = (dk_s[:] * scale).astype(dk_ref.dtype)
        dv_ref[:] = dv_s[:].astype(dv_ref.dtype)


def _auto_block(T, cap):
    """Largest block <= cap dividing T, preferring lane-friendly multiples
    of 128. Measured on v5e (T=2048, d64, before PR 1): per-layer
    fwd+bwd cost falls 76.6 ms -> 14.9 ms going from
    128x128 to 512x1024 blocks — the per-grid-step overhead dominates at
    small blocks, so default as large as VMEM comfortably allows."""
    for b in range(min(cap, T) // 128 * 128, 127, -128):
        if T % b == 0:
            return b
    # no 128-multiple divides T: fall back to the largest sublane-aligned
    # (multiple-of-8) divisor — odd blocks mis-tile on the TPU
    for b in range(min(cap, T) // 8 * 8, 7, -8):
        if T % b == 0:
            return b
    # T < 8 or not 8-divisible (interpreter-scale shapes): any divisor
    for b in range(min(cap, T), 0, -1):
        if T % b == 0:
            return b
    return min(cap, T)


def _blocks(block_q, block_k, T):
    bq = _auto_block(T, 512) if block_q is None else min(block_q, T)
    bk = _auto_block(T, 1024) if block_k is None else min(block_k, T)
    assert T % bq == 0 and T % bk == 0, \
        f"seq len {T} must be a multiple of block sizes ({bq}, {bk})"
    return bq, bk


def _flash_candidates(T):
    """Candidate ``(block_q, block_k)`` grid for the flash kernels: the
    lane-friendly 128-multiples dividing T, capped at 6 configurations —
    the trial budget is priced against replica spawn latency
    (DESIGN_DECISIONS), and past 6 the remaining combinations are the
    small-block corner ``_auto_block`` already measured as dominated.
    When T has no 128-multiple divisor (interpreter-scale shapes) the
    heuristic block is the single candidate: one trial, and the timing
    still lands in the cache so the next process pays zero."""
    qs = [b for b in (512, 256, 128) if T % b == 0]
    ks = [b for b in (1024, 512, 256, 128) if T % b == 0]
    if not qs:
        qs = [_auto_block(T, 512)]
    if not ks:
        ks = [_auto_block(T, 1024)]
    return [{"block_q": a, "block_k": b} for a in qs for b in ks][:6]


def _tuned_blocks(kernel, q, segments, causal, block_q, block_k,
                  interpret):
    """Block selection with the autotuner as the default path
    (ISSUE 16). Explicit ``block_q``/``block_k`` bypass the tuner
    entirely (bit-identical to the pre-tuner resolution); with the tuner
    disabled the ``_auto_block`` heuristic answers untimed, with zero
    trials and zero disk I/O. Enabled, each candidate runs the REAL
    kernel once on zero operands with its blocks passed explicitly —
    which is what terminates the recursion — as plain concrete
    execution, legal even while this call sits inside an outer trace
    (a concrete eager call during tracing is ordinary Python)."""
    T = q.shape[2]
    if block_q is not None or block_k is not None:
        return _blocks(block_q, block_k, T)
    default_bq, default_bk = _blocks(None, None, T)
    if not autotune.is_enabled():
        return default_bq, default_bk
    B, H, _, D = q.shape
    segmented = segments is not None
    key = autotune.make_key(kernel, shape=(B, H, T, D), dtype=q.dtype,
                            extra=(int(bool(causal)), int(segmented)))

    def runner(block_q, block_k):
        z = jnp.zeros((B, H, T, D), q.dtype)
        seg = jnp.ones((B, T), jnp.int32) if segmented else None
        if kernel == "flash_bwd":
            return jax.grad(lambda a: flash_attention(
                a, z, z, seg, causal, None, block_q, block_k,
                interpret).astype(jnp.float32).sum())(z)
        return flash_attention(z, z, z, seg, causal, None, block_q,
                               block_k, interpret)

    cfg = autotune.choose(kernel, key=key,
                          candidates=_flash_candidates(T),
                          runner=runner,
                          default={"block_q": default_bq,
                                   "block_k": default_bk})
    try:
        return _blocks(cfg.get("block_q"), cfg.get("block_k"), T)
    except AssertionError:
        # a cache entry with non-dividing blocks (hand-edited or from
        # another build) must not crash the model — heuristic fallback
        return default_bq, default_bk


def _kv_index_map(causal, bq, bk, H=1):
    """K/V block index map for q-major kernels. Under causal masking the
    skipped upper-triangle steps clamp to the row's last needed key block,
    so the pipeline re-references the resident block instead of fetching
    one that pl.when will discard (skipping FLOPs alone still paid the
    DMA). ``H``: grid axis 0 is batch*heads; head-invariant operands
    (segment ids) use ``H > 1`` to index by batch row."""
    if not causal:
        return lambda b, i, j: (b // H, j, 0)
    return lambda b, i, j: (b // H, jnp.minimum(j, ((i + 1) * bq - 1) // bk), 0)


def _q_index_map(causal, bq, bk, H=1):
    """Q-side map for the key-major dk/dv kernel: clamp the skipped
    before-the-diagonal steps up to the first query block that sees this
    key block."""
    if not causal:
        return lambda b, i, j: (b // H, j, 0)
    return lambda b, i, j: (b // H, jnp.maximum(j, (i * bk) // bq), 0)


def _row_map(H=1):
    return lambda b, i, j: (b // H, i, 0)


def _key_row_map(H=1):
    return lambda b, i, j: (b // H, i, 0)


def _flash_forward(q, k, v, segments, causal, scale, block_q, block_k,
                   interpret):
    B, H, T, D = q.shape
    bq, bk = _tuned_blocks("flash_fwd", q, segments, causal, block_q,
                           block_k, interpret)
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    kvmap = _kv_index_map(causal, bq, bk)
    segs = segments is not None
    in_specs = [
        pl.BlockSpec((None, bq, D), _row_map()),
        pl.BlockSpec((None, bk, D), kvmap),
        pl.BlockSpec((None, bk, D), kvmap),
    ]
    operands = [qf, kf, vf]
    if segs:
        segf = segments.reshape(B, T, 1).astype(jnp.int32)
        in_specs += [
            pl.BlockSpec((None, bq, 1), _row_map(H)),
            pl.BlockSpec((None, bk, 1), _kv_index_map(causal, bq, bk, H)),
        ]
        operands += [segf, segf]
    out, lse = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal,
                          segs=segs),
        grid=(B * H, T // bq, T // bk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, bq, D), _row_map()),
            # trailing unit dim keeps the block 2-D (TPU tiling rejects
            # rank-1 blocks)
            pl.BlockSpec((None, bq, 1), _row_map()),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret, name="flash_fwd",
    )(*operands)
    return out.reshape(B, H, T, D), lse.reshape(B, H, T)


def _flash_backward(q, k, v, segments, out, lse, g, causal, scale, block_q,
                    block_k, interpret):
    B, H, T, D = q.shape
    bq, bk = _tuned_blocks("flash_bwd", q, segments, causal, block_q,
                           block_k, interpret)
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    gf = g.reshape(B * H, T, D)
    lsef = lse.reshape(B * H, T, 1)
    segs = segments is not None
    segf = (segments.reshape(B, T, 1).astype(jnp.int32) if segs else None)
    # delta = rowsum(dO * O) — O(T*D) elementwise, fine outside the kernel
    delta = jnp.sum(gf.astype(jnp.float32)
                    * out.reshape(B * H, T, D).astype(jnp.float32),
                    axis=-1, keepdims=True)

    kvmap = _kv_index_map(causal, bq, bk)
    in_specs = [
        pl.BlockSpec((None, bq, D), _row_map()),
        pl.BlockSpec((None, bk, D), kvmap),
        pl.BlockSpec((None, bk, D), kvmap),
        pl.BlockSpec((None, bq, D), _row_map()),
        pl.BlockSpec((None, bq, 1), _row_map()),
        pl.BlockSpec((None, bq, 1), _row_map()),
    ]
    operands = [qf, kf, vf, gf, lsef, delta]
    if segs:
        in_specs += [
            pl.BlockSpec((None, bq, 1), _row_map(H)),
            pl.BlockSpec((None, bk, 1), _kv_index_map(causal, bq, bk, H)),
        ]
        operands += [segf, segf]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, segs=segs),
        grid=(B * H, T // bq, T // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, bq, D), _row_map()),
        out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret, name="flash_bwd_dq",
    )(*operands)

    qmap = _q_index_map(causal, bq, bk)
    in_specs = [
        pl.BlockSpec((None, bk, D), _key_row_map()),
        pl.BlockSpec((None, bk, D), _key_row_map()),
        pl.BlockSpec((None, bq, D), qmap),
        pl.BlockSpec((None, bq, D), qmap),
        pl.BlockSpec((None, bq, 1), qmap),
        pl.BlockSpec((None, bq, 1), qmap),
    ]
    operands = [kf, vf, qf, gf, lsef, delta]
    if segs:
        in_specs += [
            pl.BlockSpec((None, bk, 1), _key_row_map(H)),
            pl.BlockSpec((None, bq, 1), _q_index_map(causal, bq, bk, H)),
        ]
        operands += [segf, segf]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          segs=segs),
        grid=(B * H, T // bk, T // bq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, bk, D), _key_row_map()),
            pl.BlockSpec((None, bk, D), _key_row_map()),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, T, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret, name="flash_bwd_dkv",
    )(*operands)

    return (dq.reshape(B, H, T, D), dk.reshape(B, H, T, D),
            dv.reshape(B, H, T, D))


def _resolve_defaults(q, scale, interpret):
    """One place for the default scale / interpreter-mode decision so the
    forward, fwd-rule, and bwd-rule can never drift apart."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = pallas_mode.interpret()
    return scale, interpret


# ---------------------------------------------------------------------------
# decode-shaped attention: q_len = 1 over a paged KV cache (serving path)
# ---------------------------------------------------------------------------

def _unpack_pages(pages):
    """``(values, scales)`` for a quantized pool (``serve.kv_cache``'s
    int8 tuple convention), ``(values, None)`` for a plain one."""
    if isinstance(pages, tuple):
        return pages[0], pages[1]
    return pages, None


def _layer_operand(layer):
    """The layer number as the ``[1]`` int32 array a scalar-prefetch
    operand has to be."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _scale_spec(H, bs, kv_map):
    """A block's whole ``[H, bs]`` scale page (every head's: the block's
    last two dimensions have to be whole or tile-aligned), at the pool
    block the K/V index map names."""
    def scale_map(*grid_and_prefetch):
        layer, block, _, _, _ = kv_map(*grid_and_prefetch)
        return (layer, block, 0, 0)
    return pl.BlockSpec((None, None, H, bs), scale_map)


def _gathered(pages, tables):
    """Dequantized position-order gather for the reference oracles —
    the serving pool's own gather, so the oracles can never drift from
    the XLA serving path's dequant convention."""
    from ..serve.kv_cache import gather_pages
    return gather_pages(pages, tables)


def _gathered_for(q_heads, pages, tables):
    """:func:`_gathered` with every KV head repeated for the query heads
    that read it (query head ``h`` reads KV head ``h // G``)."""
    kv = _gathered(pages, tables)
    return jnp.repeat(kv, q_heads // kv.shape[2], axis=2)


def paged_reference_attention(q, pages_k, pages_v, tables, lengths,
                              scale: Optional[float] = None,
                              window: Optional[int] = None):
    """Numeric oracle for :func:`paged_decode_attention` — gather the
    block-table pages into position order (dequantized for int8 pools)
    and run masked softmax attention for the single query token. ``q``
    ``[S, H, D]``; pages ``[N, H_kv, bs, D]`` (``H % H_kv == 0``) or the
    quantized ``(int8, scales)`` tuple; ``tables`` ``[S, MB]``;
    ``lengths`` ``[S]`` (0 = inactive slot -> zero output); ``window``:
    the last ``window`` positions only."""
    S, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    k = _gathered_for(H, pages_k, tables)
    v = _gathered_for(H, pages_v, tables)
    W = k.shape[1]
    s = jnp.einsum("shd,skhd->shk", q, k) * scale
    mask = jnp.arange(W)[None] < lengths[:, None]
    if window is not None:
        mask = mask & (jnp.arange(W)[None] >= lengths[:, None] - window)
    s = jnp.where(mask[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)       # length-0 (inactive) rows
    return jnp.einsum("shk,skhd->shd", p, v)


def paged_span_reference_attention(q, pages_k, pages_v, tables, start, n,
                                   scale: Optional[float] = None,
                                   window: Optional[int] = None):
    """Numeric oracle for :func:`paged_span_attention` — per-row masked
    softmax over the gathered (dequantized) context. ``q``
    ``[S, Q, H, D]`` (row ``j`` of slot ``s`` sits at position
    ``start[s] + j``); rows ``>= n[s]`` are padding whose output is
    unspecified (compare live rows only); ``n == 0`` marks an inactive
    slot (zero output on every row). Grouped KV heads and ``window`` as
    in :func:`paged_reference_attention`."""
    S, Q, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    k = _gathered_for(H, pages_k, tables)     # [S, W, H, D]
    v = _gathered_for(H, pages_v, tables)
    W = k.shape[1]
    s = jnp.einsum("sqhd,skhd->sqhk", q, k) * scale
    k_idx = jnp.arange(W)[None, None, :]
    # causal within the span: row j sees positions <= start + j
    q_pos = (start[:, None] + jnp.arange(Q)[None, :])[..., None]
    vis = (k_idx <= q_pos) & (n[:, None, None] > 0)
    if window is not None:
        vis = vis & (k_idx > q_pos - window)
    s = jnp.where(vis[:, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)       # inactive slots
    return jnp.einsum("sqhk,skhd->sqhd", p, v)


def _kv_blocks(k_ref, v_ref, sk_ref, sv_ref, head):
    """``(K block, V block, K scales, V scales)`` of one grid step. A
    plain pool's blocks go to the products as they are. A quantized
    pool's int8 blocks are widened in VMEM and their per-token scales
    come as ``[1, bs]`` rows, this head's row of the block's ``[H, bs]``
    scale page: a token's scale multiplies its score and its
    probability, which is the same product as scaling its K and V rows
    and needs no ``[bs, 1]`` column (as an operand that column is a
    relayout of the whole scale pool, 128 times its size in the TPU's
    tiling)."""
    if sk_ref is None:
        return k_ref[:], v_ref[:], None, None
    row = pl.ds(head, 1)
    return (k_ref[:].astype(jnp.float32), v_ref[:].astype(jnp.float32),
            sk_ref[row, :], sv_ref[row, :])


def _walk_page_groups(n_groups, copies, multiply):
    """The paged kernels' loop over ONE slot's page groups, as many as
    ``n_groups`` (traced) says and no more, double-buffered: group
    ``g + 1``'s copies (``copies(g, buffer)``: the DMAs that fill that
    buffer) are in flight while ``multiply(g, buffer)`` works on group
    ``g``."""
    @pl.when(n_groups > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    def body(g, carry):
        slot = g % 2

        @pl.when(g + 1 < n_groups)
        def _():
            for c in copies(g + 1, 1 - slot):
                c.start()

        for c in copies(g, slot):
            c.wait()
        multiply(g, slot)
        return carry

    jax.lax.fori_loop(0, n_groups, body, 0)


# what one slot's K and V page groups may hold of VMEM, both of them
# double-buffered: at 128 KB an all-heads page (16 heads x 16 rows x 128
# float32) that is 4 pages a group (on the chip 4, 8 and 16 pages read
# 67.7%, 64.8% and 58.3% of the bytes' roofline: the last group's unused
# pages cost more than the loop's turns)
_PAGE_GROUP_BYTES = 2 << 20
# the page copies of a group are unrolled: no more than this many
_PAGE_GROUP_MAX = 32


def _pages_per_group(page_bytes, max_blocks):
    """Pages :func:`paged_decode_attention` multiplies at a time, from the
    bytes of one all-heads page against :data:`_PAGE_GROUP_BYTES`."""
    return max(1, min(_PAGE_GROUP_BYTES // (4 * page_bytes),
                      _PAGE_GROUP_MAX, max_blocks))


def _paged_decode_kernel(tbl_ref, len_ref, lay_ref, q_ref, k_hbm, v_hbm,
                         o_ref, kbuf, vbuf, sem, m_s, l_s, acc_s, *, scale):
    """One slot's heads against its own pages. Grid ``(S,)``; the pools
    stay in HBM and a loop walks the slot's page GROUPS, as many as its
    length needs and no more (an inactive slot, length 0, copies nothing
    and writes zeros). ``pool[layer, block]`` is every head's page in one
    contiguous ``[H, bs, D]`` region, so one DMA a page brings all heads'
    K and one their V, double-buffered: group ``g + 1`` is in flight
    while group ``g`` is multiplied. A head's query is one row, which the
    MXU would take a whole tile of K as weights to push through; both
    products are the VPU's instead (a broadcast multiply and a sum, in
    float32 whatever the pool holds: bfloat16 pages are widened in VMEM),
    with a page's rows on sublanes throughout: the scores
    ``[G, H, bs, 1]`` never change layout, and the online softmax runs
    once a group."""
    _, G, H, bs, D = kbuf.shape
    s_idx = pl.program_id(0)
    length = len_ref[s_idx]
    lay = lay_ref[0]
    T = G * bs
    n_groups = (length + T - 1) // T
    last = tbl_ref.shape[1] - 1

    def copies(g, slot):
        out = []
        for i in range(G):
            # past the slot's last page: any page it may read, masked below
            block = tbl_ref[s_idx, jnp.minimum(g * G + i, last)]
            out += [pltpu.make_async_copy(pool.at[lay, block],
                                          buf.at[slot, i], sem.at[slot])
                    for pool, buf in ((k_hbm, kbuf), (v_hbm, vbuf))]
        return out

    m_s[:] = jnp.full(m_s.shape, _NEG, jnp.float32)
    l_s[:] = jnp.zeros(l_s.shape, jnp.float32)
    acc_s[:] = jnp.zeros(acc_s.shape, jnp.float32)
    q = q_ref[:].astype(jnp.float32) * scale                  # [H, 1, D]
    # a row's position within its group
    pos = (jax.lax.broadcasted_iota(jnp.int32, (G, H, bs, 1), 0) * bs
           + jax.lax.broadcasted_iota(jnp.int32, (G, H, bs, 1), 2))

    def multiply(g, slot):
        k = kbuf[slot].astype(jnp.float32)                    # [G, H, bs, D]
        s = jnp.sum(k * q[None], axis=-1, keepdims=True)      # [G, H, bs, 1]
        s = jnp.where(g * T + pos < length, s, _NEG)
        m = m_s[:]                                            # [H, 1, 1]
        m_new = jnp.maximum(m, jnp.max(s, axis=(0, 2), keepdims=True)[0])
        p = jnp.exp(s - m_new[None])
        corr = jnp.exp(m - m_new)
        l_s[:] = l_s[:] * corr + jnp.sum(p, axis=(0, 2), keepdims=True)[0]
        v = vbuf[slot].astype(jnp.float32)
        # a page's rows are summed once, after the last group
        acc_s[:] = acc_s[:] * corr + jnp.sum(p * v, axis=0)   # [H, bs, D]
        m_s[:] = m_new

    _walk_page_groups(n_groups, copies, multiply)
    out = jnp.sum(acc_s[:], axis=1, keepdims=True)            # [H, 1, D]
    o_ref[:] = (out / jnp.maximum(l_s[:], 1e-30)).astype(o_ref.dtype)


def _grouped_decode_kernel(tbl_ref, len_ref, lay_ref, q_ref, k_hbm, v_hbm,
                           o_ref, kbuf, vbuf, sem, m_s, l_s, acc_s, *, scale,
                           window):
    """:func:`_paged_decode_kernel` for GROUPED KV heads and for a window:
    one slot's ``H_kv`` KV heads, each read by ``G`` query heads (padded
    to the ``Gp`` rows of a sublane tile), against the slot's own pages.
    Same grid ``(S,)``, same page groups, same double buffer; what differs
    is the product: a KV head's ``G`` queries are ``[Gp, D] x [D, T]``
    against the group's ``T`` rows and ``[Gp, T] x [T, D]`` against their
    values, both the MXU's (a page's bytes are read once for ``G`` query
    heads, and ``G`` rows on the VPU would cost ``G`` times the one-row
    kernel's multiplies), so the buffers hold a group as ``[H_kv, pages,
    bs, D]``: one KV head's rows are one contiguous ``[T, D]`` tile.
    With ``window`` the walk starts at the page that holds position
    ``length - window`` and rows before that position are masked: a slot
    reads ``ceil(window / bs) + 1`` pages at most, whatever its length."""
    _, Hk, P, bs, D = kbuf.shape
    s_idx = pl.program_id(0)
    length = len_ref[s_idx]
    lay = lay_ref[0]
    T = P * bs
    oldest = 0 if window is None else jnp.maximum(length - window, 0)
    first = oldest // bs                        # the walk's first page
    n_groups = ((length + bs - 1) // bs - first + P - 1) // P
    last = tbl_ref.shape[1] - 1

    def copies(g, slot):
        out = []
        for i in range(P):
            # past the slot's last page: any page it may read, masked below
            block = tbl_ref[s_idx, jnp.minimum(first + g * P + i, last)]
            out += [pltpu.make_async_copy(pool.at[lay, block],
                                          buf.at[slot, :, i], sem.at[slot])
                    for pool, buf in ((k_hbm, kbuf), (v_hbm, vbuf))]
        return out

    m_s[:] = jnp.full(m_s.shape, _NEG, jnp.float32)
    l_s[:] = jnp.zeros(l_s.shape, jnp.float32)
    acc_s[:] = jnp.zeros(acc_s.shape, jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)

    def multiply(g, slot):
        # every group that runs holds a live row (the first holds position
        # ``oldest``), so a masked score's exp is nought against a real max
        pos = (first + g * P) * bs + row
        live = pos < length
        if window is not None:
            live &= pos >= oldest
        for h in range(Hk):
            k = kbuf[slot, h].reshape(T, D)
            s = jax.lax.dot_general(
                q_ref[h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale     # [Gp, T]
            s = jnp.where(live, s, _NEG)
            m = m_s[h]                                          # [Gp, 1]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_s[h] = l_s[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            v = vbuf[slot, h].reshape(T, D)
            acc_s[h] = acc_s[h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_s[h] = m_new

    _walk_page_groups(n_groups, copies, multiply)
    o_ref[:] = (acc_s[:] / jnp.maximum(l_s[:], 1e-30)).astype(o_ref.dtype)


def _grouped_decode_call(q, pages_k, pages_v, tables, lengths, layer, scale,
                         interpret, window):
    """:func:`paged_decode_attention` for ``H_q = G * H_kv`` query heads
    or a window, as the Mosaic kernel ``paged_decode``."""
    S, H, D = q.shape
    L, N, Hk, bs, _ = pages_k.shape
    G = H // Hk
    sub = 32 // pages_k.dtype.itemsize          # rows of a sublane tile
    Gp = -(-G // sub) * sub
    scale, interpret = _resolve_defaults(q, scale, interpret)
    group = _pages_per_group(Hk * bs * D * pages_k.dtype.itemsize,
                             tables.shape[1])
    if window is not None:
        # the pages a window spans, in as many groups as they need and no
        # page more: 33 pages walk as 3 x 11, not as 3 x 16
        span = -(-window // bs) + 1
        group = min(group, -(-span // -(-span // group)))
    qg = jnp.pad(q.reshape(S, Hk, G, D).astype(pages_k.dtype),
                 ((0, 0), (0, 0), (0, Gp - G), (0, 0)))

    def q_map(s, tbl, lens, lay):
        return (s, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((None, Hk, Gp, D), q_map), hbm, hbm],
        out_specs=pl.BlockSpec((None, Hk, Gp, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, Hk, group, bs, D), pages_k.dtype),
            pltpu.VMEM((2, Hk, group, bs, D), pages_v.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((Hk, Gp, 1), jnp.float32),
            pltpu.VMEM((Hk, Gp, 1), jnp.float32),
            pltpu.VMEM((Hk, Gp, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_grouped_decode_kernel, scale=scale,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hk, Gp, D), q.dtype),
        interpret=interpret, name="paged_decode",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      _layer_operand(layer), qg, pages_k, pages_v)
    return out[:, :, :G].reshape(S, H, D)


def paged_decode_attention(q, pages_k, pages_v, tables, lengths, layer,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           window: Optional[int] = None):
    """Decode-shaped (q_len = 1) flash attention over a paged KV cache.

    The serving hot op: each active slot attends its single new-token
    query against the KV blocks its block table names, a group of pages
    at a time with the online softmax (lse-correct across the slot's
    ragged length; tail positions masked). The pools stay in HBM
    (``memory_space=pl.ANY``) and the kernel copies the pages the slot's
    SCALAR-PREFETCHED block table names, up to its length: it never
    touches blocks the sequence does not own, which is what makes the
    pool's ragged sharing free, and nothing is sliced, gathered or laid
    out anew outside it.

    Args: ``q`` ``[S, H, D]`` (slot-major, one token per slot);
    ``pages_k``/``pages_v`` ``[L, N, H, bs, D]``: EVERY layer's pool,
    of which the kernel reads layer ``layer`` (heads ahead of the page's
    tokens, so ``pool[layer, block]`` is every head's page in one
    contiguous region and one copy brings it), or the quantized ``(int8
    values, scales [L, N, H, bs])`` tuple, dequantized in VMEM;
    ``tables`` ``[S, MB]`` int32; ``lengths`` ``[S]`` int32 — the number
    of valid tokens INCLUDING the one just written; 0 marks an inactive
    slot (zero output); ``layer`` an int32 scalar, traced or not: the
    third prefetched operand, so the serving tick's layer scan hands the
    kernel its carried pools whole and no layer is ever sliced out of
    them. How many pages make a group follows from the shapes
    (:func:`_pages_per_group`). ``interpret`` defaults to True off-TPU
    (same contract as :func:`flash_attention`).

    GROUPED KV heads: ``q`` may carry ``H = G * H_kv`` heads against
    pools of ``H_kv`` (query head ``h`` reads KV head ``h // G``), and
    ``window`` keeps a slot to its last ``window`` positions: the walk
    covers pages ``max(0, length - window) // bs`` onward and masks
    inside the first one. Either goes to :func:`_grouped_decode_kernel`
    (the same grid, walk and name, the products on the MXU); ``G == 1``
    with no window is the one-row kernel below, as it was.

    Mosaic copies no window narrower than its 128 lanes out of an array
    in HBM: not a quantized pool's ``[H, bs]`` scale page, not a page of
    head size 64. Those pools keep the ``(slot, head, page)`` grid, whose
    BlockSpec pipeline can: the span kernel's at ``Q = 1``, under this
    kernel's name."""
    S, H, D = q.shape
    if isinstance(pages_k, tuple) or D % 128:
        return _paged_span_call(
            q[:, None], pages_k, pages_v, tables,
            jnp.maximum(lengths - 1, 0), (lengths > 0).astype(jnp.int32),
            layer, scale, interpret, name="paged_decode",
            window=window)[:, 0]
    L, N, Hk, bs, Dk = pages_k.shape
    assert D == Dk and H % Hk == 0, f"q heads {(H, D)} != pages {(Hk, Dk)}"
    if H != Hk or window is not None:
        return _grouped_decode_call(q, pages_k, pages_v, tables, lengths,
                                    layer, scale, interpret, window)
    scale, interpret = _resolve_defaults(q, scale, interpret)
    group = _pages_per_group(H * bs * D * pages_k.dtype.itemsize,
                             tables.shape[1])

    def q_map(s, tbl, lens, lay):
        return (s, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((None, H, 1, D), q_map), hbm, hbm],
        out_specs=pl.BlockSpec((None, H, 1, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, group, H, bs, D), pages_k.dtype),
            pltpu.VMEM((2, group, H, bs, D), pages_v.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((H, 1, 1), jnp.float32),
            pltpu.VMEM((H, 1, 1), jnp.float32),
            pltpu.VMEM((H, bs, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, 1, D), q.dtype),
        interpret=interpret, name="paged_decode",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      _layer_operand(layer), q.reshape(S, H, 1, D), pages_k, pages_v)
    return out.reshape(S, H, D)


def lane_packed_decode_attention(q, pages_k, pages_v, tables, lengths,
                                 layer, *, pack: int,
                                 scale: Optional[float] = None,
                                 interpret: Optional[bool] = None,
                                 window: Optional[int] = None):
    """:func:`paged_decode_attention` over pools whose rows hold ``pack``
    KV heads of ``d = D / pack`` side by side: ``pages_k`` / ``pages_v``
    ``[L, N, H_kv / pack, bs, D]``, KV head ``p * pack + j`` in lanes
    ``j * d`` to ``(j + 1) * d`` of row ``p`` (what
    ``GroupedQueryAttention`` keeps for a head narrower than 128 lanes).
    ``q [S, H, d]``; query head ``h`` reads KV head ``h // G``.

    The query heads of row ``p``'s KV heads are consecutive, ``pack * G``
    of them, so the pools are a grouped pool of ``H_kv / pack`` heads of
    size ``D``: each query goes in zero-padded to ``D`` lanes, its own
    KV head's lanes holding it and the others nought, so its score
    against a row is its score against its own head. The grouped kernel
    runs as it is at head size ``D`` (``scale`` defaults to ``d ** -0.5``,
    that of the heads), and of each query's ``D`` output lanes its own head's ``d``
    are kept: the probabilities times the row's values, in those lanes,
    are its head's. Returns ``[S, H, d]``."""
    S, H, d = q.shape
    G = H // (pages_k.shape[2] * pack)
    scale = d ** -0.5 if scale is None else scale
    # the lanes of its own KV head, a query head's
    own = jax.nn.one_hot((jnp.arange(H) // G) % pack, pack, dtype=q.dtype)
    wide = (q[:, :, None, :] * own[None, :, :, None]).reshape(S, H, pack * d)
    out = paged_decode_attention(wide, pages_k, pages_v, tables, lengths,
                                 layer, scale=scale, interpret=interpret,
                                 window=window)
    return jnp.sum(out.reshape(S, H, pack, d) * own[None, :, :, None],
                   axis=2)


def _paged_span_kernel(tbl_ref, start_ref, n_ref, lay_ref, q_ref, k_ref,
                       v_ref, *rest, scale, bs, quant, window=None,
                       groups=1):
    """One (slot, head) SPAN's online softmax over its block table
    (ISSUE 14). Grid ``(S, H, MB)``: the innermost axis streams the
    slot's KV blocks (sequential on TPU — the m/l/acc scratch carries
    across it), with the pool block resolved by the PREFETCHED block
    table in the index map, so the DMA fetches exactly the pages the
    sequence owns. The span's ``Q`` rows are resident in one VMEM block
    with per-row online-softmax state ``[Q, 1]``/``[Q, D]``; causality
    WITHIN the span is a per-element mask (row ``j`` sees positions
    ``<= start + j``), so the speculative verify tick and chunked
    prefill stream exactly the pages the slot owns instead of
    materializing an O(W)-per-row XLA gather. With ``quant`` the K/V
    blocks arrive int8 with per-row scale pages and are dequantized IN
    VMEM (never in HBM — the whole point of the int8 pool is HBM bytes;
    :func:`_kv_blocks`). With ``window`` row ``j`` sees the positions
    ``start + j - window < p <= start + j`` only, and the blocks wholly
    behind the FIRST row's window are skipped like those past the last
    (a later row whose window has not begun in a block that runs sums
    masked scores there, which its own position's block then scales to
    nought)."""
    if quant:
        sk_ref, sv_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        sk_ref = sv_ref = None
        o_ref, m_s, l_s, acc_s = rest
    Q, d = q_ref.shape
    s_idx = pl.program_id(0)
    head = pl.program_id(1)
    j = pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_s[:] = jnp.full(m_s.shape, _NEG, jnp.float32)
        l_s[:] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[:] = jnp.zeros(acc_s.shape, jnp.float32)

    start = start_ref[s_idx]
    n = n_ref[s_idx]

    # blocks past the span's last live position are skipped entirely,
    # and an inactive slot — n == 0 — skips every block regardless of
    # a stale start and writes zeros (the oracle's convention); block 0
    # always runs for a live slot, so every live row's softmax state
    # lifts off the _NEG floor there (row j's own position
    # start+j >= 0 is always visible)
    runs = (n > 0) & (j * bs < start + n)
    if window is not None:
        runs = runs & ((j + 1) * bs > start - window + 1)

    @pl.when(runs)
    def _():
        kb, vb, sk, sv = _kv_blocks(k_ref, v_ref, sk_ref, sv_ref,
                                    head // groups)
        s = jax.lax.dot_general(q_ref[:], kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quant:
            s = s * sk
        k_idx = j * bs + jax.lax.broadcasted_iota(jnp.int32, (Q, bs), 1)
        q_idx = jax.lax.broadcasted_iota(jnp.int32, (Q, bs), 0)
        visible = k_idx <= start + q_idx
        if window is not None:
            visible = visible & (k_idx > start + q_idx - window)
        s = jnp.where(visible, s, _NEG)
        m = m_s[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_s[:] = l_s[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = p * sv if quant else p
        acc_s[:] = acc_s[:] * corr + jax.lax.dot_general(
            pv.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:] = m_new

    @pl.when(j == nkb - 1)
    def _():
        l = jnp.maximum(l_s[:], 1e-30)
        o_ref[:] = (acc_s[:] / l).astype(o_ref.dtype)


def paged_span_attention(q, pages_k, pages_v, tables, start, n, layer,
                         scale: Optional[float] = None,
                         interpret: Optional[bool] = None,
                         window: Optional[int] = None):
    """Multi-query (q_len = 1+k) flash attention over a paged KV cache —
    the span-tick hot op (ISSUE 14). Each slot's span of ``Q``
    consecutive new-token queries attends its block-table pages with one
    streamed online softmax per row, causal within the span; the
    speculative verify tick and chunked prefill ride this instead of the
    gather-everything XLA path on TPU.

    Args: ``q`` ``[S, Q, H, D]`` (row ``j`` of slot ``s`` sits at
    position ``start[s] + j``); ``pages_k``/``pages_v`` every layer's
    pool ``[L, N, H, bs, D]`` (plain or the quantized ``(int8, scales)``
    tuple — dequantized in VMEM) and ``layer`` the one to read, as in
    :func:`paged_decode_attention`; ``tables`` ``[S, MB]``;
    ``start``/``n`` ``[S]`` int32 — rows
    ``>= n[s]`` are padding (finite garbage output the host ignores),
    ``n == 0`` marks an inactive slot (zero output). At ``Q = 1`` it
    agrees with :func:`paged_decode_attention` to rounding, as both do
    with their oracles, and no closer: that kernel sums a group of pages
    at a time. ``interpret`` defaults to True off-TPU. Grouped KV heads
    (``H = G * H_kv``) and ``window`` as in
    :func:`paged_decode_attention`: a query head's grid steps read KV
    head ``h // G``'s pages, and a row sees its last ``window``
    positions. The pages have to be where the TABLE says for every
    position a row sees: a ring that the span's own writes have wrapped
    is not (``nn/attention.py:GroupedQueryAttention.decode_span`` reads
    before it writes)."""
    return _paged_span_call(q, pages_k, pages_v, tables, start, n, layer,
                            scale, interpret, name="paged_span",
                            window=window)


def _paged_span_call(q, pages_k, pages_v, tables, start, n, layer, scale,
                     interpret, name, window=None):
    """:func:`paged_span_attention` as the Mosaic kernel ``name``."""
    S, Q, H, D = q.shape
    pages_k, scale_k = _unpack_pages(pages_k)
    pages_v, scale_v = _unpack_pages(pages_v)
    quant = scale_k is not None
    L, N, Hk, bs, Dk = pages_k.shape
    assert D == Dk and H % Hk == 0, f"q heads {(H, D)} != pages {(Hk, Dk)}"
    G = H // Hk
    MB = tables.shape[1]
    scale, interpret = _resolve_defaults(q, scale, interpret)
    qt = jnp.swapaxes(q, 1, 2)               # [S, H, Q, D]

    def q_map(s, h, j, tbl, st, nn, lay):
        return (s, h, 0, 0)

    def kv_map(s, h, j, tbl, st, nn, lay):
        return (lay[0], tbl[s, j], h // G, 0, 0)

    in_specs = [
        pl.BlockSpec((None, None, Q, D), q_map),
        pl.BlockSpec((None, None, None, bs, D), kv_map),
        pl.BlockSpec((None, None, None, bs, D), kv_map),
    ]
    operands = [qt, pages_k, pages_v]
    if quant:
        in_specs += [_scale_spec(Hk, bs, kv_map)] * 2
        operands += [scale_k, scale_v]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S, H, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, Q, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((Q, 1), jnp.float32),
            pltpu.VMEM((Q, 1), jnp.float32),
            pltpu.VMEM((Q, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_span_kernel, scale=scale, bs=bs,
                          quant=quant, window=window, groups=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, Q, D), q.dtype),
        interpret=interpret, name=name,
    )(tables.astype(jnp.int32), start.astype(jnp.int32),
      n.astype(jnp.int32), _layer_operand(layer), *operands)
    return jnp.swapaxes(out, 1, 2)           # [S, Q, H, D]


# ---------------------------------------------------------------------------
# latent decode: q_len = 1 over a paged LATENT cache (one shared row a token)
# ---------------------------------------------------------------------------

def latent_paged_reference(q, pool, tables, lengths, layer, value_width,
                           scale):
    """The XLA gather path of :func:`latent_paged_decode`, same
    arithmetic: the serving path where the kernels would only be
    interpreted, and the kernel's test oracle. ``q`` ``[S, H, W]``;
    ``pool`` ``[L, N, bs, W]``; one cached row is every head's key (all
    ``W`` columns) and every head's value (its first ``value_width``
    columns). Scores and softmax in float32, products on the pool's
    dtype with float32 accumulation; a slot of length 0 gives zeros."""
    S = q.shape[0]
    rows = pool[layer, tables]                       # [S, MB, bs, W]
    rows = rows.reshape(S, -1, rows.shape[-1])       # [S, Wctx, W]
    s = jnp.einsum("shw,skw->shk", q.astype(rows.dtype), rows,
                   preferred_element_type=jnp.float32) * scale
    live = jnp.arange(rows.shape[1])[None] < lengths[:, None]
    s = jnp.where(live[:, None], s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(live[:, None], jnp.exp(s - m), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("shk,skc->shc", p.astype(rows.dtype),
                     rows[..., :value_width],
                     preferred_element_type=jnp.float32)
    return (out / l).astype(q.dtype)


def _latent_decode_kernel(tbl_ref, len_ref, lay_ref, q_ref, pool_ref, o_ref,
                          buf, sem, m_s, l_s, acc_s, *, scale, bs, group,
                          value_width):
    """One slot's 128 heads against its cached rows. Grid ``(S,)``; inside,
    a loop over the slot's OWN page groups (``group`` pages of ``bs`` rows,
    as many groups as its length needs and no more), each group brought
    from the pool in HBM by one DMA a page into one contiguous
    ``[group * bs, W]`` buffer, double-buffered: group ``g + 1`` is in
    flight while group ``g`` is multiplied. Every row is read ONCE for all
    heads: the scores are ``q [H, W] . rows^T`` and the values the rows'
    first ``value_width`` columns, so no per-head key or value exists."""
    s_idx = pl.program_id(0)
    length = len_ref[s_idx]
    lay = lay_ref[0]
    T = group * bs
    n_groups = (length + T - 1) // T
    last = tbl_ref.shape[1] - 1

    def copies(g, slot):
        return [pltpu.make_async_copy(
            pool_ref.at[lay, tbl_ref[s_idx, jnp.minimum(g * group + i, last)]],
            buf.at[slot, pl.ds(i * bs, bs)], sem.at[slot])
            for i in range(group)]

    m_s[:] = jnp.full(m_s.shape, _NEG, jnp.float32)
    l_s[:] = jnp.zeros(l_s.shape, jnp.float32)
    acc_s[:] = jnp.zeros(acc_s.shape, jnp.float32)

    def multiply(g, slot):
        rows = buf[slot]                                   # [T, W]
        s = jax.lax.dot_general(q_ref[:], rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_idx = g * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        s = jnp.where(k_idx < length, s, _NEG)
        m = m_s[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_s[:] = l_s[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[:] = acc_s[:] * corr + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_s[:] = m_new

    _walk_page_groups(n_groups, copies, multiply)
    o_ref[:] = (acc_s[:] / jnp.maximum(l_s[:], 1e-30)).astype(o_ref.dtype)


def latent_paged_decode(q, pool, tables, lengths, layer, *, value_width: int,
                        scale: float, group: int = 16,
                        interpret: Optional[bool] = None):
    """Decode-shaped (q_len = 1) attention over a paged LATENT cache: the
    absorbed form of latent attention, where a cached token is ONE row
    shared by every head (its key all ``W`` columns, its value the first
    ``value_width``).

    Args: ``q`` ``[S, H, W]``, the queries already carried into the
    latent space (``q_nope W_kvb,k^T`` beside the rotated ``q_rope``);
    ``pool`` ``[L, N, bs, W]``: every layer's pages, of which layer
    ``layer`` (an int32 scalar, traced or not: a prefetched operand, as in
    :func:`paged_decode_attention`) is read in place; ``tables``
    ``[S, MB]`` int32; ``lengths`` ``[S]`` int32, the valid rows INCLUDING
    the one just written, 0 for an inactive slot (zero output); ``scale``
    the softmax scale (``1 / sqrt(qk_nope + qk_rope)``: the width of the
    un-absorbed key, which the caller knows and ``W`` does not say).
    Returns ``[S, H, value_width]`` in ``q``'s dtype. ``group`` pages are
    multiplied at a time (the pool stays in HBM; see the kernel)."""
    S, H, W = q.shape
    L, N, bs, Wp = pool.shape
    assert W == Wp, f"q width {W} != pool row width {Wp}"
    if interpret is None:
        interpret = pallas_mode.interpret()
    T = group * bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((None, H, W), lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, H, value_width),
                               lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, T, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, value_width), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, scale=scale, bs=bs,
                          group=group, value_width=value_width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, value_width), q.dtype),
        interpret=interpret, name="latent_decode",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      _layer_operand(layer), q.astype(pool.dtype), pool)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention(q, k, v, segments=None, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Fused attention over [B, H, T, D]. ``T`` must divide by the block
    sizes (pack/pad upstream — static shapes are the framework contract).
    ``block_q``/``block_k`` default to the largest T-dividing blocks up to
    512/1024 — measured ~5x faster than 128x128 on v5e at T=2048
    (``_auto_block``); pass explicit sizes to override (e.g. tighter VMEM).
    ``segments``: optional [B, T] packed-sequence ids (``core.sequence``
    convention: 1-based, 0 = padding) confining attention within each
    sub-sequence — shared across heads. ``interpret`` defaults to True
    off-TPU so the CPU test harness runs the same kernels through the
    Pallas interpreter."""
    scale, interpret = _resolve_defaults(q, scale, interpret)
    out, _ = _flash_forward(q, k, v, segments, causal, scale, block_q,
                            block_k, interpret)
    return out


def _fwd(q, k, v, segments, causal, scale, block_q, block_k, interpret):
    scale, interpret = _resolve_defaults(q, scale, interpret)
    out, lse = _flash_forward(q, k, v, segments, causal, scale, block_q,
                              block_k, interpret)
    return out, (q, k, v, segments, out, lse)


def _bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, segments, out, lse = res
    scale, interpret = _resolve_defaults(q, scale, interpret)
    dq, dk, dv = _flash_backward(q, k, v, segments, out, lse, g, causal,
                                 scale, block_q, block_k, interpret)
    return dq, dk, dv, None


flash_attention.defvjp(_fwd, _bwd)
