"""Attention layers.

Reference: ``simple_attention`` (``/root/reference/python/paddle/
trainer_config_helpers/networks.py:1320`` — additive/concat attention over
encoder states inside the recurrent group) and ``dot_product_attention``
(``networks.py:1400``+). Multi-head scaled-dot-product attention is the
transformer-era generalization (beyond the 2017 reference, required for the
long-context axis; the sequence-parallel ring variant lives in
``paddle_tpu.parallel.ring_attention``).
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import initializers as I
from ..core.dtypes import current_policy
from ..core.module import Module
from .layers import Linear

__all__ = ["AdditiveAttention", "DotProductAttention", "MultiHeadAttention",
           "LatentAttention", "GroupedQueryAttention",
           "dot_product_attention_weights"]


def _tp_paged_kernel(kernel, q, pages_k, pages_v, *rest, head_dim: int):
    """Run a paged Pallas kernel PER SHARD over the active tp scope's
    head groups (ISSUE 15): the kernel is head-parallel by construction
    (every head's softmax is its own), so a ``shard_map`` over the
    model axis hands each device its ``H/tp`` local heads of the query
    and of every pool block — block tables and lengths replicate. With
    no scope active the kernel runs whole, unchanged. ``head_dim`` is
    the axis of ``q`` (and of the kernel's output) carrying heads; pool
    leaves always carry heads on axis 2 (``[L, N, H, bs, hd]`` values,
    ``[L, N, H, bs]`` scale pages)."""
    from ..parallel.sharding import current_tp_shard
    scope = current_tp_shard()
    if scope is None:
        return kernel(q, pages_k, pages_v, *rest)
    mesh, axis = scope
    qspec = P(*[axis if i == head_dim else None for i in range(q.ndim)])

    def pool_spec(pool):
        return jax.tree_util.tree_map(
            lambda leaf: P(*[axis if i == 2 else None
                             for i in range(leaf.ndim)]), pool)

    sharded = jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(qspec, pool_spec(pages_k), pool_spec(pages_v))
        + tuple(P() for _ in rest),
        out_specs=qspec, check_vma=False)
    return sharded(q, pages_k, pages_v, *rest)


def _flash_per_shard(q, k, v, segments, causal):
    """:func:`~paddle_tpu.nn.pallas_attention.flash_attention` under
    whatever mesh the caller is traced in. Mosaic kernels cannot be
    partitioned automatically, so with more than one device the call
    runs PER SHARD in a ``shard_map`` (the :func:`_tp_paged_kernel`
    pattern): batch over the mesh's ``data`` axis, heads over the
    tensor-parallel axis — the serving engine's ``tp_shard_scope``
    axis, else ``model``. The mesh is the scope's, else the one the
    Trainer traces its step in (``core.mesh.use_mesh``). Inside someone
    else's ``shard_map`` (megatron, the pipeline, the Trainer's manual
    dp region) only the axes still automatic there are wrapped; with
    none left, or on one device, the kernel runs bare. An axis that
    does not divide its dimension replicates the work. ``q``/``k``/``v``
    ``[B, H, T, D]``; ``segments`` ``[B, T]`` or None."""
    from ..core import mesh as mesh_lib
    from ..parallel.sharding import current_tp_shard
    from .pallas_attention import flash_attention
    scope = current_tp_shard()
    head_axis = scope[1] if scope is not None else mesh_lib.MODEL_AXIS
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty:
        # already inside a shard_map: nest over its still-automatic axes
        mesh, sizes, manual = None, dict(ctx.shape), set(ctx.manual_axes)
    else:
        mesh = scope[0] if scope is not None else mesh_lib.current_mesh()
        sizes, manual = (dict(mesh.shape) if mesh is not None else {}), ()
    free = {a for a, n in sizes.items() if n > 1 and a not in manual}
    if not free:
        return flash_attention(q, k, v, segments, causal)

    def axis_for(name, dim):
        return name if name in free and dim % sizes[name] == 0 else None

    b_ax = axis_for(mesh_lib.DATA_AXIS, q.shape[0])
    spec = P(b_ax, axis_for(head_axis, q.shape[1]))
    seg = () if segments is None else (segments,)

    def per_shard(q, k, v, *seg):
        return flash_attention(q, k, v, seg[0] if seg else None, causal)

    kw = {} if mesh is not None else {"axis_names": frozenset(free)}
    return jax.shard_map(
        per_shard, mesh=mesh, in_specs=(spec,) * 3 + (P(b_ax),) * len(seg),
        out_specs=spec, check_vma=False, **kw)(q, k, v, *seg)


def dot_product_attention_weights(q, k, mask=None, scale: Optional[float] = None):
    """softmax(q·kᵀ/√d) with additive masking; q [B, Tq, D], k [B, Tk, D]."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    if mask is not None:
        logits = jnp.where(mask > 0, logits, -1e9)
    w = jax.nn.softmax(logits, axis=-1)
    if mask is not None:
        w = w * (mask > 0)
    return w


class AdditiveAttention(Module):
    """Bahdanau / the reference's ``simple_attention``: score = vᵀ tanh(W_d d +
    W_e e). ``__call__(decoder_state [B, D], enc [B, T, E], enc_mask [B, T])``
    returns the context vector [B, E]."""

    def __init__(self, hidden: int, name=None):
        super().__init__(name=name)
        self.hidden = hidden
        self.proj_d = Linear(hidden, use_bias=False, name="proj_decoder")
        self.proj_e = Linear(hidden, use_bias=False, name="proj_encoder")
        self.v = Linear(1, use_bias=False, name="score")

    def forward(self, decoder_state, enc, enc_mask=None, enc_proj=None):
        # enc_proj may be precomputed once per sequence (the reference caches
        # the encoder projection outside the recurrent group).
        if enc_proj is None:
            enc_proj = self.proj_e(enc)
        s = jnp.tanh(enc_proj + self.proj_d(decoder_state)[:, None, :])
        scores = self.v(s)[..., 0]                       # [B, T]
        from .activations import sequence_softmax
        w = sequence_softmax(scores, mask=enc_mask)
        return jnp.einsum("bt,bte->be", w, enc), w


class DotProductAttention(Module):
    """The reference's ``dot_product_attention`` (networks.py): context =
    softmax(d·Eᵀ)·E for a single query state."""

    def __init__(self, scale: Optional[float] = None, name=None):
        super().__init__(name=name)
        self.scale = scale

    def forward(self, decoder_state, enc, enc_mask=None):
        w = dot_product_attention_weights(
            decoder_state[:, None, :], enc,
            mask=None if enc_mask is None else enc_mask[:, None, :],
            scale=self.scale)[:, 0]                      # [B, T]
        return jnp.einsum("bt,bte->be", w, enc), w


class MultiHeadAttention(Module):
    """Scaled-dot-product multi-head attention, bf16-friendly, with optional
    causal + segment masking (packed sequences). Self- or cross-attention.

    ``attention_impl`` selects the self-attention compute path:

    - ``"xla"``: materialized-scores einsum path; supports arbitrary
      ``mask=`` and cross-attention. The oracle path.
    - ``"flash"``: fused Pallas blockwise kernel
      (:mod:`paddle_tpu.nn.pallas_attention`) — linear HBM traffic forward
      AND backward (both are fully blockwise; nothing [T, T]-shaped in
      HBM). Supports ``causal=`` and packed-sequence ``segments=``.
    - ``"ring"``: sequence-parallel ring attention over the mesh's ``seq``
      axis (:mod:`paddle_tpu.parallel.ring`); needs ``seq_mesh=``.
    - ``"seq"``/``"ulysses"``: all-to-all sequence parallelism
      (:mod:`paddle_tpu.parallel.ulysses`); needs ``seq_mesh=``.

    All fast paths consume the framework's variable-length contract
    (``core.sequence`` packing: ``segments`` [B, T], 1-based, 0 = pad) —
    the successor of the reference's never-padded
    ``Argument::sequenceStartPositions`` ragged batches
    (``paddle/parameter/Argument.h:84-93``). Arbitrary dense ``mask=`` is
    XLA-path only. ``use_flash=True`` is an alias for
    ``attention_impl="flash"``."""

    def __init__(self, num_heads: int, head_dim: Optional[int] = None,
                 out_dim: Optional[int] = None, use_flash: bool = False,
                 attention_impl: Optional[str] = None, seq_mesh=None,
                 seq_axis: str = "seq", batch_axis: Optional[str] = None,
                 name=None):
        super().__init__(name=name)
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.out_dim = out_dim
        impl = attention_impl or ("flash" if use_flash else "xla")
        if impl == "ulysses":
            impl = "seq"
        if impl not in ("xla", "flash", "ring", "seq"):
            raise ValueError(f"unknown attention_impl {impl!r}")
        if impl in ("ring", "seq") and seq_mesh is None:
            raise ValueError(f"attention_impl={impl!r} needs seq_mesh=")
        self.attention_impl = impl
        self.use_flash = impl == "flash"
        self.seq_mesh = seq_mesh
        self.seq_axis = seq_axis
        self.batch_axis = batch_axis

    def _fast_path_checks(self, q_in, kv_in, mask):
        if mask is not None:
            raise ValueError(
                f"attention_impl={self.attention_impl!r} supports causal= "
                "and segments= (packed sequences), not arbitrary mask=; "
                "use the default XLA path for dense masks")
        if kv_in is not q_in:
            raise ValueError(
                f"attention_impl={self.attention_impl!r} is self-attention "
                "only; pass kv_in=None or use the XLA path")

    def forward(self, q_in, kv_in=None, mask=None, causal: bool = False,
                segments=None, return_kv: bool = False):
        """q_in [B, Tq, D]; kv_in defaults to q_in (self-attention);
        mask [B, Tq, Tk] (1 = attend); segments [B, T] packed-sequence ids
        (1-based, 0 = padding — ``core.sequence.pack_sequences``).
        ``return_kv``: also return the projected ``(k, v)`` ([B, Tk, H,
        hd] each, pre-attention) — the serving prefill captures them into
        the paged KV cache (``paddle_tpu.serve``)."""
        kv_in = q_in if kv_in is None else kv_in
        pol = current_policy()
        d_model = q_in.shape[-1]
        h = self.num_heads
        hd = self.head_dim or d_model // h
        out_d = self.out_dim or d_model

        def proj(name, x, feats):
            w = self.param(name, I.xavier_uniform, (x.shape[-1], feats))
            return jnp.dot(pol.cast_compute(x), pol.cast_compute(w),
                           preferred_element_type=pol.accum_dtype)

        # named_scope annotations: profiler traces resolve the projections
        # and the attention core by name instead of anonymous fusions.
        with jax.named_scope("qkv_proj"):
            q = proj("wq", q_in, h * hd).reshape(*q_in.shape[:2], h, hd)
            k = proj("wk", kv_in, h * hd).reshape(*kv_in.shape[:2], h, hd)
            v = proj("wv", kv_in, h * hd).reshape(*kv_in.shape[:2], h, hd)
        impl = self.attention_impl
        if impl == "flash":
            self._fast_path_checks(q_in, kv_in, mask)
            T = q.shape[1]
            if next((b for b in (128, 64, 32, 16, 8) if T % b == 0),
                    None) is None:
                raise ValueError(
                    f"flash path needs seq len divisible by 8; pad T={T}")
            # block sizes auto-select in the kernel (large blocks: the
            # per-grid-step overhead dominated at the old fixed 128 —
            # measured 5x per-layer on v5e, see _auto_block).
            # The kernel takes its operands in the policy's compute
            # dtype (bf16 pairs multiply into f32 on the MXU at full
            # rate) and saves q/k/v/out for its backward in that dtype:
            # fed the f32 projections, the d1024 step at 16 x 2048
            # tokens needs 16.06 GB of a v5e's 15.75 GB
            with jax.named_scope("flash_attention"):
                ctx = _flash_per_shard(
                    *(jnp.moveaxis(pol.cast_compute(t), 2, 1)
                      for t in (q, k, v)), segments, causal)
                ctx = jnp.moveaxis(ctx, 1, 2)
        elif impl in ("ring", "seq"):
            self._fast_path_checks(q_in, kv_in, mask)
            if impl == "ring":
                from ..parallel.ring import make_ring_attention as make
            else:
                from ..parallel.ulysses import make_ulysses_attention as make
            attn = make(self.seq_mesh, seq_axis=self.seq_axis,
                        batch_axis=self.batch_axis, causal=causal,
                        with_segments=segments is not None)
            with jax.named_scope(f"{impl}_attention"):
                ctx = (attn(q, k, v, segments) if segments is not None
                       else attn(q, k, v)).astype(pol.compute_dtype)
        else:
            with jax.named_scope("sdpa_xla"):
                logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
                logits = logits.astype(jnp.float32)
                if causal:
                    Tq, Tk = logits.shape[-2:]
                    cm = jnp.tril(jnp.ones((Tq, Tk), bool))
                    logits = jnp.where(cm[None, None], logits, -1e9)
                if segments is not None:
                    sm = (segments[:, :, None] == segments[:, None, :]) \
                        & (segments[:, :, None] > 0)
                    logits = jnp.where(sm[:, None], logits, -1e9)
                if mask is not None:
                    logits = jnp.where(mask[:, None, :, :] > 0, logits, -1e9)
                w = jax.nn.softmax(logits, axis=-1).astype(pol.compute_dtype)
                ctx = jnp.einsum("bhqk,bkhd->bqhd", w, v)
        ctx = ctx.reshape(*q_in.shape[:2], h * hd)
        with jax.named_scope("out_proj"):
            out = proj("wo", ctx, out_d)
        if return_kv:
            # serving prefill captures (k, v) into the paged pools; under
            # a tp_shard_scope (ISSUE 15) pin them head-sharded so the
            # engine's scatter lands on the sharded pools reshard-free
            from ..parallel.sharding import tp_constrain
            return out, (tp_constrain(k, 2), tp_constrain(v, 2))
        return out

    def decode(self, q_in, pages_k, pages_v, layer, tables, positions,
               active, impl: str = "xla"):
        """One decode step (q_len = 1) against a paged KV cache: project
        the new token, write its K/V into this layer's pool pages, and
        attend over the slot's whole ragged context.

        Args: ``q_in`` [S, 1, D] (one token per serving slot);
        ``pages_k``/``pages_v`` [L, N, H, bs, hd]: EVERY layer's pool,
        the layer scan's carry, of which this layer is number ``layer``
        (a traced int32 scalar). The pools are written in place and read
        by index; the layer's pool is never taken out of them or put
        back (that was two fifths of a decode tick, PERF.md PR 26);
        ``tables`` [S, MB] block tables; ``positions`` [S] the incoming
        token's 0-based position (== the pre-step sequence length);
        ``active`` [S] bool slot mask (inactive slots write the null
        block and output zeros). ``impl``: ``"paged"`` = the Pallas
        decode kernel (:func:`~paddle_tpu.nn.pallas_attention.
        paged_decode_attention`); ``"xla"`` = the gather + masked-softmax
        reference path, bit-exact (f32) with the training forward at the
        same padded width. Returns ``(out [S, 1, out_d], pages_k,
        pages_v)`` with the updated pools.

        Callable outside forward (the ``scope()`` helper-method pattern):
        the serving engine reaches it via
        ``model.apply(..., method="decode_step")``. Quantized pools (the
        ``(int8, scales)`` tuples, ISSUE 14) flow through transparently:
        the write quantizes, the kernel/gather dequantizes. Under an
        active ``tp_shard_scope`` (ISSUE 15) the projections and pools
        are constrained head-sharded — qkv column-parallel, attention on
        local heads, the out projection's row-parallel partial sums
        all-reduced — the Megatron tp recipe with the partitioner
        inserting the collectives; the paged kernel path runs per shard
        via :func:`_tp_paged_kernel`."""
        from ..serve.kv_cache import gather_pages, write_token
        from ..parallel.sharding import tp_constrain
        with self.scope():
            pol = current_policy()
            d_model = q_in.shape[-1]
            h = self.num_heads
            hd = self.head_dim or d_model // h
            out_d = self.out_dim or d_model
            S = q_in.shape[0]

            def proj(name, x, feats):
                w = self.param(name, I.xavier_uniform, (x.shape[-1], feats))
                return jnp.dot(pol.cast_compute(x), pol.cast_compute(w),
                               preferred_element_type=pol.accum_dtype)

            with jax.named_scope("qkv_proj"):
                q = tp_constrain(
                    proj("wq", q_in, h * hd).reshape(S, 1, h, hd), 2)
                k = tp_constrain(
                    proj("wk", q_in, h * hd).reshape(S, 1, h, hd), 2)
                v = tp_constrain(
                    proj("wv", q_in, h * hd).reshape(S, 1, h, hd), 2)
            with jax.named_scope("kv_scatter"):
                pages_k = tp_constrain(
                    write_token(pages_k, layer, k[:, 0], tables,
                                positions, active), 2)
                pages_v = tp_constrain(
                    write_token(pages_v, layer, v[:, 0], tables,
                                positions, active), 2)
            # the new token sees itself: effective length = position + 1
            eff_len = jnp.where(active, positions + 1, 0)
            if impl == "paged":
                from .pallas_attention import paged_decode_attention
                with jax.named_scope("paged_attention"):
                    ctx = _tp_paged_kernel(
                        paged_decode_attention, q[:, 0], pages_k,
                        pages_v, tables, eff_len, layer, head_dim=1)
                    ctx = ctx.reshape(S, 1, h, hd).astype(pol.compute_dtype)
            else:
                with jax.named_scope("sdpa_xla"):
                    # [S, W, h, hd]
                    kg = gather_pages(pages_k, tables, layer)
                    vg = gather_pages(pages_v, tables, layer)
                    ctx = self._sdpa_row(q, kg, vg, eff_len, pol, hd)
            ctx = tp_constrain(ctx, 2).reshape(S, 1, h * hd)
            with jax.named_scope("out_proj"):
                out = tp_constrain(proj("wo", ctx, out_d))
            return out, pages_k, pages_v

    @staticmethod
    def _sdpa_row(q, kg, vg, eff_len, pol, hd):
        """ONE query row against the gathered paged context ``kg``/``vg``
        ``[S, W, h, hd]`` -> ``ctx [S, 1, h, hd]`` — the single op chain
        BOTH :meth:`decode` (q_len=1) and every :meth:`decode_span` row
        share, so their bit-equality lock-step is structural: an edit
        here changes the tick and the verify/chunk span together, never
        one without the other.

        Mirrors the forward "sdpa_xla" branch op for op — WITH the
        single query row broadcast to all W rows, so every op in the
        chain has the training forward's exact shape. XLA's CPU gemm is
        row-stable across row counts but the q_len=1 PV contraction
        lowers with a DIFFERENT k-accumulation order (measured: ~1 ulp
        drift), so shape-matching is what makes decode logits bit-equal
        (f32) to the full-sequence forward's row. O(W^2) — this is the
        correctness-oracle path; the paged Pallas kernel is the
        decode-shaped production path."""
        S, W = kg.shape[:2]
        h = q.shape[2]
        qb = jnp.broadcast_to(q, (S, W, h, hd))
        logits = jnp.einsum("bqhd,bkhd->bhqk", qb, kg) / np.sqrt(hd)
        logits = logits.astype(jnp.float32)
        mask = jnp.arange(W)[None, :] < eff_len[:, None]
        logits = jnp.where(mask[:, None, None, :], logits, -1e9)
        w = jax.nn.softmax(logits, axis=-1).astype(pol.compute_dtype)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", w, vg)[:, :1]
        # a length-0 lane's softmax is uniform over -1e9 logits (an
        # average of stale pages, not zeros) — zero it to match the
        # paged kernel's convention; live lanes pass through unchanged
        return jnp.where((eff_len > 0)[:, None, None, None], ctx, 0.0)

    def decode_span(self, q_in, pages_k, pages_v, layer, tables, start,
                    n, active, impl: str = "xla", write_from=None):
        """A SPAN of consecutive new tokens per slot against the paged
        KV cache — the multi-query generalization of :meth:`decode`
        shared by the speculative verify tick (``Q = 1 + draft_k``) and
        chunked prefill (``Q = chunk``), ISSUE 12.

        Args: ``q_in`` [S, Q, D] (token ``j`` of slot ``s`` sits at
        position ``start[s] + j``); ``pages_k``/``pages_v``/``layer``
        the carried pools and this layer's number, as in :meth:`decode`;
        ``n`` [S] live token count per slot
        (rows ``>= n`` are padding: null-block write, garbage logits
        the host ignores); ``active`` [S]; ``write_from`` [S] optional
        absolute position below which the write is masked (a chunk
        re-attending a shared prefix must not write co-owned pages).
        Returns ``(out [S, Q, out_d], pages_k, pages_v)``.

        ``impl="xla"``: each row is computed by the EXACT q_len=1
        broadcast-to-W op sequence (an unrolled loop over the static
        ``Q``), so every position's output is bit-equal (f32) to what a
        sequence of single-token :meth:`decode` ticks would have
        produced — the lossless-speculation and chunked-prefill
        bit-equality guarantees are structural, not tolerances.
        ``impl="paged"``: the multi-query paged Pallas kernel
        (:func:`~paddle_tpu.nn.pallas_attention.paged_span_attention`,
        ISSUE 14) — streams only the slot's own pages instead of the
        O(W)-per-row gather; tolerance-accurate vs the oracle, bit-equal
        to the q_len=1 kernel at Q=1. Quantized pools flow through both
        (the write quantizes, kernel/gather dequantizes). Under an active
        ``tp_shard_scope`` (ISSUE 15) the span runs tp-sharded exactly
        like :meth:`decode` — head-sharded projections/pools/kernel,
        all-reduced out projection."""
        from ..serve.kv_cache import gather_pages, write_span
        from ..parallel.sharding import tp_constrain
        if impl not in ("xla", "paged"):
            raise ValueError(
                f"decode_span supports impl='xla'|'paged', got {impl!r}")
        with self.scope():
            pol = current_policy()
            d_model = q_in.shape[-1]
            h = self.num_heads
            hd = self.head_dim or d_model // h
            out_d = self.out_dim or d_model
            S, Q = q_in.shape[:2]

            def proj(name, x, feats):
                w = self.param(name, I.xavier_uniform, (x.shape[-1], feats))
                return jnp.dot(pol.cast_compute(x), pol.cast_compute(w),
                               preferred_element_type=pol.accum_dtype)

            with jax.named_scope("qkv_proj"):
                q = tp_constrain(
                    proj("wq", q_in, h * hd).reshape(S, Q, h, hd), 2)
                k = tp_constrain(
                    proj("wk", q_in, h * hd).reshape(S, Q, h, hd), 2)
                v = tp_constrain(
                    proj("wv", q_in, h * hd).reshape(S, Q, h, hd), 2)
            n_eff = jnp.where(active, n, 0)
            with jax.named_scope("kv_scatter"):
                pages_k = tp_constrain(
                    write_span(pages_k, layer, k, tables, start, n_eff,
                               write_from), 2)
                pages_v = tp_constrain(
                    write_span(pages_v, layer, v, tables, start, n_eff,
                               write_from), 2)
            if impl == "paged":
                from .pallas_attention import paged_span_attention
                with jax.named_scope("paged_span_attention"):
                    ctx = _tp_paged_kernel(
                        paged_span_attention, q, pages_k, pages_v,
                        tables, start, n_eff, layer, head_dim=2)
                    ctx = ctx.astype(pol.compute_dtype)
            else:
                with jax.named_scope("sdpa_xla"):
                    # [S, W, h, hd]
                    kg = gather_pages(pages_k, tables, layer)
                    vg = gather_pages(pages_v, tables, layer)
                    ctxs = []
                    for j in range(Q):
                        # row j sees context start+j+1 (itself
                        # included); later span rows sit beyond the
                        # mask, and masked logits are the constant -1e9
                        # regardless of page content — identical to the
                        # sequential tick's view
                        eff_len = jnp.where(active & (j < n_eff),
                                            start + j + 1, 0)
                        ctxs.append(self._sdpa_row(q[:, j:j + 1], kg,
                                                   vg, eff_len, pol,
                                                   hd))
                    ctx = jnp.concatenate(ctxs, axis=1)  # [S, Q, h, hd]
            ctx = tp_constrain(ctx, 2).reshape(S, Q, h * hd)
            with jax.named_scope("out_proj"):
                out = tp_constrain(proj("wo", ctx, out_d))
            return out, pages_k, pages_v


class LatentAttention(Module):
    """Multi-head latent attention: queries through a low-rank latent
    ``c_q`` (``q_rank``), keys and values through one shared latent
    ``c_kv`` (``kv_rank``) beside ONE rotary key ``k_rope`` (``rope_dim``)
    that every head shares. A head's query and key are a part without
    position (``nope_dim``, from the latents) beside a rotated part
    (``rope_dim``); its value has ``v_dim``. No biases; both latents are
    RMS-normalised; scores are scaled by ``1 / sqrt(nope_dim + rope_dim)``.
    ``q_scale`` and ``kv_scale`` are constant factors on the normalised
    latents (LongCat-Flash's ``sqrt(dim / rank)``; 1.0 multiplies nothing).

    What a token leaves in the cache is ONE row, ``[c_kv | k_rope]``
    (after the norm, ``kv_scale`` and the rotation), ``kv_rank + rope_dim`` values
    padded with zeros to ``row_width``, a multiple of ``ROW_ALIGN``: the
    chip stores a ``[.., bs, 576]`` bfloat16 array as 640 columns anyway,
    and the decode kernel's page copies want the array's shape to say so.

    Two forms of one arithmetic:

    - :meth:`forward` and :meth:`decode_span` EXPAND the context's latents
      to per-head keys and values (``c_kv W_kvb``) and attend as any
      multi-head attention does: the cheaper form for many queries
      (``2 H (nope + rope + v)`` FLOPs a query-key pair, plus the
      expansion once a chunk).
    - :meth:`decode` ABSORBS the up-projections: the query is carried into
      the latent space (``q_nope W_kvb,k^T``), scored against the cached
      rows themselves, the probabilities weigh the rows' ``c_kv`` part and
      the result goes through ``W_kvb,v``. No per-head key or value of a
      cached token is ever formed, and a row is read once for all heads
      (:func:`~paddle_tpu.nn.pallas_attention.latent_paged_decode`).

    ``W_kvb`` is held as its key and value parts, ``kv_b_k [kv_rank, H,
    nope_dim]`` and ``kv_b_v [kv_rank, H, v_dim]``, so that neither form
    slices a weight."""

    SPAN_TILE = 512      # context rows expanded at a time by decode_span
    ROW_ALIGN = 128      # the lane tile a cached row is padded to

    def __init__(self, dim: int, num_heads: int, q_rank: int, kv_rank: int,
                 nope_dim: int, rope_dim: int, v_dim: int,
                 rope_base: float = 10000.0, eps: float = 1e-5,
                 q_scale: float = 1.0, kv_scale: float = 1.0,
                 w_init=I.fan_in_uniform, name=None):
        super().__init__(name=name)
        from .layers import RMSNorm
        self.dim, self.num_heads = dim, num_heads
        self.q_scale, self.kv_scale = float(q_scale), float(kv_scale)
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.rope_base = float(rope_base)
        self.scale = 1.0 / float(np.sqrt(nope_dim + rope_dim))
        self.row_width = -(-(kv_rank + rope_dim) // self.ROW_ALIGN) \
            * self.ROW_ALIGN
        self.w_init = w_init
        lin = lambda n: Linear(n, use_bias=False, w_init=w_init)
        self.q_a, self.q_norm = lin(q_rank), RMSNorm(eps)
        self.q_b = lin(num_heads * (nope_dim + rope_dim))
        self.kv_a, self.kv_norm = lin(kv_rank + rope_dim), RMSNorm(eps)
        self.o = lin(dim)

    # -- the parts both forms share ----------------------------------------

    def _kv_b(self):
        pol = current_policy()
        h = self.num_heads
        k = self.param("kv_b_k", self.w_init,
                       (self.kv_rank, h, self.nope_dim))
        v = self.param("kv_b_v", self.w_init, (self.kv_rank, h, self.v_dim))
        return pol.cast_compute(k), pol.cast_compute(v)

    def _project(self, x, positions):
        """``x [B, T, D]``, ``positions [B, T]`` -> ``(q_nope [B, T, H,
        nope], q_rope [B, T, H, rope], c_kv [B, T, kv_rank], k_rope [B, T,
        rope])``, latents normalised and scaled, rotary parts rotated."""
        from .rotary import apply_rotary, rotary_angles
        B, T = x.shape[:2]
        h = self.num_heads
        scaled = lambda c, f: c if f == 1.0 else c * f
        q = self.q_b(scaled(self.q_norm(self.q_a(x)), self.q_scale)).reshape(
            B, T, h, self.nope_dim + self.rope_dim)
        kv = self.kv_a(x)
        cos, sin = rotary_angles(positions, self.rope_dim, self.rope_base)
        q_rope = apply_rotary(q[..., self.nope_dim:], cos[:, :, None],
                              sin[:, :, None])
        k_rope = apply_rotary(kv[..., self.kv_rank:], cos, sin)
        return (q[..., :self.nope_dim], q_rope,
                scaled(self.kv_norm(kv[..., :self.kv_rank]), self.kv_scale),
                k_rope)

    def _rows(self, c_kv, k_rope, dtype):
        """The cached rows ``[..., row_width]`` of ``dtype``."""
        pad = self.row_width - self.kv_rank - self.rope_dim
        parts = [c_kv, k_rope]
        if pad:
            parts.append(jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype))
        return jnp.concatenate(parts, axis=-1).astype(dtype)

    def _expanded(self, q_nope, q_rope, c_kv, k_rope, visible):
        """Attention of ``q [B, Q, H, .]`` over the context ``c_kv [B, K,
        kv_rank]``, ``k_rope [B, K, rope]`` expanded to per-head keys and
        values; ``visible [B, Q, K]``. Returns the un-normalised
        ``(acc [B, Q, H, v], m [B, Q, H], l [B, Q, H])`` of a softmax over
        this context, so that a caller can go on to another tile."""
        pol = current_policy()
        wk, wv = self._kv_b()
        c = pol.cast_compute(c_kv)
        k_nope = jnp.einsum("bkc,chd->bkhd", c, wk,
                            preferred_element_type=pol.accum_dtype)
        v = jnp.einsum("bkc,chd->bkhd", c, wv,
                       preferred_element_type=pol.accum_dtype)
        cc = pol.cast_compute
        s = (jnp.einsum("bqhd,bkhd->bqhk", cc(q_nope), cc(k_nope),
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhd,bkd->bqhk", cc(q_rope), cc(k_rope),
                          preferred_element_type=jnp.float32)) * self.scale
        s = jnp.where(visible[:, :, None, :], s, -1e30)
        m = jnp.max(s, axis=-1)
        p = jnp.where(visible[:, :, None, :], jnp.exp(s - m[..., None]), 0.0)
        acc = jnp.einsum("bqhk,bkhd->bqhd", cc(p), cc(v),
                         preferred_element_type=jnp.float32)
        return acc, m, jnp.sum(p, axis=-1)

    def _out(self, ctx):
        """``ctx [B, T, H, v] -> [B, T, D]``."""
        return self.o(ctx.reshape(*ctx.shape[:2], -1))

    # -- entry points --------------------------------------------------------

    def forward(self, x, positions=None):
        """Causal attention over a whole sequence ``x [B, T, D]``, expanded
        form, no cache."""
        B, T = x.shape[:2]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        with jax.named_scope("latent_attn"):
            q_nope, q_rope, c_kv, k_rope = self._project(x, positions)
            causal = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)),
                                      (B, T, T))
            acc, _, l = self._expanded(q_nope, q_rope, c_kv, k_rope, causal)
            return self._out(acc / l[..., None])

    def decode(self, x, pool, layer, tables, positions, active,
               impl: str = "xla"):
        """One new token a slot against the paged latent cache, absorbed
        form. ``x [S, 1, D]``; ``pool [L, N, bs, row_width]``: every
        layer's pages, this layer (``layer``, traced or not) written in
        place and read by index; ``tables [S, MB]``; ``positions [S]`` the
        new token's position; ``active [S]``. ``impl``: ``"paged"`` the
        Pallas kernel, ``"xla"`` the gather path of the same arithmetic.
        Returns ``(out [S, 1, D], pool)``."""
        from ..serve.kv_cache import write_token
        from .pallas_attention import (latent_paged_decode,
                                       latent_paged_reference)
        with self.scope(), jax.named_scope("latent_attn"):
            pol = current_policy()
            q_nope, q_rope, c_kv, k_rope = self._project(
                x, positions[:, None])
            pool = write_token(pool, layer,
                               self._rows(c_kv, k_rope, pool.dtype)[:, 0],
                               tables, positions, active)
            wk, wv = self._kv_b()
            q_lat = jnp.einsum("shd,chd->shc", pol.cast_compute(q_nope[:, 0]),
                               wk, preferred_element_type=pol.accum_dtype)
            q = self._rows(q_lat, q_rope[:, 0], pool.dtype)  # [S, H, row]
            eff_len = jnp.where(active, positions + 1, 0)
            if impl == "paged":
                o_lat = latent_paged_decode(
                    q, pool, tables, eff_len, layer,
                    value_width=self.kv_rank, scale=self.scale)
            else:
                o_lat = latent_paged_reference(
                    q, pool, tables, eff_len, layer, self.kv_rank,
                    self.scale)
            ctx = jnp.einsum("shc,chd->shd", pol.cast_compute(o_lat), wv,
                             preferred_element_type=pol.accum_dtype)
            return self._out(ctx[:, None]), pool

    def decode_span(self, x, pool, layer, tables, start, n, active,
                    write_from=None):
        """A span of consecutive new tokens a slot (a prefill chunk, a
        speculative tick's drafts), expanded form: the span's rows are
        written first, then the slot's context is read back from the pool
        a tile of ``SPAN_TILE`` rows at a time, as many tiles as the
        longest live slot needs, each expanded to keys and values once for
        the whole span (online softmax across tiles). ``x [S, Q, D]``;
        token ``j`` of slot ``s`` sits at ``start[s] + j`` and sees the
        positions up to itself; rows ``j >= n[s]`` are padding (written to
        the null block, their output unspecified). Returns ``(out [S, Q,
        D], pool)``. Every row read is the pool's rounding of it, the
        span's own rows too, as a later decode will read them."""
        from ..serve.kv_cache import write_span
        with self.scope(), jax.named_scope("latent_attn"):
            S, Q = x.shape[:2]
            bs, MB = pool.shape[2], tables.shape[1]
            pos = start[:, None] + jnp.arange(Q, dtype=jnp.int32)[None]
            q_nope, q_rope, c_kv, k_rope = self._project(x, pos)
            n_eff = jnp.where(active, n, 0)
            pool = write_span(pool, layer,
                              self._rows(c_kv, k_rope, pool.dtype), tables,
                              start, n_eff, write_from)
            per = max(1, min(self.SPAN_TILE // bs, MB))   # pages a tile
            T = per * bs
            n_tiles = (jnp.max(jnp.where(n_eff > 0, start + n_eff, 0))
                       + T - 1) // T
            h = self.num_heads

            def tile(t, carry):
                acc, m, l = carry
                cols = jnp.minimum(t * per + jnp.arange(per), MB - 1)
                rows = pool[layer, jnp.take(tables, cols, axis=1)]
                rows = rows.reshape(S, T, -1)
                k_pos = t * T + jnp.arange(T, dtype=jnp.int32)
                visible = ((k_pos[None, None, :] <= pos[:, :, None])
                           & (t * per + jnp.arange(per) < MB).repeat(bs)[
                               None, None, :])
                a, m_t, l_t = self._expanded(
                    q_nope, q_rope, rows[..., :self.kv_rank],
                    rows[..., self.kv_rank:self.kv_rank + self.rope_dim],
                    visible)
                m_new = jnp.maximum(m, m_t)
                c_old, c_new = jnp.exp(m - m_new), jnp.exp(m_t - m_new)
                return (acc * c_old[..., None] + a * c_new[..., None],
                        m_new, l * c_old + l_t * c_new)

            init = (jnp.zeros((S, Q, h, self.v_dim), jnp.float32),
                    jnp.full((S, Q, h), -1e30, jnp.float32),
                    jnp.zeros((S, Q, h), jnp.float32))
            acc, _, l = jax.lax.fori_loop(0, n_tiles, tile, init)
            ctx = acc / jnp.maximum(l, 1e-30)[..., None]
            return self._out(ctx), pool


class GroupedQueryAttention(Module):
    """Causal self-attention with GROUPED KV heads, rotary positions, an
    optional WINDOW and an optional per-head output gate; no biases.

    ``num_heads`` query heads read ``num_kv_heads`` KV heads of size
    ``head_dim`` (query head ``h`` reads KV head ``h // G``, ``G =
    num_heads / num_kv_heads``): a token leaves ``num_kv_heads`` keys and
    values in the cache however many heads query them. Queries and keys
    are rotated (``nn/rotary.py``): the first ``rope_dim`` values of a
    head (default all: ``rope_dim < head_dim`` is a partial rotation), at
    ``rope_base``'s frequencies or, with ``yarn = {"factor",
    "original_len", "beta_fast", "beta_slow", "attention_factor"}``,
    YaRN's, cosines and sines times its attention factor. Scores are
    ``q . k / sqrt(head_dim)``, softmax in float32. With ``window`` query
    ``i`` sees keys ``i - window < j <= i``. With ``head_gate`` head
    ``h``'s output is multiplied by ``sigmoid(x W_g)_h`` (``W_g [dim,
    num_heads]``, from the layer's input) before the output projection.

    Against the paged cache (``pages_k`` / ``pages_v`` ``[L, N, H_kv, bs,
    head_dim]``, this layer number ``layer``, written in place):

    - :meth:`decode`, ``impl="paged"``: the token's row is written, then
      :func:`~paddle_tpu.nn.pallas_attention.paged_decode_attention`
      walks the slot's pages (from the window's first page on);
    - :meth:`decode_span` and ``decode(impl="xla")``: the new rows attend
      to THEMSELVES as computed (rounded as the pool will hold them) and
      to the older context READ FROM THE POOL a tile of pages at a time
      (online softmax), and are written afterwards. Reading before
      writing is what a window group's ring needs
      (``serve/kv_cache.py``): a chunk's writes wrap onto the rows its
      own first queries still see.
    """

    SPAN_TILE = 512      # older context rows read at a time

    def __init__(self, dim: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, rope_base: float = 10000.0,
                 rope_dim: Optional[int] = None, yarn: Optional[dict] = None,
                 window: Optional[int] = None, head_gate: bool = False,
                 w_init=I.fan_in_uniform, name=None):
        super().__init__(name=name)
        from .rotary import yarn_frequencies
        assert num_heads % num_kv_heads == 0, (num_heads, num_kv_heads)
        self.dim, self.head_dim = dim, head_dim
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.window = None if window is None else int(window)
        self.rope_base = float(rope_base)
        self.rope_dim = head_dim if rope_dim is None else int(rope_dim)
        self.inv_freq, self.rope_factor = None, 1.0
        if yarn:
            yarn = dict(yarn)
            self.rope_factor = float(yarn.pop("attention_factor", 1.0))
            self.inv_freq = yarn_frequencies(self.rope_dim, self.rope_base,
                                             **yarn)
        self.scale = 1.0 / float(np.sqrt(head_dim))
        self.w_init = w_init
        lin = lambda n: Linear(n, use_bias=False, w_init=w_init)
        self.gate = lin(num_heads) if head_gate else None
        self.o = lin(dim)

    # -- the parts every entry point shares -------------------------------

    def _heads(self, name, x, heads):
        """``x [B, T, D] -> [B, T, heads, hd]`` through the matrix ``name``,
        held ``[heads * hd, D]``: the axis the product sums over is the
        minor one, which is how both compiled programs want the q, k and v
        matrices (held ``[D, heads * hd]`` XLA re-laid all three in every
        tick and chunk: 0.52 GB copied a tick at Laguna-S-2.1's widths)."""
        pol = current_policy()
        w = self.param(
            name, lambda rng, shape, dtype: self.w_init(
                rng, shape[::-1], dtype).T, (heads * self.head_dim, self.dim))
        y = jnp.einsum("btd,fd->btf", pol.cast_compute(x),
                       pol.cast_compute(w),
                       preferred_element_type=pol.accum_dtype)
        return y.reshape(*x.shape[:2], heads, self.head_dim)

    def _project(self, x, positions):
        """``x [B, T, D]``, ``positions [B, T]`` -> ``(q [B, T, H, hd],
        k, v [B, T, H_kv, hd], gate [B, T, H] | None)``, q and k
        rotated."""
        from .rotary import apply_rotary, rotary_angles
        cos, sin = rotary_angles(positions, self.rope_dim, self.rope_base,
                                 self.inv_freq, self.rope_factor)
        cos, sin = cos[:, :, None], sin[:, :, None]
        q = apply_rotary(self._heads("wq", x, self.num_heads), cos, sin)
        k = apply_rotary(self._heads("wk", x, self.num_kv_heads), cos, sin)
        gate = None
        if self.gate is not None:
            with jax.named_scope("head_gate"):
                gate = jax.nn.sigmoid(self.gate(x).astype(jnp.float32))
        return q, k, self._heads("wv", x, self.num_kv_heads), gate

    def _attend(self, q, k, v, q_pos, k_pos, k_live=None):
        """Queries ``q [B, Q, H, hd]`` at ``q_pos [B, Q]`` over the keys
        ``k``, ``v`` ``[B, K, H_kv, hd]`` at ``k_pos [B, K]`` (``k_live
        [B, K]``: the rows that hold one), causal and windowed. Returns
        the un-normalised ``(acc [B, Q, H, hd], m [B, Q, H], l)`` of a
        softmax over these keys, so that a caller can go on to others."""
        cc = current_policy().cast_compute
        B, Q = q.shape[:2]
        grouped = q.reshape(B, Q, self.num_kv_heads, -1, self.head_dim)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", cc(grouped), cc(k),
                       preferred_element_type=jnp.float32) * self.scale
        visible = k_pos[:, None, :] <= q_pos[:, :, None]
        if self.window is not None:
            visible &= k_pos[:, None, :] > q_pos[:, :, None] - self.window
        if k_live is not None:
            visible &= k_live[:, None, :]
        visible = visible[:, :, None, None, :]
        s = jnp.where(visible, s, -1e30)
        m = jnp.max(s, axis=-1)
        p = jnp.where(visible, jnp.exp(s - m[..., None]), 0.0)
        acc = jnp.einsum("bqhgk,bkhd->bqhgd", cc(p), cc(v),
                         preferred_element_type=jnp.float32)
        flat = lambda t: t.reshape(B, Q, self.num_heads, *t.shape[4:])
        return flat(acc), flat(m), flat(jnp.sum(p, axis=-1))

    def _out(self, acc, l, gate):
        """``acc / l`` gated a head, through the output projection."""
        ctx = acc / jnp.maximum(l, 1e-30)[..., None]
        if gate is not None:
            with jax.named_scope("head_gate"):
                ctx = ctx * gate[..., None]
        return self.o(ctx.reshape(*ctx.shape[:2], -1))

    def _cached(self, q, k, v, pages_k, pages_v, layer, table, start, n):
        """The span's queries ``q [S, Q, H, hd]`` (row ``j`` at ``start[s]
        + j``, ``n[s]`` of them live) over their own rows ``k``, ``v``
        (as the pool will round them) and over the slot's OLDER context,
        positions below ``start``, read from the pool by ``table`` a tile
        of pages at a time from the window's first page on."""
        S, Q = q.shape[:2]
        bs, MB = pages_k.shape[3], table.shape[1]
        pos = start[:, None] + jnp.arange(Q, dtype=jnp.int32)[None]
        state = self._attend(q, k.astype(pages_k.dtype),
                             v.astype(pages_v.dtype), pos, pos,
                             jnp.arange(Q)[None] < n[:, None])
        if self.window is None:
            first = jnp.zeros((S,), jnp.int32)
            per = self.SPAN_TILE // bs
        else:
            first = jnp.maximum(start - self.window + 1, 0) // bs
            per = -(-self.window // bs) + 1
        per = max(1, min(per, MB))                      # pages a tile
        T = per * bs

        def tile(t, carry):
            acc, m, l = carry
            cols = first[:, None] + t * per + jnp.arange(per)[None]
            blocks = jnp.take_along_axis(table, jnp.minimum(cols, MB - 1),
                                         axis=1)        # [S, per]
            rows = lambda pages: jnp.swapaxes(
                pages[layer, blocks], 2, 3).reshape(S, T, *pages.shape[2:3],
                                                    pages.shape[-1])
            k_pos = (cols[:, :, None] * bs
                     + jnp.arange(bs)[None, None]).reshape(S, T)
            a, m_t, l_t = self._attend(q, rows(pages_k), rows(pages_v), pos,
                                       k_pos, k_pos < start[:, None])
            m_new = jnp.maximum(m, m_t)
            c_old, c_new = jnp.exp(m - m_new), jnp.exp(m_t - m_new)
            return (acc * c_old[..., None] + a * c_new[..., None], m_new,
                    l * c_old + l_t * c_new)

        if self.window is not None and per * bs >= self.window + bs - 1:
            # a window's older rows lie in ONE tile: no loop (a loop that
            # reads the pool ahead of the span's in-place writes makes
            # XLA copy the whole pool round it)
            acc, _, l = tile(0, state)
        else:
            n_tiles = jnp.max(jnp.where(n > 0, start - first * bs + T - 1,
                                        0)) // T
            acc, _, l = jax.lax.fori_loop(0, n_tiles, tile, state)
        return acc, l

    # -- entry points -------------------------------------------------------

    def forward(self, x, positions=None):
        """Causal (windowed) attention over a whole sequence ``x [B, T,
        D]``, no cache."""
        B, T = x.shape[:2]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        q, k, v, gate = self._project(x, positions)
        acc, _, l = self._attend(q, k, v, positions, positions)
        return self._out(acc, l, gate)

    def decode(self, x, pages_k, pages_v, layer, table, positions, active,
               impl: str = "xla"):
        """One new token a slot. ``x [S, 1, D]``; ``table [S, MB]`` this
        layer's group's; ``positions [S]``; ``active [S]``. Returns ``(out
        [S, 1, D], pages_k, pages_v)``."""
        from ..serve.kv_cache import write_token
        with self.scope():
            q, k, v, gate = self._project(x, positions[:, None])
            if impl != "paged":
                acc, l = self._cached(q, k, v, pages_k, pages_v, layer,
                                      table, positions,
                                      active.astype(jnp.int32))
            pages_k = write_token(pages_k, layer, k[:, 0], table, positions,
                                  active)
            pages_v = write_token(pages_v, layer, v[:, 0], table, positions,
                                  active)
            if impl == "paged":
                from .pallas_attention import paged_decode_attention
                acc = paged_decode_attention(
                    q[:, 0], pages_k, pages_v, table,
                    jnp.where(active, positions + 1, 0), layer,
                    scale=self.scale, window=self.window)[:, None]
                l = jnp.ones(acc.shape[:-1], acc.dtype)
            return self._out(acc, l, gate), pages_k, pages_v

    def decode_span(self, x, pages_k, pages_v, layer, table, start, n,
                    active, write_from=None):
        """A span of consecutive new tokens a slot (a prefill chunk):
        ``x [S, Q, D]``, token ``j`` of slot ``s`` at ``start[s] + j``,
        ``n[s]`` of them live (the others are padding: written to the
        null block, their output unspecified). Returns ``(out [S, Q, D],
        pages_k, pages_v)``."""
        from ..serve.kv_cache import write_span
        with self.scope():
            Q = x.shape[1]
            pos = start[:, None] + jnp.arange(Q, dtype=jnp.int32)[None]
            q, k, v, gate = self._project(x, pos)
            n_eff = jnp.where(active, n, 0)
            acc, l = self._cached(q, k, v, pages_k, pages_v, layer, table,
                                  start, n_eff)
            pages_k = write_span(pages_k, layer, k, table, start, n_eff,
                                 write_from, by_page=True)
            pages_v = write_span(pages_v, layer, v, table, start, n_eff,
                                 write_from, by_page=True)
            return self._out(acc, l, gate), pages_k, pages_v
