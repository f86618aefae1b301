"""Decoder-only LM with latent attention and expert layers: the block of
the DeepSeek-V3 line of models (multi-head latent attention, rotary
positions, RMS norms, SiLU-gated feed-forwards, a sigmoid router over many
small experts beside a shared one), with the sandwich norms of
openPangu-Ultra-MoE, as ONE CHIP of an expert-parallel deployment runs it.

What differs from :class:`~paddle_tpu.models.TransformerLM`, by layer:

- positions are rotary (``nn/rotary.py``): there is no position table, and
  ``max_len`` only bounds a serving slot's block table;
- a block is ``h = x + RMS(Attn(RMS(x)))``, ``y = h + RMS(FFN(RMS(h)))``:
  four norms (``sandwich_norm``); a final RMS norm and an UNTIED head;
- attention is :class:`~paddle_tpu.nn.attention.LatentAttention`, and what
  a token leaves in the paged cache is one latent row a layer, not per-head
  K and V: :meth:`LatentMoELM.cache_spec` says so and the engine asks;
- the stack is not homogeneous: ``num_dense_layers`` leading blocks have a
  dense feed-forward, the others an expert layer
  (:class:`~paddle_tpu.nn.moe.HeldExpertsFFN`: told which experts it
  holds, it routes over all of them and computes its own experts' part)
  beside a shared expert that every chip computes whole. The layers run
  unrolled, each on its own parameters: nothing is stacked, sliced or cast
  inside a compiled program, so weights held in bfloat16 stay where they
  are.

A second block, :class:`ShortcutMoEBlock`, is LongCat-Flash's
shortcut-connected DOUBLE layer: two latent attentions and two dense
feed-forwards in sequence, and one expert layer (a softmax router whose
last outputs are identity experts) that reads the first feed-forward's
input and joins the stream after the second. It leaves TWO latent rows a
token in the cache. :class:`LatentMoELM` is built from either (``block``),
and :meth:`LatentMoELM.cache_spec` counts the rows.

Serving entry points keep :class:`TransformerLM`'s signatures; ``kv`` is
``(latent pool, tables)`` and each returns, as a third result, the
counters the spec declares: ``expert_tokens [expert layers, experts
held]``, the rows each held expert received in this call, and
``expert_rows [expert layers]``, the rows its grouped product was handed;
with identity experts also ``zero_pairs [expert layers]``
(:class:`~paddle_tpu.nn.moe.HeldExpertsFFN`).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core import initializers as I
from paddle_tpu.core.module import Module
from paddle_tpu.nn.attention import LatentAttention
from paddle_tpu.nn.layers import Embedding, GatedFFN, Linear, RMSNorm
from paddle_tpu.nn.moe import HeldExpertsFFN

__all__ = ["LatentMoEBlock", "ShortcutMoEBlock", "LatentMoELM"]


class LatentMoEBlock(Module):
    """One sandwich-norm block; ``moe`` (the :class:`HeldExpertsFFN`
    arguments and ``shared_hidden``) makes its feed-forward an expert
    layer, ``dense_hidden`` a dense one."""

    cache_rows = 1       # latent rows a token leaves in the paged cache

    def __init__(self, dim: int, attn: dict, dense_hidden: Optional[int],
                 moe: Optional[dict], eps: float, w_init, name=None):
        super().__init__(name=name)
        self.attn = LatentAttention(dim, eps=eps, w_init=w_init, **attn)
        self.norm_in, self.norm_post_attn = RMSNorm(eps), RMSNorm(eps)
        self.norm_pre_mlp, self.norm_post_mlp = RMSNorm(eps), RMSNorm(eps)
        self.is_moe = moe is not None
        if self.is_moe:
            moe = dict(moe)
            self.shared = GatedFFN(dim, moe.pop("shared_hidden"), w_init)
            self.experts = HeldExpertsFFN(dim, w_init=w_init, **moe)
        else:
            self.ffn = GatedFFN(dim, dense_hidden, w_init)

    def _ffn(self, h, live=None):
        """``h [B, T, D] -> (y, the expert layer's counters | None)``;
        ``live [B, T]`` marks the rows that are not padding."""
        z = self.norm_pre_mlp(h)
        if not self.is_moe:
            with jax.named_scope("dense_ffn"):
                return self.norm_post_mlp(self.ffn(z)), None
        with jax.named_scope("moe_shared"):
            y = self.shared(z)
        routed, counts = self.experts(
            z.reshape(-1, z.shape[-1]),
            None if live is None else live.reshape(-1))
        return self.norm_post_mlp(y + routed.reshape(z.shape)), counts

    def forward(self, x, positions=None):
        h = x + self.norm_post_attn(self.attn(self.norm_in(x), positions))
        y, counts = self._ffn(h)
        return h + y, counts

    def decode_step(self, x, pool, layer, tables, positions, active,
                    attn_impl: str = "xla"):
        with self.scope():
            a, pool = self.attn.decode(self.norm_in(x), pool, layer, tables,
                                       positions, active, impl=attn_impl)
            h = x + self.norm_post_attn(a)
            y, counts = self._ffn(h, active[:, None])
            return h + y, pool, counts

    def decode_span(self, x, pool, layer, tables, start, n, active,
                    write_from=None):
        with self.scope():
            a, pool = self.attn.decode_span(self.norm_in(x), pool, layer,
                                            tables, start, n, active,
                                            write_from=write_from)
            h = x + self.norm_post_attn(a)
            live = active[:, None] & (jnp.arange(x.shape[1])[None]
                                      < n[:, None])
            y, counts = self._ffn(h, live)
            return h + y, pool, counts


class ShortcutMoEBlock(Module):
    """LongCat-Flash's shortcut-connected double layer, four norms (none
    on a sublayer's output):

    ``h0 = x + Attn_0(RMS(x))``; ``u = RMS(h0)``; ``m = Experts(u)``;
    ``h1 = h0 + FFN_0(u)``; ``h2 = h1 + Attn_1(RMS(h1))``;
    ``h3 = h2 + FFN_1(RMS(h2))``; ``y = h3 + m``.

    The expert layer reads the first feed-forward's input and joins the
    stream after the second: the second attention and both dense
    feed-forwards do not depend on it (in a deployment its exchange hides
    behind them). ``moe`` is the :class:`HeldExpertsFFN` arguments; there
    is no shared expert. Two latent rows a token: pool layers ``layer``
    and ``layer + 1``."""

    cache_rows = 2
    is_moe = True

    def __init__(self, dim: int, attn: dict, dense_hidden: int, moe: dict,
                 eps: float, w_init, name=None):
        super().__init__(name=name)
        assert moe is not None, \
            "every double layer has an expert layer: num_dense_layers == 0"
        moe = dict(moe)
        assert not moe.pop("shared_hidden", 0), "no shared expert here"
        self.attn0 = LatentAttention(dim, eps=eps, w_init=w_init, **attn)
        self.attn1 = LatentAttention(dim, eps=eps, w_init=w_init, **attn)
        self.norm_attn0, self.norm_ffn0 = RMSNorm(eps), RMSNorm(eps)
        self.norm_attn1, self.norm_ffn1 = RMSNorm(eps), RMSNorm(eps)
        self.ffn0 = GatedFFN(dim, dense_hidden, w_init)
        self.ffn1 = GatedFFN(dim, dense_hidden, w_init)
        self.experts = HeldExpertsFFN(dim, w_init=w_init, **moe)

    @property
    def attn(self):
        return self.attn0

    def _layer(self, x, attend, live=None):
        """The double layer on ``x [B, T, D]``; ``attend(j, attn, z)`` is
        attention ``j`` of the two on its normalised input."""
        with jax.named_scope("attn0"):
            h = x + attend(0, self.attn0, self.norm_attn0(x))
        u = self.norm_ffn0(h)
        with jax.named_scope("moe_shortcut"):
            m, counts = self.experts(
                u.reshape(-1, u.shape[-1]),
                None if live is None else live.reshape(-1))
        with jax.named_scope("ffn0"):
            h = h + self.ffn0(u)
        with jax.named_scope("attn1"):
            h = h + attend(1, self.attn1, self.norm_attn1(h))
        with jax.named_scope("ffn1"):
            h = h + self.ffn1(self.norm_ffn1(h))
        return h + m.reshape(h.shape), counts

    def forward(self, x, positions=None):
        return self._layer(x, lambda j, attn, z: attn(z, positions))

    def _paged(self, x, pool, layer, live, method, *args, **kw):
        """The double layer against the paged cache: both attentions go
        through ``LatentAttention.<method>`` on pool layers ``layer`` and
        ``layer + 1``, each handing the pool on."""
        with self.scope():
            pools = [pool]

            def attend(j, attn, z):
                a, pools[0] = getattr(attn, method)(z, pools[0], layer + j,
                                                    *args, **kw)
                return a

            y, counts = self._layer(x, attend, live)
            return y, pools[0], counts

    def decode_step(self, x, pool, layer, tables, positions, active,
                    attn_impl: str = "xla"):
        return self._paged(x, pool, layer, active[:, None], "decode",
                           tables, positions, active, impl=attn_impl)

    def decode_span(self, x, pool, layer, tables, start, n, active,
                    write_from=None):
        live = active[:, None] & (jnp.arange(x.shape[1])[None] < n[:, None])
        return self._paged(x, pool, layer, live, "decode_span", tables,
                           start, n, active, write_from=write_from)


class LatentMoELM(Module):
    """``ids [B, T] -> logits [B, T, vocab]``.

    ``experts_held = (first id, count)`` is this chip's share of every
    expert layer's ``num_experts`` (default: all of them, the uncut
    layer); ``vocab`` is the slice of the vocabulary held here (embedding
    and head alike). ``forward(ids, return_aux=True)`` also returns the
    counters. ``block`` is the class the stack is made of
    (:class:`LatentMoEBlock`, or :class:`ShortcutMoEBlock` with no leading
    dense layers and no shared expert); ``scoring``, ``select_bias`` and
    ``num_zero_experts`` are the router's
    (:class:`~paddle_tpu.nn.moe.HeldExpertsFFN`), ``q_scale`` and
    ``kv_scale`` the latents' constant factors
    (:class:`~paddle_tpu.nn.attention.LatentAttention`)."""

    def __init__(self, vocab: int, dim: int, num_layers: int,
                 num_dense_layers: int, num_heads: int, q_rank: int,
                 kv_rank: int, nope_dim: int, rope_dim: int, v_dim: int,
                 dense_hidden: int, expert_hidden: int, num_experts: int,
                 top_k: int, experts_held: Optional[Tuple[int, int]] = None,
                 num_shared: int = 1, routed_scaling: float = 1.0,
                 rope_base: float = 10000.0, eps: float = 1e-5,
                 max_len: int = 131072, block=LatentMoEBlock,
                 scoring: str = "sigmoid", select_bias: bool = False,
                 num_zero_experts: int = 0, q_scale: float = 1.0,
                 kv_scale: float = 1.0, w_init=I.fan_in_uniform,
                 name="latent_moe_lm"):
        super().__init__(name=name)
        assert 0 <= num_dense_layers <= num_layers
        self.max_len = max_len
        self.emb = Embedding(vocab, dim)
        attn = dict(num_heads=num_heads, q_rank=q_rank, kv_rank=kv_rank,
                    nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
                    rope_base=rope_base, q_scale=q_scale, kv_scale=kv_scale)
        moe = dict(hidden=expert_hidden, num_experts=num_experts,
                   top_k=top_k, experts_held=experts_held,
                   scaling=routed_scaling, scoring=scoring,
                   select_bias=select_bias, num_zero=num_zero_experts,
                   shared_hidden=expert_hidden * num_shared)
        self.blocks = [
            block(dim, attn, dense_hidden,
                  None if i < num_dense_layers else moe, eps, w_init,
                  name=f"block{i}")
            for i in range(num_layers)]
        # the pool layer of each block's first latent row
        self.first_row = [sum(b.cache_rows for b in self.blocks[:i])
                          for i in range(num_layers)]
        self.norm_f = RMSNorm(eps)
        self.head = Linear(vocab, use_bias=False, w_init=w_init)

    def cache_spec(self):
        """What the serving engine asks a model (``serve/engine.py``):
        ``layers``, the cache layers: the latent rows a token leaves, one
        a block or two (``cache_rows``); ``pools``, the paged state a
        token leaves in one cache layer, as named rows (here ONE latent
        row, ``[c_kv | k_rope]`` padded to the lane tile); ``counters``,
        what ``decode_step`` and ``decode_span`` return beside logits and
        pools."""
        moe = [b for b in self.blocks if b.is_moe]
        spec = {"layers": sum(b.cache_rows for b in self.blocks),
                "pools": {"latent": (self.blocks[0].attn.row_width,)}}
        if moe:
            spec["counters"] = {
                "expert_tokens": (len(moe), moe[0].experts.count),
                "expert_rows": (len(moe),)}
            if moe[0].experts.num_zero:
                spec["counters"]["zero_pairs"] = (len(moe),)
        return spec

    def serving_variables(self, variables):
        """What the serving engine asks a model beside ``cache_spec()``:
        the tree the entry points run on. This one: its leaves are stored
        in the type the products take and the layers are unrolled, so
        nothing is cast, sliced or stacked, at build or inside a
        program."""
        return variables

    def _counters(self, counts):
        counts = [c for c in counts if c is not None]
        return {k: jnp.stack([c[k] for c in counts])
                for k in (counts[0] if counts else ())}

    def _embed(self, ids):
        with jax.named_scope("embed"):
            return self.emb(ids).astype(jnp.float32)

    def _logits(self, x):
        with jax.named_scope("head"):
            return self.head(self.norm_f(x))

    def forward(self, ids, return_aux: bool = False, positions=None):
        x = self._embed(ids)
        counts = []
        for blk in self.blocks:
            with jax.named_scope(blk._name):
                x, c = blk(x, positions)
            counts.append(c)
        logits = self._logits(x)
        return (logits, self._counters(counts)) if return_aux else logits

    # -- serving entry points (paddle_tpu.serve) ---------------------------

    def decode_step(self, token, kv, positions, active=None,
                    attn_impl: str = "xla"):
        """One new token a slot: ``token [S]``, ``kv = (pool [L, N, bs,
        row], tables [S, MB])``, ``positions [S]``. Returns ``(logits [S,
        vocab], kv', counters)``; the pool is written in place, a row a
        slot a layer."""
        pool, tables = kv
        if active is None:
            active = jnp.ones(token.shape, bool)
        with jax.named_scope("decode/step"):
            x = self._embed(token[:, None])
            counts = []
            for i, blk in enumerate(self.blocks):
                with jax.named_scope(blk._name):
                    x, pool, c = blk.decode_step(
                        x, pool, self.first_row[i], tables, positions,
                        active, attn_impl=attn_impl)
                counts.append(c)
            logits = self._logits(x)
        return logits[:, 0], (pool, tables), self._counters(counts)

    def decode_span(self, tokens, kv, start, n, active=None,
                    attn_impl: str = "xla", write_from=None):
        """``Q`` consecutive new tokens a slot (a prefill chunk, a
        speculative tick): ``tokens [S, Q]`` at positions ``start[s] +
        j``, of which ``n[s]`` are live. Returns ``(logits [S, Q, vocab],
        kv', counters)``. The span attends in the expanded form on every
        ``attn_impl`` (``LatentAttention.decode_span``); padding rows keep
        no expert pair and count for none."""
        pool, tables = kv
        if active is None:
            active = jnp.ones(tokens.shape[:1], bool)
        with jax.named_scope("decode/span"):
            x = self._embed(tokens)
            counts = []
            for i, blk in enumerate(self.blocks):
                with jax.named_scope(blk._name):
                    x, pool, c = blk.decode_span(
                        x, pool, self.first_row[i], tables, start, n,
                        active, write_from=write_from)
                counts.append(c)
            logits = self._logits(x)
        return logits, (pool, tables), self._counters(counts)
