"""Decoder-only LM whose layers are of TWO KINDS of attention, full and
windowed, over grouped KV heads, with expert layers: the block of
poolside's Laguna line of models, as ONE CHIP of an expert-parallel
deployment runs it.

What differs from :class:`~paddle_tpu.models.LatentMoELM`, by layer:

- a block is pre-norm, two norms: ``h = x + Attn(RMS(x))``, ``y = h +
  MLP(RMS(h))``; a final RMS norm and an untied head; token embeddings
  enter unscaled;
- attention is :class:`~paddle_tpu.nn.attention.GroupedQueryAttention`:
  few KV heads, rotary positions, a per-head output gate; a layer's kind
  (``layer_windows[l]``: a window, or None for full attention) sets how
  many heads query it (``layer_heads[l]``), how far it sees and which
  rotary settings it takes (``rotary["window"]`` / ``rotary["full"]``);
- the paged cache therefore holds two GROUPS of ``k`` / ``v`` pools
  (:meth:`WindowMoELM.cache_spec`): the full layers' grow with the context,
  a window layer keeps its window and no more (``serve/kv_cache.py``: a
  ring a slot);
- ``dense_layers`` name the layers with a dense feed-forward; the others
  have an expert layer (:class:`~paddle_tpu.nn.moe.HeldExpertsFFN`, a
  softmax router whose k scores are renormalised) beside one shared
  expert, added ungated. The layers run unrolled, each on its own
  parameters, as :class:`LatentMoELM`'s do.

Serving entry points keep :class:`TransformerLM`'s signatures; ``kv`` is
``(*pools in cache_spec()'s order, tables)`` with ``tables`` a dict, a
table a group, and the counters of :class:`LatentMoELM` come third.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core import initializers as I
from paddle_tpu.core.module import Module
from paddle_tpu.nn.attention import GroupedQueryAttention
from paddle_tpu.nn.layers import Embedding, GatedFFN, Linear, RMSNorm
from paddle_tpu.nn.moe import HeldExpertsFFN

__all__ = ["WindowMoEBlock", "WindowMoELM"]


class WindowMoEBlock(Module):
    """One pre-norm block; ``attn`` the
    :class:`GroupedQueryAttention` arguments (its ``window`` makes the
    block's ``kind`` ``"window"``, else ``"full"``), ``moe`` (the
    :class:`HeldExpertsFFN` arguments and ``shared_hidden``) an expert
    layer, ``dense_hidden`` a dense feed-forward."""

    def __init__(self, dim: int, attn: dict, dense_hidden: Optional[int],
                 moe: Optional[dict], eps: float, w_init, name=None):
        super().__init__(name=name)
        self.attn = GroupedQueryAttention(dim, w_init=w_init, **attn)
        self.kind = "full" if attn.get("window") is None else "window"
        self.norm_attn, self.norm_mlp = RMSNorm(eps), RMSNorm(eps)
        self.is_moe = moe is not None
        if self.is_moe:
            moe = dict(moe)
            self.shared = GatedFFN(dim, moe.pop("shared_hidden"), w_init)
            self.experts = HeldExpertsFFN(dim, w_init=w_init, **moe)
        else:
            self.ffn = GatedFFN(dim, dense_hidden, w_init)

    def _layer(self, x, attend, live=None):
        """The block on ``x [B, T, D]``; ``attend(z)`` is its attention on
        the normalised input; ``live [B, T]`` marks the rows that are not
        padding. Returns ``(y, the expert layer's counters | None)``."""
        with jax.named_scope(f"attn_{self.kind}"):
            h = x + attend(self.norm_attn(x))
        z = self.norm_mlp(h)
        if not self.is_moe:
            with jax.named_scope("dense_ffn"):
                return h + self.ffn(z), None
        with jax.named_scope("moe_shared"):
            y = self.shared(z)
        routed, counts = self.experts(
            z.reshape(-1, z.shape[-1]),
            None if live is None else live.reshape(-1))
        return h + y + routed.reshape(z.shape), counts

    def forward(self, x, positions=None):
        return self._layer(x, lambda z: self.attn(z, positions))

    def _paged(self, x, pages, live, method, *args, **kw):
        with self.scope():
            pages = list(pages)

            def attend(z):
                a, pages[0], pages[1] = getattr(self.attn, method)(
                    z, *pages, *args, **kw)
                return a

            y, counts = self._layer(x, attend, live)
            return (y, *pages, counts)

    def decode_step(self, x, pages_k, pages_v, layer, table, positions,
                    active, attn_impl: str = "xla"):
        return self._paged(x, (pages_k, pages_v), active[:, None], "decode",
                           layer, table, positions, active, impl=attn_impl)

    def decode_span(self, x, pages_k, pages_v, layer, table, start, n,
                    active, write_from=None):
        live = active[:, None] & (jnp.arange(x.shape[1])[None] < n[:, None])
        return self._paged(x, (pages_k, pages_v), live, "decode_span",
                           layer, table, start, n, active,
                           write_from=write_from)


class WindowMoELM(Module):
    """``ids [B, T] -> logits [B, T, vocab]``.

    ``layer_windows[l]`` is layer ``l``'s window (None: full attention)
    and ``layer_heads[l]`` its query heads, over ``num_kv_heads`` KV heads
    of ``head_dim``; ``rotary`` maps ``"full"`` and ``"window"`` to the
    rotary arguments of :class:`GroupedQueryAttention` (``rope_base``,
    ``rope_dim``, ``yarn``). ``experts_held = (first id, count)`` is this
    chip's share of every expert layer's ``num_experts`` (default: all).
    ``forward(ids, return_aux=True)`` also returns the counters."""

    def __init__(self, vocab: int, dim: int,
                 layer_windows: Sequence[Optional[int]],
                 layer_heads: Sequence[int], num_kv_heads: int,
                 head_dim: int, rotary: Dict[str, dict], dense_hidden: int,
                 expert_hidden: int, shared_hidden: int, num_experts: int,
                 top_k: int, experts_held: Optional[Tuple[int, int]] = None,
                 dense_layers: Sequence[int] = (0,),
                 routed_scaling: float = 1.0, head_gate: bool = True,
                 eps: float = 1e-6, max_len: int = 1048576,
                 w_init=I.fan_in_uniform, name="window_moe_lm"):
        super().__init__(name=name)
        assert len(layer_windows) == len(layer_heads)
        self.max_len = max_len
        self.emb = Embedding(vocab, dim)
        moe = dict(hidden=expert_hidden, num_experts=num_experts,
                   top_k=top_k, experts_held=experts_held,
                   scaling=routed_scaling, scoring="softmax", normalise=True,
                   shared_hidden=shared_hidden)
        self.blocks = [
            WindowMoEBlock(
                dim, dict(num_heads=heads, num_kv_heads=num_kv_heads,
                          head_dim=head_dim, window=window,
                          head_gate=head_gate,
                          **rotary["full" if window is None else "window"]),
                dense_hidden, None if i in dense_layers else moe, eps,
                w_init, name=f"block{i}")
            for i, (window, heads) in enumerate(zip(layer_windows,
                                                    layer_heads))]
        # a block's layer number within its kind's pools
        self.group_layer = [sum(b.kind == blk.kind for b in self.blocks[:i])
                            for i, blk in enumerate(self.blocks)]
        self.norm_f = RMSNorm(eps)
        self.head = Linear(vocab, use_bias=False, w_init=w_init)
        # the pools in the order ``kv`` carries them: ``cache_spec()``'s
        self.pool_names = [f"{g}/{n}"
                           for g, spec in self.cache_spec()["groups"].items()
                           for n in spec["pools"]]

    def cache_spec(self):
        """What the serving engine asks a model (``serve/engine.py``).
        ``groups``: the paged state by layer KIND, each with its layer
        count, the ``k`` / ``v`` rows a token leaves in one of its layers
        and, for the window layers, the ``window`` beyond which the cache
        keeps nothing; ``counters`` as :class:`LatentMoELM`'s."""
        row = (self.blocks[0].attn.num_kv_heads, self.blocks[0].attn.head_dim)
        groups = {}
        for blk in self.blocks:
            g = groups.setdefault(blk.kind, {"layers": 0,
                                             "pools": {"k": row, "v": row}})
            g["layers"] += 1
            if blk.attn.window is not None:
                assert g.setdefault("window", blk.attn.window) \
                    == blk.attn.window, "one window a model"
        spec = {"groups": groups}
        moe = [b for b in self.blocks if b.is_moe]
        if moe:
            spec["counters"] = {
                "expert_tokens": (len(moe), moe[0].experts.count),
                "expert_rows": (len(moe),)}
        return spec

    def serving_variables(self, variables):
        """The tree the entry points run on: the caller's. Its leaves are
        stored in the type the products take and the layers are unrolled,
        so nothing is cast, sliced or stacked."""
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

    def _serve(self, scope, x, kv, method, *args, **kw):
        """Every block's ``method`` on its own kind's pools and table."""
        *pools, tables = kv
        names = self.pool_names
        pools = dict(zip(names, pools))
        counts = []
        with jax.named_scope(scope):
            for blk, layer in zip(self.blocks, self.group_layer):
                k, v = f"{blk.kind}/k", f"{blk.kind}/v"
                with jax.named_scope(blk._name):
                    x, pools[k], pools[v], c = getattr(blk, method)(
                        x, pools[k], pools[v], layer, tables[blk.kind],
                        *args, **kw)
                counts.append(c)
            logits = self._logits(x)
        return (logits, (*(pools[n] for n in names), tables),
                self._counters(counts))

    def decode_step(self, token, kv, positions, active=None,
                    attn_impl: str = "xla"):
        """One new token a slot: ``token [S]``, ``kv = (*pools, {group:
        table [S, MB]})``, ``positions [S]``. Returns ``(logits [S,
        vocab], kv', counters)``; the pools are written in place, a row a
        slot a layer."""
        if active is None:
            active = jnp.ones(token.shape, bool)
        logits, kv, counters = self._serve(
            "decode/step", self._embed(token[:, None]), kv, "decode_step",
            positions, active, attn_impl=attn_impl)
        return logits[:, 0], kv, counters

    def decode_span(self, tokens, kv, start, n, active=None,
                    attn_impl: str = "xla", write_from=None):
        """``Q`` consecutive new tokens a slot (a prefill chunk):
        ``tokens [S, Q]`` at positions ``start[s] + j``, of which ``n[s]``
        are live. Returns ``(logits [S, Q, vocab], kv', counters)``. The
        span reads the older context from the pools and writes its own
        rows afterwards on every ``attn_impl``
        (``GroupedQueryAttention.decode_span``); padding rows keep no
        expert pair and count for none."""
        if active is None:
            active = jnp.ones(tokens.shape[:1], bool)
        return self._serve("decode/span", self._embed(tokens), kv,
                           "decode_span", start, n, active,
                           write_from=write_from)
