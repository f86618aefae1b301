"""Decoder-only LM whose layers mix tokens in TWO ways, gated short
convolutions and grouped-query attention, with expert layers: the block
of Liquid AI's LFM2 MoE models (``lfm2_moe``), as one pipeline stage that
holds every expert runs it.

What differs from :class:`~paddle_tpu.models.WindowMoELM`, by layer:

- a block is pre-norm, two norms: ``h = x + Mix(RMS(x))``, ``y = h +
  FFN(RMS(h))``; a final RMS norm and an untied head; token embeddings
  enter unscaled;
- ``Mix`` is, by ``layer_types[l]``, a
  :class:`~paddle_tpu.nn.recurrent.GatedShortConv` (``"conv"``) or a
  :class:`~paddle_tpu.nn.attention.GroupedQueryAttention` with q and k
  RMS-normalised a head before the rotation (``"full_attention"``);
- the serving cache therefore holds a paged ``full`` group of ``k`` /
  ``v`` pools for the attention layers and a STATE group ``conv`` for the
  convolutions: each slot's last ``kernel - 1`` inputs a layer, whatever
  its length (:meth:`ConvMoELM.cache_spec`, ``serve/kv_cache.py``);
- the first ``dense_layers`` layers have a dense feed-forward, the
  others an expert layer (:class:`~paddle_tpu.nn.moe.HeldExpertsFFN`: a
  sigmoid router, the choice by score plus a selection bias, the k gates
  renormalised) and no shared expert. The layers run unrolled, each on
  its own parameters.

Serving entry points keep :class:`WindowMoELM`'s signatures; ``kv`` is
``(*pools in cache_spec()'s order, tables)`` with ``tables`` a dict: the
``full`` group's block table and the ``conv`` group's first slot (a
call's rows are consecutive slots).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core import initializers as I
from paddle_tpu.core.module import Module
from paddle_tpu.nn.attention import GroupedQueryAttention
from paddle_tpu.nn.layers import Embedding, GatedFFN, Linear, RMSNorm
from paddle_tpu.nn.moe import HeldExpertsFFN
from paddle_tpu.nn.recurrent import GatedShortConv

__all__ = ["ConvMoEBlock", "ConvMoELM"]

# a block's kind, by the source's layer type
KINDS = {"conv": "conv", "full_attention": "full"}


class ConvMoEBlock(Module):
    """One pre-norm block of ``kind`` ``"conv"`` (``conv`` the
    :class:`GatedShortConv` arguments) or ``"full"`` (``attn`` the
    :class:`GroupedQueryAttention` arguments); ``moe`` (the
    :class:`HeldExpertsFFN` arguments) an expert layer, else
    ``dense_hidden`` a dense feed-forward."""

    def __init__(self, dim: int, kind: str, attn: dict, conv: dict,
                 dense_hidden: Optional[int], moe: Optional[dict],
                 eps: float, w_init, name=None):
        super().__init__(name=name)
        assert kind in KINDS.values(), kind
        self.kind = kind
        if kind == "conv":
            self.conv = GatedShortConv(dim, w_init=w_init, **conv)
        else:
            self.attn = GroupedQueryAttention(dim, w_init=w_init, **attn)
        self.norm_mix, self.norm_ffn = RMSNorm(eps), RMSNorm(eps)
        self.is_moe = moe is not None
        if self.is_moe:
            self.experts = HeldExpertsFFN(dim, w_init=w_init, **moe)
        else:
            self.ffn = GatedFFN(dim, dense_hidden, w_init)

    @property
    def mixer(self):
        return self.conv if self.kind == "conv" else self.attn

    def _layer(self, x, mix, live=None):
        """The block on ``x [B, T, D]``; ``mix(z)`` is its token mixer on
        the normalised input; ``live [B, T]`` marks the rows that are not
        padding. Returns ``(y, the expert layer's counters | None)``."""
        with jax.named_scope("short_conv" if self.kind == "conv"
                             else "attn_full"):
            h = x + mix(self.norm_mix(x))
        z = self.norm_ffn(h)
        if not self.is_moe:
            with jax.named_scope("dense_ffn"):
                return h + self.ffn(z), None
        routed, counts = self.experts(
            z.reshape(-1, z.shape[-1]),
            None if live is None else live.reshape(-1))
        return h + routed.reshape(z.shape), counts

    def forward(self, x, positions=None):
        if self.kind == "conv":
            return self._layer(x, self.conv)
        return self._layer(x, lambda z: self.attn(z, positions))

    def _cached(self, x, pools, live, method, *args, **kw):
        """The block against its group's pools (``k``, ``v`` or the
        state), through its mixer's ``method``."""
        with self.scope():
            pools = list(pools)

            def mix(z):
                out, *written = getattr(self.mixer, method)(
                    z, *pools, *args, **kw)
                pools[:] = written
                return out

            y, counts = self._layer(x, mix, live)
            return (y, pools, counts)

    def decode_step(self, x, pools, layer, table, positions, active,
                    attn_impl: str = "xla"):
        if self.kind == "conv":
            return self._cached(x, pools, active[:, None], "decode", layer,
                                table, active)
        return self._cached(x, pools, active[:, None], "decode", layer,
                            table, positions, active, impl=attn_impl)

    def decode_span(self, x, pools, layer, table, start, n, active,
                    write_from=None):
        live = active[:, None] & (jnp.arange(x.shape[1])[None] < n[:, None])
        if self.kind == "conv":
            return self._cached(x, pools, live, "decode_span", layer, table,
                                start, n, active)
        return self._cached(x, pools, live, "decode_span", layer, table,
                            start, n, active, write_from=write_from)


class ConvMoELM(Module):
    """``ids [B, T] -> logits [B, T, vocab]``.

    ``layer_types[l]`` is ``"conv"`` or ``"full_attention"`` (the
    source's names); attention layers have ``num_heads`` query heads over
    ``num_kv_heads`` KV heads of ``head_dim``, rotary at ``rope_base`` and
    q and k normalised a head; convolutions a kernel of ``conv_kernel``.
    The first ``dense_layers`` layers have a dense feed-forward of
    ``dense_hidden``, the others ``num_experts`` experts of
    ``expert_hidden``, ``top_k`` a token, of which this stage holds
    ``experts_held = (first id, count)`` (default: all).
    ``forward(ids, return_aux=True)`` also returns the counters."""

    def __init__(self, vocab: int, dim: int, layer_types: Sequence[str],
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 dense_hidden: int, expert_hidden: int, num_experts: int,
                 top_k: int, experts_held: Optional[Tuple[int, int]] = None,
                 dense_layers: int = 1, conv_kernel: int = 3,
                 rope_base: float = 1e6, routed_scaling: float = 1.0,
                 router_eps: float = 1e-6, eps: float = 1e-5,
                 max_len: int = 128000, w_init=I.fan_in_uniform,
                 name="conv_moe_lm"):
        super().__init__(name=name)
        self.max_len = max_len
        self.emb = Embedding(vocab, dim)
        attn = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                    head_dim=head_dim, rope_base=rope_base, qk_norm=True,
                    norm_eps=eps)
        moe = dict(hidden=expert_hidden, num_experts=num_experts,
                   top_k=top_k, experts_held=experts_held,
                   scaling=routed_scaling, scoring="sigmoid",
                   select_bias=True, normalise=True, norm_eps=router_eps)
        self.blocks = [
            ConvMoEBlock(dim, KINDS[t], attn, {"kernel": conv_kernel},
                         dense_hidden, None if i < dense_layers else moe,
                         eps, w_init, name=f"block{i}")
            for i, t in enumerate(layer_types)]
        # a block's layer number within its group's pools
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
        ``groups``: ``full``, the attention layers' paged ``k`` / ``v``
        rows (``GroupedQueryAttention.pool_row``: two KV heads of 64 to a
        128-lane row), and ``conv``, a STATE group: the convolutions'
        ``kernel - 1`` rows of ``dim`` a slot, side by side in one row;
        ``counters`` as
        :class:`WindowMoELM`'s."""
        groups = {}
        for blk in self.blocks:
            if blk.kind == "conv":
                g = groups.setdefault("conv", {
                    "layers": 0, "state": True,
                    "pools": {"state": blk.conv.state_row}})
            else:
                row = blk.attn.pool_row
                g = groups.setdefault("full", {
                    "layers": 0, "pools": {"k": row, "v": row}})
            g["layers"] += 1
        spec = {"groups": groups}
        moe = [b for b in self.blocks if b.is_moe]
        if moe:
            spec["counters"] = {
                "expert_tokens": (len(moe), moe[0].experts.count),
                "expert_rows": (len(moe),)}
        return spec

    def serving_variables(self, variables):
        """The tree the entry points run on: the caller's (leaves stored
        in the type the products take, layers unrolled)."""
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
        """Every block's ``method`` on its own group's pools and table."""
        *pools, tables = kv
        pools = dict(zip(self.pool_names, pools))
        group = {"conv": ("conv", ("conv/state",)),
                 "full": ("full", ("full/k", "full/v"))}
        counts = []
        with jax.named_scope(scope):
            for blk, layer in zip(self.blocks, self.group_layer):
                g, names = group[blk.kind]
                with jax.named_scope(blk._name):
                    x, out, c = getattr(blk, method)(
                        x, [pools[n] for n in names], layer, tables[g],
                        *args, **kw)
                pools.update(zip(names, out))
                counts.append(c)
            logits = self._logits(x)
        return (logits, (*(pools[n] for n in self.pool_names), tables),
                self._counters(counts))

    def decode_step(self, token, kv, positions, active=None,
                    attn_impl: str = "xla"):
        """One new token a slot: ``token [S]``, ``kv = (*pools, {"full":
        table [S, MB], "conv": first slot, a scalar})``, ``positions
        [S]``. Returns
        ``(logits [S, vocab], kv', counters)``; the pools and the state
        are written in place, a row a slot a layer."""
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
        are live. Returns ``(logits [S, Q, vocab], kv', counters)``; a
        convolution's state is left as it is after the last live row, and
        a span from position 0 starts from a zero state."""
        if active is None:
            active = jnp.ones(tokens.shape[:1], bool)
        return self._serve("decode/span", self._embed(tokens), kv,
                           "decode_span", start, n, active,
                           write_from=write_from)
