"""Mixture-of-experts FFN with expert parallelism over the ``expert`` mesh
axis — a forward-looking capability (the 2017 reference has no MoE; the
mesh declares the axis, ``core/mesh.py``, and this layer is what uses it).

TPU-native shape: the classic static dispatch/combine einsum formulation —
top-k routing with a fixed per-expert capacity, dispatch as a one-hot
[tokens, experts, capacity] tensor, expert FFNs batched over the expert
dimension. Everything is dense matmuls with static shapes (MXU-friendly, no
sorting/gathering), and sharding the expert-major weights/activations over
the ``expert`` axis (see :func:`moe_sharding_rules`) makes XLA insert the
token all-to-alls over ICI.

Routing is top-k (k static; k=1 is the Switch formulation, k=2 the classic
GShard/expert-choice-free variant): each token's k expert choices claim
capacity slots in choice-major order (first choices of all tokens beat
second choices — the standard priority), gates optionally renormalized over
the kept choices. Overflowing (token, choice) pairs are dropped
(contribute zero), and the layer REPORTS the drop rate instead of hiding it
(``return_stats=True``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddle_tpu.core import initializers as I
from paddle_tpu.core.module import Module

__all__ = ["MoEFFN", "HeldExpertsFFN", "moe_sharding_rules"]

# Sorted rows a window of ``HeldExpertsFFN``'s grouped product. A constant
# of the chip and not an option: a visit of an expert streams its matrix
# (``D * F * 2`` bytes) whatever the rows, and multiplies a whole window
# of them; on a v5e (197 TFLOP/s, 819 GB/s) the two take the same time at
# about 240 rows, so under that a visit is bound by the stream it cannot
# avoid, and 128 is the MXU's row count (``DESIGN_DECISIONS.md``).
ROW_WINDOW = 128


class MoEFFN(Module):
    """Top-k routed expert FFN: ``x [B, T, D] -> [B, T, D]``.

    ``capacity_factor`` sizes each expert's token buffer
    (``C = ceil(tokens*k/experts * factor)``); overflowing (token, choice)
    pairs are dropped (contribute zero — the standard static-capacity
    trade). ``forward(x, return_aux=True)`` also returns the Switch-style
    load-balancing auxiliary loss; ``return_stats=True`` additionally
    returns routing telemetry: ``drop_rate`` (fraction of token-choices
    that overflowed) and ``expert_fraction`` (per-expert token share).
    """

    def __init__(self, num_experts: int, hidden: int,
                 capacity_factor: float = 1.25, act: str = "gelu",
                 top_k: int = 1, renormalize: bool = True, name=None):
        super().__init__(name=name)
        assert 1 <= top_k <= num_experts
        self.num_experts = num_experts
        self.hidden = hidden
        self.capacity_factor = capacity_factor
        self.act_name = act
        self.top_k = top_k
        self.renormalize = renormalize

    def forward(self, x, return_aux: bool = False,
                return_stats: bool = False):
        from . import activations
        B, T, D = x.shape
        E = self.num_experts
        K = self.top_k
        N = B * T
        C = max(1, math.ceil(N * K / E * self.capacity_factor))
        act = activations.get(self.act_name)

        wg = self.param("wg", I.xavier_uniform, (D, E))
        w1 = self.param("w1", I.fan_in_uniform, (E, D, self.hidden))
        b1 = self.param("b1", I.zeros, (E, self.hidden))
        w2 = self.param("w2", I.fan_in_uniform, (E, self.hidden, D))
        b2 = self.param("b2", I.zeros, (E, D))

        xf = x.reshape(N, D)
        logits = xf @ wg                                    # [N, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top_gates, top_idx = jax.lax.top_k(probs, K)        # [N, K]
        if self.renormalize and K > 1:
            top_gates = top_gates / jnp.maximum(
                jnp.sum(top_gates, axis=-1, keepdims=True), 1e-9)

        # Capacity assignment, choice-major priority: all first choices
        # claim slots before any second choice. Routing bookkeeping stays
        # int32 regardless of x.dtype: a bf16 cumsum only counts exactly to
        # 256, which would collide capacity slots on real batch sizes.
        counts = jnp.zeros((E,), jnp.int32)                 # slots used
        dispatch = jnp.zeros((N, E, C), x.dtype)
        combine = jnp.zeros((N, E, C), x.dtype)
        kept_total = jnp.zeros((), jnp.int32)
        for j in range(K):                                  # K is static
            onehot_j = jax.nn.one_hot(top_idx[:, j], E, dtype=jnp.int32)
            pos_j = (jnp.cumsum(onehot_j, axis=0) - 1
                     + counts[None, :]) * onehot_j          # [N, E]
            kept = (pos_j < C) & (onehot_j > 0)
            pos_c = jnp.clip(pos_j, 0, C - 1)
            pos_onehot = jax.nn.one_hot(pos_c, C, dtype=x.dtype)  # [N, E, C]
            disp_j = pos_onehot * kept.astype(x.dtype)[..., None]
            dispatch = dispatch + disp_j
            combine = combine + disp_j * top_gates[:, j, None, None].astype(
                x.dtype)
            counts = counts + jnp.sum(onehot_j * kept.astype(jnp.int32),
                                      axis=0)
            kept_total = kept_total + jnp.sum(kept.astype(jnp.int32))

        # [E, C, D] expert inputs; batched expert FFN; combine back
        expert_in = jnp.einsum("nd,nec->ecd", xf, dispatch)
        h = act(jnp.einsum("ecd,edh->ech", expert_in, w1) + b1[:, None, :])
        expert_out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
        out = jnp.einsum("ecd,nec->nd", expert_out, combine)

        out = out.reshape(B, T, D)
        if not (return_aux or return_stats):
            return out
        # Switch-style load-balance aux over FIRST choices:
        # E * sum_e (frac_tokens_e * mean_prob_e)
        onehot1 = jax.nn.one_hot(top_idx[:, 0], E, dtype=jnp.float32)
        frac = jnp.mean(onehot1, axis=0)
        mean_prob = jnp.mean(probs.astype(jnp.float32), axis=0)
        aux = E * jnp.sum(frac * mean_prob)
        if not return_stats:
            return out, aux
        stats = {
            "drop_rate": 1.0 - kept_total.astype(jnp.float32) / (N * K),
            "expert_fraction": frac,
            "capacity": jnp.asarray(C, jnp.int32),
        }
        if not return_aux:
            return out, stats
        return out, aux, stats


class HeldExpertsFFN(Module):
    """This chip's share of an expert layer of an expert-parallel
    deployment: ``x [N, D] -> (y [N, D], counters)``, the counters a dict
    of int32 arrays (``expert_tokens [held]``, ``expert_rows``, and with
    identity experts ``zero_pairs``).

    The router scores ALL its outputs in float32 and takes each token's
    ``top_k``, as the whole layer would. ``scoring``:

    - ``"sigmoid"``: ``s = sigmoid(x W_g)``, gates normalised over the k
      (``scaling * s_e / (sum of the k + 1e-20)``);
    - ``"softmax"``: ``s = softmax(x W_g)``, gates the raw scores
      (``scaling * s_e``, not renormalised);

    ``normalise`` overrides which of the two a scoring does with its k
    scores (``"softmax"`` with ``normalise=True``: the softmax's scores
    renormalised over the k, Qwen2-MoE's ``norm_topk_prob``).

    ``select_bias`` adds a per-output bias (parameter ``select_bias``) to
    the scores FOR THE CHOICE ONLY: it moves which outputs are taken and
    never a gate. ``num_zero`` identity ("zero-computation") experts
    follow the ``num_experts`` real ones in the router's outputs (width
    ``num_experts + num_zero``): a chosen identity expert adds ``g_e * x``
    and computes nothing, so its pairs never enter the grouped product
    (in the sort of the ``N * top_k`` pairs they are absent pairs, like
    those of experts held elsewhere); their gates are summed a token and
    multiply the input, on every chip for its own tokens.

    Of the ``N * top_k`` (token, expert) pairs this layer keeps those whose
    expert is one of the ``experts_held = (first id, count)`` it holds,
    sorts them by expert and runs each held expert's SiLU-gated
    feed-forward over its own rows as a grouped product
    (``jax.lax.ragged_dot`` over the sorted pairs, the kept ones first:
    a group is as long as its expert has pairs, so there is no capacity
    and no pair is dropped). The product, the gather that feeds it and
    the gates that weigh it run over the KEPT pairs' rows only,
    ``ROW_WINDOW`` sorted rows a window in one ``jax.lax.while_loop``
    whose trip count is the kept pairs' (a device scalar): XLA's grouped
    product multiplies a whole tile of the rows it is handed for every
    expert with a row in it, so the pairs of absent experts, 15 of 16 in
    a deployment's share, are never handed to it. The result is the held
    experts' part of ``sum_e g_e Expert_e(x)``; what the absent experts
    would add is not computed here and nothing stands in for it. A shared
    expert is the caller's (every chip computes it alike).

    ``expert_tokens`` counts the rows each held expert received: the
    engine's ``expert_pairs`` / ``expert_hits`` counters. ``expert_rows``
    (a scalar) counts the rows handed to the grouped product, windows
    times ``min(N * top_k, ROW_WINDOW)``: over the pairs it says how much
    of the product is padding. ``zero_pairs`` (a scalar, only with
    ``num_zero``) counts the live rows' choices that went to identity
    experts."""

    def __init__(self, dim: int, hidden: int, num_experts: int, top_k: int,
                 experts_held=None, scaling: float = 1.0,
                 scoring: str = "sigmoid", select_bias: bool = False,
                 num_zero: int = 0, normalise=None,
                 w_init=I.fan_in_uniform, name=None):
        super().__init__(name=name)
        first, count = experts_held or (0, num_experts)
        assert 0 <= first and first + count <= num_experts and count > 0
        assert 1 <= top_k <= num_experts + num_zero and num_zero >= 0
        assert scoring in ("sigmoid", "softmax"), scoring
        self.dim, self.hidden = dim, hidden
        self.num_experts, self.top_k = num_experts, top_k
        self.first, self.count = int(first), int(count)
        self.scaling = float(scaling)
        self.scoring, self.select_bias = scoring, bool(select_bias)
        self.normalise = (scoring == "sigmoid" if normalise is None
                          else bool(normalise))
        self.num_zero = int(num_zero)
        self.w_init = w_init

    def route(self, x):
        """``(expert ids [N, k] int32, gates [N, k] float32)`` over all
        the router's outputs. The product is float32 at the highest
        precision: the eighth and the ninth of 256 scores lie close
        together."""
        width = self.num_experts + self.num_zero
        wg = self.param("router", self.w_init, (self.dim, width))
        logits = jnp.dot(x.astype(jnp.float32), wg.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        scores = (jax.nn.sigmoid(logits) if self.scoring == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        if self.select_bias:
            bias = self.param("select_bias", I.zeros, (width,))
            _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                   self.top_k)
            top = jnp.take_along_axis(scores, idx, axis=-1)
        else:
            top, idx = jax.lax.top_k(scores, self.top_k)
        if self.normalise:
            gates = self.scaling * top / (
                jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
        else:
            gates = self.scaling * top
        return idx.astype(jnp.int32), gates

    def forward(self, x, live=None):
        """``live [N]`` bool (optional): rows that are padding (a chunk's
        tail, an empty slot) keep no pair and count for no expert."""
        from paddle_tpu.core.dtypes import current_policy
        pol = current_policy()
        N, D = x.shape
        K, E = self.top_k, self.count
        w_gate = self.param("gate", self.w_init, (E, D, self.hidden))
        w_up = self.param("up", self.w_init, (E, D, self.hidden))
        w_down = self.param("down", self.w_init, (E, self.hidden, D))
        with jax.named_scope("moe_route"):
            idx, gates = self.route(x)
            local = idx - self.first                         # [N, K]
            held = (local >= 0) & (local < E)
            if live is not None:
                held = held & live[:, None]
            # pairs by expert, the pairs of absent experts last
            key = jnp.where(held, local, E).reshape(N * K)
            order = jnp.argsort(key, stable=True)
            sizes = jnp.sum(jax.nn.one_hot(key, E, dtype=jnp.int32), axis=0)
            token = (order // K).astype(jnp.int32)
        with jax.named_scope("moe_experts"):
            # the kept pairs' rows, ``M`` sorted rows a window, as many
            # windows as the kept pairs need: the trip count is a device
            # scalar, so a tick of 32 kept pairs and a chunk of 256 run
            # one program each and multiply no tile of absent pairs
            M = min(N * K, ROW_WINDOW)
            pad = -(N * K) % M
            n_kept = jnp.sum(sizes)
            windows = (n_kept + M - 1) // M
            ends = jnp.cumsum(sizes)
            starts = ends - sizes
            xc = pol.cast_compute(x)
            w_gate, w_up, w_down = (pol.cast_compute(w)
                                    for w in (w_gate, w_up, w_down))
            token = jnp.pad(token, (0, pad))
            gate = jnp.pad(gates.reshape(N * K)[order], (0, pad))

            def window(carry):
                i, y = carry
                lo = i * M
                # every group's [start, end) clipped to the window; an
                # expert whose rows straddle an edge is visited in both
                size = (jnp.clip(ends, lo, lo + M)
                        - jnp.clip(starts, lo, lo + M))
                grouped = lambda a, w: jax.lax.ragged_dot(
                    pol.cast_compute(a), w, size,
                    preferred_element_type=pol.accum_dtype)
                rows = jnp.take(
                    xc, jax.lax.dynamic_slice(token, (lo,), (M,)), axis=0)
                h = jax.nn.silu(grouped(rows, w_gate)) * grouped(rows, w_up)
                g = jax.lax.dynamic_slice(gate, (lo,), (M,))
                # rows of the window past the kept pairs belong to no
                # group: zero
                kept = lo + jnp.arange(M) < n_kept
                gated = jnp.where(kept[:, None],
                                  grouped(h, w_down) * g[:, None], 0.0)
                return i + 1, jax.lax.dynamic_update_slice(y, gated, (lo, 0))

            _, y = jax.lax.while_loop(
                lambda carry: carry[0] < windows, window,
                (jnp.int32(0), jnp.zeros(
                    (N * K + pad, D),
                    jnp.result_type(pol.accum_dtype, gates.dtype))))
            # back to token order: a token's K rows, summed
            out = jnp.take(y, jnp.argsort(order),
                           axis=0).reshape(N, K, D).sum(axis=1)
        counters = {"expert_tokens": sizes,
                    "expert_rows": windows * M}
        if self.num_zero:
            with jax.named_scope("moe_zero"):
                zero = idx >= self.num_experts               # [N, K]
                if live is not None:
                    zero = zero & live[:, None]
                out = out + jnp.sum(jnp.where(zero, gates, 0.0), axis=-1)[
                    :, None] * x.astype(out.dtype)
                counters["zero_pairs"] = jnp.sum(zero.astype(jnp.int32))
        return out, counters


def moe_sharding_rules(expert_axis: str = "expert"):
    """fnmatch-style ``(pattern, PartitionSpec)`` rules sharding the
    expert-major MoE weights over the expert mesh axis (feed to
    :class:`paddle_tpu.parallel.ShardingRules`, composable with other
    rules)."""
    from jax.sharding import PartitionSpec as P
    return [
        ("*/w1", P(expert_axis, None, None)),
        ("*/b1", P(expert_axis, None)),
        ("*/w2", P(expert_axis, None, None)),
        ("*/b2", P(expert_axis, None)),
    ]
