"""Model FLOPs of LongCat-Flash's shortcut-connected decoder AS THIS CHIP
HOLDS IT, from shapes: the numerator of ``serve_step_mfu_pct``. The
operations and bytes of its kernels are ``pangu_costs``'s as they stand
(the same ``latent_decode`` kernel and the same grouped product), which
``latent_decode_roofline_pct`` and ``moe_ffn_roofline_pct`` read through
this module. Nothing is read from the compiler or the program.

Conventions (``benchmarks/costs.py``, ``benchmarks/pangu_costs.py``): a
multiply-add is 2 FLOPs; causal attention counts the lower triangle only;
attention in whichever form needs fewer FLOPs. What is counted is this
chip's share. A double layer has TWO latent attentions and two dense
feed-forwards, whole, and one expert layer: of a token's ``K`` choices
over the router's ``E + Z`` outputs, ``K * held / (E + Z)`` reach a real
expert held here in expectation (0.25 at 12 of 768 with 16 held); a
choice of an identity expert is one multiply-add a value and is counted;
the head over the rows of the vocabulary held here.
"""

from __future__ import annotations

from typing import Iterable, Optional, Tuple

from benchmarks.longcat_reference import Dims
from benchmarks.pangu_costs import (absorbed_pair_flops,  # noqa: F401
                                    expanded_pair_flops, latent_decode_cost,
                                    moe_ffn_cost)


def attention_shape(z: Dims) -> Tuple[int, int, int]:
    """Attention layers (two a double layer, each with a cache layer of
    its own), heads and the width of a head's (un-absorbed) key."""
    return 2 * z.L, z.H, z.nope + z.rope


def real_pairs_held(z: Dims) -> float:
    """The (token, real expert held here) pairs a token makes in one
    expert layer, in expectation over a router that chooses evenly."""
    return z.K * z.held / (z.E + z.Z)


def layer_params(z: Dims, pairs_held: Optional[float] = None) -> float:
    """Weights a token meets in matrix products, summed over the double
    layers, without ``W_kvb`` (its use depends on the attention's form):
    two attentions' four projections, two dense feed-forwards, the router
    and ``pairs_held`` routed experts (default: the expectation)."""
    attn = (z.D * z.q_rank + z.q_rank * z.H * (z.nope + z.rope)
            + z.D * (z.kv_rank + z.rope) + z.H * z.v * z.D)
    pairs = real_pairs_held(z) if pairs_held is None else pairs_held
    moe = z.D * (z.E + z.Z) + 3 * z.D * z.F_e * pairs
    return z.L * (2 * attn + 2 * 3 * z.D * z.F + moe)


def identity_flops(z: Dims) -> float:
    """The identity experts' terms of one token: ``g_e * u`` summed, one
    multiply-add a value a double layer (the gates are summed first)."""
    return 2 * z.D * z.L


def decode_flops(z: Dims, context_len: int,
                 pairs_held: Optional[float] = None) -> float:
    """One forward for one new token that attends to ``context_len``
    cached rows (itself included) in each of the ``2 L`` attentions,
    absorbed."""
    absorb = 2 * z.H * z.kv_rank * (z.nope + z.v)
    return (2 * layer_params(z, pairs_held) + identity_flops(z)
            + 2 * z.D * z.V
            + 2 * z.L * (absorb + absorbed_pair_flops(z) * context_len))


def prefill_flops(z: Dims, prompt_len: int,
                  pairs_held: Optional[float] = None) -> float:
    """One forward over a prompt at its TRUE length, head for the last row
    only; attention in the form with the fewer FLOPs at this length."""
    pairs = prompt_len * (prompt_len + 1) / 2.0
    through_kvb = 2 * z.H * z.kv_rank * (z.nope + z.v) * prompt_len
    attention = through_kvb + min(expanded_pair_flops(z),
                                  absorbed_pair_flops(z)) * pairs
    return ((2 * layer_params(z, pairs_held) + identity_flops(z))
            * prompt_len + 2 * z.D * z.V + 2 * z.L * attention)


def serve_flops(z: Dims, prompt_lens: Iterable[int],
                decode_contexts: Iterable[int],
                pairs_held: Optional[float] = None) -> float:
    """Model FLOPs of the tokens really processed: each prefill at its
    true length, one forward per decoded token at its context.
    ``pairs_held``: the real pairs a token made on this chip a layer as
    the engine's counters give them (``expert_pairs`` over tokens and
    expert layers); the expectation where none is given."""
    return (sum(prefill_flops(z, p, pairs_held) for p in prompt_lens)
            + sum(decode_flops(z, c, pairs_held) for c in decode_contexts))
