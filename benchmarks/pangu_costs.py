"""Model FLOPs of the latent-attention expert decoder AS THIS CHIP HOLDS
IT, from shapes, and the operations and bytes of its own kernels: the
numerators of ``serve_step_mfu_pct``, ``latent_decode_roofline_pct`` and
``moe_ffn_roofline_pct``. Nothing is read from the compiler or the program.

Conventions (``benchmarks/costs.py``): a multiply-add is 2 FLOPs; causal
attention counts the lower triangle only; recomputation never counts. What
is counted is this chip's share: of a token's ``K`` routed experts
``K * held / E`` are held here in expectation (0.5 at 8 of 256 with 16
held), the shared expert and everything else whole, the head over the rows
of the vocabulary held here. Attention is counted in whichever form needs
fewer FLOPs: absorbed for one query against a cache (no key or value of a
cached token is formed), expanded for a whole prompt (each token's keys
and values formed once).
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from benchmarks.pangu_reference import Dims


def attention_shape(z: Dims) -> Tuple[int, int, int]:
    """Layers, heads and the width of a head's (un-absorbed) key."""
    return z.L, z.H, z.nope + z.rope


def layer_params(z: Dims) -> float:
    """Weights a token meets in matrix products, summed over the layers,
    without ``W_kvb`` (its use depends on the attention's form): the
    attention's four projections, the dense feed-forwards, and of an
    expert layer the router, the shared expert and the expected share of
    the routed experts held here."""
    attn = (z.D * z.q_rank + z.q_rank * z.H * (z.nope + z.rope)
            + z.D * (z.kv_rank + z.rope) + z.H * z.v * z.D)
    dense = 3 * z.D * z.F
    expert = 3 * z.D * z.F_e
    moe = z.D * z.E + expert * (z.shared + z.K * z.held / z.E)
    return z.L * attn + z.L_dense * dense + (z.L - z.L_dense) * moe


def absorbed_pair_flops(z: Dims) -> int:
    """One query against one cached row, every head: the score over the
    row's ``kv_rank + rope`` values and the weighted sum of its
    ``kv_rank``."""
    return 2 * z.H * (2 * z.kv_rank + z.rope)


def expanded_pair_flops(z: Dims) -> int:
    return 2 * z.H * (z.nope + z.rope + z.v)


def decode_flops(z: Dims, context_len: int) -> float:
    """One forward for one new token that attends to ``context_len``
    cached rows (itself included), absorbed: the query into the latent
    space and the result out of it once a token."""
    absorb = 2 * z.H * z.kv_rank * (z.nope + z.v)
    return (2 * layer_params(z) + 2 * z.D * z.V
            + z.L * (absorb + absorbed_pair_flops(z) * context_len))


def prefill_flops(z: Dims, prompt_len: int) -> float:
    """One forward over a prompt at its TRUE length, head for the last row
    only; attention in the form with the fewer FLOPs at this length."""
    pairs = prompt_len * (prompt_len + 1) / 2.0
    through_kvb = 2 * z.H * z.kv_rank * (z.nope + z.v) * prompt_len
    attention = min(through_kvb + expanded_pair_flops(z) * pairs,
                    through_kvb + absorbed_pair_flops(z) * pairs)
    return (2 * layer_params(z) * prompt_len + 2 * z.D * z.V
            + z.L * attention)


def serve_flops(z: Dims, prompt_lens: Iterable[int],
                decode_contexts: Iterable[int]) -> float:
    """Model FLOPs of the tokens really processed: each prefill at its
    true length, one forward per decoded token at its context."""
    return (sum(prefill_flops(z, p) for p in prompt_lens)
            + sum(decode_flops(z, c) for c in decode_contexts))


def latent_decode_cost(live_rows: int, slots: int, heads: int,
                       row_values: int, value_width: int,
                       pool_bytes: int) -> Dict[str, float]:
    """``latent_paged_decode`` for ONE layer of one tick: ``live_rows`` is
    the sum over slots of the cached rows each attends to. A live row is
    read ONCE for all heads (``row_values`` values at the pool's dtype: the
    information in it, not the lane padding it is stored with) and costs
    ``2 * (row_values + value_width)`` FLOPs a head; q and out come and go
    once a slot at the pool's dtype."""
    flops = 2 * (row_values + value_width) * heads * live_rows
    nbytes = (live_rows * row_values * pool_bytes
              + slots * heads * (row_values + value_width) * pool_bytes)
    return {"flops": flops, "bytes": nbytes}


def moe_ffn_cost(z: Dims, pairs: int, hits: int,
                 weight_bytes: int = 2) -> Dict[str, float]:
    """The routed experts' three grouped products for ``pairs`` (token,
    expert) pairs that reached ``hits`` (layer, expert) weight sets: each
    pair goes through one expert's three matrices; each set that was hit
    is read once; a pair's input and output rows at the compute dtype and
    the accumulator's."""
    per_expert = 3 * z.D * z.F_e
    acts = pairs * (2 * z.D * 2 + 3 * z.F_e * 4 + z.D * 4)
    return {"flops": 2 * per_expert * pairs,
            "bytes": hits * per_expert * weight_bytes + acts}
