"""Model FLOPs of LFM2-24B-A2B's decoder AS THIS STAGE HOLDS IT, from
shapes, and the operations and bytes of its decode attention: the
numerators of ``serve_step_mfu_pct`` and ``grouped_decode_roofline_pct``.
The grouped product's are ``pangu_costs.moe_ffn_cost``'s as they stand
(the same ``HeldExpertsFFN``), which ``moe_ffn_roofline_pct`` reads
through this module. Nothing is read from the compiler or the program.

Conventions (``benchmarks/costs.py``, ``benchmarks/pangu_costs.py``): a
multiply-add is 2 FLOPs; causal attention counts the keys a query really
sees, the lower triangle; a convolution layer's products are its two
projections (``4 D^2`` weights a token) and its taps (``conv_k``
multiply-adds a channel). Every expert is held here, so a token meets
``K`` of them.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from benchmarks.lfm2_reference import Dims
from benchmarks.pangu_costs import moe_ffn_cost          # noqa: F401

# the TPU's lanes: a pool row of the attention layers holds
# ``LANES / head_dim`` KV heads (GroupedQueryAttention.pool_row)
LANES = 128


def attention_shape(z: Dims) -> Tuple[int, int, int]:
    """Cache layers (the attention layers), the KV heads a token leaves in
    each, the head size."""
    return z.kinds.count("full"), z.H_kv, z.hd


def layer_params(z: Dims) -> float:
    """Weights a token meets in matrix products, summed over the layers:
    a convolution's in and out projections or the attention's four, the
    dense feed-forward or the router and the ``K`` experts a token is
    sent to (all held here)."""
    total = 0.0
    for kind, dense in zip(z.kinds, z.dense):
        total += (4 * z.D * z.D if kind == "conv"
                  else z.D * (2 * z.H * z.hd + 2 * z.H_kv * z.hd))
        total += 3 * z.D * z.F if dense else (
            z.D * z.E + 3 * z.D * z.F_e * z.K * z.held / z.E)
    return total


def _taps(z: Dims) -> float:
    """FLOPs of the convolutions' taps for one token, all conv layers."""
    return 2 * z.conv_k * z.D * z.kinds.count("conv")


def decode_flops(z: Dims, context_len: int) -> float:
    """One forward for one new token that attends to ``context_len``
    cached positions (itself included) on every attention layer."""
    attention = z.kinds.count("full") * 4 * z.H * z.hd * context_len
    return 2 * layer_params(z) + _taps(z) + 2 * z.D * z.V + attention


def prefill_flops(z: Dims, prompt_len: int) -> float:
    """One forward over a prompt at its TRUE length, head for the last row
    only."""
    attention = (z.kinds.count("full") * 4 * z.H * z.hd
                 * prompt_len * (prompt_len + 1) / 2.0)
    return ((2 * layer_params(z) + _taps(z)) * prompt_len + 2 * z.D * z.V
            + attention)


def serve_flops(z: Dims, prompt_lens: Iterable[int],
                decode_contexts: Iterable[int]) -> float:
    """Model FLOPs of the tokens really processed: each prefill at its
    true length, one forward per decoded token at its context."""
    return (sum(prefill_flops(z, p) for p in prompt_lens)
            + sum(decode_flops(z, c) for c in decode_contexts))


def train_flops_per_token(z: Dims, seq_len: int) -> float:
    raise NotImplementedError("this configuration is served, not trained")


def grouped_decode_cost(rows: int, slots: int, query_heads: int,
                        kv_heads: int, head_dim: int, pool_bytes: int,
                        out_bytes: int = 4) -> Dict[str, float]:
    """``paged_decode`` over grouped KV heads packed ``LANES / head_dim``
    to a pool row, for ONE layer of one tick. ``rows`` is the sum over
    slots of the cached positions the layer's queries read. Bytes: those
    positions' K and V rows at the pool's dtype, ``kv_heads * head_dim``
    values each (packing puts two heads in a row and reads no more),
    read ONCE for the ``query_heads / kv_heads`` query heads that share
    them, plus q in (at the pool's dtype) and out (float32) once a slot,
    a query head's at the row's ``LANES`` (its own head's lanes and the
    zeros beside them). FLOPs: QK^T and PV over those positions for every
    query head, at its own ``head_dim``."""
    flops = 4 * query_heads * head_dim * rows
    nbytes = (2 * rows * kv_heads * head_dim * pool_bytes
              + slots * query_heads * max(head_dim, LANES)
              * (pool_bytes + out_bytes))
    return {"flops": flops, "bytes": nbytes}
