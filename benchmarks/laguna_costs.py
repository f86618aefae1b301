"""Model FLOPs of Laguna-S-2.1's decoder AS THIS CHIP HOLDS IT, from
shapes, and the operations and bytes of its decode attention: the
numerators of ``serve_step_mfu_pct`` and ``grouped_decode_roofline_pct``.
The grouped product's are ``pangu_costs.moe_ffn_cost``'s as they stand
(the same ``HeldExpertsFFN``), which ``moe_ffn_roofline_pct`` reads
through this module. Nothing is read from the compiler or the program.

Conventions (``benchmarks/costs.py``, ``benchmarks/pangu_costs.py``): a
multiply-add is 2 FLOPs; causal attention counts the keys a query really
sees: the lower triangle on a full layer, at most ``sliding_window`` keys
a query on a sliding one. What is counted is this chip's share: of a
token's ``K`` routed experts ``K * held / E`` are held here in expectation
(0.625 at 10 of 256 with 16 held), the shared expert, the router, the
attention with its gate and the head whole.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from benchmarks.laguna_reference import Dims
from benchmarks.pangu_costs import moe_ffn_cost          # noqa: F401


def attention_shape(z: Dims) -> Tuple[int, int, int]:
    """Cache layers, the KV heads a token leaves in each, the head size
    (the query heads differ by layer: ``z.heads``)."""
    return z.L, z.H_kv, z.hd


def layer_params(z: Dims) -> float:
    """Weights a token meets in matrix products, summed over the layers:
    the attention's five projections (the gate's among them), the dense
    feed-forward or the router, the shared expert and the expected share
    of the routed experts held here."""
    total = 0.0
    for H, dense in zip(z.heads, z.dense):
        total += z.D * (2 * H * z.hd + 2 * z.H_kv * z.hd + H)
        total += 3 * z.D * z.F if dense else (
            z.D * z.E + 3 * z.D * z.F_s
            + 3 * z.D * z.F_e * z.K * z.held / z.E)
    return total


def keys_seen(window, context_len: int) -> int:
    """The keys ONE query at the end of ``context_len`` positions sees."""
    return context_len if window is None else min(context_len, window)


def pairs_seen(window, prompt_len: int) -> float:
    """The (query, key) pairs of a causal pass over ``prompt_len``
    positions: the lower triangle, cut to the window's band."""
    if window is None or prompt_len <= window:
        return prompt_len * (prompt_len + 1) / 2.0
    return window * (window + 1) / 2.0 + (prompt_len - window) * window


def decode_flops(z: Dims, context_len: int) -> float:
    """One forward for one new token that attends to ``context_len``
    cached positions (itself included) on a full layer and to its window
    of them on a sliding one."""
    attention = sum(4 * H * z.hd * keys_seen(w, context_len)
                    for H, w in zip(z.heads, z.windows))
    return 2 * layer_params(z) + 2 * z.D * z.V + attention


def prefill_flops(z: Dims, prompt_len: int) -> float:
    """One forward over a prompt at its TRUE length, head for the last row
    only."""
    attention = sum(4 * H * z.hd * pairs_seen(w, prompt_len)
                    for H, w in zip(z.heads, z.windows))
    return 2 * layer_params(z) * prompt_len + 2 * z.D * z.V + attention


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
    """``paged_decode`` over grouped KV heads for ONE layer of one tick.
    ``rows`` is the sum over slots of the cached positions the layer's
    queries really read: every one of a slot's on a full layer, its window
    of them on a sliding one. Bytes: those rows' K and V at the pool's
    dtype, ``kv_heads`` heads each (read ONCE for the ``query_heads /
    kv_heads`` query heads that share them), plus q in (at the pool's
    dtype) and out (float32) once a slot. FLOPs: QK^T and PV over those
    rows for every QUERY head."""
    flops = 4 * query_heads * head_dim * rows
    nbytes = (2 * rows * kv_heads * head_dim * pool_bytes
              + slots * query_heads * head_dim * (pool_bytes + out_bytes))
    return {"flops": flops, "bytes": nbytes}
