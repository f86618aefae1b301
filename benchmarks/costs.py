"""Operations and bytes the KERNELS need, from shapes, and the chip's
roofline: the numerators of every roofline share the benchmark reports.
Nothing here is read from the compiler (XLA's count includes
recomputation and sees no FLOPs inside a Mosaic custom call), nothing
from the program, and nothing of a model: a model's FLOPs are in the
``costs`` module its configuration names (``benchmarks/gpt2_costs.py``).

Conventions: a multiply-add is 2 FLOPs; a backward pass costs twice its
forward; recomputation never counts; causal attention counts the lower
triangle only (half of the square).
"""

from __future__ import annotations

from typing import Dict, Tuple


def roofline_seconds(flops: float, nbytes: float,
                     peaks: Dict[str, float]) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


def flash_cost(batch: int, heads: int, seq_len: int, head_dim: int,
               operand_bytes: int = 2) -> Dict[str, float]:
    """Causal flash attention, forward AND backward, for ONE layer of one
    step. FLOPs: forward two products over the lower triangle, backward
    twice that (the recomputed scores do not count). Bytes: forward
    reads q, k, v and writes out; backward reads q, k, v, out, d_out and
    writes dq, dk, dv: 12 passes over a [B, H, T, dh] operand, plus the
    float32 log-sum-exp row written once and read once."""
    pairs = batch * heads * seq_len * (seq_len + 1) / 2.0
    flops = 3 * 4 * head_dim * pairs
    operand = batch * heads * seq_len * head_dim * operand_bytes
    nbytes = 12 * operand + 2 * batch * heads * seq_len * 4
    return {"flops": flops, "bytes": nbytes}


def paged_decode_cost(live_tokens: int, slots: int, heads: int,
                      head_dim: int, pool_bytes: int,
                      act_bytes: int = 4) -> Dict[str, float]:
    """``paged_decode`` for ONE layer of one tick: ``live_tokens`` is the
    sum over slots of the keys each slot attends to. Bytes are the K and
    V rows of the live tokens at the pool's dtype plus q and out,
    whatever grid implements it; FLOPs are QK^T and PV over those rows."""
    flops = 4 * heads * head_dim * live_tokens
    nbytes = (2 * live_tokens * heads * head_dim * pool_bytes
              + 2 * slots * heads * head_dim * act_bytes)
    return {"flops": flops, "bytes": nbytes}
