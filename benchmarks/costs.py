"""Operations and bytes the algorithms NEED, from shapes: the numerators
of every utilisation and roofline share the benchmark reports. Nothing
here is read from the compiler (XLA's count includes recomputation and
sees no FLOPs inside a Mosaic custom call) and nothing from the program.

Conventions: a multiply-add is 2 FLOPs; a backward pass costs twice its
forward; recomputation never counts; causal attention counts the lower
triangle only (half of the square).
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from benchmarks.reference import Dims


def matmul_params(z: Dims) -> int:
    """Weights that sit in a matrix product once per token: the blocks'
    six matrices and the tied readout. (The embedding LOOKUP is a gather,
    the positions are added; biases and LayerNorm are elementwise.)"""
    return z.L * (4 * z.D * z.D + 2 * z.D * z.F) + z.V * z.D


def train_flops_per_token(z: Dims, seq_len: int) -> float:
    """Forward and backward model FLOPs per trained token at ``seq_len``
    (causal: a token attends to (seq_len + 1) / 2 keys on average)."""
    pairs_per_token = (seq_len + 1) / 2.0
    fwd = 2 * matmul_params(z) + 4 * z.D * z.L * pairs_per_token
    return 3.0 * fwd


def prefill_flops(z: Dims, prompt_len: int) -> float:
    """One forward over a prompt at its TRUE length (not the padded
    width), readout for the last row only."""
    pairs = prompt_len * (prompt_len + 1) / 2.0
    body = 2 * (matmul_params(z) - z.V * z.D) * prompt_len
    return body + 2 * z.V * z.D + 4 * z.D * z.L * pairs


def decode_flops(z: Dims, context_len: int) -> float:
    """One forward for one new token that attends to ``context_len``
    keys (itself included)."""
    return 2 * matmul_params(z) + 4 * z.D * z.L * context_len


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


def serve_flops(z: Dims, prompt_lens: Iterable[int],
                decode_contexts: Iterable[int]) -> float:
    """Model FLOPs of the tokens really processed: each prefill at its
    true length, one forward per decoded token at its context."""
    return (sum(prefill_flops(z, p) for p in prompt_lens)
            + sum(decode_flops(z, c) for c in decode_contexts))
