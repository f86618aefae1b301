"""Model FLOPs of the GPT-2-shaped decoder, from shapes: the numerators
of ``train_step_mfu_pct`` and ``serve_step_mfu_pct``, and the attention's
shape for the kernel readers. The ``costs`` module of the Cerebras-GPT
configurations; the functions are ``benchmarks/costs.py``'s of PR 24,
moved here unchanged. Nothing is read from the compiler or the program.

Conventions (``benchmarks/costs.py``): a multiply-add is 2 FLOPs; a
backward pass costs twice its forward; recomputation never counts;
causal attention counts the lower triangle only.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from benchmarks.reference import Dims


def attention_shape(z: Dims) -> Tuple[int, int, int]:
    """Layers, heads and head size of the attention the kernels run."""
    return z.L, z.H, z.D // z.H


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


def serve_flops(z: Dims, prompt_lens: Iterable[int],
                decode_contexts: Iterable[int]) -> float:
    """Model FLOPs of the tokens really processed: each prefill at its
    true length, one forward per decoded token at its context."""
    return (sum(prefill_flops(z, p) for p in prompt_lens)
            + sum(decode_flops(z, c) for c in decode_contexts))
